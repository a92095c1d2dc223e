"""The plain reference: weights from a seed, forward, loss, gradients and
the momentum-SGD update of the one transformer block both cells run, in
straightforward ``jax.numpy``. It imports nothing of the program and
takes nothing the program made.

The block (the departures from GPT-NeoX are listed in each
configuration file under ``assumed``): RMSNorm (eps 1e-6), full-width
interleaved RoPE (base 10000), causal softmax attention scaled by
``head_dim ** -0.5``, sequential residual, ReLU FFN with biases, untied
embedding and head, no final bias.

``precision`` says how the matrix products are computed:

``"highest"``  float32 operands, ``jax.default_matmul_precision
               ("highest")`` — the reference proper.
``"bfloat16"`` operands AND the residual stream rounded to bfloat16,
               float32 accumulation — the control of a float32
               configuration.
``"int8"``     as ``"bfloat16"``, and every matmul operand first
               rounded to 8 bits (symmetric, one scale per tensor,
               straight-through gradient) — the control of a bfloat16
               configuration.

The weights' layout is the one the program's entry points take
(``embed``, ``head``, ``final_norm``, ``blocks``: one dict per layer,
each leaf with a leading stage axis of 1): an input format, like the
token ids.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Model:
    """The sizes a configuration file states (its HF-style keys)."""

    vocab: int
    d_model: int
    n_heads: int
    d_head: int
    d_ff: int
    n_layers: int

    @staticmethod
    def from_config(cfg: Dict[str, Any]) -> "Model":
        return Model(vocab=int(cfg["vocab_size"]),
                     d_model=int(cfg["hidden_size"]),
                     n_heads=int(cfg["num_attention_heads"]),
                     d_head=int(cfg["head_dim"]),
                     d_ff=int(cfg["intermediate_size"]),
                     n_layers=int(cfg["num_hidden_layers"]))


# ---------------------------------------------------------------------------
# weights: one jitted call on the device, float32, from the seed


def _key(seed: int):
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


@functools.partial(jax.jit, static_argnums=(0,))
def _make_params(m: Model, key) -> Dict[str, Any]:
    def dense(k, shape):
        return 0.02 * jax.random.normal(k, shape, jnp.float32)

    d, h, dh, f = m.d_model, m.n_heads, m.d_head, m.d_ff
    blocks: List[Dict[str, Any]] = []
    for layer in range(m.n_layers):
        kq, kk, kv, ko, k1, k2 = jax.random.split(
            jax.random.fold_in(key, layer + 2), 6)
        blocks.append({
            "ln1": jnp.ones((1, d), jnp.float32),
            "wq": dense(kq, (1, d, h, dh)),
            "wk": dense(kk, (1, d, h, dh)),
            "wv": dense(kv, (1, d, h, dh)),
            "wo": dense(ko, (1, h, dh, d)),
            "ln2": jnp.ones((1, d), jnp.float32),
            "w1": dense(k1, (1, d, f)),
            "b1": jnp.zeros((1, f), jnp.float32),
            "w2": dense(k2, (1, f, d)),
            "b2": jnp.zeros((1, d), jnp.float32),
        })
    return {"embed": dense(jax.random.fold_in(key, 0), (m.vocab, d)),
            "head": dense(jax.random.fold_in(key, 1), (d, m.vocab)),
            "final_norm": jnp.ones((d,), jnp.float32),
            "blocks": blocks}


def make_params(m: Model, seed: int) -> Dict[str, Any]:
    """The weights of ``seed``: the same seed gives the same weights."""
    return _make_params(m, _key(seed))


def n_params(m: Model) -> int:
    per_layer = (4 * m.d_model * m.n_heads * m.d_head
                 + 2 * m.d_model * m.d_ff + m.d_ff + 3 * m.d_model)
    return 2 * m.vocab * m.d_model + m.d_model + m.n_layers * per_layer


# ---------------------------------------------------------------------------
# forward


def _round8(x):
    """Symmetric 8-bit rounding, one scale per tensor, straight-through."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30).astype(jnp.float32) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / s), -127, 127) * s
    return x + jax.lax.stop_gradient(q.astype(x.dtype) - x)


def _operand(x, precision: str):
    if precision == "highest":
        return x
    x = x.astype(jnp.bfloat16)
    return _round8(x) if precision == "int8" else x


def _mm(spec: str, a, b, precision: str):
    return jnp.einsum(spec, _operand(a, precision), _operand(b, precision),
                      preferred_element_type=jnp.float32)


def _rmsnorm(x, g):
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + 1e-6) * g


def _rope(x, pos):
    dh = x.shape[-1]
    freqs = 1.0 / (10000.0 ** (jnp.arange(0, dh, 2) / dh))
    ang = pos[:, None] * freqs[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def _block(x, bp, pos, m: Model, precision: str):
    stream = jnp.float32 if precision == "highest" else jnp.bfloat16
    h = _rmsnorm(x, bp["ln1"])
    q = _rope(_mm("bsd,dhk->bshk", h, bp["wq"], precision), pos)
    k = _rope(_mm("bsd,dhk->bshk", h, bp["wk"], precision), pos)
    v = _mm("bsd,dhk->bshk", h, bp["wv"], precision)
    s = _mm("bqhd,bkhd->bhqk", q, k, precision) * (m.d_head ** -0.5)
    n = x.shape[1]
    causal = jnp.arange(n)[:, None] >= jnp.arange(n)[None, :]
    s = jnp.where(causal[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    a = _mm("bhqk,bkhd->bqhd", p, v, precision)
    x = (x.astype(jnp.float32)
         + _mm("bshk,hkd->bsd", a, bp["wo"], precision)).astype(stream)
    h = _rmsnorm(x, bp["ln2"])
    z = jax.nn.relu(_mm("bsd,df->bsf", h, bp["w1"], precision) + bp["b1"])
    y = _mm("bsf,fd->bsd", z, bp["w2"], precision) + bp["b2"]
    return (x.astype(jnp.float32) + y).astype(stream)


def _stack(blocks: List[Dict[str, Any]]) -> Dict[str, Any]:
    """``[{leaf: [1, ...]}] * L`` -> ``{leaf: [L, ...]}``."""
    return {k: jnp.concatenate([b[k] for b in blocks], axis=0)
            for k in blocks[0]}


def _hidden(params, stacked, tokens, m: Model, precision: str):
    stream = jnp.float32 if precision == "highest" else jnp.bfloat16
    x = params["embed"][tokens].astype(stream)
    pos = jnp.arange(tokens.shape[1])

    @jax.checkpoint
    def layer(x, bp):
        return _block(x, bp, pos, m, precision), None

    x, _ = jax.lax.scan(layer, x, stacked)
    return _rmsnorm(x, params["final_norm"])


def _logits(params, stacked, tokens, m: Model, precision: str):
    h = _hidden(params, stacked, tokens, m, precision)
    return _mm("bsd,dv->bsv", h, params["head"], precision)


def _with_precision(precision: str):
    return jax.default_matmul_precision(
        "highest" if precision == "highest" else "default")


@functools.partial(jax.jit, static_argnums=(2, 3))
def _logits_jit(params, tokens, m: Model, precision: str):
    return _logits(params, _stack(params["blocks"]), tokens, m, precision)


def logits(params, tokens, m: Model, precision: str = "highest"):
    """``[b, s, vocab]`` next-token logits of the full causal forward."""
    with _with_precision(precision):
        return _logits_jit(params, jnp.asarray(tokens), m, precision)


# ---------------------------------------------------------------------------
# loss, gradients, momentum SGD: one batch row at a time, so that the
# float32 pass at 2048 positions fits beside the weights


def _row_loss_sum(flat, tokens, labels, mask, m: Model, precision: str):
    params, stacked = flat
    lg = _logits(params, stacked, tokens[None], m, precision)[0]
    lse = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, labels[:, None], axis=-1)[:, 0]
    return jnp.sum((lse - gold) * mask)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _row_grad(flat, tokens, labels, mask, m: Model, precision: str):
    return jax.value_and_grad(_row_loss_sum)(flat, tokens, labels, mask,
                                             m, precision)


@jax.jit
def _accumulate(acc, g):
    return jax.tree.map(jnp.add, acc, g)


@functools.partial(jax.jit, static_argnums=(3, 4), donate_argnums=(0, 1))
def _sgd(flat, velocity, grads, lr: float, momentum: float):
    velocity = jax.tree.map(lambda v, g: momentum * v + g, velocity, grads)
    flat = jax.tree.map(lambda p, v: p - lr * v, flat, velocity)
    return flat, velocity


@jax.jit
def _leaf_norms(tree):
    """Norm of every leaf of the PROGRAM's layout: a leaf of the stacked
    layout ``[L, ...]`` gives ``L`` norms."""
    def norms(x, per_layer):
        x = x.astype(jnp.float32)
        if per_layer:
            return jnp.sqrt(jnp.sum(jnp.square(x.reshape(x.shape[0], -1)),
                                    axis=1))
        return jnp.sqrt(jnp.sum(jnp.square(x)))[None]
    params, stacked = tree
    return ({k: norms(v, False) for k, v in params.items()},
            {k: norms(v, True) for k, v in stacked.items()})


@jax.jit
def _split(params):
    top = {k: v for k, v in params.items() if k != "blocks"}
    return top, _stack(params["blocks"])


@jax.jit
def _diff(a, b):
    return jax.tree.map(jnp.subtract, a, b)


def leaf_names(m: Model) -> List[str]:
    """The leaves in the order :func:`flatten_norms` lists them."""
    names = ["embed", "final_norm", "head"]
    for k in ("b1", "b2", "ln1", "ln2", "w1", "w2", "wk", "wo", "wq", "wv"):
        names += [f"blocks.{i}.{k}" for i in range(m.n_layers)]
    return names


def flatten_norms(norms) -> np.ndarray:
    top, stacked = norms
    out = [np.asarray(top[k]) for k in sorted(top)]
    out += [np.asarray(stacked[k]) for k in sorted(stacked)]
    return np.concatenate(out).astype(np.float64)


def program_leaf_norms(tree) -> np.ndarray:
    """The same listing for a tree in the program's layout (its
    parameters, its velocity, or a difference of two such trees)."""
    return flatten_norms(_leaf_norms(_split(tree)))


def train_readings(m: Model, seed: int, batches, lr: float,
                   momentum: float, precision: str = "highest",
                   rows_kept: int = 0) -> Dict[str, Any]:
    """Follow ``len(batches)`` steps of momentum SGD from ``seed``'s
    weights. Returns each step's loss, the per-leaf norms of the first
    gradient, and the per-leaf norms of the parameters' change after the
    last step. ``rows_kept > 0`` is the planted fault: only the first
    ``rows_kept`` rows of each batch count, the mean taken over them."""
    with _with_precision(precision):
        flat = _split(make_params(m, seed))
        start = jax.tree.map(jnp.copy, flat)
        velocity = jax.tree.map(jnp.zeros_like, flat)
        losses, grad_norms = [], None
        for tokens, labels, mask in batches:
            n_rows = rows_kept or tokens.shape[0]
            total, count, grads = 0.0, 0.0, None
            for r in range(n_rows):
                val, g = _row_grad(flat, jnp.asarray(tokens[r]),
                                   jnp.asarray(labels[r]),
                                   jnp.asarray(mask[r]), m, precision)
                grads = g if grads is None else _accumulate(grads, g)
                total += float(val)
                count += float(np.sum(mask[r]))
            grads = jax.tree.map(lambda x: x / count, grads)
            losses.append(total / count)
            if grad_norms is None:
                grad_norms = flatten_norms(_leaf_norms(grads))
            flat, velocity = _sgd(flat, velocity, grads, float(lr),
                                  float(momentum))
            del grads
        change = flatten_norms(_leaf_norms(_diff(flat, start)))
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change}


# ---------------------------------------------------------------------------
# the comparisons that decide `correct`


def worst_leaf_gap(got: np.ndarray, want: np.ndarray,
                   keep: np.ndarray = None) -> Tuple[float, int]:
    """The gap between the program's norm and the reference's, leaf by
    leaf, against the reference's norm of that leaf or of the median
    leaf, whichever is larger; the worst leaf and its index."""
    want = np.asarray(want, np.float64)
    got = np.asarray(got, np.float64)
    scale = np.maximum(want, np.median(want))
    gap = np.abs(got - want) / np.maximum(scale, 1e-300)
    if keep is not None:
        gap = np.where(keep, gap, 0.0)
    i = int(np.argmax(gap))
    return float(gap[i]), i


def moving_leaves(ref_grad_norms: np.ndarray) -> np.ndarray:
    """Leaves whose reference gradient is not nought to rounding: at
    least a thousandth of the median leaf's."""
    g = np.asarray(ref_grad_norms, np.float64)
    return g >= 1e-3 * np.median(g)


def served_gaps(row_logits: np.ndarray, served: np.ndarray) -> np.ndarray:
    """For each position, how far the served token's reference logit
    lies below the reference's best."""
    row_logits = np.asarray(row_logits, np.float32)
    best = row_logits.max(axis=-1)
    got = row_logits[np.arange(len(served)), np.asarray(served)]
    return (best - got).astype(np.float64)
