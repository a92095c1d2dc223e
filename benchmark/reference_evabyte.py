"""The plain reference of the EvaByte block (`configs/evabyte-6.5b.json`):
weights from a seed, one full forward over a sequence, in
straightforward ``jax.numpy``, float32. It imports nothing of the
program and takes nothing the program made; no cache, no kernel.

The layer, as equations (``W`` window, ``C`` chunk, ``d`` head size,
``s = d ** -0.5``; what the source's ``config.json`` does not fix is
listed in the configuration file under ``assumed``):

    norm(x)  = x * rsqrt(mean(x^2) + eps) * (1 + g)
    x1       = x + W_o Attn(norm1(x))
    y        = x1 + W_down(silu(W_gate h) * (W_up h)),  h = norm2(x1)
    q_n, k_n = rope(W_q h_n), rope(W_k h_n)   half-split, base theta,
    v_n      = W_v h_n                        position n, whole head
    chunk c  = positions [C c, C c + C):
      a_m = softmax_{m in c}(phi . k_m)
      ks_c = sum_m a_m k_m + mu,   vs_c = sum_m a_m v_m
    o_n = [ sum_{m <= n, w(m) = w(n)} e^{s q_n.k_m} v_m
            + sum_{c: floor(C c / W) < w(n)} e^{s q_n.ks_c} vs_c ]
          / [ the same sums without v ],      w(n) = floor(n / W)
    logits = W_head norm_f(y)     columns [0, vocab) are the next byte's

Attention is computed a query window at a time, with its mask written
out: window ``j``'s queries over window ``j``'s keys (``m <= n``) and
over the summaries of the windows before it. The weights are made and
used a layer at a time (:func:`layer_params`), so that the float32
reference of a 2.4 B-parameter cut never holds more than a layer, and
:func:`served_logits` takes several sequences through each layer before
the next is made.

``precision`` says how the matrix products are computed, as in
``reference.py``: ``"highest"`` float32 operands under
``default_matmul_precision("highest")`` (the reference proper);
``"bfloat16"`` every matmul operand rounded to bfloat16, float32
accumulation (what the configuration states: the residual stream stays
float32, ``fp32_skip_add``); ``"int8"`` as bfloat16 and every operand
first rounded to 8 bits, one scale per tensor (the control).

``drop_summaries=True`` is the fault of the mechanism that the limits
are set against: the second sum of ``o_n`` left out, so that a query
sees its own window only.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Model:
    """The sizes ``configs/evabyte-6.5b.json`` states (its HF keys)."""

    vocab: int
    d_model: int
    n_heads: int
    d_head: int
    d_ff: int
    n_layers: int
    window: int
    chunk: int
    n_pred_heads: int
    rope_theta: float
    norm_eps: float
    init_std: float

    @staticmethod
    def from_config(cfg: Dict[str, Any]) -> "Model":
        return Model(vocab=int(cfg["vocab_size"]),
                     d_model=int(cfg["hidden_size"]),
                     n_heads=int(cfg["num_attention_heads"]),
                     d_head=int(cfg["head_dim"]),
                     d_ff=int(cfg["intermediate_size"]),
                     n_layers=int(cfg["num_hidden_layers"]),
                     window=int(cfg["window_size"]),
                     chunk=int(cfg["chunk_size"]),
                     n_pred_heads=int(cfg["num_pred_heads"]),
                     rope_theta=float(cfg["rope_theta"]),
                     norm_eps=float(cfg["rms_norm_eps"]),
                     init_std=float(cfg["init_std"]))


# ---------------------------------------------------------------------------
# weights: float32, from the seed, a layer at a time


def _key(seed: int):
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


@functools.partial(jax.jit, static_argnums=(0,))
def _layer_params(m: Model, key) -> Dict[str, Any]:
    d, h, dh, f = m.d_model, m.n_heads, m.d_head, m.d_ff
    ks = jax.random.split(key, 9)

    def normal(k, shape, std):
        return std * jax.random.normal(k, shape, jnp.float32)

    return {"ln1": jnp.zeros((d,), jnp.float32),
            "wq": normal(ks[0], (d, h, dh), m.init_std),
            "wk": normal(ks[1], (d, h, dh), m.init_std),
            "wv": normal(ks[2], (d, h, dh), m.init_std),
            "wo": normal(ks[3], (h, dh, d), m.init_std),
            "ln2": jnp.zeros((d,), jnp.float32),
            "w_gate": normal(ks[4], (d, f), m.init_std),
            "w_up": normal(ks[5], (d, f), m.init_std),
            "w_down": normal(ks[6], (f, d), m.init_std),
            "phi": normal(ks[7], (h, dh), dh ** -0.5),
            "mu": normal(ks[8], (h, dh), dh ** -0.5)}


@functools.partial(jax.jit, static_argnums=(0,))
def _top_params(m: Model, key) -> Dict[str, Any]:
    return {"embed": m.init_std * jax.random.normal(
                jax.random.fold_in(key, 0), (m.vocab, m.d_model),
                jnp.float32),
            "head": m.init_std * jax.random.normal(
                jax.random.fold_in(key, 1),
                (m.d_model, m.n_pred_heads * m.vocab), jnp.float32),
            "final_norm": jnp.zeros((m.d_model,), jnp.float32)}


def layer_params(m: Model, seed: int, layer: int) -> Dict[str, Any]:
    """Layer ``layer``'s weights of ``seed``, float32."""
    return _layer_params(m, jax.random.fold_in(_key(seed), layer + 2))


def top_params(m: Model, seed: int) -> Dict[str, Any]:
    """Embedding, head and final norm of ``seed``, float32."""
    return _top_params(m, _key(seed))


def make_params(m: Model, seed: int, dtype=jnp.float32) -> Dict[str, Any]:
    """All the weights of ``seed`` in the layout the program's entry
    points take (an input format, like the token ids), matrices cast to
    ``dtype`` a layer at a time, norm gains float32."""
    def cast(tree):
        return {k: v if k in ("ln1", "ln2", "final_norm")
                else v.astype(dtype) for k, v in tree.items()}
    return dict(cast(top_params(m, seed)),
                blocks=[cast(layer_params(m, seed, layer))
                        for layer in range(m.n_layers)])


def n_params(m: Model) -> int:
    per_layer = (4 * m.d_model * m.n_heads * m.d_head
                 + 3 * m.d_model * m.d_ff + 2 * m.d_model
                 + 2 * m.n_heads * m.d_head)
    return (m.vocab * m.d_model + m.d_model * m.n_pred_heads * m.vocab
            + m.d_model + m.n_layers * per_layer)


# ---------------------------------------------------------------------------
# forward


def _round8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30).astype(jnp.float32) / 127.0
    return (jnp.clip(jnp.round(x.astype(jnp.float32) / s), -127, 127)
            * s).astype(x.dtype)


def _operand(x, precision: str):
    if precision == "highest":
        return x
    x = x.astype(jnp.bfloat16)
    return _round8(x) if precision == "int8" else x


def _mm(spec: str, a, b, precision: str):
    return jnp.einsum(spec, _operand(a, precision), _operand(b, precision),
                      preferred_element_type=jnp.float32)


def _norm(x, g, eps: float):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + g)


def _rope(x, pos, theta: float):
    dh = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = pos[:, None].astype(jnp.float32) * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


@functools.partial(jax.jit, static_argnums=(6, 7, 8))
def _layer_window(lp, x, pos0, sum_k, sum_v, n_sum, m: Model,
                  precision: str, drop_summaries: bool):
    """One window ``x`` [W, D] (positions ``pos0 + arange(W)``) through
    one layer, given the ``n_sum`` summary rows of the windows before it
    (``sum_k``/``sum_v`` [M, H, Dh]); returns the window's output and
    its own ``W / C`` summaries."""
    w_len = x.shape[0]
    pos = pos0 + jnp.arange(w_len)
    h = _norm(x, lp["ln1"], m.norm_eps)
    q = _rope(_mm("td,dhk->thk", h, lp["wq"], precision), pos, m.rope_theta)
    k = _rope(_mm("td,dhk->thk", h, lp["wk"], precision), pos, m.rope_theta)
    v = _mm("td,dhk->thk", h, lp["wv"], precision)
    if precision != "highest":
        # the configuration's cache rows are bfloat16
        k, v = (t.astype(jnp.bfloat16).astype(jnp.float32) for t in (k, v))
    scale = m.d_head ** -0.5
    # the mask, written out: own window causally, summaries before it
    s_win = _mm("qhd,khd->hqk", q, k, precision) * scale
    causal = jnp.arange(w_len)[:, None] >= jnp.arange(w_len)[None, :]
    s_win = jnp.where(causal[None], s_win, -1e30)
    s_sum = _mm("qhd,khd->hqk", q, sum_k, precision) * scale
    seen = jnp.arange(sum_k.shape[0]) < (0 if drop_summaries else n_sum)
    s_sum = jnp.where(seen[None, None], s_sum, -1e30)
    p = jax.nn.softmax(jnp.concatenate([s_sum, s_win], axis=-1), axis=-1)
    n_s = sum_k.shape[0]
    a = (_mm("hqk,khd->qhd", p[..., :n_s], sum_v, precision)
         + _mm("hqk,khd->qhd", p[..., n_s:], v, precision))
    x = x + _mm("thk,hkd->td", a, lp["wo"], precision)
    h = _norm(x, lp["ln2"], m.norm_eps)
    z = (jax.nn.silu(_mm("td,df->tf", h, lp["w_gate"], precision))
         * _mm("td,df->tf", h, lp["w_up"], precision))
    x = x + _mm("tf,fd->td", z, lp["w_down"], precision)
    # this window's summaries, for the windows after it
    kc = k.reshape(w_len // m.chunk, m.chunk, m.n_heads, m.d_head)
    vc = v.reshape(w_len // m.chunk, m.chunk, m.n_heads, m.d_head)
    a_c = jax.nn.softmax(jnp.einsum("cmhd,hd->cmh", kc, lp["phi"]), axis=1)
    ks = jnp.einsum("cmh,cmhd->chd", a_c, kc) + lp["mu"]
    vs = jnp.einsum("cmh,cmhd->chd", a_c, vc)
    if precision != "highest":
        ks, vs = (t.astype(jnp.bfloat16).astype(jnp.float32)
                  for t in (ks, vs))
    return x, ks, vs


@functools.partial(jax.jit, static_argnums=(2, 3))
def _head(top, x, m: Model, precision: str):
    return _mm("td,dv->tv", _norm(x, top["final_norm"], m.norm_eps),
               top["head"], precision)


def _with_precision(precision: str):
    return jax.default_matmul_precision(
        "highest" if precision == "highest" else "default")


def _through_layer(lp, x, m: Model, precision: str, drop_summaries: bool,
                   max_windows: int):
    """``x`` [S, D] (``S`` a multiple of the window) through one layer,
    a query window at a time. The summary rows are held at the length
    ``max_windows`` needs, so that every sequence of a call runs the
    one compiled window function."""
    w_len = m.window
    per = w_len // m.chunk
    n_win = x.shape[0] // w_len
    shape = (max(max_windows - 1, 1) * per, m.n_heads, m.d_head)
    sum_k, sum_v = jnp.zeros(shape), jnp.zeros(shape)
    outs = []
    for j in range(n_win):
        y, ks, vs = _layer_window(
            lp, x[j * w_len:(j + 1) * w_len], np.int32(j * w_len), sum_k,
            sum_v, np.int32(j * per), m, precision, drop_summaries)
        outs.append(y)
        if j + 1 < n_win:
            sum_k = sum_k.at[j * per:(j + 1) * per].set(ks)
            sum_v = sum_v.at[j * per:(j + 1) * per].set(vs)
    return jnp.concatenate(outs)


def served_logits(m: Model, seed: int, sequences: Sequence[np.ndarray],
                  rows: Sequence[slice], precision: str = "highest",
                  drop_summaries: bool = False) -> List[np.ndarray]:
    """The full forward over each of ``sequences`` (token ids), and of
    each the float32 logits of ALL prediction heads at ``rows``: a list
    of ``[len(rows[i]), n_pred_heads * vocab]`` arrays. Each layer's
    weights are made once and every sequence goes through them before
    the next layer is made."""
    with _with_precision(precision):
        top = top_params(m, seed)
        xs = []
        for seq in sequences:
            pad = -len(seq) % m.window
            ids = np.concatenate([np.asarray(seq, np.int32),
                                  np.zeros(pad, np.int32)])
            xs.append(top["embed"][jnp.asarray(ids)])
        most = max(x.shape[0] // m.window for x in xs)
        for layer in range(m.n_layers):
            lp = layer_params(m, seed, layer)
            xs = [_through_layer(lp, x, m, precision, drop_summaries, most)
                  for x in xs]
            del lp
        return [np.asarray(_head(top, x[r], m, precision))
                for x, r in zip(xs, rows)]


def logits(m: Model, seed: int, tokens: np.ndarray,
           precision: str = "highest", drop_summaries: bool = False
           ) -> np.ndarray:
    """``[S, n_pred_heads * vocab]`` logits of one sequence's full
    forward."""
    return served_logits(m, seed, [tokens], [slice(0, len(tokens))],
                         precision, drop_summaries)[0]
