"""The plain reference of the looped softmax stack (`configs/ouro-2.6b
.json`): weights from a seed, one full forward over a sequence, in
straightforward ``jax.numpy``, float32. It imports nothing of the
program and takes nothing the program made; no cache, no kernel, no
batching, no loop construct but Python's.

The model, whole (``T`` = ``total_ut_steps`` passes over the SAME ``L``
layers; ``RMSNorm(x; g) = x / sqrt(mean(x^2) + eps) * g`` in float32):

    x = E[token]                                  no scaling
    for t in 1..T:
      for l in 1..L:
        a = RMSNorm(x; g1_l)
        q, k, v = a Wq_l, a Wk_l, a Wv_l          H heads of Dh, no bias
        q, k = RoPE(q, p), RoPE(k, p)             half-split (rotate_half)
                                                  over all Dh, base theta
        o = softmax(q K[t,l][<=p] / sqrt(Dh)) V[t,l][<=p]
                                                  causal; pass t's OWN rows
        x = x + RMSNorm(o Wo_l; g2_l)             norm AFTER the sublayer
        b = RMSNorm(x; g3_l)
        m = (silu(b Wg_l) * (b Wu_l)) Wd_l        gated SiLU, no bias
        x = x + RMSNorm(m; g4_l)
      h_t = RMSNorm(x; g_final);  x = h_t         the normalised state
                                                  feeds the next pass
      lam_t = sigmoid(w_exit . h_t + b_exit)      the exit gate
    p_t = lam_t prod_{j<t} (1 - lam_j)  (t < T),  p_T = prod_{j<T} (1 - lam_j)
    exit at the first t whose cumulated p reaches early_exit_threshold
                                                  (1.0: always t = T)
    logits = h_T W_head                           untied

What the source's ``config.json`` pins: the sizes, the activation, the
eps, the rotary base, the untied head, ``T`` and the threshold. What it
does not, and is written here from the family's published description
(arXiv:2510.25741) and modelling code, from memory: the four norms a
layer (sandwich), the norm between passes, the gate's form, no
projection biases, no query/key norm, the rotary layout. Each is
listed in the configuration file under ``assumed``. Departures from
the published description: none known in the equations; the weights
are random (:func:`layer_params`; the configuration's ``init`` group
says how).

Weights are made and used a layer at a time, again in every pass (the
float32 stack is 10.7 GB at the published sizes), and every sequence
goes through a layer before the next is made.

``precision`` says how the matrix products are computed, as in
``reference.py``: ``"highest"`` float32 operands under
``default_matmul_precision("highest")`` (the reference proper);
``"bfloat16"`` every matmul operand rounded to bfloat16, float32
accumulation (what the configuration states: the residual stream, the
norms, the softmax, the gate and the logits stay float32); ``"int8"`` as
bfloat16 and every operand first rounded to 8 bits, one scale per tensor
(the control).

``fault`` plants a fault of the mechanism that the limits are set
against: ``"loop_dropped"`` makes ``T - 1`` passes; ``"loop_cache_
shared"`` lets every pass attend the FIRST pass's K and V rows (a cache
keyed by layer alone).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

FAULTS = ("loop_dropped", "loop_cache_shared")


@dataclasses.dataclass(frozen=True)
class Model:
    """The sizes ``configs/ouro-2.6b.json`` states, and its ``init``."""

    vocab: int
    d_model: int
    n_heads: int
    d_head: int
    d_ff: int
    n_layers: int
    n_loops: int
    exit_threshold: float
    rope_theta: float
    norm_eps: float
    #: random weights: the embedding's std and the gain of the norms
    #: behind each sublayer (g2, g4)
    embed_std: float
    post_norm_gain: float

    @staticmethod
    def from_config(cfg: Dict[str, Any]) -> "Model":
        if cfg.get("model_type") != "ouro":
            raise KeyError("model_type")      # another block's file
        heads = int(cfg["num_attention_heads"])
        if int(cfg["num_key_value_heads"]) != heads:
            raise ValueError("every head keeps its own K/V")
        return Model(
            vocab=int(cfg["vocab_size"]), d_model=int(cfg["hidden_size"]),
            n_heads=heads, d_head=int(cfg["head_dim"]),
            d_ff=int(cfg["intermediate_size"]),
            n_layers=int(cfg["num_hidden_layers"]),
            n_loops=int(cfg["total_ut_steps"]),
            exit_threshold=float(cfg["early_exit_threshold"]),
            rope_theta=float(cfg["rope_theta"]),
            norm_eps=float(cfg["rms_norm_eps"]),
            embed_std=float(cfg["init"]["embed_std"]),
            post_norm_gain=float(cfg["init"]["post_norm_gain"]))


# ---------------------------------------------------------------------------
# weights: float32, from the seed, a layer at a time

#: leaves that stay float32 whatever the configuration's dtype
FLOAT32_LEAVES = ("ln1", "ln1_post", "ln2", "ln2_post", "final_norm",
                  "exit_w", "exit_b")


def _key(seed: int):
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _matrix(key, shape, fan_in: int):
    """Normal with std ``fan_in ** -0.5``: a unit-rms input gives a
    unit-rms output."""
    return fan_in ** -0.5 * jax.random.normal(key, shape, jnp.float32)


def _cast(tree: Dict[str, Any], dtype: str) -> Dict[str, Any]:
    """The matrices rounded to ``dtype`` inside the jitted makers (a
    layer's float32 values are never held beside the rounded copy);
    :data:`FLOAT32_LEAVES` as they are."""
    return {k: v if k in FLOAT32_LEAVES else v.astype(dtype)
            for k, v in tree.items()}


@functools.partial(jax.jit, static_argnums=(0, 2))
def _layer_params(m: Model, key, dtype: str = "float32") -> Dict[str, Any]:
    d, h, dh, f = m.d_model, m.n_heads, m.d_head, m.d_ff
    ks = jax.random.split(key, 7)
    one = jnp.ones((d,), jnp.float32)
    return _cast({
        "ln1": one, "ln2": one,
        "ln1_post": m.post_norm_gain * one,
        "ln2_post": m.post_norm_gain * one,
        "wq": _matrix(ks[0], (d, h, dh), d),
        "wk": _matrix(ks[1], (d, h, dh), d),
        "wv": _matrix(ks[2], (d, h, dh), d),
        "wo": _matrix(ks[3], (h, dh, d), h * dh),
        "w_gate": _matrix(ks[4], (d, f), d),
        "w_up": _matrix(ks[5], (d, f), d),
        "w_down": _matrix(ks[6], (f, d), f)}, dtype)


@functools.partial(jax.jit, static_argnums=(0, 2))
def _top_params(m: Model, key, dtype: str = "float32") -> Dict[str, Any]:
    d = m.d_model
    return _cast({
        "embed": m.embed_std * jax.random.normal(
            jax.random.fold_in(key, 0), (m.vocab, d), jnp.float32),
        "head": _matrix(jax.random.fold_in(key, 1), (d, m.vocab), d),
        "final_norm": jnp.ones((d,), jnp.float32),
        # w . h is of unit variance for a unit-rms h: the gate is
        # neither shut nor open
        "exit_w": _matrix(jax.random.fold_in(key, 2), (d,), d),
        "exit_b": jnp.zeros((), jnp.float32)}, dtype)


def layer_params(m: Model, seed: int, layer: int, dtype="float32"
                 ) -> Dict[str, Any]:
    """Layer ``layer``'s weights of ``seed`` (the same in every pass),
    the matrices in ``dtype``."""
    return _layer_params(m, jax.random.fold_in(_key(seed), layer + 3),
                         jnp.dtype(dtype).name)


def top_params(m: Model, seed: int, dtype="float32") -> Dict[str, Any]:
    """Embedding, head, final norm and exit gate of ``seed``."""
    return _top_params(m, _key(seed), jnp.dtype(dtype).name)


def make_params(m: Model, seed: int, dtype=jnp.float32) -> Dict[str, Any]:
    """All the weights of ``seed`` in the layout the program's entry
    points take (an input format, like the token ids): the float32
    values the reference uses, the matrices rounded to ``dtype``,
    :data:`FLOAT32_LEAVES` float32; ``blocks`` one dict a layer, each
    leaf with a leading stage axis of 1."""
    return dict(top_params(m, seed, dtype),
                blocks=[{k: v[None] for k, v in
                         layer_params(m, seed, layer, dtype).items()}
                        for layer in range(m.n_layers)])


def n_params(m: Model) -> int:
    d = m.d_model
    layer = 4 * d * m.n_heads * m.d_head + 3 * d * m.d_ff + 4 * d
    return m.n_layers * layer + 2 * m.vocab * d + d + (d + 1)


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
    return x * jax.lax.rsqrt(var + eps) * g


def _rope(x, theta: float):
    """Half-split rotary over the whole head: ``x`` [S, H, Dh] at
    positions ``arange(S)``; column ``i`` pairs with ``i + Dh / 2``."""
    s_len, _, dh = x.shape
    freqs = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(s_len, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _layer(lp, x, m: Model, precision: str, kv=None):
    """One layer over one sequence ``x`` [S, D] -> ``(x', (k, v))``.
    ``kv`` given, attention reads THOSE rows and not its own (the
    ``loop_cache_shared`` fault)."""
    s_len = x.shape[0]
    a = _norm(x, lp["ln1"], m.norm_eps)
    q = _rope(_mm("td,dhk->thk", a, lp["wq"], precision), m.rope_theta)
    k = _rope(_mm("td,dhk->thk", a, lp["wk"], precision), m.rope_theta)
    v = _mm("td,dhk->thk", a, lp["wv"], precision)
    own = (k, v)
    if kv is not None:
        k, v = kv
    s = _mm("qhd,khd->hqk", q, k, precision) * m.d_head ** -0.5
    causal = jnp.arange(s_len)[:, None] >= jnp.arange(s_len)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], s, -1e30), axis=-1)
    o = _mm("hqk,khd->qhd", p, v, precision)
    x = x + _norm(_mm("thk,hkd->td", o, lp["wo"], precision),
                  lp["ln1_post"], m.norm_eps)
    b = _norm(x, lp["ln2"], m.norm_eps)
    z = (jax.nn.silu(_mm("td,df->tf", b, lp["w_gate"], precision))
         * _mm("td,df->tf", b, lp["w_up"], precision))
    return x + _norm(_mm("tf,fd->td", z, lp["w_down"], precision),
                     lp["ln2_post"], m.norm_eps), own


@functools.partial(jax.jit, static_argnums=(2,))
def _between(top, x, m: Model):
    """The end of a pass: the normalised state and its exit gate."""
    h = _norm(x, top["final_norm"], m.norm_eps)
    return h, jax.nn.sigmoid(jnp.sum(h * top["exit_w"], -1)
                             + top["exit_b"])


def exit_distribution(lam: np.ndarray) -> np.ndarray:
    """``lam`` [T, ...] -> ``p`` [T, ...], written out pass by pass."""
    lam = np.asarray(lam, np.float64)
    p = np.zeros_like(lam)
    stay = np.ones_like(lam[0])
    for t in range(len(lam) - 1):
        p[t] = lam[t] * stay
        stay = stay * (1.0 - lam[t])
    p[-1] = stay
    return p


def exit_pass(lam: np.ndarray, threshold: float) -> np.ndarray:
    """The pass (from 1) at which each position leaves the loop: the
    first whose cumulated exit probability reaches ``threshold``."""
    cdf = np.cumsum(exit_distribution(lam), axis=0)
    return 1 + np.argmax(cdf >= threshold - 1e-12, axis=0)


def _with_precision(precision: str):
    return jax.default_matmul_precision(
        "highest" if precision == "highest" else "default")


def _bucket(n: int, floor: int = 64) -> int:
    """The power of two a sequence is padded to (padding lies after the
    real positions, which a causal model never sees)."""
    return max(1 << int(n - 1).bit_length(), floor)


def forward(m: Model, seed: int, sequences: Sequence[np.ndarray],
            precision: str = "highest", fault: Optional[str] = None
            ) -> Tuple[Any, List[Any], List[Any]]:
    """The full forward over each of ``sequences`` (token ids) ->
    ``(top, hs, lams)``: the last pass's normalised state ``[S_pad, D]``
    and the gates ``[passes, S_pad]`` of each. A layer's weights are
    made once a pass and every sequence goes through them before the
    next layer's are made."""
    if fault not in (None,) + FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    passes = m.n_loops - (fault == "loop_dropped")
    with _with_precision(precision):
        top = top_params(m, seed)
        xs = []
        for seq in sequences:
            ids = np.zeros(_bucket(len(seq)), np.int32)
            ids[:len(seq)] = seq
            xs.append(top["embed"][jnp.asarray(ids)])
        first: Dict[Tuple[int, int], Any] = {}
        lams: List[List[Any]] = [[] for _ in xs]
        for t in range(passes):
            for layer in range(m.n_layers):
                lp = layer_params(m, seed, layer)
                for i, x in enumerate(xs):
                    xs[i], own = _layer(lp, x, m, precision,
                                        first.get((layer, i)))
                    if fault == "loop_cache_shared" and t == 0:
                        first[layer, i] = own
                del lp
            for i, x in enumerate(xs):
                xs[i], lam = _between(top, x, m)
                lams[i].append(lam)
        return top, xs, [jnp.stack(lam) for lam in lams]


def served_logits(m: Model, seed: int, sequences: Sequence[np.ndarray],
                  rows: Sequence[slice], precision: str = "highest",
                  fault: Optional[str] = None) -> List[np.ndarray]:
    """Of each sequence's full forward the float32 logits at ``rows``:
    a list of ``[len(rows[i]), vocab]`` arrays. With the published
    threshold every position leaves at the last pass, whose state the
    head reads; another threshold is refused (the program builds no
    early exit either)."""
    if m.exit_threshold < 1.0:
        raise NotImplementedError("early_exit_threshold < 1")
    top, hs, _ = forward(m, seed, sequences, precision, fault)
    with _with_precision(precision):
        return [np.asarray(_mm("td,dv->tv", h[r], top["head"], precision))
                for h, r in zip(hs, rows)]


def logits_and_gates(m: Model, seed: int, tokens: np.ndarray,
                     precision: str = "highest",
                     fault: Optional[str] = None
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """``([S, vocab] logits, [passes, S] exit gates)`` of one sequence's
    full forward."""
    top, hs, lams = forward(m, seed, [tokens], precision, fault)
    n = len(tokens)
    with _with_precision(precision):
        lg = _mm("td,dv->tv", hs[0][:n], top["head"], precision)
    return np.asarray(lg), np.asarray(lams[0])[:, :n]
