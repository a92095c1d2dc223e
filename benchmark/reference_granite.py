"""The plain reference of the Granite-hybrid block
(`configs/granite-4.0-h-small.json`): weights from a seed, one full
forward over a sequence, in straightforward ``jax.numpy``, float32. It
imports nothing of the program and takes nothing the program made; no
cache, no kernel, no chunking.

The layers, as equations (``rm`` the residual multiplier; what the
source's ``config.json`` does not fix is listed in the configuration
file under ``assumed``):

    norm(x)  = x * rsqrt(mean(x^2) + eps) * g
    x0       = embedding_multiplier * E[token]
    h        = x + rm * mixer(norm1(x))
    x'       = h + rm * (routed(norm2(h)) + shared(norm2(h)))
    logits   = norm_f(x_L) E^T / logits_scaling            (tied)

  Mamba-2 mixer (H heads of P, one group, state N, conv width K):
    [z | xBC | dt] = W_in u                 (H P | H P + 2 N | H)
    xBC_t    = silu(sum_k w_k xBC_{t-K+1+k} + b)   causal, depthwise
    [x | B | C] = xBC                       (H P | N | N)
    dt_t     = softplus(dt_t + dt_bias),  A = -exp(A_log)   per head
    S_t      = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T   (P x N a head)
    y_t      = S_t C_t + D x_t
    out      = W_out norm_w(y * silu(z))    over all H P columns
  the recurrence is a ``lax.scan`` over positions, the state float32.

  Attention layer: ``n_heads`` query heads over ``n_kv_heads`` K/V
  heads (query head j reads K/V head j // (n_heads / n_kv_heads)), no
  positional encoding, no biases, causal softmax of ``attention_
  multiplier * q.k``, computed a block of queries at a time with the
  mask written out.

  Experts: p = softmax(W_r u) over ALL ``n_experts``; the ``top_k``
  largest, renormalised to sum to one; expert e gives
  W_out,e (silu(g) * v), [g | v] = W_in,e u. This chip HOLDS the
  experts ``experts_held``: a routing to an expert that is not held
  contributes nothing (the other chip of the stage adds it in the
  deployment), here as in the program. The shared expert is the same
  form at its own width, every token, weight one.

Departures from the published description: none known in the
equations; the weights are random (``layer_params``), the depth, the
experts held and the vocabulary are cut (``reduced`` in the
configuration file). The experts are computed an expert at a time over
the tokens routed to it (gathered on the host, padded to a power of
two), which is the same sum as a token at a time over its experts.
Weights are made and used a layer at a time, so that the float32
reference of a 4.8 B-parameter cut never holds more than a layer, and
:func:`served_logits` takes several sequences through each layer
before the next is made.

``precision`` says how the matrix products are computed, as in
``reference.py``: ``"highest"`` float32 operands under
``default_matmul_precision("highest")`` (the reference proper);
``"bfloat16"`` every matmul operand rounded to bfloat16, float32
accumulation (what the configuration states: the residual stream, the
recurrent state, the router and the logits stay float32); ``"int8"`` as
bfloat16 and every operand first rounded to 8 bits, one scale per
tensor (the control).

``fault`` plants a fault of the mechanism that the limits are set
against: ``"state_reset"`` zeroes the recurrent state at every
``reset_every``-th position; ``"expert_dropped"`` leaves out the least
of each token's held routings.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

FAULTS = ("state_reset", "expert_dropped")


@dataclasses.dataclass(frozen=True)
class Model:
    """The sizes ``configs/granite-4.0-h-small.json`` states."""

    vocab: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    layer_types: Tuple[str, ...]
    n_experts: int
    top_k: int
    experts_held: Tuple[int, ...]
    d_expert: int
    d_shared: int
    ssm_heads: int
    ssm_head_dim: int
    ssm_state: int
    ssm_conv: int
    ssm_chunk: int
    embedding_multiplier: float
    residual_multiplier: float
    attention_multiplier: float
    logits_scaling: float
    norm_eps: float
    embed_std: float

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def d_conv_in(self) -> int:
        """Columns the depthwise convolution runs over: x, B and C."""
        return self.d_inner + 2 * self.ssm_state

    @staticmethod
    def from_config(cfg: Dict[str, Any]) -> "Model":
        if int(cfg["mamba_n_groups"]) != 1:
            raise ValueError("one group of B and C only")
        if int(cfg["mamba_expand"]) * int(cfg["hidden_size"]) != (
                int(cfg["mamba_n_heads"]) * int(cfg["mamba_d_head"])):
            raise ValueError("mamba_expand * hidden_size must be "
                             "mamba_n_heads * mamba_d_head")
        lo, hi = cfg["experts_held"]
        if hi - lo != int(cfg["num_local_experts"]):
            raise ValueError("experts_held must span num_local_experts")
        return Model(
            vocab=int(cfg["vocab_size"]), d_model=int(cfg["hidden_size"]),
            n_heads=int(cfg["num_attention_heads"]),
            n_kv_heads=int(cfg["num_key_value_heads"]),
            d_head=int(cfg["head_dim"]),
            layer_types=tuple(
                cfg["layer_types"][:int(cfg["num_hidden_layers"])]),
            n_experts=int(cfg["num_routed_experts"]),
            top_k=int(cfg["num_experts_per_tok"]),
            experts_held=tuple(range(int(lo), int(hi))),
            d_expert=int(cfg["intermediate_size"]),
            d_shared=int(cfg["shared_intermediate_size"]),
            ssm_heads=int(cfg["mamba_n_heads"]),
            ssm_head_dim=int(cfg["mamba_d_head"]),
            ssm_state=int(cfg["mamba_d_state"]),
            ssm_conv=int(cfg["mamba_d_conv"]),
            ssm_chunk=int(cfg["mamba_chunk_size"]),
            embedding_multiplier=float(cfg["embedding_multiplier"]),
            residual_multiplier=float(cfg["residual_multiplier"]),
            attention_multiplier=float(cfg["attention_multiplier"]),
            logits_scaling=float(cfg["logits_scaling"]),
            norm_eps=float(cfg["rms_norm_eps"]),
            embed_std=float(cfg["init"]["embed_std"]))


# ---------------------------------------------------------------------------
# weights: float32, from the seed, a layer at a time

#: the gain of the random query and key matrices over the unit gain of
#: every other matrix: with unit gain the published attention multiplier
#: (1 / d_head, not d_head ** -0.5) leaves every softmax uniform, and
#: the K/V rows would weigh nothing in the logits
QK_GAIN = 4.0

#: leaves that stay float32 whatever the configuration's dtype
FLOAT32_LEAVES = ("norm1", "norm2", "final_norm", "gate_norm", "conv_w",
                  "conv_b", "dt_bias", "a_log", "d_skip", "router")


def _key(seed: int):
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _matrix(key, shape, fan_in: int, gain: float = 1.0):
    """Normal with std ``gain * fan_in ** -0.5``: a unit-rms input gives
    an output of rms ``gain``."""
    return (gain * fan_in ** -0.5) * jax.random.normal(key, shape,
                                                       jnp.float32)


def _cast(tree: Dict[str, Any], dtype: str) -> Dict[str, Any]:
    """The matrices rounded to ``dtype``, :data:`FLOAT32_LEAVES` as they
    are. Inside the jitted makers, so that at the published widths a
    layer's float32 values are rounded as they are drawn and never held
    beside the rounded copy."""
    return {k: v if k in FLOAT32_LEAVES else v.astype(dtype)
            for k, v in tree.items()}


def _moe_params(m: Model, ks) -> Dict[str, Any]:
    d = m.d_model
    held = jnp.asarray(m.experts_held)

    def per_expert(k, shape, fan_in, gain=1.0):
        # expert e's weights are e's whichever chip holds it
        return jax.vmap(lambda e: _matrix(jax.random.fold_in(k, e), shape,
                                          fan_in, gain))(held)

    return {"norm2": jnp.ones((d,), jnp.float32),
            "router": _matrix(ks[0], (d, m.n_experts), d),
            "w_in_e": per_expert(ks[1], (d, 2 * m.d_expert), d),
            "w_out_e": per_expert(ks[2], (m.d_expert, d), m.d_expert),
            "w_in_s": _matrix(ks[3], (d, 2 * m.d_shared), d),
            "w_out_s": _matrix(ks[4], (m.d_shared, d), m.d_shared)}


@functools.partial(jax.jit, static_argnums=(0, 1, 3))
def _layer_params(m: Model, kind: str, key, dtype: str = "float32"
                  ) -> Dict[str, Any]:
    d = m.d_model
    ks = jax.random.split(key, 12)
    out = _moe_params(m, ks[:5])
    out["norm1"] = jnp.ones((d,), jnp.float32)
    if kind == "attention":
        hd = m.n_heads * m.d_head
        out.update(
            wq=_matrix(ks[5], (d, m.n_heads, m.d_head), d, QK_GAIN),
            wk=_matrix(ks[6], (d, m.n_kv_heads, m.d_head), d, QK_GAIN),
            wv=_matrix(ks[7], (d, m.n_kv_heads, m.d_head), d),
            wo=_matrix(ks[8], (m.n_heads, m.d_head, d), hd))
        return _cast(out, dtype)
    h = m.ssm_heads
    # dt log-uniform over [0.001, 0.1] and A uniform over [1, 16], as
    # the published Mamba-2 initialisation has them: a head's state
    # lasts between a position and a thousand
    dt = jnp.exp(jax.random.uniform(ks[9], (h,), jnp.float32,
                                    np.log(0.001), np.log(0.1)))
    out.update(
        w_in=_matrix(ks[5], (d, 2 * m.d_inner + 2 * m.ssm_state + h), d),
        conv_w=_matrix(ks[6], (m.ssm_conv, m.d_conv_in), m.ssm_conv),
        conv_b=0.1 * jax.random.normal(ks[7], (m.d_conv_in,), jnp.float32),
        dt_bias=dt + jnp.log(-jnp.expm1(-dt)),      # inverse softplus
        a_log=jnp.log(jax.random.uniform(ks[10], (h,), jnp.float32,
                                         1.0, 16.0)),
        d_skip=jnp.ones((h,), jnp.float32),
        gate_norm=jnp.ones((m.d_inner,), jnp.float32),
        w_out=_matrix(ks[8], (m.d_inner, d), m.d_inner))
    return _cast(out, dtype)


@functools.partial(jax.jit, static_argnums=(0, 2))
def _top_params(m: Model, key, dtype: str = "float32") -> Dict[str, Any]:
    return _cast({"embed": m.embed_std * jax.random.normal(
                      jax.random.fold_in(key, 0), (m.vocab, m.d_model),
                      jnp.float32),
                  "final_norm": jnp.ones((m.d_model,), jnp.float32)},
                 dtype)


def layer_params(m: Model, seed: int, layer: int, dtype="float32"
                 ) -> Dict[str, Any]:
    """Layer ``layer``'s weights of ``seed``, the matrices in ``dtype``."""
    return _layer_params(m, m.layer_types[layer],
                         jax.random.fold_in(_key(seed), layer + 2),
                         jnp.dtype(dtype).name)


def top_params(m: Model, seed: int, dtype="float32") -> Dict[str, Any]:
    """The (tied) embedding and the final norm of ``seed``."""
    return _top_params(m, _key(seed), jnp.dtype(dtype).name)


def make_params(m: Model, seed: int, dtype=jnp.float32) -> Dict[str, Any]:
    """All the weights of ``seed`` in the layout the program's entry
    points take (an input format, like the token ids): the float32
    values the reference uses, the matrices rounded to ``dtype``,
    :data:`FLOAT32_LEAVES` float32."""
    return dict(top_params(m, seed, dtype),
                blocks=[layer_params(m, seed, layer, dtype)
                        for layer in range(m.n_layers)])


def n_params(m: Model) -> int:
    """Parameters this chip holds."""
    d, e = m.d_model, len(m.experts_held)
    moe = (d + d * m.n_experts + e * 3 * d * m.d_expert
           + 3 * d * m.d_shared)
    mamba = (d + d * (2 * m.d_inner + 2 * m.ssm_state + m.ssm_heads)
             + (m.ssm_conv + 1) * m.d_conv_in + 3 * m.ssm_heads
             + m.d_inner + m.d_inner * d)
    attn = d + 2 * d * (m.n_heads + m.n_kv_heads) * m.d_head
    return (m.vocab * d + d + sum(
        moe + (attn if kind == "attention" else mamba)
        for kind in m.layer_types))


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


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _mamba_mixer(lp, x, m: Model, precision: str, reset_every: int):
    """``x`` [S, D] -> ``x + rm * mixer(norm1(x))``: the recurrence one
    position at a time. ``reset_every`` > 0 plants ``state_reset``."""
    s_len = x.shape[0]
    h, p, n, kw = m.ssm_heads, m.ssm_head_dim, m.ssm_state, m.ssm_conv
    u = _norm(x, lp["norm1"], m.norm_eps)
    zxbcdt = _mm("td,de->te", u, lp["w_in"], precision)
    z = zxbcdt[:, :m.d_inner]
    xbc = zxbcdt[:, m.d_inner:m.d_inner + m.d_conv_in]
    dt = zxbcdt[:, m.d_inner + m.d_conv_in:]
    # causal depthwise convolution, written out: tap k reads the
    # position K - 1 - k before
    padded = jnp.pad(xbc, ((kw - 1, 0), (0, 0)))
    conv = lp["conv_b"] + sum(lp["conv_w"][k] * padded[k:k + s_len]
                              for k in range(kw))
    xbc = jax.nn.silu(conv)
    xs = xbc[:, :m.d_inner].reshape(s_len, h, p)
    b_t = xbc[:, m.d_inner:m.d_inner + n]
    c_t = xbc[:, m.d_inner + n:]
    dt = jax.nn.softplus(dt + lp["dt_bias"])                 # [S, H]
    a = -jnp.exp(lp["a_log"])                                # [H]

    def step(state, inp):
        t, x_t, bt, ct, dt_t = inp
        if reset_every:
            state = jnp.where((t > 0) & (t % reset_every == 0), 0.0, state)
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * bt[None, None, :])
        y = jnp.sum(state * ct[None, None, :], axis=-1)
        return state, y + lp["d_skip"][:, None] * x_t

    _, y = jax.lax.scan(step, jnp.zeros((h, p, n), jnp.float32),
                        (jnp.arange(s_len), xs, b_t, c_t, dt), unroll=8)
    y = y.reshape(s_len, m.d_inner) * jax.nn.silu(z)
    y = _norm(y, lp["gate_norm"], m.norm_eps)
    return x + m.residual_multiplier * _mm("te,ed->td", y, lp["w_out"],
                                           precision)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _attention_mixer(lp, x, m: Model, precision: str, block: int):
    """``x`` [S, D] (``S`` a multiple of ``block``) -> ``x + rm *
    attention(norm1(x))``, a block of queries at a time."""
    s_len = x.shape[0]
    g = m.n_heads // m.n_kv_heads
    u = _norm(x, lp["norm1"], m.norm_eps)
    q = _mm("td,dhk->thk", u, lp["wq"], precision)
    k = _mm("td,dhk->thk", u, lp["wk"], precision)
    v = _mm("td,dhk->thk", u, lp["wv"], precision)
    if precision != "highest":
        # the configuration's cache rows are bfloat16
        k, v = (t.astype(jnp.bfloat16).astype(jnp.float32) for t in (k, v))
    # query head j reads K/V head j // g
    q = q.reshape(s_len // block, block, m.n_kv_heads, g, m.d_head)
    kpos = jnp.arange(s_len)

    def one_block(args):
        qb, q0 = args
        s = _mm("qhgd,khd->hgqk", qb, k, precision) * m.attention_multiplier
        seen = (q0 + jnp.arange(block))[:, None] >= kpos[None, :]
        p = jax.nn.softmax(jnp.where(seen[None, None], s, -1e30), axis=-1)
        return _mm("hgqk,khd->qhgd", p, v, precision)

    a = jax.lax.map(one_block, (q, jnp.arange(0, s_len, block)))
    a = a.reshape(s_len, m.n_heads, m.d_head)
    return x + m.residual_multiplier * _mm("thk,hkd->td", a, lp["wo"],
                                           precision)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _route(lp, h, m: Model, drop_least: bool):
    """``h`` [T, D] -> norm2(h), and the router's choice: the expert
    ids [T, top_k] and their weights, renormalised over the chosen;
    a routing to an expert this chip does not hold has weight zero.
    The router is float32 at every ``precision``."""
    u = _norm(h, lp["norm2"], m.norm_eps)
    logits = jnp.einsum("td,de->te", u, lp["router"],
                        precision=jax.lax.Precision.HIGHEST)
    top, idx = jax.lax.top_k(logits, m.top_k)
    w = jax.nn.softmax(top, axis=-1)
    held = jnp.zeros((m.n_experts,), bool).at[
        jnp.asarray(m.experts_held)].set(True)[idx]
    w = jnp.where(held, w, 0.0)
    if drop_least:
        least = jnp.argmin(jnp.where(held, w, jnp.inf), axis=-1)
        w = jnp.where(jnp.arange(m.top_k)[None] == least[:, None], 0.0, w)
    return u, idx, w


@functools.partial(jax.jit, static_argnums=(4,))
def _gated(w_in, w_out, u, weight, precision: str):
    """``weight * W_out (silu(g) * v)``, ``[g | v] = W_in u``."""
    gv = _mm("td,df->tf", u, w_in, precision)
    f = gv.shape[-1] // 2
    y = _mm("tf,fd->td", jax.nn.silu(gv[:, :f]) * gv[:, f:], w_out,
            precision)
    return weight[:, None] * y


def _experts(lp, h, m: Model, precision: str, drop_least: bool):
    """``h`` [T, D] -> ``h + rm * (routed + shared)``: an expert at a
    time over the tokens routed to it."""
    u, idx, w = _route(lp, h, m, drop_least)
    out = _gated(lp["w_in_s"], lp["w_out_s"], u,
                 jnp.ones((h.shape[0],), jnp.float32), precision)
    idx_h, w_h = np.asarray(idx), np.asarray(w)
    for local, e in enumerate(m.experts_held):
        hit = (idx_h == e) & (w_h > 0)
        rows = np.nonzero(hit.any(axis=-1))[0]
        if not len(rows):
            continue
        n = 1 << max(int(len(rows) - 1).bit_length(), 4)
        pad = np.zeros(n, np.int64)
        pad[:len(rows)] = rows
        weight = np.zeros(n, np.float32)
        weight[:len(rows)] = (w_h * hit).sum(axis=-1)[rows]
        y = _gated(lp["w_in_e"][local], lp["w_out_e"][local],
                   u[jnp.asarray(pad)], jnp.asarray(weight), precision)
        # padded rows carry weight zero: they add nothing to row 0
        out = out.at[jnp.asarray(pad)].add(y)
    return h + m.residual_multiplier * out


@functools.partial(jax.jit, static_argnums=(2, 3))
def _head(top, x, m: Model, precision: str):
    return _mm("td,vd->tv", _norm(x, top["final_norm"], m.norm_eps),
               top["embed"], precision) / m.logits_scaling


def _with_precision(precision: str):
    return jax.default_matmul_precision(
        "highest" if precision == "highest" else "default")


def _bucket(n: int, floor: int = 64) -> int:
    """The power of two a sequence is padded to (padding lies after the
    real positions, which a causal model never sees)."""
    return max(1 << int(n - 1).bit_length(), floor)


def served_logits(m: Model, seed: int, sequences: Sequence[np.ndarray],
                  rows: Sequence[slice], precision: str = "highest",
                  fault: Optional[str] = None, reset_every: int = 1024
                  ) -> List[np.ndarray]:
    """The full forward over each of ``sequences`` (token ids), and of
    each the float32 logits at ``rows``: a list of ``[len(rows[i]),
    vocab]`` arrays. Each layer's weights are made once and every
    sequence goes through them before the next layer is made."""
    if fault not in (None,) + FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    every = int(reset_every) if fault == "state_reset" else 0
    with _with_precision(precision):
        top = top_params(m, seed)
        xs = []
        for seq in sequences:
            ids = np.zeros(_bucket(len(seq)), np.int32)
            ids[:len(seq)] = seq
            xs.append(m.embedding_multiplier * top["embed"][jnp.asarray(ids)])
        ends = np.cumsum([x.shape[0] for x in xs])[:-1]
        for layer, kind in enumerate(m.layer_types):
            lp = layer_params(m, seed, layer)
            for i, x in enumerate(xs):
                if kind == "attention":
                    xs[i] = _attention_mixer(lp, x, m, precision,
                                             min(x.shape[0], 512))
                else:
                    xs[i] = _mamba_mixer(lp, x, m, precision, every)
            # the experts know no position: every sequence's tokens
            # go through an expert's weights together
            xs = jnp.split(_experts(lp, jnp.concatenate(xs), m, precision,
                                    fault == "expert_dropped"), ends)
            del lp
        return [np.asarray(_head(top, x[r], m, precision))
                for x, r in zip(xs, rows)]


def logits(m: Model, seed: int, tokens: np.ndarray,
           precision: str = "highest", fault: Optional[str] = None,
           reset_every: int = 1024) -> np.ndarray:
    """``[S, vocab]`` logits of one sequence's full forward."""
    return served_logits(m, seed, [tokens], [slice(0, len(tokens))],
                         precision, fault, reset_every)[0]


def uncut(m: Model) -> Model:
    """``m`` with every routed expert held: the layer the deployment's
    two chips compute together (for the test that the shares add up)."""
    return dataclasses.replace(m, experts_held=tuple(range(m.n_experts)))
