"""The plain reference of Kimi-K2's language model (`configs/kimi-k2.6.json`,
``kimi_k2``: DeepSeek-V3's layer): weights from a seed, one full forward
over a sequence, in straightforward ``jax.numpy``, float32 under
``default_matmul_precision("highest")``. It imports nothing of the
program and takes nothing the program made; no cache, no kernel, no
tiles.

The layers, as equations (what the source's ``config.json`` does not fix
is listed in the configuration file under ``assumed``):

    norm(x; g)  = x * rsqrt(mean(x^2) + 1e-5) * g          (rms_norm_eps)
    x0          = E[token]                    E: [vocab_slice, 7168]
    h           = x + mla(norm(x; g1))
    x'          = h + ffn(norm(h; g2))
    logits      = norm(x_L; g_f) W_head        W_head: [7168, vocab_slice]

  MLA (64 heads; d_nope 128, d_rope 64, d_v 128; q_lora 1536, kv_lora 512):
    c_q        = norm(W_DQ u; g_q)                 [1536]
    [q_nope_h | q_pe_h] = (W_UQ c_q)_h             [128 | 64] a head
    [c_kv' | k_pe']     = W_DKV u                  [512 | 64]
    c_kv       = norm(c_kv'; g_kv)
    k_pe       = rope(k_pe', pos)                  one for all heads
    q_pe_h     = rope(q_pe_h, pos)
    [k_nope_h | v_h] = (W_UKV c_kv)_h              [128 | 128] a head
    s_h(t)     = scale * (q_nope_h . k_nope_h(t) + q_pe_h . k_pe(t)), causal
    o_h        = sum_t softmax(s_h)(t) v_h(t)
    out        = W_O [o_1 .. o_64]
    scale      = (128 + 64) ** -0.5 * m ** 2,  m = 0.1 * mscale_all_dim *
                 ln(factor) + 1 = 1.41589

  YaRN rope over the d_rope lanes, base 50000, factor 64, orig 4096,
  beta_fast 32, beta_slow 1:
    f_extra(i) = base ** (-2 i / 64);  f_inter(i) = f_extra(i) / factor
    low  = floor(64 ln(4096 / (32 * 2 pi)) / (2 ln 50000)) = 8
    high = ceil(64 ln(4096 / (1 * 2 pi)) / (2 ln 50000))  = 20
    ramp(i) = clip((i - low) / (high - low), 0, 1)
    inv_freq(i) = f_inter(i) ramp(i) + f_extra(i) (1 - ramp(i))
    cos, sin times mscale(factor, mscale) / mscale(factor, mscale_all_dim)
    (= 1 here); the pairs (2 i, 2 i + 1) de-interleaved to (i, i + 32),
    then rotate_half: x cos + [-x_2 | x_1] sin

  FFN: layers below ``first_k_dense_replace``: W_2 (silu(W_1 u) * W_3 u),
  width 18432. Every later layer: s = sigmoid(W_r u) over ALL 384 experts
  (float32 at every precision); the top 8 of s + b are chosen (b the
  ``e_score_correction_bias``, used to choose only; n_group = topk_group
  = 1, so ``noaux_tc`` is the plain top 8); w = s[chosen] / (sum
  s[chosen] + 1e-20) * 2.827; out = sum over the chosen experts this chip
  HOLDS of w_e W_out,e (silu(W_g,e u) * W_u,e u) (width 2048) + the shared
  expert's product at width 2048 * n_shared_experts, unweighted. A
  routing to an expert that is not held contributes nothing, here as in
  the program (the deployment's other chips add it).

Departures from the published model: the weights are random
(:func:`layer_params`, :func:`expert_params`; the bias b is the same for
every seed, :func:`expert_bias`), the depth, the experts
held and the vocabulary are cut (``reduced`` in the configuration file),
and ``seq_aux`` (a training loss) is unused. The experts are computed an
expert at a time over the tokens routed to it, each expert's weights made
from the seed just before and dropped just after; the attention a block
of queries at a time against the whole sequence with the mask written
out. :func:`served_logits` takes several sequences through each layer
before the next layer is made, the FFN one sequence at a time.

``precision`` says how the matrix products are computed: ``"highest"``
float32 operands (the reference proper); ``"bfloat16"`` every matmul
operand rounded to bfloat16 with float32 accumulation, the latent rows
``c_kv`` and ``k_pe`` rounded to bfloat16 (what the configuration
states: the residual stream, the norms, the rotary, the softmax, the
router and the logits stay float32); ``"int8"`` as bfloat16 and every
operand first rounded to 8 bits, one scale a tensor (the control).

``fault`` plants a fault of a mechanism the limits are set against:
``"expert_bias_ignored"`` chooses the experts by ``s`` alone;
``"shared_expert_dropped"`` leaves the shared expert out.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from statistics import NormalDist
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

FAULTS = ("expert_bias_ignored", "shared_expert_dropped")


@dataclasses.dataclass(frozen=True)
class Model:
    """The sizes ``configs/kimi-k2.6.json`` states."""

    vocab: int
    d_model: int
    n_heads: int
    q_lora: int
    kv_lora: int
    d_nope: int
    d_rope: int
    d_v: int
    n_layers: int
    n_dense_layers: int
    d_dense: int
    #: the router's outputs: every routed expert of the model
    n_experts: int
    top_k: int
    experts_held: Tuple[int, ...]
    d_expert: int
    n_shared: int
    norm_topk: bool
    routed_scaling: float
    rope_theta: float
    rope_factor: float
    rope_orig: int
    beta_fast: float
    beta_slow: float
    mscale: float
    mscale_all_dim: float
    norm_eps: float
    embed_std: float
    #: the seeded ``e_score_correction_bias``'s std
    bias_std: float

    def is_dense(self, layer: int) -> bool:
        return layer < self.n_dense_layers

    @property
    def n_moe(self) -> int:
        return self.n_layers - min(self.n_dense_layers, self.n_layers)

    @property
    def top_k_held(self) -> float:
        """Held experts a token is routed to in a MoE layer under a
        uniform router."""
        return self.top_k * len(self.experts_held) / float(self.n_experts)

    @staticmethod
    def from_config(cfg: Dict[str, Any]) -> "Model":
        if cfg.get("model_type") != "kimi_k2":
            raise KeyError("model_type")
        rs, init = cfg["rope_scaling"], cfg["init"]
        held = tuple(range(*cfg["experts_held"]))
        if len(held) != int(cfg["n_routed_experts"]):
            raise ValueError("experts_held must name n_routed_experts ids")
        return Model(
            vocab=int(cfg["vocab_size"]), d_model=int(cfg["hidden_size"]),
            n_heads=int(cfg["num_attention_heads"]),
            q_lora=int(cfg["q_lora_rank"]), kv_lora=int(cfg["kv_lora_rank"]),
            d_nope=int(cfg["qk_nope_head_dim"]),
            d_rope=int(cfg["qk_rope_head_dim"]), d_v=int(cfg["v_head_dim"]),
            n_layers=int(cfg["num_hidden_layers"]),
            n_dense_layers=int(cfg["first_k_dense_replace"]),
            d_dense=int(cfg["intermediate_size"]),
            n_experts=int(cfg["n_routed_experts_published"]),
            top_k=int(cfg["num_experts_per_tok"]), experts_held=held,
            d_expert=int(cfg["moe_intermediate_size"]),
            n_shared=int(cfg["n_shared_experts"]),
            norm_topk=bool(cfg["norm_topk_prob"]),
            routed_scaling=float(cfg["routed_scaling_factor"]),
            rope_theta=float(cfg["rope_theta"]),
            rope_factor=float(rs["factor"]),
            rope_orig=int(rs["original_max_position_embeddings"]),
            beta_fast=float(rs["beta_fast"]),
            beta_slow=float(rs["beta_slow"]), mscale=float(rs["mscale"]),
            mscale_all_dim=float(rs["mscale_all_dim"]),
            norm_eps=float(cfg["rms_norm_eps"]),
            embed_std=float(init["embed_std"]),
            bias_std=float(init["expert_bias_std"]))


def program_hf(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration file as the model's own ``config.json`` would
    read for this chip's cut: the router's ``n_routed_experts`` is the
    published count (the file's counts the experts held here)."""
    return dict(cfg, n_routed_experts=int(cfg["n_routed_experts_published"]))


# ---------------------------------------------------------------------------
# the rotary and the scale


def yarn_get_mscale(scale: float, mscale: float = 1.0) -> float:
    if scale <= 1:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


def softmax_scale(m: Model) -> float:
    """``(d_nope + d_rope) ** -0.5``, times ``mscale ** 2`` where
    ``mscale_all_dim`` is set."""
    scale = (m.d_nope + m.d_rope) ** -0.5
    if m.mscale_all_dim:
        s = yarn_get_mscale(m.rope_factor, m.mscale_all_dim)
        scale = scale * s * s
    return scale


def inv_freq(m: Model):
    """YaRN's frequencies of the ``d_rope / 2`` pairs, float32."""
    d, base = m.d_rope, m.rope_theta
    i = jnp.arange(0, d, 2, dtype=jnp.float32)
    f_extra = 1.0 / base ** (i / d)
    f_inter = f_extra / m.rope_factor

    def corr(rot):
        return d * math.log(m.rope_orig / (rot * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(corr(m.beta_fast)), 0)
    high = min(math.ceil(corr(m.beta_slow)), d - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return f_inter * ramp + f_extra * (1.0 - ramp)


def rope(x, pos, m: Model):
    """``x`` [S, H, d_rope] at positions ``pos`` [S]: de-interleaved,
    then ``x cos + rotate_half(x) sin`` with the angles ``pos *
    inv_freq`` repeated over the two halves."""
    d = x.shape[-1]
    x = x.reshape(x.shape[:-1] + (d // 2, 2)).swapaxes(-1, -2).reshape(
        x.shape)
    ang = pos.astype(jnp.float32)[:, None] * inv_freq(m)[None]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    mag = yarn_get_mscale(m.rope_factor, m.mscale) / yarn_get_mscale(
        m.rope_factor, m.mscale_all_dim)
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * (jnp.cos(ang) * mag) + half * (jnp.sin(ang) * mag)


# ---------------------------------------------------------------------------
# weights: float32, from the seed, a layer (and an expert) at a time

#: leaves that stay float32 whatever the configuration's dtype
FLOAT32_LEAVES = ("norm1", "norm2", "final_norm", "q_a_norm", "kv_a_norm",
                  "router", "expert_bias")


def _key(seed: int):
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _matrix(key, shape, fan_in: int):
    """Normal with std ``fan_in ** -0.5``: a unit-rms input gives an
    output of rms one."""
    return fan_in ** -0.5 * jax.random.normal(key, shape, jnp.float32)


def _cast(tree: Dict[str, Any], dtype: str) -> Dict[str, Any]:
    return {k: v if k in FLOAT32_LEAVES else v.astype(dtype)
            for k, v in tree.items()}


def _layer_key(seed: int, layer: int):
    return jax.random.fold_in(_key(seed), layer + 2)


@functools.partial(jax.jit, static_argnums=(0, 1, 3))
def _layer_params(m: Model, dense: bool, key, dtype: str) -> Dict[str, Any]:
    """A layer's weights but its routed experts, in the published
    layout: ``wq_b`` [q_lora, H (d_nope + d_rope)] and ``wkv_b``
    [kv_lora, H (d_nope + d_v)] a head's columns together, ``wo`` [H d_v,
    D]."""
    d, h = m.d_model, m.n_heads
    ks = jax.random.split(key, 12)
    out = {"norm1": jnp.ones((d,), jnp.float32),
           "norm2": jnp.ones((d,), jnp.float32),
           "wq_a": _matrix(ks[5], (d, m.q_lora), d),
           "q_a_norm": jnp.ones((m.q_lora,), jnp.float32),
           "wq_b": _matrix(ks[6], (m.q_lora, h * (m.d_nope + m.d_rope)),
                           m.q_lora),
           "wkv_a": _matrix(ks[7], (d, m.kv_lora + m.d_rope), d),
           "kv_a_norm": jnp.ones((m.kv_lora,), jnp.float32),
           "wkv_b": _matrix(ks[9], (m.kv_lora, h * (m.d_nope + m.d_v)),
                            m.kv_lora),
           "wo": _matrix(ks[8], (h * m.d_v, d), h * m.d_v)}
    if dense:
        out.update(w_in_d=_matrix(ks[1], (d, 2 * m.d_dense), d),
                   w_out_d=_matrix(ks[2], (m.d_dense, d), m.d_dense))
    else:
        f = m.d_expert * m.n_shared
        out.update(router=_matrix(ks[0], (d, m.n_experts), d),
                   w_in_s=_matrix(ks[3], (d, 2 * f), d),
                   w_out_s=_matrix(ks[4], (f, d), f))
    return _cast(out, dtype)


def _expert(m: Model, key, e, dtype: str) -> Tuple[Any, Any]:
    """Expert ``e``'s two matrices (``[gate | up]``, down): ``e``'s
    whichever chip holds it."""
    ks = jax.random.split(key, 12)
    return (_matrix(jax.random.fold_in(ks[1], e),
                    (m.d_model, 2 * m.d_expert), m.d_model).astype(dtype),
            _matrix(jax.random.fold_in(ks[2], e), (m.d_expert, m.d_model),
                    m.d_expert).astype(dtype))


_expert_params = jax.jit(_expert, static_argnums=(0, 3))


@functools.partial(jax.jit, static_argnums=(0, 2))
def _held_experts(m: Model, key, dtype: str) -> Dict[str, Any]:
    """Every held expert of a layer, stacked (the program's layout)."""
    w_in, w_out = jax.vmap(lambda e: _expert(m, key, e, dtype))(
        jnp.asarray(m.experts_held))
    return {"w_in_e": w_in, "w_out_e": w_out}


@functools.partial(jax.jit, static_argnums=(0, 2))
def _top_params(m: Model, key, dtype: str) -> Dict[str, Any]:
    return _cast({"embed": m.embed_std * jax.random.normal(
                      jax.random.fold_in(key, 0), (m.vocab, m.d_model),
                      jnp.float32),
                  "head": _matrix(jax.random.fold_in(key, 1),
                                  (m.d_model, m.vocab), m.d_model),
                  "final_norm": jnp.ones((m.d_model,), jnp.float32)},
                 dtype)


def expert_bias(m: Model, layer: int) -> np.ndarray:
    """A MoE layer's ``e_score_correction_bias``, the same for every
    seed: the ``n_experts`` quantiles, at ``(i + 0.5) / n``, of a normal
    of std ``bias_std``, the held experts taking the middle one of each
    of ``len(experts_held)`` equal strata, the others the rest in an
    order fixed by the layer. So the held experts draw the uniform
    router's share of a token's choices on every seed (0.24 of 0.25,
    reckoned over unit-normal router logits at the cell's widths), where
    a bias drawn from the seed put 0.08 to 0.65 on them and a seed
    decided how much expert work a run did."""
    n, held = m.n_experts, np.asarray(m.experts_held)
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    stride = n / len(held)
    mid = np.array([int((j + 0.5) * stride) for j in range(len(held))])
    rest = np.random.default_rng(layer).permutation(
        np.setdiff1d(np.arange(n), mid))
    bias = np.empty(n)
    bias[held] = z[mid]
    bias[np.setdiff1d(np.arange(n), held)] = z[rest]
    return (m.bias_std * bias).astype(np.float32)


def layer_params(m: Model, seed: int, layer: int, dtype="float32"
                 ) -> Dict[str, Any]:
    """Layer ``layer``'s weights of ``seed`` but its routed experts."""
    out = _layer_params(m, m.is_dense(layer), _layer_key(seed, layer),
                        jnp.dtype(dtype).name)
    if not m.is_dense(layer):
        out = dict(out, expert_bias=jnp.asarray(expert_bias(m, layer)))
    return out


def expert_params(m: Model, seed: int, layer: int, e: int,
                  dtype="float32") -> Tuple[Any, Any]:
    """Expert ``e`` of layer ``layer``: ``(W_in [D, 2 F], W_out [F,
    D])``."""
    return _expert_params(m, _layer_key(seed, layer), jnp.int32(e),
                          jnp.dtype(dtype).name)


def top_params(m: Model, seed: int, dtype="float32") -> Dict[str, Any]:
    """The embedding, the head (untied) and the final norm of ``seed``."""
    return _top_params(m, _key(seed), jnp.dtype(dtype).name)


@functools.partial(jax.jit, static_argnums=(0,))
def _program_layout(m: Model, lp: Dict[str, Any]) -> Dict[str, Any]:
    """A layer's weights in the layout the program's entry points take:
    the query's up-projection a head at a time, ``W_UKV`` split into
    ``w_uk`` / ``w_uv`` [kv_lora, H, 128], the output projection [H,
    d_v, D]."""
    h = m.n_heads
    out = {k: v for k, v in lp.items() if k not in ("wq_b", "wkv_b", "wo")}
    kvb = lp["wkv_b"].reshape(m.kv_lora, h, m.d_nope + m.d_v)
    out.update(wq_b=lp["wq_b"].reshape(m.q_lora, h, m.d_nope + m.d_rope),
               w_uk=kvb[..., :m.d_nope], w_uv=kvb[..., m.d_nope:],
               wo=lp["wo"].reshape(h, m.d_v, m.d_model))
    return out


def make_params(m: Model, seed: int, dtype=jnp.float32) -> Dict[str, Any]:
    """All the weights of ``seed`` in the layout the program's entry
    points take (an input format, like the token ids): the float32
    values the reference uses, the matrices rounded to ``dtype``,
    :data:`FLOAT32_LEAVES` float32, a MoE layer's held experts stacked
    (``w_in_e`` [E, D, 2 F], ``w_out_e`` [E, F, D]), the latent layer's
    matrices as :func:`_program_layout` lays them."""
    name = jnp.dtype(dtype).name
    blocks = []
    for layer in range(m.n_layers):
        bp = _program_layout(m, layer_params(m, seed, layer, dtype))
        if not m.is_dense(layer):
            bp = dict(bp, **_held_experts(m, _layer_key(seed, layer), name))
        blocks.append(bp)
    return dict(top_params(m, seed, dtype), blocks=blocks)


def n_params(m: Model) -> int:
    """Parameters this chip holds."""
    d, h = m.d_model, m.n_heads
    mla = (d * m.q_lora + m.q_lora + m.q_lora * h * (m.d_nope + m.d_rope)
           + d * (m.kv_lora + m.d_rope) + m.kv_lora
           + m.kv_lora * h * (m.d_nope + m.d_v) + h * m.d_v * d)
    dense = 3 * d * m.d_dense
    moe = (d * m.n_experts + m.n_experts + 3 * d * m.d_expert * m.n_shared
           + len(m.experts_held) * 3 * d * m.d_expert)
    return 2 * m.vocab * d + d + sum(
        2 * d + mla + (dense if m.is_dense(layer) else moe)
        for layer in range(m.n_layers))


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
def _mla_mixer(lp, x, m: Model, precision: str, block: int):
    """``x`` [S, D] (``S`` a multiple of ``block``) -> ``x +
    mla(norm1(x))``, expanded, a block of queries at a time."""
    s_len, h = x.shape[0], m.n_heads
    u = _norm(x, lp["norm1"], m.norm_eps)
    pos = jnp.arange(s_len)
    c_q = _norm(_mm("td,dr->tr", u, lp["wq_a"], precision), lp["q_a_norm"],
                m.norm_eps)
    q = _mm("tr,rk->tk", c_q, lp["wq_b"], precision).reshape(
        s_len, h, m.d_nope + m.d_rope)
    q_nope, q_pe = q[..., :m.d_nope], rope(q[..., m.d_nope:], pos, m)
    kv = _mm("td,dc->tc", u, lp["wkv_a"], precision)
    c_kv = _norm(kv[:, :m.kv_lora], lp["kv_a_norm"], m.norm_eps)
    k_pe = rope(kv[:, None, m.kv_lora:], pos, m)[:, 0]
    if precision != "highest":
        # the configuration's cache rows are bfloat16
        c_kv, k_pe = (t.astype(jnp.bfloat16).astype(jnp.float32)
                      for t in (c_kv, k_pe))
    kvb = _mm("tc,ck->tk", c_kv, lp["wkv_b"], precision).reshape(
        s_len, h, m.d_nope + m.d_v)
    k_nope, v = kvb[..., :m.d_nope], kvb[..., m.d_nope:]
    scale = softmax_scale(m)
    qn = q_nope.reshape(s_len // block, block, h, m.d_nope)
    qp = q_pe.reshape(s_len // block, block, h, m.d_rope)

    def one_block(args):
        qn_b, qp_b, q0 = args
        s = (_mm("qhd,khd->hqk", qn_b, k_nope, precision)
             + _mm("qhd,kd->hqk", qp_b, k_pe, precision)) * scale
        seen = (q0 + jnp.arange(block))[:, None] >= pos[None, :]
        p = jax.nn.softmax(jnp.where(seen[None], s, -1e30), axis=-1)
        return _mm("hqk,khd->qhd", p, v, precision)

    o = jax.lax.map(one_block, (qn, qp, jnp.arange(0, s_len, block)))
    o = o.reshape(s_len, h * m.d_v)
    return x + _mm("tk,kd->td", o, lp["wo"], precision)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _route(lp, h, m: Model, ignore_bias: bool):
    """``h`` [T, D] -> norm2(h), and the router's choice: the expert
    ids [T, top_k] and their weights; a routing to an expert this chip
    does not hold has weight zero. The router is float32 at every
    ``precision``."""
    u = _norm(h, lp["norm2"], m.norm_eps)
    s = jax.nn.sigmoid(jnp.einsum("td,de->te", u, lp["router"],
                                  precision=jax.lax.Precision.HIGHEST))
    choose = s if ignore_bias else s + lp["expert_bias"]
    _, idx = jax.lax.top_k(choose, m.top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if m.norm_topk and m.top_k > 1:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * m.routed_scaling
    held = jnp.zeros((m.n_experts,), bool).at[
        jnp.asarray(m.experts_held)].set(True)[idx]
    return u, idx, jnp.where(held, w, 0.0)


@functools.partial(jax.jit, static_argnums=(4,))
def _gated(w_in, w_out, u, weight, precision: str):
    """``weight * W_out (silu(g) * v)``, ``[g | v] = W_in u``."""
    gv = _mm("td,df->tf", u, w_in, precision)
    f = gv.shape[-1] // 2
    y = _mm("tf,fd->td", jax.nn.silu(gv[:, :f]) * gv[:, f:], w_out,
            precision)
    return weight[:, None] * y


#: rows a dense or shared product takes at a time: the dense layer's
#: [rows, 2 x 18432] float32 intermediate stays near 1.2 GB
ROW_BLOCK = 8192


def _by_rows(w_in, w_out, u, precision: str):
    """``W_out (silu(g) * v)`` of every row of ``u``, ``ROW_BLOCK`` rows
    at a time."""
    ones = jnp.ones((min(u.shape[0], ROW_BLOCK),), jnp.float32)
    return jnp.concatenate([
        _gated(w_in, w_out, u[i:i + ROW_BLOCK], ones[:len(u[i:i + ROW_BLOCK])],
               precision) for i in range(0, u.shape[0], ROW_BLOCK)])


def _dense(lp, h, m: Model, precision: str):
    u = _norm(h, lp["norm2"], m.norm_eps)
    return h + _by_rows(lp["w_in_d"], lp["w_out_d"], u, precision)


def _experts(m: Model, seed: int, layer: int, lp, h, precision: str,
             fault: Optional[str]):
    """``h`` [T, D] -> ``h + routed + shared``: the routed experts an
    expert at a time over the tokens routed to it, its weights made just
    before."""
    u, idx, w = _route(lp, h, m, fault == "expert_bias_ignored")
    out = jnp.zeros_like(h)
    if fault != "shared_expert_dropped":
        out = _by_rows(lp["w_in_s"], lp["w_out_s"], u, precision)
    idx_h, w_h = np.asarray(idx), np.asarray(w)
    for e in m.experts_held:
        hit = (idx_h == e) & (w_h > 0)
        rows = np.nonzero(hit.any(axis=-1))[0]
        if not len(rows):
            continue
        n = 1 << max(int(len(rows) - 1).bit_length(), 4)
        pad = np.zeros(n, np.int64)
        pad[:len(rows)] = rows
        weight = np.zeros(n, np.float32)
        weight[:len(rows)] = (w_h * hit).sum(axis=-1)[rows]
        w_in, w_out = expert_params(m, seed, layer, e)
        y = _gated(w_in, w_out, u[jnp.asarray(pad)], jnp.asarray(weight),
                   precision)
        # padded rows carry weight zero: they add nothing to row 0
        out = out.at[jnp.asarray(pad)].add(y)
        del w_in, w_out
    return h + out


@functools.partial(jax.jit, static_argnums=(2, 3))
def _head(top, x, m: Model, precision: str):
    return _mm("td,dv->tv", _norm(x, top["final_norm"], m.norm_eps),
               top["head"], precision)


def _with_precision(precision: str):
    return jax.default_matmul_precision(
        "highest" if precision == "highest" else "default")


def _bucket(n: int, floor: int = 64) -> int:
    """The power of two a sequence is padded to (padding lies after the
    real positions, which a causal model never sees)."""
    return max(1 << int(n - 1).bit_length(), floor)


def served_logits(m: Model, seed: int, sequences: Sequence[np.ndarray],
                  rows: Sequence[slice], precision: str = "highest",
                  fault: Optional[str] = None, block: int = 256
                  ) -> List[np.ndarray]:
    """The full forward over each of ``sequences`` (token ids), and of
    each the float32 logits at ``rows``: a list of ``[len(rows[i]),
    vocab]`` arrays. Each layer's weights but its routed experts are
    made once and every sequence goes through them before the next
    layer is made; a routed expert's weights are made for each sequence
    that reaches it."""
    if fault not in (None,) + FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    with _with_precision(precision):
        top = top_params(m, seed)
        xs = []
        for seq in sequences:
            ids = np.zeros(_bucket(len(seq)), np.int32)
            ids[:len(seq)] = seq
            xs.append(top["embed"][jnp.asarray(ids)])
        for layer in range(m.n_layers):
            lp = layer_params(m, seed, layer)
            for i, x in enumerate(xs):
                h = _mla_mixer(lp, x, m, precision, min(x.shape[0], block))
                # the FFN a sequence at a time, so that its [rows, D]
                # float32 temporaries are one sequence's (all eight of a
                # check at once overran the chip's memory); an expert's
                # weights are made again for each sequence
                xs[i] = (_dense(lp, h, m, precision) if m.is_dense(layer)
                         else _experts(m, seed, layer, lp, h, precision,
                                       fault))
                del x, h
            del lp
        return [np.asarray(_head(top, x[r], m, precision))
                for x, r in zip(xs, rows)]


def logits(m: Model, seed: int, tokens: np.ndarray,
           precision: str = "highest", fault: Optional[str] = None
           ) -> np.ndarray:
    """``[S, vocab]`` logits of one sequence's full forward."""
    return served_logits(m, seed, [tokens], [slice(0, len(tokens))],
                         precision, fault)[0]


def routes(m: Model, seed: int, layer: int, h, ignore_bias: bool = False):
    """Layer ``layer``'s router over ``h`` [T, D]: ``(expert ids [T,
    top_k], weights [T, top_k])``, for the tests."""
    _, idx, w = _route(layer_params(m, seed, layer), h, m, ignore_bias)
    return np.asarray(idx), np.asarray(w)


def layer_forward(m: Model, seed: int, layer: int, x,
                  held: Optional[Sequence[int]] = None,
                  with_shared: bool = True, with_mixer: bool = True):
    """One layer over ``x`` [S, D] float32 for the tests: ``h = x +
    mla(norm1(x))`` (or ``x`` where not ``with_mixer``) then ``ffn(h)``
    alone (the residual left out), the routed part over the experts
    ``held`` (default this chip's) and the shared expert where
    ``with_shared``."""
    mm = m if held is None else dataclasses.replace(
        m, experts_held=tuple(held))
    lp = layer_params(m, seed, layer)
    x = jnp.asarray(x, jnp.float32)
    h = _mla_mixer(lp, x, m, "highest", x.shape[0]) if with_mixer else x
    if m.is_dense(layer):
        return _dense(lp, h, m, "highest") - h
    return _experts(mm, seed, layer, lp, h, "highest",
                    None if with_shared else "shared_expert_dropped") - h
