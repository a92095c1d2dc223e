"""Granite-hybrid: Mamba-2 layers beside a few grouped-query attention
layers, a routed mixture of gated experts with a shared expert behind
every mixer; and, as a configuration of the same block, LFM2's layer
(``lfm2_moe``): gated short convolutions beside grouped-query attention
with rotary and QK-norm, sigmoid-routed experts with no shared expert,
leading dense FFN layers; and Kimi-K2's (``kimi_k2``, DeepSeek-V3's
layer): multi-head latent attention in every layer over a paged latent
cache, a leading dense FFN, sigmoid-routed experts beside a shared one.

A third block kind beside ``models/transformer.py``'s softmax block and
``models/evabyte.py``'s EVA block (``GraniteHybridConfig.block_kind ==
"granite_hybrid"``; the serving plane picks its decoder from that,
``serving.decode.decoder_for``). The layers, as ``granitemoehybrid``'s
``config.json`` fixes them and ``benchmark/configs/granite-4.0-h-small
.json`` lists what is assumed beyond it (``rm`` the residual
multiplier; the equations in full are at the head of
``benchmark/reference_granite.py``):

* ``x0 = embedding_multiplier * E[token]``; every layer ``h = x + rm *
  mixer(norm1(x))``, ``x' = h + rm * (routed(norm2(h)) + shared(norm2
  (h)))``; logits ``norm_f(x_L) E^T / logits_scaling`` (tied); plain
  RMSNorm. ``layer_types`` says which mixer a layer has.
* **Mamba-2 mixer**: ``[z | xBC | dt] = W_in u``, a causal depthwise
  convolution of width ``ssm_conv`` and SiLU over ``xBC``, then per
  head ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t
  + D x_t`` (one group of ``B``/``C``), ``out = W_out norm_w(y *
  silu(z))``. A prompt's tile runs the recurrence in chunks of
  ``ssm_chunk`` (:func:`ssm_chunked`, the state-space-dual form); a
  decode step runs it once (:func:`ssm_step`).
* **Conv mixer** (``"conv"``, LFM2's): ``[B | C | x] = W_in u``, ``v =
  B * x``, a depthwise causal convolution of ``conv_width`` taps over
  ``v`` (no bias, no activation), ``out = W_out (C * conv(v))``
  (:func:`conv_tile`, :func:`conv_step`).
* **Attention layer**: ``n_heads`` query heads over ``n_kv_heads`` K/V
  heads, softmax scale ``attention_multiplier``; no positional encoding
  unless ``rope_theta`` (half-split rotary over the whole head), and
  with ``qk_norm`` a per-head RMSNorm of q and k before it.
* **Latent attention layer** (``"mla"``, Kimi-K2's): ``c_q =
  norm(W_DQ u)``, ``[q_nope | q_pe] = W_UQ c_q`` a head; ``[c_kv | k_pe]
  = W_DKV u``, the latent normed, ONE rotary key for all heads; YaRN
  over the rotary lanes (:func:`yarn_rope`); keys and values ``W_UK
  c_kv`` / ``W_UV c_kv`` a head. A tile expands the lane's latents so
  (:func:`_mla_attend`; under the Pallas kernels one call a layer,
  :func:`_mla_attend_kernel`); a step folds ``W_UK`` into the query and
  applies ``W_UV`` after the weighted sum of latents (:func:`mla_step`).
* **FFN**: the first ``n_dense_layers`` layers one dense gated product
  of width ``d_dense`` (:func:`dense_ffn`), every later layer the
  experts.
* **Experts** (:func:`moe`): the router scores ALL ``n_experts`` and
  keeps ``top_k`` a token (``router``: ``"softmax"`` over the top-k
  logits; ``"sigmoid"`` scores chosen with ``expert_bias`` added and
  weighted without it, :func:`sigmoid_route`), renormalised, plus a
  shared expert where ``d_shared``; this chip computes the part of
  the experts it HOLDS (``experts_held``), and a routing to an expert
  it does not hold contributes nothing here (in the deployment the
  chip that holds it adds it; on one chip the layer runs without that
  exchange and nothing stands in for it). No routing is dropped: a
  tile's routings are sorted by expert into one grouped product
  (``jax.lax.ragged_dot``, no capacity); a decode step weights each
  held expert's product by the router (zero where a slot was not
  routed), and under the Pallas kernels copies and multiplies only
  the experts some live slot was routed to
  (:func:`~mmlspark_tpu.parallel.pallas_moe.touched_experts`: 32 slots
  at LFM2's top-4 of 64 touch some 60% of them); the dense path runs
  every held expert over every slot (the masked form). Both are exact.

**Precision** (``cfg.dtype``, bfloat16 as served): weights, K/V rows and
matmul operands in ``dtype``; accumulation, the residual stream, the
recurrent state, the convolution, the router and the logits in float32.

**The cache has two parts** (:func:`init_cache`). A slot's *state*: per
Mamba layer ``ssm[n_slots, H, P, N]`` float32 and ``conv[n_slots,
ssm_conv - 1, H P + 2 N]`` float32, per conv layer ``tail[n_slots,
conv_width - 1, d_model]`` float32 (the last ``v`` rows), overwritten by
every step and by every tile, never appended to; a request's first tile
(``pos0 == 0``) starts from zeros whatever the slot held. *Rows a
position*: per attention layer one K and one V page pool ``[n_pages,
page_size, n_kv_heads, lanes]`` in ``dtype`` (page 0 the scratch page;
``lanes`` is ``d_head``, or ``d_head`` zero-padded to whole 128-lane
registers where the decoder asks for it so that ``paged_decode_attention``
walks the live entries); per ``"mla"`` layer one latent pool
``[n_pages, page_size, lanes]``, a row ``[c_kv | k_pe]`` a position.
Every array is a buffer of its own, donated through both programs:

``build_hybrid_prefill``  one tile of a prompt (``tile`` tokens at
    ``pos0``, ``length`` of them real): carries the slot's state and
    conv tail in and out, appends the tile's K/V rows to the slot's
    pages and attends over the lane so far. Padded positions leave the
    state as it was (``dt = 0``, no conv shift, no tail shift).
``build_hybrid_step``     one token for every slot.

``forward_logits`` is the plain forward (no cache).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_NEG_INF = -1e30
#: the gain of :func:`init_params`' query and key matrices
QK_GAIN = 4.0


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    vocab: int = 100352
    d_model: int = 4096
    n_heads: int = 32
    n_kv_heads: int = 8
    d_head: int = 128
    layer_types: Tuple[str, ...] = ("mamba",) * 5 + ("attention",) \
        + ("mamba",) * 4
    n_experts: int = 72
    top_k: int = 10
    #: which of the ``n_experts`` this chip holds
    experts_held: Tuple[int, ...] = tuple(range(72))
    d_expert: int = 768
    d_shared: int = 1536
    ssm_heads: int = 128
    ssm_head_dim: int = 64
    ssm_state: int = 128
    ssm_conv: int = 4
    ssm_chunk: int = 256
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.0078125
    logits_scaling: float = 16.0
    norm_eps: float = 1e-5
    #: random weights (:func:`init_params`): the embedding's std
    embed_std: float = 0.001
    dtype: str = "bfloat16"
    #: taps of a ``"conv"`` layer's depthwise causal convolution (LFM2's
    #: ``conv_L_cache``): a slot keeps ``conv_width - 1`` rows of ``v``
    conv_width: int = 3
    #: leading layers whose FFN is one dense gated product of width
    #: ``d_dense`` in place of the experts (LFM2's ``num_dense_layers``)
    n_dense_layers: int = 0
    d_dense: int = 0
    #: the router's rule: ``"softmax"`` over the top-k logits, or
    #: ``"sigmoid"`` scores (:func:`sigmoid_route`) chosen with the
    #: ``expert_bias`` parameter added where ``expert_bias``, weighted
    #: without it, renormalised (``norm_topk``) and scaled by
    #: ``routed_scaling``
    router: str = "softmax"
    expert_bias: bool = False
    norm_topk: bool = True
    routed_scaling: float = 1.0
    #: the attention layers' rotary base (0: no positional encoding)
    rope_theta: float = 0.0
    #: a per-head RMSNorm of q and k (``q_norm``/``k_norm``) before it
    qk_norm: bool = False
    #: an ``"mla"`` layer's widths (multi-head latent attention): the
    #: query's low rank, the latent's, a head's query/key lanes without
    #: and with the rotary, and its value lanes
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    d_nope: int = 0
    d_rope: int = 0
    d_v: int = 0
    #: YaRN's stretch of an ``"mla"`` layer's rotary (:func:`yarn_inv_freq`;
    #: ``rope_factor`` 1 is the plain rotary)
    rope_factor: float = 1.0
    rope_orig_len: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0
    #: ``"sigmoid"``'s normaliser: the chosen weights over their sum
    #: plus this
    route_eps: float = 1e-6
    #: the head is the embedding (tied), or a matrix of its own
    #: (``params["head"]`` [D, vocab])
    tied_head: bool = True

    #: what the serving plane reads to pick the decoder
    block_kind = "granite_hybrid"

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        object.__setattr__(self, "experts_held", tuple(self.experts_held))
        bad = set(self.layer_types) - {"mamba", "attention", "conv", "mla"}
        if bad:
            raise ValueError(f"unknown layer types {sorted(bad)}")
        if "mla" in self.layer_types and min(
                self.q_lora_rank, self.kv_lora_rank, self.d_nope,
                self.d_rope, self.d_v) < 1:
            raise ValueError("an mla layer needs q_lora_rank, kv_lora_rank,"
                             " d_nope, d_rope and d_v")
        if self.router not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown router {self.router!r}")
        if not 0 <= self.n_dense_layers <= self.n_layers or (
                self.n_dense_layers and self.d_dense < 1):
            raise ValueError("n_dense_layers needs a d_dense and at most "
                             "n_layers")
        if self.n_heads % self.n_kv_heads:
            raise ValueError(f"{self.n_heads} query heads over "
                             f"{self.n_kv_heads} K/V heads")
        held = self.experts_held
        if len(set(held)) != len(held) or not all(
                0 <= e < self.n_experts for e in held) or not held:
            raise ValueError("experts_held must be distinct ids in "
                             f"[0, {self.n_experts})")
        if self.top_k > self.n_experts:
            raise ValueError("top_k exceeds n_experts")

    @classmethod
    def from_hf(cls, hf: Dict[str, Any], experts_held: Sequence[int] = None,
                dtype: str = "bfloat16") -> "GraniteHybridConfig":
        """From a ``granitemoehybrid``, an ``lfm2_moe`` or a ``kimi_k2``
        ``config.json`` (its HF keys); ``experts_held`` defaults to all
        the routed experts."""
        if hf.get("model_type") == "lfm2_moe":
            return cls._from_lfm2(hf, experts_held, dtype)
        if hf.get("model_type") == "kimi_k2":
            return cls._from_kimi(hf, experts_held, dtype)
        if int(hf.get("mamba_n_groups", 1)) != 1:
            raise ValueError("one group of B and C only")
        n = int(hf["num_hidden_layers"])
        n_experts = int(hf["num_local_experts"])
        return cls(
            vocab=int(hf["vocab_size"]), d_model=int(hf["hidden_size"]),
            n_heads=int(hf["num_attention_heads"]),
            n_kv_heads=int(hf["num_key_value_heads"]),
            d_head=int(hf.get("head_dim") or int(hf["hidden_size"])
                       // int(hf["num_attention_heads"])),
            layer_types=tuple(hf["layer_types"][:n]), n_experts=n_experts,
            top_k=int(hf["num_experts_per_tok"]),
            experts_held=tuple(range(n_experts) if experts_held is None
                               else experts_held),
            d_expert=int(hf["intermediate_size"]),
            d_shared=int(hf["shared_intermediate_size"]),
            ssm_heads=int(hf["mamba_n_heads"]),
            ssm_head_dim=int(hf["mamba_d_head"]),
            ssm_state=int(hf["mamba_d_state"]),
            ssm_conv=int(hf["mamba_d_conv"]),
            ssm_chunk=int(hf["mamba_chunk_size"]),
            embedding_multiplier=float(hf["embedding_multiplier"]),
            residual_multiplier=float(hf["residual_multiplier"]),
            attention_multiplier=float(hf["attention_multiplier"]),
            logits_scaling=float(hf["logits_scaling"]),
            norm_eps=float(hf["rms_norm_eps"]), dtype=dtype)

    @classmethod
    def _from_lfm2(cls, hf: Dict[str, Any], experts_held, dtype: str
                   ) -> "GraniteHybridConfig":
        """LFM2's layer: no multipliers (one), ``head_dim ** -0.5``, the
        norm gains as they are, ``full_attention`` an attention layer."""
        n = int(hf["num_hidden_layers"])
        kinds = {"conv": "conv", "full_attention": "attention"}
        d_head = int(hf.get("head_dim") or int(hf["hidden_size"])
                     // int(hf["num_attention_heads"]))
        rope = hf.get("rope_parameters") or {}
        n_experts = int(hf["num_experts"])
        return cls(
            vocab=int(hf["vocab_size"]), d_model=int(hf["hidden_size"]),
            n_heads=int(hf["num_attention_heads"]),
            n_kv_heads=int(hf["num_key_value_heads"]), d_head=d_head,
            layer_types=tuple(kinds[k] for k in hf["layer_types"][:n]),
            n_experts=n_experts, top_k=int(hf["num_experts_per_tok"]),
            experts_held=tuple(range(n_experts) if experts_held is None
                               else experts_held),
            d_expert=int(hf["moe_intermediate_size"]), d_shared=0,
            conv_width=int(hf["conv_L_cache"]),
            n_dense_layers=min(int(hf["num_dense_layers"]), n),
            d_dense=int(hf["intermediate_size"]), router="sigmoid",
            expert_bias=bool(hf["use_expert_bias"]),
            norm_topk=bool(hf["norm_topk_prob"]),
            routed_scaling=float(hf["routed_scaling_factor"]),
            rope_theta=float(rope.get("rope_theta",
                                      hf.get("rope_theta", 0.0))),
            qk_norm=True, embedding_multiplier=1.0,
            residual_multiplier=1.0, attention_multiplier=d_head ** -0.5,
            logits_scaling=1.0, norm_eps=float(hf["norm_eps"]),
            dtype=dtype)

    @classmethod
    def _from_kimi(cls, hf: Dict[str, Any], experts_held, dtype: str
                   ) -> "GraniteHybridConfig":
        """DeepSeek-V3's layer as Kimi-K2 publishes it: multi-head latent
        attention in every layer (``"mla"``) with YaRN over the rotary
        lanes and the softmax scale times ``yarn_mscale(factor,
        mscale_all_dim) ** 2``; the first ``first_k_dense_replace`` FFNs
        dense, then sigmoid scores chosen with their
        ``e_score_correction_bias`` (``noaux_tc`` over one group: the
        top ``num_experts_per_tok`` of score + bias) beside
        ``n_shared_experts`` shared ones; no multipliers."""
        if (int(hf.get("n_group", 1)), int(hf.get("topk_group", 1))) \
                != (1, 1) or hf.get("topk_method") != "noaux_tc" \
                or hf.get("scoring_func") != "sigmoid" \
                or int(hf.get("moe_layer_freq", 1)) != 1:
            raise ValueError("one group of sigmoid-scored experts chosen "
                             "by noaux_tc, in every layer, only")
        rs = hf.get("rope_scaling") or {}
        if rs and rs.get("type", rs.get("rope_type")) != "yarn":
            raise ValueError("a YaRN rope_scaling, or none")
        n = int(hf["num_hidden_layers"])
        d_nope, d_rope = int(hf["qk_nope_head_dim"]), int(
            hf["qk_rope_head_dim"])
        factor = float(rs.get("factor", 1.0))
        m_all = float(rs.get("mscale_all_dim", 0.0))
        m = yarn_mscale(factor, m_all)
        n_experts = int(hf["n_routed_experts"])
        return cls(
            vocab=int(hf["vocab_size"]), d_model=int(hf["hidden_size"]),
            n_heads=int(hf["num_attention_heads"]),
            n_kv_heads=int(hf["num_attention_heads"]),
            d_head=int(hf["v_head_dim"]), layer_types=("mla",) * n,
            n_experts=n_experts, top_k=int(hf["num_experts_per_tok"]),
            experts_held=tuple(range(n_experts) if experts_held is None
                               else experts_held),
            d_expert=int(hf["moe_intermediate_size"]),
            d_shared=(int(hf["moe_intermediate_size"])
                      * int(hf.get("n_shared_experts") or 0)),
            n_dense_layers=min(int(hf["first_k_dense_replace"]), n),
            d_dense=int(hf["intermediate_size"]), router="sigmoid",
            expert_bias=True, norm_topk=bool(hf["norm_topk_prob"]),
            routed_scaling=float(hf["routed_scaling_factor"]),
            route_eps=1e-20, rope_theta=float(hf["rope_theta"]),
            q_lora_rank=int(hf["q_lora_rank"]),
            kv_lora_rank=int(hf["kv_lora_rank"]), d_nope=d_nope,
            d_rope=d_rope, d_v=int(hf["v_head_dim"]), rope_factor=factor,
            rope_orig_len=int(rs.get("original_max_position_embeddings",
                                     0)),
            beta_fast=float(rs.get("beta_fast", 32)),
            beta_slow=float(rs.get("beta_slow", 1)),
            yarn_mscale=float(rs.get("mscale", 1.0)),
            yarn_mscale_all_dim=m_all,
            tied_head=bool(hf.get("tie_word_embeddings", False)),
            embedding_multiplier=1.0, residual_multiplier=1.0,
            attention_multiplier=(d_nope + d_rope) ** -0.5 * m * m,
            logits_scaling=1.0, norm_eps=float(hf["rms_norm_eps"]),
            dtype=dtype)

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def latent_lanes(self) -> int:
        """Lanes of an ``"mla"`` layer's cache row: the normed latent,
        then the turned rotary key all heads share."""
        return self.kv_lora_rank + self.d_rope

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def d_conv_in(self) -> int:
        """Columns the convolution runs over: x, B and C."""
        return self.d_inner + 2 * self.ssm_state

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    def index_in_kind(self, layer: int) -> int:
        """Layer ``layer``'s index among the layers of its kind: where
        its part of the cache stands in :func:`init_cache`'s lists."""
        kind = self.layer_types[layer]
        return sum(k == kind for k in self.layer_types[:layer])


def init_params(cfg: GraniteHybridConfig, seed: int = 0) -> Dict[str, Any]:
    """Random weights. Every matrix is normal with std ``gain * fan_in
    ** -0.5`` in ``cfg.dtype`` (a unit-rms input gives an output of rms
    ``gain``): gain one, but :data:`QK_GAIN` for the query and key
    matrices (the published ``attention_multiplier`` of 1/128 leaves a
    unit-gain softmax uniform, and the K/V rows would then weigh
    nothing). The embedding
    is normal ``embed_std``, small enough that the layers' sum
    outweighs it in the residual stream: with the tied head a large
    embedding makes a position's best next token its own input token.
    Norm gains one; the convolution, ``dt_bias``, ``a_log``, ``d_skip``
    and the router float32; ``a_log = log U[1, 16]`` and ``dt_bias``
    the inverse softplus of a log-uniform ``dt`` in [0.001, 0.1] (the
    published Mamba-2 initialisation): a head's state lasts between a
    position and a thousand, so it neither dies at once nor grows. The
    layouts of LFM2's parts (``"conv"`` layers, dense FFN layers, QK
    norms, the expert bias, no shared expert) are
    ``benchmark/reference_lfm2.py``'s, whose weights the tests use."""
    dt_, d = cfg.compute_dtype, cfg.d_model
    e, h = len(cfg.experts_held), cfg.ssm_heads

    def matrix(key, shape, fan_in, gain=1.0, dtype=dt_):
        return ((gain * fan_in ** -0.5) * jax.random.normal(
            key, shape, jnp.float32)).astype(dtype)

    root = jax.random.PRNGKey(int(seed))
    blocks = []
    for layer, kind in enumerate(cfg.layer_types):
        ks = jax.random.split(jax.random.fold_in(root, layer + 2), 12)
        bp = {"norm1": jnp.ones((d,), jnp.float32),
              "norm2": jnp.ones((d,), jnp.float32)}
        if layer < cfg.n_dense_layers:
            bp.update(w_in_d=matrix(ks[1], (d, 2 * cfg.d_dense), d),
                      w_out_d=matrix(ks[2], (cfg.d_dense, d), cfg.d_dense))
        else:
            bp.update(
                router=matrix(ks[0], (d, cfg.n_experts), d,
                              dtype=jnp.float32),
                w_in_e=matrix(ks[1], (e, d, 2 * cfg.d_expert), d),
                w_out_e=matrix(ks[2], (e, cfg.d_expert, d), cfg.d_expert))
            if cfg.expert_bias:
                bp["expert_bias"] = jnp.zeros((cfg.n_experts,), jnp.float32)
            if cfg.d_shared:
                bp.update(
                    w_in_s=matrix(ks[3], (d, 2 * cfg.d_shared), d),
                    w_out_s=matrix(ks[4], (cfg.d_shared, d), cfg.d_shared))
        if kind == "attention":
            hd = cfg.n_heads * cfg.d_head
            bp.update(
                wq=matrix(ks[5], (d, cfg.n_heads, cfg.d_head), d, QK_GAIN),
                wk=matrix(ks[6], (d, cfg.n_kv_heads, cfg.d_head), d,
                          QK_GAIN),
                wv=matrix(ks[7], (d, cfg.n_kv_heads, cfg.d_head), d),
                wo=matrix(ks[8], (cfg.n_heads, cfg.d_head, d), hd))
            if cfg.qk_norm:
                bp.update(q_norm=jnp.ones((cfg.d_head,), jnp.float32),
                          k_norm=jnp.ones((cfg.d_head,), jnp.float32))
        elif kind == "mla":
            h_, r, rq = cfg.n_heads, cfg.kv_lora_rank, cfg.q_lora_rank
            bp.update(
                wq_a=matrix(ks[5], (d, rq), d),
                q_a_norm=jnp.ones((rq,), jnp.float32),
                wq_b=matrix(ks[6], (rq, h_, cfg.d_nope + cfg.d_rope), rq),
                wkv_a=matrix(ks[7], (d, cfg.latent_lanes), d),
                kv_a_norm=jnp.ones((r,), jnp.float32),
                w_uk=matrix(ks[9], (r, h_, cfg.d_nope), r),
                w_uv=matrix(ks[10], (r, h_, cfg.d_v), r),
                wo=matrix(ks[8], (h_, cfg.d_v, d), h_ * cfg.d_v))
        elif kind == "conv":
            bp.update(
                w_in=matrix(ks[5], (d, 3 * d), d),
                conv_w=matrix(ks[6], (cfg.conv_width, d), cfg.conv_width,
                              dtype=jnp.float32),
                w_out=matrix(ks[8], (d, d), d))
        else:
            dt0 = jnp.exp(jax.random.uniform(
                ks[9], (h,), jnp.float32, np.log(0.001), np.log(0.1)))
            bp.update(
                w_in=matrix(ks[5], (d, 2 * cfg.d_inner
                                    + 2 * cfg.ssm_state + h), d),
                conv_w=matrix(ks[6], (cfg.ssm_conv, cfg.d_conv_in),
                              cfg.ssm_conv, dtype=jnp.float32),
                conv_b=0.1 * jax.random.normal(ks[7], (cfg.d_conv_in,),
                                               jnp.float32),
                dt_bias=dt0 + jnp.log(-jnp.expm1(-dt0)),
                a_log=jnp.log(jax.random.uniform(
                    ks[10], (h,), jnp.float32, 1.0, 16.0)),
                d_skip=jnp.ones((h,), jnp.float32),
                gate_norm=jnp.ones((cfg.d_inner,), jnp.float32),
                w_out=matrix(ks[8], (cfg.d_inner, d), cfg.d_inner))
        blocks.append(bp)
    embed = cfg.embed_std * jax.random.normal(
        jax.random.fold_in(root, 0), (cfg.vocab, d), jnp.float32)
    params = {"embed": embed.astype(dt_),
              "final_norm": jnp.ones((d,), jnp.float32), "blocks": blocks}
    if not cfg.tied_head:
        params["head"] = matrix(jax.random.fold_in(root, 1),
                                (d, cfg.vocab), d)
    return params


# ---------------------------------------------------------------------------
# the layers' parts


def _norm(x, g, cfg: GraniteHybridConfig):
    """float32 in, float32 out."""
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + cfg.norm_eps) * g


def _mm(spec: str, a, b):
    return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)


def ssm_chunked(x, dt, a, b, c, state0, chunk: int, dtype=jnp.float32):
    """The recurrence ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t b_t^T``,
    ``y_t = S_t c_t`` over ``T`` positions from ``state0``, in chunks
    of ``chunk`` (the state-space-dual form): inside a chunk the output
    is one masked product over its positions, and only the chunks'
    ends are walked one after the other.

    ``x`` [T, H, P], ``dt`` [T, H] (zero at a padded position, which
    then neither decays nor feeds the state), ``a`` [H] (negative),
    ``b``/``c`` [T, N], ``state0`` [H, P, N] float32. Matrix products
    take operands in ``dtype`` and accumulate in float32; decays and
    the carried state are float32. ``T`` need not be a multiple of
    ``chunk``. Returns ``(y [T, H, P] float32, state [H, P, N])``."""
    t, h, p = x.shape
    n = b.shape[-1]
    pad = -t % chunk
    if pad:
        x, dt, b, c = (jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1))
                       for v in (x, dt, b, c))
    nc, q = (t + pad) // chunk, chunk
    dt = dt.astype(jnp.float32)
    # cs[c, i, h]: the log-decay from the chunk's start through i
    cs = jnp.cumsum((dt * a).reshape(nc, q, h), axis=1)
    xd = (x.astype(jnp.float32) * dt[..., None]).reshape(nc, q, h, p)
    bq = b.astype(dtype).reshape(nc, q, n)
    cq = c.astype(dtype).reshape(nc, q, n)
    # inside a chunk: y_i += sum_{j <= i} (c_i . b_j) e^{cs_i - cs_j} xd_j
    seen = jnp.tril(jnp.ones((q, q), bool))
    seg = cs[:, :, None, :] - cs[:, None, :, :]             # [nc, i, j, h]
    decay = jnp.exp(jnp.where(seen[None, :, :, None], seg, -jnp.inf))
    w = _mm("cin,cjn->cij", cq, bq)[..., None] * decay
    y = _mm("cijh,cjhp->cihp", w.astype(dtype), xd.astype(dtype))
    # what each chunk adds to the state at its end, and its decay
    to_end = jnp.exp(cs[:, -1:, :] - cs)                    # [nc, q, h]
    s_c = _mm("cjn,cjhp->chpn", bq,
              (xd * to_end[..., None]).astype(dtype))
    whole = jnp.exp(cs[:, -1, :])                           # [nc, h]

    def carry(run, inp):
        s, d = inp
        return d[:, None, None] * run + s, run

    state, before = jax.lax.scan(carry, state0.astype(jnp.float32),
                                 (s_c, whole))
    # the state a chunk starts from, seen from inside it
    y = y + (_mm("cin,chpn->cihp", cq, before.astype(dtype))
             * jnp.exp(cs)[..., None])
    return y.reshape(nc * q, h, p)[:t], state


def ssm_step(state, x, dt, a, b, c):
    """The recurrence once, for every slot: ``state`` [n, H, P, N]
    float32, ``x`` [n, H, P], ``dt`` [n, H], ``b``/``c`` [n, N], all
    float32. Returns ``(y [n, H, P], state)``: elementwise, so that the
    donated state is updated where it lies."""
    state = (jnp.exp(dt * a)[:, :, None, None] * state
             + (dt[..., None] * x)[..., None] * b[:, None, None, :])
    return jnp.sum(state * c[:, None, None, :], axis=-1), state


def _mamba_in(bp, u, cfg: GraniteHybridConfig):
    """``u`` [T, D] -> z [T, H P], xBC [T, H P + 2 N], dt [T, H]
    (before the bias and the softplus), float32."""
    with jax.named_scope("ssm.in_proj"):
        zxbcdt = _mm("td,de->te", u.astype(cfg.compute_dtype), bp["w_in"])
    di, dc = cfg.d_inner, cfg.d_conv_in
    return zxbcdt[:, :di], zxbcdt[:, di:di + dc], zxbcdt[:, di + dc:]


def _mamba_split(xbc, dt, bp, cfg: GraniteHybridConfig):
    """The convolved ``xBC`` and the raw ``dt`` -> x [T, H, P], B, C
    [T, N], dt [T, H] (positive), A [H] (negative)."""
    di, n = cfg.d_inner, cfg.ssm_state
    xbc = jax.nn.silu(xbc)
    x = xbc[:, :di].reshape(-1, cfg.ssm_heads, cfg.ssm_head_dim)
    return (x, xbc[:, di:di + n], xbc[:, di + n:],
            jax.nn.softplus(dt + bp["dt_bias"]), -jnp.exp(bp["a_log"]))


def _mamba_out(bp, y, x, z, cfg: GraniteHybridConfig):
    """``y`` [T, H, P] (the state's part) -> the mixer's output."""
    with jax.named_scope("ssm.gate_norm"):
        y = (y + bp["d_skip"][:, None] * x).reshape(-1, cfg.d_inner)
        y = _norm(y * jax.nn.silu(z), bp["gate_norm"], cfg)
    with jax.named_scope("ssm.out"):
        return _mm("te,ed->td", y.astype(cfg.compute_dtype), bp["w_out"])


def mamba_tile(bp, u, tail, state, length, cfg: GraniteHybridConfig):
    """The Mamba-2 mixer over a tile: ``u`` [T, D] float32 (normed),
    the ``ssm_conv - 1`` rows of ``xBC`` before it (``tail``) and the
    state it starts from; ``length`` of the ``T`` positions are real.
    Returns ``(out [T, D], tail, state)`` as the next tile needs them:
    the padded positions shift no row into the tail and leave the
    state bit for bit."""
    t, kw = u.shape[0], cfg.ssm_conv
    z, xbc, dt = _mamba_in(bp, u, cfg)
    with jax.named_scope("ssm.conv"):
        cat = jnp.concatenate([tail, xbc])                  # [K-1 + T, C]
        conv = bp["conv_b"] + sum(bp["conv_w"][k] * cat[k:k + t]
                                  for k in range(kw))
        tail = jax.lax.dynamic_slice_in_dim(cat, length, kw - 1)
    x, b, c, dt, a = _mamba_split(conv, dt, bp, cfg)
    dt = jnp.where((jnp.arange(t) < length)[:, None], dt, 0.0)
    with jax.named_scope("ssm.scan"):
        y, state = ssm_chunked(x, dt, a, b, c, state, cfg.ssm_chunk,
                               cfg.compute_dtype)
    return _mamba_out(bp, y, x, z, cfg), tail, state


def mamba_step(bp, u, tail, state, cfg: GraniteHybridConfig):
    """The mixer for one token of every slot: ``u`` [n, D], ``tail``
    [n, K - 1, C], ``state`` [n, H, P, N]."""
    z, xbc, dt = _mamba_in(bp, u, cfg)
    with jax.named_scope("ssm.conv"):
        cat = jnp.concatenate([tail, xbc[:, None]], axis=1)  # [n, K, C]
        conv = bp["conv_b"] + jnp.sum(bp["conv_w"][None] * cat, axis=1)
        tail = cat[:, 1:]
    x, b, c, dt, a = _mamba_split(conv, dt, bp, cfg)
    with jax.named_scope("ssm.scan"):
        y, state = ssm_step(state, x, dt, a, b, c)
    return _mamba_out(bp, y, x, z, cfg), tail, state


def _conv_in(bp, u, cfg: GraniteHybridConfig):
    """``u`` [T, D] -> ``v = B * x`` and ``C``, [T, D] float32 each
    (``[B | C | x] = W_in u``)."""
    d = cfg.d_model
    with jax.named_scope("conv.in"):
        bcx = _mm("td,de->te", u.astype(cfg.compute_dtype), bp["w_in"])
    return bcx[:, :d] * bcx[:, 2 * d:], bcx[:, d:2 * d]


def _conv_out(bp, y, cfg: GraniteHybridConfig):
    with jax.named_scope("conv.out"):
        return _mm("td,de->te", y.astype(cfg.compute_dtype), bp["w_out"])


def conv_tile(bp, u, tail, length, cfg: GraniteHybridConfig):
    """The gated short convolution over a tile: ``u`` [T, D] float32
    (normed), the ``conv_width - 1`` rows of ``v`` before it (``tail``);
    ``length`` of the ``T`` positions are real. Returns ``(out [T, D],
    tail)``: the padded positions shift no row into the tail."""
    t, kw = u.shape[0], cfg.conv_width
    v, c = _conv_in(bp, u, cfg)
    with jax.named_scope("conv.core"):
        cat = jnp.concatenate([tail, v])                    # [K-1 + T, D]
        y = c * sum(bp["conv_w"][k] * cat[k:k + t] for k in range(kw))
        tail = jax.lax.dynamic_slice_in_dim(cat, length, kw - 1)
    return _conv_out(bp, y, cfg), tail


def conv_step(bp, u, tail, cfg: GraniteHybridConfig):
    """The gated short convolution for one token of every slot: ``u``
    [n, D], ``tail`` [n, K - 1, D]."""
    v, c = _conv_in(bp, u, cfg)
    with jax.named_scope("conv.core"):
        cat = jnp.concatenate([tail, v[:, None]], axis=1)   # [n, K, D]
        y = c * jnp.sum(bp["conv_w"][None] * cat, axis=1)
    return _conv_out(bp, y, cfg), cat[:, 1:]


def _gated(u, w_in, w_out, dtype):
    gv = _mm("td,df->tf", u, w_in)
    f = gv.shape[-1] // 2
    return _mm("tf,fd->td",
               (jax.nn.silu(gv[:, :f]) * gv[:, f:]).astype(dtype), w_out)


def dense_ffn(bp, h, cfg: GraniteHybridConfig):
    """A leading dense layer's FFN: ``W_out (silu(g) * v)``, ``[g | v]
    = W_in norm2(h)``, width ``d_dense``."""
    u = _norm(h, bp["norm2"], cfg).astype(cfg.compute_dtype)
    with jax.named_scope("ffn.dense"):
        return _gated(u, bp["w_in_d"], bp["w_out_d"], cfg.compute_dtype)


def sigmoid_route(logits, bias, cfg: GraniteHybridConfig):
    """LFM2's and DeepSeek-V3's router: ``s = sigmoid(logits)``; the
    ``top_k`` of ``s + bias`` (``bias`` None: of ``s``) are chosen and
    weighted by their UNBIASED ``s``, over their sum + ``route_eps``
    (LFM2's 1e-6, DeepSeek-V3's 1e-20) where ``norm_topk``, times
    ``routed_scaling``. Returns ``(idx, w)`` [T, top_k]."""
    s = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(s if bias is None else s + bias, cfg.top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg.norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + cfg.route_eps)
    return idx, w * cfg.routed_scaling


def masked_experts(u, weight, w_in_e, w_out_e):
    """Every held expert over every token, each product weighted by the
    router's ``weight`` [T, E] (zero where a token was not routed): the
    masked form of a decode step's experts. ``u`` [T, D] in the weights'
    dtype; float32 [T, D]."""
    f = w_out_e.shape[1]
    gv = _mm("td,edf->etf", u, w_in_e)
    act = jax.nn.silu(gv[..., :f]) * gv[..., f:]
    return _mm("etf,efd->td", (act * weight.T[:, :, None]).astype(u.dtype),
               w_out_e)


def moe(bp, h, cfg: GraniteHybridConfig, grouped: bool, live=None,
        impl: str = "dense"):
    """The expert layer's share on this chip: ``h`` [T, D] float32 ->
    ``(routed + shared [T, D], routings int32[len(experts_held)],
    touched int32)``: the router over all ``n_experts``, ``top_k`` a
    token renormalised (``cfg.router``), the held experts' gated
    products weighted by the router and summed, the shared expert once
    (where ``d_shared``). A routing to an
    expert that is not held, or of a token that is not ``live`` (bool
    [T]: padding, a free slot), computes and counts nothing.
    ``routings`` are the routings each held expert received, ``touched``
    how many of them received any.

    ``grouped`` sorts the routings by expert into one grouped product
    (a prompt's tile). Otherwise (a decode step's few tokens) each
    expert's product is weighted by the router, zero where it was not
    routed: under ``impl`` ``"pallas"`` (``"pallas_interpret"`` on the
    CPU) :func:`~mmlspark_tpu.parallel.pallas_moe.touched_experts` copies
    and multiplies only the ``touched`` experts, listed first; under
    ``"dense"`` every held expert runs over every token (the masked
    form). All are exact: no routing is dropped."""
    dt_, f = cfg.compute_dtype, cfg.d_expert
    e = len(cfg.experts_held)
    u32 = _norm(h, bp["norm2"], cfg)
    u = u32.astype(dt_)
    with jax.named_scope("moe.route"):
        logits = jnp.einsum("td,de->te", u32, bp["router"],
                            precision=jax.lax.Precision.HIGHEST)
        if cfg.router == "softmax":
            top, idx = jax.lax.top_k(logits, cfg.top_k)
            w = jax.nn.softmax(top, axis=-1)
        else:
            idx, w = sigmoid_route(
                logits, bp["expert_bias"] if cfg.expert_bias else None, cfg)
        # a held expert's place among the held; ``e`` for one that is not
        place = np.full(cfg.n_experts, e, np.int32)
        place[list(cfg.experts_held)] = np.arange(e)
        local = jnp.asarray(place)[idx]                     # [T, k]
        if live is not None:
            local = jnp.where(live[:, None], local, e)
        w = jnp.where(local < e, w, 0.0)
        routings = jnp.zeros((e + 1,), jnp.int32).at[
            local.reshape(-1)].add(1)[:e]
        touched = jnp.sum(routings > 0).astype(jnp.int32)
    if cfg.d_shared:
        with jax.named_scope("moe.shared"):
            shared = _gated(u, bp["w_in_s"], bp["w_out_s"], dt_)
    with jax.named_scope("moe.experts"):
        if grouped:
            order = jnp.argsort(local.reshape(-1))      # absent go last
            tok = order // cfg.top_k
            gv = jax.lax.ragged_dot(u[tok], bp["w_in_e"], routings,
                                    preferred_element_type=jnp.float32)
            act = (jax.nn.silu(gv[:, :f]) * gv[:, f:]).astype(dt_)
            y = jax.lax.ragged_dot(act, bp["w_out_e"], routings,
                                   preferred_element_type=jnp.float32)
            # back in token order by a gather, then a token's top-k
            # summed: a scatter-add of the rows took Kimi-K2's 512-token
            # tile ~7 of its 33 ms on the v5e. Rows past the last group
            # belong to no expert (weight 0)
            y = y[jnp.argsort(order)].reshape(h.shape[0], cfg.top_k, -1)
            routed = jnp.sum(jnp.where(w[..., None] > 0, y * w[..., None],
                                       0.0), axis=1)
        else:
            weight = jnp.zeros((h.shape[0], e + 1), jnp.float32).at[
                jnp.arange(h.shape[0])[:, None], local].add(w)[:, :e]
            if impl == "dense":
                routed = masked_experts(u, weight, bp["w_in_e"],
                                        bp["w_out_e"])
            else:
                from mmlspark_tpu.parallel.pallas_moe import touched_experts
                # the touched experts first, in order of place
                ids = jnp.argsort(routings == 0, stable=True)
                routed = touched_experts(
                    u, weight, bp["w_in_e"], bp["w_out_e"], ids, touched,
                    interpret=impl == "pallas_interpret")
    if cfg.d_shared:
        routed = routed + shared
    return routed, routings, touched


def attention_dense(q, k, v, q_pos, scale: float):
    """Grouped-query causal attention in plain XLA: ``q`` [S, H, Dh] at
    positions ``q_pos`` [S] over ``k``/``v`` [L, H_kv, Dh] at positions
    ``arange(L)``; query head j reads K/V head ``j // (H / H_kv)``, and
    K/V are not repeated. Returns float32 [S, H, Dh]."""
    s_len, h, dh = q.shape
    h_kv = k.shape[1]
    qg = q.reshape(s_len, h_kv, h // h_kv, dh)
    s = _mm("qhgd,khd->hgqk", qg, k) * scale
    seen = q_pos[:, None] >= jnp.arange(k.shape[0])[None, :]
    p = jax.nn.softmax(jnp.where(seen[None, None], s, _NEG_INF), axis=-1)
    return _mm("hgqk,khd->qhgd", p.astype(v.dtype), v).reshape(s_len, h, dh)


def rope(x, pos, theta: float):
    """Half-split rotary over the whole head: ``x`` [T, H, Dh] float32
    at positions ``pos`` [T]; pair ``(i, i + Dh / 2)`` turns by ``pos *
    theta ** (-2 i / Dh)``."""
    dh = x.shape[-1]
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = pos.astype(jnp.float32)[:, None] * inv[None]      # [T, Dh / 2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's magnitude: ``0.1 mscale ln(factor) + 1``, and 1 where the
    rotary is not stretched."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(cfg: GraniteHybridConfig) -> np.ndarray:
    """An ``"mla"`` layer's ``d_rope / 2`` rotary frequencies (float32,
    a constant of the program): ``f_extra(i) = rope_theta ** (-2 i /
    d_rope)`` for the pairs that turn fast, ``f_extra / rope_factor``
    for those that turn slowly, and a linear ramp between pair ``low``
    and pair ``high``, the pairs that turn ``beta_fast`` and
    ``beta_slow`` times over ``rope_orig_len`` positions (floored and
    ceiled, clamped to ``[0, d_rope - 1]``)."""
    d, base = cfg.d_rope, cfg.rope_theta
    extra = base ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    if cfg.rope_factor <= 1:
        return extra.astype(np.float32)

    def pair(turns):
        return d * math.log(cfg.rope_orig_len / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(pair(cfg.beta_fast)), 0)
    high = min(math.ceil(pair(cfg.beta_slow)), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3),
                   0.0, 1.0)
    return (extra / cfg.rope_factor * ramp + extra * (1.0 - ramp)
            ).astype(np.float32)


def yarn_rope(x, pos, cfg: GraniteHybridConfig):
    """``x`` [T, ..., d_rope] float32 at positions ``pos`` [T], turned as
    DeepSeek-V3's published code turns it: the lanes de-interleaved
    (pair ``(2 i, 2 i + 1)`` to ``(i, i + d_rope / 2)``), then the
    half-split rotary at :func:`yarn_inv_freq`, cos and sin times
    ``yarn_mscale(factor, mscale) / yarn_mscale(factor,
    mscale_all_dim)``. The result stays de-interleaved, queries and keys
    alike, so that every score is the published one."""
    d = x.shape[-1]
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(yarn_inv_freq(cfg))
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + (d // 2,))
    mag = yarn_mscale(cfg.rope_factor, cfg.yarn_mscale) / yarn_mscale(
        cfg.rope_factor, cfg.yarn_mscale_all_dim)
    cos, sin = mag * jnp.cos(ang), mag * jnp.sin(ang)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mla_q(bp, u, pos, cfg: GraniteHybridConfig):
    """``u`` [T, D] at ``pos`` [T] -> the queries, float32: ``q_nope``
    [T, H, d_nope] and the turned ``q_pe`` [T, H, d_rope] (``c_q =
    norm(W_DQ u)``, ``[q_nope | q_pe] = W_UQ c_q`` a head)."""
    dt_ = cfg.compute_dtype
    with jax.named_scope("mla.q"):
        c_q = _norm(_mm("td,dr->tr", u.astype(dt_), bp["wq_a"]),
                    bp["q_a_norm"], cfg)
        q = _mm("tr,rhk->thk", c_q.astype(dt_), bp["wq_b"])
        return q[..., :cfg.d_nope], yarn_rope(q[..., cfg.d_nope:], pos, cfg)


def _mla_rows(bp, u, pos, cfg: GraniteHybridConfig):
    """``u`` [T, D] at ``pos`` [T] -> its cache rows [T, latent_lanes]
    in ``dtype``: ``[norm(c_kv) | rope(k_pe)]``, ``[c_kv | k_pe] =
    W_DKV u``; one rotary key for all the heads."""
    dt_, r = cfg.compute_dtype, cfg.kv_lora_rank
    with jax.named_scope("mla.q"):
        kv = _mm("td,dc->tc", u.astype(dt_), bp["wkv_a"])
        return jnp.concatenate(
            [_norm(kv[:, :r], bp["kv_a_norm"], cfg),
             yarn_rope(kv[:, r:], pos, cfg)], -1).astype(dt_)


def _mla_out(bp, a, cfg: GraniteHybridConfig):
    with jax.named_scope("mla.out"):
        return _mm("thv,hvd->td", a.astype(cfg.compute_dtype), bp["wo"])


def _mla_chunk(bp, q_nope, q_pe, lat, q_pos, k0, carry,
               cfg: GraniteHybridConfig):
    """One chunk of cache rows ``lat`` [S, lanes] (positions
    ``k0 + arange(S)``) into the online softmax ``carry = (m, l, acc)``
    ([H, T], [H, T], [H, T, d_v] float32) of the queries ``q_nope`` /
    ``q_pe`` [T, H, .] in ``dtype`` at ``q_pos`` [T], causally: the
    chunk's latents up through ``W_UK`` / ``W_UV`` to per-head keys and
    values first (the expanded form)."""
    dt_, r = cfg.compute_dtype, cfg.kv_lora_rank
    with jax.named_scope("mla.expand"):
        k_nope = _mm("sc,chk->shk", lat[:, :r], bp["w_uk"]).astype(dt_)
        v = _mm("sc,chv->shv", lat[:, :r], bp["w_uv"]).astype(dt_)
    with jax.named_scope("mla.latent_attn"):
        m, l, acc = carry
        s = (_mm("thk,shk->hts", q_nope, k_nope)
             + _mm("thk,sk->hts", q_pe, lat[:, r:r + cfg.d_rope])
             ) * cfg.attention_multiplier
        seen = q_pos[:, None] >= k0 + jnp.arange(lat.shape[0])[None, :]
        s = jnp.where(seen[None], s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        return (m_new, l * alpha + jnp.sum(p, axis=-1),
                acc * alpha[..., None]
                + _mm("hts,shv->htv", p.astype(dt_), v))


def _mla_attend(bp, u, q_pos, chunk_rows, n_chunks,
                cfg: GraniteHybridConfig):
    """The expanded latent attention of ``u`` [T, D] at ``q_pos`` over
    the first ``n_chunks`` chunks of ``T`` cache rows, chunk ``j``'s
    rows [T, lanes] (positions ``j T + arange(T)``) read by
    ``chunk_rows(j)`` when the chunk is reached (``n_chunks`` a traced
    count: the chunks past the last query's position are not looked
    at) -> the layer's output [T, D]."""
    t, h, dt_ = u.shape[0], cfg.n_heads, cfg.compute_dtype
    q_nope, q_pe = _mla_q(bp, u, q_pos, cfg)
    q_nope, q_pe = q_nope.astype(dt_), q_pe.astype(dt_)

    def chunk(j, carry):
        return _mla_chunk(bp, q_nope, q_pe, chunk_rows(j), q_pos, j * t,
                          carry, cfg)

    f32 = jnp.float32
    _, l, acc = jax.lax.fori_loop(
        0, n_chunks, chunk, (jnp.full((h, t), _NEG_INF, f32),
                             jnp.zeros((h, t), f32),
                             jnp.zeros((h, t, cfg.d_v), f32)))
    return _mla_out(bp, (acc / l[..., None]).transpose(1, 0, 2), cfg)


def _mla_attend_kernel(bp, u, q_pos, lane, n_chunks,
                       cfg: GraniteHybridConfig, interpret: bool):
    """:func:`_mla_attend` under the Pallas kernels: ``lane`` [S, lanes]
    is the slot's whole lane of cache rows in position order, and
    :func:`~mmlspark_tpu.parallel.pallas_mla.latent_tile_attention`
    expands and scores its first ``n_chunks`` chunks of ``T`` rows in
    one call, the scores and probabilities kept on chip (the XLA form
    writes and reads back four [H, T, T] float32 arrays a chunk)."""
    from mmlspark_tpu.parallel.pallas_mla import latent_tile_attention
    dt_ = cfg.compute_dtype
    q_nope, q_pe = _mla_q(bp, u, q_pos, cfg)
    with jax.named_scope("mla.latent_attn"):
        a = latent_tile_attention(
            q_nope.astype(dt_).transpose(1, 0, 2),
            q_pe.astype(dt_).transpose(1, 0, 2), lane,
            bp["w_uk"].astype(dt_).transpose(1, 0, 2),
            bp["w_uv"].astype(dt_).transpose(1, 0, 2), n_chunks,
            scale=cfg.attention_multiplier, interpret=interpret)
    return _mla_out(bp, a.transpose(1, 0, 2), cfg)


def mla_step(bp, u, pos, pool, page, row, page_tables,
             cfg: GraniteHybridConfig, page_size: int, impl: str):
    """The latent attention for one token of every slot, absorbed:
    ``W_UK`` folded into the query (``q_lat = W_UK^T q_nope``, [n, H,
    kv_lora_rank]), the token's row written to row ``row`` of page
    ``page``, the query ``[q_lat | q_pe]`` scored against the slot's
    latent rows as they lie, and ``W_UV`` applied after the weighted sum
    of latents. A pool of more lanes than ``latent_lanes`` holds the row
    zero-padded, and the query alike. Returns ``(out [n, D], pool)``."""
    dt_, r = cfg.compute_dtype, cfg.kv_lora_rank
    q_nope, q_pe = _mla_q(bp, u, pos, cfg)
    rows = _mla_rows(bp, u, pos, cfg)
    lanes = pool.shape[-1]
    with jax.named_scope("mla.q"):
        q = _lanes(jnp.concatenate(
            [_mm("thk,chk->thc", q_nope.astype(dt_), bp["w_uk"]), q_pe],
            -1).astype(dt_), lanes)
    with jax.named_scope("mla.latent_write"):
        pool = pool.at[page, row].set(_lanes(rows, lanes))
    from mmlspark_tpu.parallel import pallas_mla
    with jax.named_scope("mla.latent_attn"):
        if impl == "dense":
            o = pallas_mla.latent_decode_attention_dense(
                q, pool, page_tables, pos, cfg.attention_multiplier, r)
        else:
            o = pallas_mla.paged_latent_decode_attention(
                q, pool, page_tables, pos, cfg.attention_multiplier,
                page_size, r, interpret=impl == "pallas_interpret")
    with jax.named_scope("mla.out"):
        a = _mm("thc,chv->thv", o.astype(dt_), bp["w_uv"])
    return _mla_out(bp, a, cfg), pool


def _qkv(bp, u, cfg: GraniteHybridConfig, pos=None):
    """q, k, v [T, H(_kv), Dh] in ``dtype``; with ``qk_norm`` / a
    ``rope_theta``, q and k are normed / turned (at ``pos`` [T]) in
    float32 first."""
    dt_ = cfg.compute_dtype
    u = u.astype(dt_)
    with jax.named_scope("attn.qkv"):
        if not (cfg.qk_norm or cfg.rope_theta):
            return (_mm("td,dhk->thk", u, bp["wq"]).astype(dt_),
                    _mm("td,dhk->thk", u, bp["wk"]).astype(dt_),
                    _mm("td,dhk->thk", u, bp["wv"]).astype(dt_))
        q = _mm("td,dhk->thk", u, bp["wq"])
        k = _mm("td,dhk->thk", u, bp["wk"])
        if cfg.qk_norm:
            with jax.named_scope("attn.qknorm"):
                q, k = _norm(q, bp["q_norm"], cfg), _norm(k, bp["k_norm"], cfg)
        if cfg.rope_theta:
            with jax.named_scope("attn.rope"):
                q, k = rope(q, pos, cfg.rope_theta), rope(k, pos,
                                                          cfg.rope_theta)
        return (q.astype(dt_), k.astype(dt_),
                _mm("td,dhk->thk", u, bp["wv"]).astype(dt_))


def _lanes(x, width: int):
    """Head vectors ``x`` [..., Dh] zero-padded to a pool's ``width``
    lanes (as they are where the pool keeps ``Dh``)."""
    pad = width - x.shape[-1]
    return x if not pad else jnp.pad(x, [(0, 0)] * (x.ndim - 1)
                                     + [(0, pad)])


def _attn_out(bp, a, cfg: GraniteHybridConfig):
    with jax.named_scope("attn.out"):
        return _mm("thk,hkd->td", a.astype(cfg.compute_dtype), bp["wo"])


def _embed(params, tokens, cfg: GraniteHybridConfig):
    with jax.named_scope("embed"):
        return cfg.embedding_multiplier * params["embed"][tokens].astype(
            jnp.float32)


def _head(params, x, cfg: GraniteHybridConfig):
    """``x`` [T, D] float32 -> float32 logits [T, vocab] (the embedding
    where ``tied_head``, else ``params["head"]``)."""
    h = _norm(x, params["final_norm"], cfg).astype(cfg.compute_dtype)
    with jax.named_scope("head"):
        if not cfg.tied_head:
            return _mm("td,dv->tv", h, params["head"]) / cfg.logits_scaling
        return _mm("td,vd->tv", h, params["embed"]) / cfg.logits_scaling


# ---------------------------------------------------------------------------
# the plain forward: no cache


def forward_logits(params, tokens, cfg: GraniteHybridConfig):
    """``tokens`` [S] -> float32 logits [S, vocab] of the whole
    sequence: every Mamba and conv layer from a zero state, the
    attention dense over the sequence's own rows (an ``"mla"`` layer's
    expanded, in one chunk)."""
    s_len = tokens.shape[0]
    rm = cfg.residual_multiplier
    x = _embed(params, tokens, cfg)
    tail = jnp.zeros((cfg.ssm_conv - 1, cfg.d_conv_in), jnp.float32)
    state = jnp.zeros((cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
                      jnp.float32)
    for layer, (kind, bp) in enumerate(zip(cfg.layer_types,
                                           params["blocks"])):
        u = _norm(x, bp["norm1"], cfg)
        if kind == "attention":
            q, k, v = _qkv(bp, u, cfg, jnp.arange(s_len))
            with jax.named_scope("attn.core"):
                a = attention_dense(q, k, v, jnp.arange(s_len),
                                    cfg.attention_multiplier)
            mixed = _attn_out(bp, a, cfg)
        elif kind == "mla":
            rows = _mla_rows(bp, u, jnp.arange(s_len), cfg)
            mixed = _mla_attend(bp, u, jnp.arange(s_len), lambda j: rows,
                                1, cfg)
        elif kind == "conv":
            mixed, _ = conv_tile(bp, u, jnp.zeros(
                (cfg.conv_width - 1, cfg.d_model), jnp.float32), s_len, cfg)
        else:
            mixed, _, _ = mamba_tile(bp, u, tail, state, s_len, cfg)
        x = x + rm * mixed
        x = x + rm * _ffn(bp, layer, x, cfg, grouped=True)[0]
    return _head(params, x, cfg)


def _ffn(bp, layer: int, h, cfg: GraniteHybridConfig, grouped: bool,
         live=None, impl: str = "dense"):
    """Layer ``layer``'s FFN: :func:`dense_ffn` for a leading dense
    layer (no routings: ``None``), else :func:`moe`."""
    if layer < cfg.n_dense_layers:
        return dense_ffn(bp, h, cfg), None, None
    return moe(bp, h, cfg, grouped=grouped, live=live, impl=impl)


# ---------------------------------------------------------------------------
# the cache and the two programs over it


def init_cache(cfg: GraniteHybridConfig, n_slots: int, n_pages: int,
               page_size: int, head_lanes: int = None,
               latent_lanes: int = None) -> Dict[str, List[jax.Array]]:
    """``{"ssm", "conv"}``: one array a Mamba layer, a slot's recurrent
    state and the rows its convolution still needs; ``{"tail"}`` (where
    there are conv layers): one array a conv layer, the ``v`` rows its
    convolution still needs; ``{"k", "v"}``: one page pool an attention
    layer (page 0 the scratch page), ``head_lanes`` (default
    ``d_head``) lanes a head, the lanes past ``d_head`` zeros;
    ``{"lat"}`` (where there are ``"mla"`` layers): one latent page pool
    ``[n_pages, page_size, lanes]`` an ``"mla"`` layer, a row a
    position (the normed latent, then the turned rotary key), ``lanes``
    ``latent_lanes`` (default ``cfg.latent_lanes``), the lanes past it
    zeros. Lists, so that every array is a buffer of its own."""
    n_mamba = sum(k == "mamba" for k in cfg.layer_types)
    n_conv = sum(k == "conv" for k in cfg.layer_types)
    n_mla = sum(k == "mla" for k in cfg.layer_types)
    n_attn = sum(k == "attention" for k in cfg.layer_types)
    pool = (int(n_pages), int(page_size), cfg.n_kv_heads,
            int(head_lanes or cfg.d_head))
    cache = {
        "ssm": [jnp.zeros((n_slots, cfg.ssm_heads, cfg.ssm_head_dim,
                           cfg.ssm_state), jnp.float32)
                for _ in range(n_mamba)],
        "conv": [jnp.zeros((n_slots, cfg.ssm_conv - 1, cfg.d_conv_in),
                           jnp.float32) for _ in range(n_mamba)],
        "k": [jnp.zeros(pool, cfg.compute_dtype) for _ in range(n_attn)],
        "v": [jnp.zeros(pool, cfg.compute_dtype) for _ in range(n_attn)]}
    if n_conv:
        cache["tail"] = [jnp.zeros((n_slots, cfg.conv_width - 1,
                                    cfg.d_model), jnp.float32)
                         for _ in range(n_conv)]
    if n_mla:
        cache["lat"] = [jnp.zeros((int(n_pages), int(page_size),
                                   int(latent_lanes or cfg.latent_lanes)),
                                  cfg.compute_dtype)
                        for _ in range(n_mla)]
    return cache


def _lists(cache):
    return {name: list(arrays) for name, arrays in cache.items()}


def build_hybrid_prefill(cfg: GraniteHybridConfig, page_size: int,
                         donate: bool = True, attn_impl: str = "dense"):
    """Jitted ``hybrid_prefill(params, cache, tokens, page_table, slot,
    pos0, length) -> (cache, next_token, logits)``.

    ``tokens`` [T] is one tile of a prompt at positions ``pos0 +
    arange(T)`` (``pos0`` a multiple of ``T``, ``T`` a multiple of
    ``page_size``); ``length`` of them are real. Slot ``slot``'s state
    and conv tails are read (zeros when ``pos0 == 0``: a request's first
    tile resets whatever the slot held), carried through the tile and
    written back; the attention layers' K/V rows go to the slot's pages
    (``page_table`` [pages_per_slot]; entries past the claimed pages
    aim at the scratch page) and the tile attends over the lane causally.
    ``logits`` [vocab] are the next token's at the last real row."""
    page_size = int(page_size)
    rm = cfg.residual_multiplier

    def hybrid_prefill(params, cache, tokens, page_table, slot, pos0,
                       length):
        t = tokens.shape[0]
        n_chunks = t // page_size
        c = _lists(cache)
        x = _embed(params, tokens, cfg)
        fresh = pos0 == 0
        live = jnp.arange(t) < length
        for layer, (kind, bp) in enumerate(zip(cfg.layer_types,
                                               params["blocks"])):
            i = cfg.index_in_kind(layer)
            u = _norm(x, bp["norm1"], cfg)
            if kind == "attention":
                q, k, v = _qkv(bp, u, cfg, pos0 + jnp.arange(t))
                lanes = c["k"][i].shape[-1]
                with jax.named_scope("kv.write"):
                    pages = jax.lax.dynamic_slice_in_dim(
                        page_table, pos0 // page_size, n_chunks)
                    shape = (n_chunks, page_size, cfg.n_kv_heads, lanes)
                    c["k"][i] = c["k"][i].at[pages].set(
                        _lanes(k, lanes).reshape(shape))
                    c["v"][i] = c["v"][i].at[pages].set(
                        _lanes(v, lanes).reshape(shape))
                with jax.named_scope("attn.core"):
                    rows = (-1, cfg.n_kv_heads, lanes)
                    lane_k = c["k"][i][page_table].reshape(rows)
                    lane_v = c["v"][i][page_table].reshape(rows)
                    if lanes != cfg.d_head:
                        lane_k = lane_k[..., :cfg.d_head]
                        lane_v = lane_v[..., :cfg.d_head]
                    if attn_impl == "dense":
                        a = attention_dense(q, lane_k, lane_v,
                                            pos0 + jnp.arange(t),
                                            cfg.attention_multiplier)
                    else:
                        from mmlspark_tpu.parallel.pallas_attention import (
                            flash_prefill_attention)
                        a = flash_prefill_attention(
                            q[None], lane_k[None], lane_v[None],
                            cfg.attention_multiplier,
                            interpret=attn_impl == "pallas_interpret",
                            q_offset=pos0)[0]
                mixed = _attn_out(bp, a, cfg)
            elif kind == "mla":
                q_pos = pos0 + jnp.arange(t)
                lanes = c["lat"][i].shape[-1]
                rows = _lanes(_mla_rows(bp, u, q_pos, cfg), lanes)
                with jax.named_scope("mla.latent_write"):
                    pages = jax.lax.dynamic_slice_in_dim(
                        page_table, pos0 // page_size, n_chunks)
                    c["lat"][i] = c["lat"][i].at[pages].set(
                        rows.reshape(n_chunks, page_size, lanes))
                pool = c["lat"][i]
                if attn_impl != "dense":
                    mixed = _mla_attend_kernel(
                        bp, u, q_pos, pool[page_table].reshape(-1, lanes),
                        pos0 // t + 1, cfg,
                        attn_impl == "pallas_interpret")
                else:
                    # the lane so far, a tile of rows a chunk, each
                    # chunk's pages gathered when it is reached: the
                    # chunks past this tile's are not looked at
                    def chunk_rows(j, pool=pool):
                        return pool[jax.lax.dynamic_slice_in_dim(
                            page_table, j * n_chunks, n_chunks)].reshape(
                                t, lanes)

                    mixed = _mla_attend(bp, u, q_pos, chunk_rows,
                                        pos0 // t + 1, cfg)
            elif kind == "conv":
                tail = jnp.where(fresh, 0.0, c["tail"][i][slot])
                mixed, tail = conv_tile(bp, u, tail, length, cfg)
                c["tail"][i] = c["tail"][i].at[slot].set(tail)
            else:
                tail = jnp.where(fresh, 0.0, c["conv"][i][slot])
                state = jnp.where(fresh, 0.0, c["ssm"][i][slot])
                mixed, tail, state = mamba_tile(bp, u, tail, state, length,
                                                cfg)
                c["conv"][i] = c["conv"][i].at[slot].set(tail)
                c["ssm"][i] = c["ssm"][i].at[slot].set(state)
            x = x + rm * mixed
            x = x + rm * _ffn(bp, layer, x, cfg, grouped=True, live=live)[0]
        last = jax.lax.dynamic_index_in_dim(x, length - 1, axis=0,
                                            keepdims=True)
        logits = _head(params, last, cfg)[0]
        return c, jnp.argmax(logits, -1).astype(jnp.int32), logits

    return jax.jit(hybrid_prefill, donate_argnums=(1,) if donate else ())


def build_hybrid_step(cfg: GraniteHybridConfig, page_size: int,
                      donate: bool = True, attn_impl: str = "dense"):
    """Jitted ``hybrid_step(params, cache, tokens, pos, page_tables) ->
    (cache, fetched, logits, next_tokens)``: one token for every slot.
    ``fetched`` is everything the host reads back a step, as ONE int32
    vector: ``[next_tokens (n) | routings (len(experts_held)) |
    touched]``; ``next_tokens`` alone is what a step dispatched before
    this one is fetched takes, as it lies on the device. Every slot's state is advanced once where it lies (the
    cache is donated); the attention layers' new K/V row goes to row
    ``pos`` of the slot's lane, an ``"mla"`` layer's latent row
    likewise (:func:`mla_step`, the absorbed form). Free slots ride
    along at position 0 with an all-scratch table; their routings are not counted (a live
    slot's position is at least one). ``routings`` int32[len(
    experts_held)] are the routings each held expert received over the
    layers, ``touched`` how many (layer, held expert) pairs received
    any: the expert weights the step had to read, and under an
    ``attn_impl`` other than ``"dense"`` the ones it copied (the same
    setting picks the attention kernel and the expert walk, :func:`moe`)."""
    page_size = int(page_size)
    rm = cfg.residual_multiplier

    def hybrid_step(params, cache, tokens, pos, page_tables):
        n = tokens.shape[0]
        c = _lists(cache)
        x = _embed(params, tokens, cfg)
        live = pos > 0
        page = page_tables[jnp.arange(n), pos // page_size]
        row = pos % page_size
        routings = jnp.zeros((len(cfg.experts_held),), jnp.int32)
        touched = jnp.zeros((), jnp.int32)
        for layer, (kind, bp) in enumerate(zip(cfg.layer_types,
                                               params["blocks"])):
            i = cfg.index_in_kind(layer)
            u = _norm(x, bp["norm1"], cfg)
            if kind == "attention":
                q, k, v = _qkv(bp, u, cfg, pos)
                # a pool of more lanes than d_head: q, k and v zero-
                # padded to them (the products over the pad are zeros)
                lanes = c["k"][i].shape[-1]
                q = _lanes(q, lanes)
                with jax.named_scope("kv.write"):
                    c["k"][i] = c["k"][i].at[page, row].set(_lanes(k, lanes))
                    c["v"][i] = c["v"][i].at[page, row].set(_lanes(v, lanes))
                with jax.named_scope("attn.core"):
                    if attn_impl == "dense":
                        a = _lane_attention(q, c["k"][i], c["v"][i],
                                            page_tables, pos,
                                            cfg.attention_multiplier)
                    else:
                        from mmlspark_tpu.parallel.pallas_attention import (
                            paged_decode_attention)
                        a = paged_decode_attention(
                            q, c["k"][i], c["v"][i], page_tables, pos,
                            cfg.attention_multiplier, page_size,
                            interpret=attn_impl == "pallas_interpret")
                if lanes != cfg.d_head:
                    a = a[..., :cfg.d_head]
                mixed = _attn_out(bp, a, cfg)
            elif kind == "mla":
                mixed, c["lat"][i] = mla_step(
                    bp, u, pos, c["lat"][i], page, row, page_tables, cfg,
                    page_size, attn_impl)
            elif kind == "conv":
                mixed, c["tail"][i] = conv_step(bp, u, c["tail"][i], cfg)
            else:
                mixed, c["conv"][i], c["ssm"][i] = mamba_step(
                    bp, u, c["conv"][i], c["ssm"][i], cfg)
            x = x + rm * mixed
            out, r, tch = _ffn(bp, layer, x, cfg, grouped=False, live=live,
                               impl=attn_impl)
            x = x + rm * out
            if r is not None:
                routings, touched = routings + r, touched + tch
        logits = _head(params, x, cfg)
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)
        return (c, jnp.concatenate([nxt, routings, touched[None]]), logits,
                nxt)

    return jax.jit(hybrid_step, donate_argnums=(1,) if donate else ())


def _lane_attention(q, c_k, c_v, page_tables, pos, scale: float):
    """The dense-gather twin of ``paged_decode_attention`` for grouped
    queries: every slot's lane materialised, and each slot's one query
    through :func:`attention_dense`."""
    n, _, dh = q.shape
    h_kv = c_k.shape[2]
    lane_k = c_k[page_tables].reshape(n, -1, h_kv, dh)
    lane_v = c_v[page_tables].reshape(n, -1, h_kv, dh)
    return jax.vmap(lambda q1, k1, v1, p1: attention_dense(
        q1[None], k1, v1, p1[None], scale)[0])(q, lane_k, lane_v, pos)
