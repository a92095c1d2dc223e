"""SPMD transformer LM: dp/tp/pp/sp/ep over one named device mesh.

The reference's only distribution strategy is data parallelism over Spark
partitions plus MPI data-parallel SGD (`CommandBuilders.scala:108-267`,
SURVEY.md §2.9); tensor/pipeline/sequence/expert parallelism are absent
there. This framework treats them as first-class: a single
``shard_map``-based train step over a mesh with axes

- ``data``   — batch sharding, gradient psum (DP)
- ``seq``    — sequence/context parallelism via ring attention (SP)
- ``model``  — Megatron-style tensor parallelism: attention heads and
               MLP hidden sharded; psum fan-in after out-proj / MLP (TP)
- ``expert`` — MoE experts sharded; psum combine over the axis (EP)
- ``pipe``   — GPipe pipeline: one stage per rank, activations rotate
               with ``ppermute``, microbatches fill the bubble (PP)

Every collective is explicit (psum / ppermute), so the computation maps
1:1 onto ICI; XLA overlaps the ring steps with compute. Any subset of
axes may be absent (size-1 or missing) and the same code runs unchanged
— the test suite exercises the full composition on a virtual 8-device
CPU mesh exactly like a pod run.

Backprop over the manual shardings relies on shard_map's VMA
(varying-manual-axes) type system (``check_vma=True``, the default):
every value carries the set of mesh axes it varies over, psum/ppermute
transpose type-correctly, and gradient reductions for replicated
parameters (the all-reduce a hand-written DP/TP backward would insert)
fall out of autodiff — verified exactly against an unsharded reference
model in tests/test_transformer.py.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from mmlspark_tpu.parallel.ring_attention import (
    dense_attention, ring_attention_local,
)
from mmlspark_tpu.parallel.topology import (
    AXIS_DATA, AXIS_EXPERT, AXIS_MODEL, AXIS_PIPE, AXIS_SEQ,
)


@dataclasses.dataclass(frozen=True)
class BlockRecipe:
    """What one layer of a softmax configuration is, and how often the
    stack runs: data the decode programs read (``_decode_layers``), not
    flags at their call sites. The defaults are the block this module
    trains (``_attention`` / ``_mlp``): the train step builders, the
    int8 FFN and tensor-parallel decode implement them and nothing
    else, and refuse another recipe by name; the verify step and the
    unpaged draft programs read the recipe too, but run one pass.

    ``ffn``        ``"relu_bias"`` (``w1``/``b1``/``w2``/``b2``) or
                   ``"gated_silu"`` (``w_gate``/``w_up``/``w_down``, no
                   bias).
    ``norms``      ``"pre"`` (``ln1``/``ln2`` before attention and FFN)
                   or ``"sandwich"`` (also ``ln1_post``/``ln2_post``
                   over each sublayer's output, before the residual
                   add).
    ``rope_layout``
                   ``"interleaved"`` (pairs ``(2i, 2i + 1)``) or
                   ``"half"`` (pairs ``(i, i + Dh / 2)``: rotate_half)
                   over the whole head, at base ``rope_base``.
    ``n_loops``    passes over the SAME layers a token makes: the final
                   norm between passes, an exit gate (``exit_w``,
                   ``exit_b``) read after each, a K/V row of its own a
                   (pass, layer, position). ``exit_threshold`` is the
                   mass of the exit distribution at which a token would
                   leave the loop; only 1.0 (every pass, always) is
                   built (ROADMAP B12)."""

    ffn: str = "relu_bias"
    norms: str = "pre"
    rope_layout: str = "interleaved"
    rope_base: float = 10000.0
    norm_eps: float = 1e-6
    n_loops: int = 1
    exit_threshold: float = 1.0

    def __post_init__(self):
        for field, allowed in (("ffn", ("relu_bias", "gated_silu")),
                               ("norms", ("pre", "sandwich")),
                               ("rope_layout", ("interleaved", "half"))):
            if getattr(self, field) not in allowed:
                raise ValueError(f"recipe {field}={getattr(self, field)!r}:"
                                 f" one of {allowed}")
        if self.n_loops < 1:
            raise ValueError(f"n_loops={self.n_loops} must be >= 1")

    @property
    def looped(self) -> bool:
        """The passes are a ``fori_loop`` and the layers' weights its
        carry (``_decode_layers``): the ONE condition behind everything
        a looped stack does differently, in the programs (the pool's
        shift, the gate, ``_heads``' product, the programs' names) and
        in what is refused."""
        return self.n_loops > 1


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    """Architecture + schedule. ``n_stages`` must equal the pipe-axis size."""

    vocab: int = 256
    d_model: int = 64
    n_heads: int = 4
    d_head: int = 16
    d_ff: int = 128
    n_stages: int = 1
    layers_per_stage: int = 1
    n_experts: int = 0        # 0 = dense MLP; >0 = MoE in every block
    # 0 = dense dispatch (every token through every local expert, psum
    # combine — compute scales with n_experts); > 0 = capacity-factor
    # routing: per-expert token budget ceil(factor * T / E), all_to_all
    # over the expert axis, overflow tokens dropped to the residual —
    # compute scales with the factor, not the expert count
    moe_capacity_factor: float = 0.0
    # Switch-style load-balancing auxiliary loss weight (0 = off). With
    # capacity routing this is what keeps experts from collapsing to a
    # favored few (and overflow drops bounded): per MoE layer,
    # aux = E * sum_e f_e * P_e with f_e the routed-token fraction and
    # P_e the mean router probability — 1.0 at perfect balance.
    moe_aux_weight: float = 0.0
    # experts consulted per token. 1 = Switch-style (combine weight is
    # the raw router probability); k >= 2 = Mixtral-style (weights are
    # the top-k probabilities renormalized to sum to 1). The capacity
    # budget scales with k: C = ceil(factor * T * k / E).
    moe_top_k: int = 1
    # router z-loss weight (0 = off): weight * mean_tokens
    # logsumexp(router_logits)^2 — keeps router logits from drifting
    # large (train instability / bf16 overflow), the ST-MoE regularizer
    # that production MoE configs run alongside the balance aux.
    moe_zloss_weight: float = 0.0
    # capacity-dispatch engine. "sort" (default): stable-sort routings
    # by expert so per-expert queues are CONTIGUOUS runs — dispatch is E
    # dynamic slices and combine is E ascending dynamic-update-slices
    # (no scatter in either direction; the permutation rides a
    # gather-both-ways custom VJP). "scatter": the one-hot cumsum +
    # scatter/gather queue build (kept for A/B and as the golden
    # cross-check — both engines drop the same overflow routings).
    moe_dispatch: str = "sort"
    # routing direction. "token" (default): tokens pick their top-k
    # experts (Switch/Mixtral semantics, needs the balance aux to stay
    # balanced). "expert_choice": each expert picks its top-C tokens
    # (C = ceil(moe_capacity_factor * T_local / E)) from its affinity
    # column — perfectly balanced BY CONSTRUCTION (no aux needed; a
    # token may be served by 0..E experts). Expert choice is applied
    # within each rank's token shard (the standard group-wise form);
    # requires moe_capacity_factor > 0, ignores moe_top_k.
    moe_router: str = "token"
    microbatches: int = 1
    dtype: str = "float32"
    # un-ring-sharded attention engine: "dense" = XLA softmax-attention;
    # "folded" = the feature-major Pallas kernel (heads on the sublane
    # axis — no lane padding at short head dims; custom VJP, nothing
    # (S x S) ever reaches HBM); "flash" = the head-per-program Pallas
    # kernel (for shapes the folded layout can't take); "auto" = folded
    # on TPU from S >= 256 at short head dims (< 128), flash from
    # S >= 2048 otherwise, dense below (at short S, XLA's fused dense
    # path with stored probabilities wins)
    attention_impl: str = "auto"
    # cross-entropy engine for the vocab head: "fused" = the Pallas
    # streaming kernel (ops/fused_ce.py — logit tiles live in VMEM,
    # d_logits never reaches HBM; the move that cut the CE section of
    # the b8/s1024 step from ~8.5 ms of f32 logit round-trips);
    # "fused_interpret" runs it interpreted (CPU tests); "xla" = the
    # einsum + logsumexp path; "auto" = fused on TPU when eligible
    # (d_model lane-aligned), xla otherwise
    ce_impl: str = "auto"
    # what a layer is and how often the stack runs (decode programs)
    recipe: BlockRecipe = BlockRecipe()

    @property
    def n_layers(self) -> int:
        return self.n_stages * self.layers_per_stage

    @classmethod
    def from_hf(cls, hf: Dict[str, Any], dtype: str = "bfloat16"
                ) -> "TransformerConfig":
        """From a ``config.json`` of ``model_type`` ``ouro`` (its HF
        keys): the looped stack's sizes and recipe. What the keys do
        not fix (sandwich norms, the gate's form, no biases, half-split
        rotary over the whole head) is the family's published layer,
        stated in ``benchmark/reference_ouro.py``."""
        if hf.get("model_type") != "ouro":
            raise ValueError(f"no recipe for model_type "
                             f"{hf.get('model_type')!r}")
        if hf.get("hidden_act", "silu") != "silu" or hf.get("rope_scaling") \
                or hf.get("sliding_window") or hf.get("tie_word_embeddings"):
            raise ValueError("an ouro config with another activation, "
                             "scaled rotary, a sliding window or a tied "
                             "head is not this recipe")
        heads = int(hf["num_attention_heads"])
        if int(hf.get("num_key_value_heads", heads)) != heads:
            raise ValueError("the softmax block keeps every head's K/V")
        return cls(
            vocab=int(hf["vocab_size"]), d_model=int(hf["hidden_size"]),
            n_heads=heads,
            d_head=int(hf.get("head_dim")
                       or int(hf["hidden_size"]) // heads),
            d_ff=int(hf["intermediate_size"]), n_stages=1,
            layers_per_stage=int(hf["num_hidden_layers"]), dtype=dtype,
            recipe=BlockRecipe(
                ffn="gated_silu", norms="sandwich", rope_layout="half",
                rope_base=float(hf["rope_theta"]),
                norm_eps=float(hf["rms_norm_eps"]),
                n_loops=int(hf["total_ut_steps"]),
                exit_threshold=float(hf["early_exit_threshold"])))


# ---------------------------------------------------------------------------
# parameters


def _dense(key, shape, scale=0.02):
    return (scale * jax.random.normal(key, shape)).astype(jnp.float32)


def init_params(cfg: TransformerConfig, seed: int = 0) -> Dict[str, Any]:
    """Host pytree. Stage leaves carry a leading ``n_stages`` dim (pipe)."""
    key = jax.random.PRNGKey(seed)
    ks = iter(jax.random.split(key, 16 + 16 * cfg.n_layers))
    p: Dict[str, Any] = {
        "embed": _dense(next(ks), (cfg.vocab, cfg.d_model)),
        "head": _dense(next(ks), (cfg.d_model, cfg.vocab)),
        "final_norm": jnp.ones((cfg.d_model,)),
    }
    blocks: List[Dict[str, Any]] = []
    s, d, h, dh, f = (cfg.n_stages, cfg.d_model, cfg.n_heads, cfg.d_head,
                      cfg.d_ff)
    for _ in range(cfg.layers_per_stage):
        b = {
            "ln1": jnp.ones((s, d)),
            "wq": _dense(next(ks), (s, d, h, dh)),
            "wk": _dense(next(ks), (s, d, h, dh)),
            "wv": _dense(next(ks), (s, d, h, dh)),
            "wo": _dense(next(ks), (s, h, dh, d)),
            "ln2": jnp.ones((s, d)),
        }
        if cfg.recipe.norms == "sandwich":
            b["ln1_post"] = jnp.ones((s, d))
            b["ln2_post"] = jnp.ones((s, d))
        if cfg.n_experts:
            b["router"] = _dense(next(ks), (s, d, cfg.n_experts))
            b["ew1"] = _dense(next(ks), (s, cfg.n_experts, d, f))
            b["ew2"] = _dense(next(ks), (s, cfg.n_experts, f, d))
        elif cfg.recipe.ffn == "gated_silu":
            b["w_gate"] = _dense(next(ks), (s, d, f))
            b["w_up"] = _dense(next(ks), (s, d, f))
            b["w_down"] = _dense(next(ks), (s, f, d))
        else:
            b["w1"] = _dense(next(ks), (s, d, f))
            b["b1"] = jnp.zeros((s, f))
            b["w2"] = _dense(next(ks), (s, f, d))
            b["b2"] = jnp.zeros((s, d))
        blocks.append(b)
    p["blocks"] = blocks
    if cfg.recipe.looped:
        p["exit_w"] = _dense(next(ks), (cfg.d_model,))
        p["exit_b"] = jnp.zeros(())
    return p


def param_specs(cfg: TransformerConfig, mesh) -> Dict[str, Any]:
    """PartitionSpec tree matching ``init_params`` for ``mesh``.

    Axes not present in the mesh are dropped from the specs (replicated).
    """
    from jax.sharding import PartitionSpec as P

    names = set(mesh.axis_names)

    def ax(a):
        return a if a in names else None

    pipe, model, expert = ax(AXIS_PIPE), ax(AXIS_MODEL), ax(AXIS_EXPERT)
    specs: Dict[str, Any] = {
        "embed": P(), "head": P(), "final_norm": P(),
    }
    blocks = []
    for _ in range(cfg.layers_per_stage):
        b = {
            "ln1": P(pipe), "ln2": P(pipe),
            "wq": P(pipe, None, model, None),
            "wk": P(pipe, None, model, None),
            "wv": P(pipe, None, model, None),
            "wo": P(pipe, model, None, None),
        }
        if cfg.n_experts:
            b["router"] = P(pipe, None, None)
            b["ew1"] = P(pipe, expert, None, None)
            b["ew2"] = P(pipe, expert, None, None)
        else:
            b["w1"] = P(pipe, None, model)
            b["b1"] = P(pipe, model)
            b["w2"] = P(pipe, model, None)
            b["b2"] = P(pipe, None)
        blocks.append(b)
    specs["blocks"] = blocks
    return specs


# ---------------------------------------------------------------------------
# per-device forward (runs inside shard_map)


@dataclasses.dataclass(frozen=True)
class _Axes:
    """Mesh axes visible to the per-device program (None = absent)."""

    data: Optional[str]
    seq: Optional[str]
    model: Optional[str]
    expert: Optional[str]
    pipe: Optional[str]

    @staticmethod
    def of(mesh) -> "_Axes":
        names = set(mesh.axis_names)
        return _Axes(*(a if a in names else None for a in
                       (AXIS_DATA, AXIS_SEQ, AXIS_MODEL, AXIS_EXPERT,
                        AXIS_PIPE)))


def _size(axis):
    return jax.lax.axis_size(axis) if axis else 1


def _index(axis):
    return jax.lax.axis_index(axis) if axis else jnp.int32(0)


def _psum_if(x, axis):
    return jax.lax.psum(x, axis) if axis else x


# Every part of the block and of the programs built from it runs under
# a ``jax.named_scope`` of its own: embed, norm, attn.qkv, attn.core,
# attn.out, ffn, moe, head, ce, optimizer, and in the decode programs
# kv.write and kv.gather (the dense engines' gather of a slot's lane
# through its page table; a Pallas engine is handed a layer's pool
# whole and gathers nothing). A scope is metadata (each op's ``op_name``,
# read from the compiled text or a profiler trace); the backward of a
# scoped part carries it as ``transpose(jvp(<scope>))``. The names are
# public: PERF.md's split of a step's device time reads them.


def _rmsnorm(x, g, eps=1e-6):
    with jax.named_scope("norm"):
        var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + eps) * g


def _rope(x, pos):
    """Rotary embedding from *global* positions (seq-shard aware)."""
    dh = x.shape[-1]
    freqs = 1.0 / (10000.0 ** (jnp.arange(0, dh, 2) / dh))
    ang = pos[:, None] * freqs[None, :]                  # [S, Dh/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    cos = cos[None, :, None, :]
    sin = sin[None, :, None, :]
    r1 = x1 * cos - x2 * sin
    r2 = x1 * sin + x2 * cos
    out = jnp.stack([r1, r2], axis=-1).reshape(x.shape)
    return out


def _compute_dtype(cfg: TransformerConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def _attention(bp, x, cfg: TransformerConfig, ax: _Axes, pos):
    # mixed precision: the heavy projections AND the two S^2 attention
    # matmuls run in cfg.dtype (bf16 hits the MXU's fast path, f32 MXU
    # accumulation via preferred_element_type — no upcast pass over the
    # scores); rope/softmax and the residual stream stay f32
    dt = _compute_dtype(cfg)
    mm_dt = dt if dt != jnp.float32 else None
    if cfg.attention_impl not in ("auto", "dense", "folded", "flash"):
        raise ValueError(
            f"unknown attention_impl {cfg.attention_impl!r}")
    h = _rmsnorm(x, bp["ln1"])
    with jax.named_scope("attn.qkv"):
        h = h.astype(dt)
        q = jnp.einsum("bsd,dhk->bshk", h,
                       bp["wq"].astype(dt)).astype(jnp.float32)
        k = jnp.einsum("bsd,dhk->bshk", h,
                       bp["wk"].astype(dt)).astype(jnp.float32)
        v = jnp.einsum("bsd,dhk->bshk", h,
                       bp["wv"].astype(dt)).astype(jnp.float32)
        q, k = _rope(q, pos), _rope(k, pos)
    with jax.named_scope("attn.core"):
        a = _attention_core(q, k, v, cfg, ax, dt, mm_dt)
    with jax.named_scope("attn.out"):
        o = jnp.einsum("bshk,hkd->bsd", a.astype(dt),
                       bp["wo"].astype(dt)).astype(jnp.float32)
        return _psum_if(o, ax.model)


def _attention_core(q, k, v, cfg: TransformerConfig, ax: _Axes, dt,
                    mm_dt):
    """The train step's attention over roped q/k and v, by
    ``cfg.attention_impl`` (scope ``attn.core`` at the call)."""
    if ax.seq:
        # auto_train: the ring module's shared policy resolves to the
        # differentiable folded kernel where it pays off (never the
        # forward-only flash), dense otherwise
        if cfg.attention_impl == "flash":
            raise ValueError(
                "attention_impl='flash' cannot train under a 'seq' "
                "axis: the ring's flash block kernel is forward-only "
                "— name 'folded' or 'dense', or leave 'auto'")
        ring_impl = ("auto_train" if cfg.attention_impl == "auto"
                     else cfg.attention_impl)
        a = ring_attention_local(q, k, v, ax.seq, causal=True,
                                 compute_dtype=mm_dt,
                                 block_impl=ring_impl)
    else:
        from mmlspark_tpu.parallel.pallas_attention import (
            _folded_shape_ok, flash_attention, flash_attention_folded,
            flash_available, folded_available)
        b_, s_, h_, dh_ = q.shape
        impl = cfg.attention_impl
        if impl == "auto":
            # the folded (feature-major) kernel wins from S >= 256 at
            # short head dims (measured at dh=64: 2.1x whole-step at
            # S=1024 and 1.29x at S=256 vs XLA dense —
            # tools/probe_transformer_perf.py); at dh >= 128 its
            # rationale (dodging lane padding) vanishes and it is
            # unmeasured, so those shapes keep the flash kernel's
            # long-S gate; below both, XLA's fused dense attention
            # (which stores p instead of recomputing) is faster
            if folded_available(s_, s_, dh_, h_) and s_ >= 256 and dh_ < 128:
                impl = "folded"
            elif flash_available() and s_ >= 2048:
                impl = "flash"
            else:
                impl = "dense"
        elif impl == "folded" and not _folded_shape_ok(s_, s_, dh_, h_):
            # only "auto" may pick another engine: a NAMED engine that
            # cannot take the shape raises (and one the platform cannot
            # run fails in the Pallas lowering) — a run never reports
            # one engine's name over another engine's numbers
            raise ValueError(
                f"attention_impl='folded' cannot take shape (S={s_}, "
                f"head_dim={dh_}, H*Dh={h_ * dh_}): it needs head_dim "
                f"% 8 == 0, a 128-tileable S, and H*Dh inside the "
                f"folded VMEM budget — name 'flash' or 'dense', or "
                f"leave 'auto'")
        if impl in ("folded", "flash") and mm_dt is not None:
            q, k, v = q.astype(dt), k.astype(dt), v.astype(dt)
        if impl == "folded":
            a = flash_attention_folded(q, k, v, True)
        elif impl == "flash":
            a = flash_attention(q, k, v, True)
        else:
            a = dense_attention(q, k, v, causal=True, compute_dtype=mm_dt)
    return a


def _mlp(bp, x, ax: _Axes, cfg: TransformerConfig):
    dt = _compute_dtype(cfg)
    h = _rmsnorm(x, bp["ln2"])
    with jax.named_scope("ffn"):
        h = h.astype(dt)
        z = jax.nn.relu(jnp.einsum("bsd,df->bsf", h, bp["w1"].astype(dt))
                        + bp["b1"].astype(dt))
        y = jnp.einsum("bsf,fd->bsd", z,
                       bp["w2"].astype(dt)).astype(jnp.float32)
        return _psum_if(y, ax.model) + bp["b2"]


def _route_top_k(probs, k: int):
    """``(weights, experts)`` for the top-k choices, trailing dim k.

    k == 1 keeps Switch semantics (raw top probability); k >= 2 uses the
    Mixtral rule (top-k probabilities renormalized to sum to one).
    """
    vals, idx = jax.lax.top_k(probs, k)
    if k > 1:
        vals = vals / jnp.maximum(
            jnp.sum(vals, axis=-1, keepdims=True), 1e-12)
    return vals, idx


def _pmean_token_axes(x, axes):
    """pmean a token-linear statistic over every token-holding axis."""
    for a in axes:
        if a:
            x = jax.lax.pmean(x, a)
    return x


def _router_stats(probs2d, top, E: int, axes):
    """GLOBAL per-layer routing statistics for the Switch aux loss.

    ``probs2d`` (T_local, E) / ``top`` (T_local,) are this rank's token
    share; returns ``(f, P)`` — routed-fraction and mean-probability
    vectors pmean'd over every token-holding axis in ``axes``. The aux
    ``E * sum_e f_e P_e`` is NONLINEAR in (f, P), so only these linear
    statistics may be averaged across shards (and across microbatches —
    see ``local_loss``); the product is taken once, at the end, from the
    fully aggregated vectors, exactly matching the unsharded golden.
    """
    f = jnp.mean(jax.nn.one_hot(top, E, dtype=jnp.float32), axis=0)
    P = jnp.mean(probs2d.astype(jnp.float32), axis=0)
    for a in axes:
        if a:
            f = jax.lax.pmean(f, a)
            P = jax.lax.pmean(P, a)
    return f, P


@jax.custom_vjp
def _permute_rows(x, order, inv):
    """``x[order]`` with a gather in BOTH autodiff directions.

    A permutation gather's transpose is a scatter in general, but for a
    bijection it equals gathering with the inverse permutation — XLA
    cannot see that, so without this rewrite every sorted-dispatch
    gather would pay a full row-scatter in the backward pass (the exact
    cost the sort exists to avoid)."""
    return x[order]


def _permute_rows_fwd(x, order, inv):
    return x[order], (order, inv)


def _permute_rows_bwd(res, g):
    order, inv = res
    zero = np.zeros(order.shape, dtype=jax.dtypes.float0)
    return g[inv], zero, zero


_permute_rows.defvjp(_permute_rows_fwd, _permute_rows_bwd)


def _sorted_capacity_queues(h_rep, top, wf, E: int, C: int, dt):
    """Counting-sort capacity dispatch: returns ``(disp (E, C, dtype
    dt), combine)`` where ``combine(y (E, C, d) f32) -> (Tk, d) f32``
    routes expert outputs back to routing order with router weights
    applied.

    With only E distinct keys no comparison sort is needed: the one-hot
    cumsum gives each routing its arrival-order slot within its expert,
    ``dest = starts[expert] + slot`` IS the grouping permutation
    (stable by construction — the SAME overflow routings drop as in the
    scatter engine), and its inverse costs one O(Tk) int scatter. Rows
    then move only through permutation gathers (gather in BOTH autodiff
    directions via :func:`_permute_rows`) and per-expert dynamic
    slices; the combine rebuilds sorted rows with ascending
    dynamic-update-slices (group e's tail overlap is always rewritten
    by group e+1). Queue rows beyond an expert's count hold other
    groups' tokens — the keep mask zeroes their contribution, and their
    zero cotangent keeps gradients exact. No row scatter exists in
    either direction of either pass."""
    Tk, d = h_rep.shape
    onehot = jax.nn.one_hot(top, E, dtype=jnp.int32)     # (Tk, E)
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1
    slot = jnp.take_along_axis(pos, top[:, None], axis=1)[:, 0]
    counts = jnp.sum(onehot, axis=0)                     # (E,)
    starts = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(counts)[:-1]])
    inv = starts[top] + slot          # routing -> its sorted row (dest)
    order = jnp.zeros((Tk,), jnp.int32).at[inv].set(
        jnp.arange(Tk, dtype=jnp.int32))                 # sorted -> routing
    keep = (slot < C).astype(jnp.float32)                # routing order
    hs = _permute_rows(h_rep, order, inv)                # (Tk, d) sorted
    hs_pad = jnp.concatenate([hs, jnp.zeros((C, d), hs.dtype)])
    disp = jnp.stack([
        jax.lax.dynamic_slice_in_dim(hs_pad, starts[e], C)
        for e in range(E)]).astype(dt)                   # (E, C, d)

    def combine(y):
        y_s = jnp.zeros((Tk + C, d), jnp.float32)
        for e in range(E):
            y_s = jax.lax.dynamic_update_slice_in_dim(
                y_s, y[e], starts[e], 0)
        y_r = _permute_rows(y_s[:Tk], inv, order)        # routing order
        return y_r * (keep * wf)[:, None]

    return disp, combine


def _scatter_capacity_queues(h_rep, top, wf, E: int, C: int, dt):
    """One-hot cumsum + scatter/gather capacity dispatch: the golden
    reference engine :func:`_sorted_capacity_queues` is A/B'd against.
    Same contract: ``(disp (E, C, dtype dt), combine)`` with router
    weights applied on the way back; overflow routings land in a
    scratch column that is sliced away (dispatch) / zero-weighted
    (combine). Shared by the model's ``moe_dispatch='scatter'`` branch
    and ``tools/bench_moe_engines.py``, so the bench times exactly the
    code the model runs."""
    Tk, d = h_rep.shape
    onehot = jax.nn.one_hot(top, E, dtype=jnp.int32)      # (Tk, E)
    # position of each routing within its expert's queue (arrival order)
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1
    slot = jnp.take_along_axis(pos, top[:, None], axis=1)[:, 0]
    keep = slot < C
    # overflow routings land in a scratch column C, sliced away
    slot_c = jnp.where(keep, slot, C)
    disp = jnp.zeros((E, C + 1, d), dt).at[top, slot_c].set(
        h_rep.astype(dt))[:, :C]                          # (E, C, d)

    def combine(y):
        y = jnp.pad(y, ((0, 0), (0, 1), (0, 0)))          # overflow row
        return y[top, slot_c] * (keep * wf)[:, None]

    return disp, combine


def _moe_capacity(bp, x, cfg: TransformerConfig, ax: _Axes):
    """Capacity-factor top-k MoE dispatch (the production shape).

    Each token contributes ``moe_top_k`` routings; each rank builds
    per-expert routing queues bounded by ``C = ceil(factor * T * k /
    E)`` (routings beyond an expert's budget drop to the residual),
    ``all_to_all`` over the ``expert`` axis swaps queue shards so every
    rank holds the full cross-rank queues of its LOCAL experts, the
    expert FFNs run as one batched einsum, and a second ``all_to_all``
    routes results home, combined with the top-k router weights
    (:func:`_route_top_k`). Per-token FLOPs scale with the capacity
    factor and k, not ``n_experts`` — unlike :func:`_moe`'s dense
    dispatch, which multiplies every token through every local expert.
    """
    import math
    dt = _compute_dtype(cfg)
    h = _rmsnorm(x, bp["ln2"])
    logits = jnp.einsum("bsd,de->bse", h, bp["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    b, s, d = x.shape
    T, E = b * s, cfg.n_experts
    e_size, e_rank = _size(ax.expert), _index(ax.expert)
    if T % e_size:
        raise ValueError(
            f"capacity MoE dispatch needs local tokens ({T}) divisible "
            f"by the expert axis ({e_size})")
    # activations arrive REPLICATED over the expert axis; treat that
    # axis as extra token parallelism: each rank routes its own token
    # shard, so expert compute per rank scales with T/e_size
    T_sh = T // e_size
    off = e_rank * T_sh
    k = cfg.moe_top_k
    hT = jax.lax.dynamic_slice_in_dim(h.reshape(T, d), off, T_sh)
    wts, experts = _route_top_k(probs.reshape(T, E), k)  # [T, k]
    wts = jax.lax.dynamic_slice_in_dim(wts, off, T_sh)
    experts = jax.lax.dynamic_slice_in_dim(experts, off, T_sh)
    # each (token, choice) routing occupies one queue slot; the budget
    # scales with k so factor=1 still holds everything at perfect balance
    C = max(int(math.ceil(cfg.moe_capacity_factor * T_sh * k / E)), 1)

    top = experts.reshape(T_sh * k)                      # routing slots
    wf = wts.reshape(T_sh * k)
    if cfg.moe_dispatch == "sort":
        # the whole permute/queue chain runs in the compute dtype: the
        # sorted rows are matmul inputs, and bf16 halves the sort-path
        # HBM traffic
        disp, combine = _sorted_capacity_queues(
            jnp.repeat(hT.astype(dt), k, axis=0), top, wf, E, C, dt)
    elif cfg.moe_dispatch == "scatter":
        disp, combine = _scatter_capacity_queues(
            jnp.repeat(hT.astype(dt), k, axis=0), top, wf, E, C, dt)
    else:
        raise ValueError(f"unknown moe_dispatch {cfg.moe_dispatch!r}")

    if ax.expert:
        # queues regrouped so each rank holds the ALL-RANK queues of
        # its local experts: [E, C, d] -> [e_local, e_size*C, d]
        disp = jax.lax.all_to_all(disp, ax.expert, split_axis=0,
                                  concat_axis=1, tiled=True)
    z = jax.nn.relu(jnp.einsum("ecd,edf->ecf", disp,
                               bp["ew1"].astype(dt)))
    y = jnp.einsum("ecf,efd->ecd", z,
                   bp["ew2"].astype(dt)).astype(jnp.float32)
    if ax.expert:
        # route results back to their owner ranks: [E, C, d] again
        y = jax.lax.all_to_all(y, ax.expert, split_axis=1,
                               concat_axis=0, tiled=True)
    yflat = combine(y)                                   # [T_sh*k, d]
    ytok = jnp.sum(yflat.reshape(T_sh, k, d), axis=1)    # combine choices
    f_stat = (jnp.zeros(E, jnp.float32), jnp.zeros(E, jnp.float32))
    if cfg.moe_aux_weight > 0:
        pT = jax.lax.dynamic_slice_in_dim(
            probs.reshape(T, E), off, T_sh)
        # aux counts the FIRST choice (Switch definition) for any k
        f_stat = _router_stats(pT, experts[:, 0], E,
                               (ax.data, ax.seq, ax.expert))
    z_stat = jnp.float32(0.0)
    if cfg.moe_zloss_weight > 0:
        lse = jax.nn.logsumexp(
            jax.lax.dynamic_slice_in_dim(logits.reshape(T, E), off, T_sh),
            axis=-1)
        z_stat = _pmean_token_axes(jnp.mean(jnp.square(lse)),
                                   (ax.data, ax.seq, ax.expert))
    stats = (*f_stat, z_stat)
    # restore expert-axis replication: every rank contributes its own
    # token shard, psum rebuilds the full (invariant) token set
    full = jnp.zeros((T, d), jnp.float32)
    full = jax.lax.dynamic_update_slice_in_dim(full, ytok, off, axis=0)
    return _psum_if(full, ax.expert).reshape(b, s, d), stats


def _moe_expert_choice(bp, x, cfg: TransformerConfig, ax: _Axes):
    """Expert-choice routing (Zhou et al. 2022): each expert picks its
    top-C tokens from its affinity column instead of tokens picking
    experts — per-expert load is exactly C by construction, so no
    balance aux is needed and no overflow drops exist. Applied within
    each rank's token shard (the standard group-wise form at scale);
    the dispatch/return ``all_to_all`` skeleton and token-shard
    parallelism over the ``expert`` axis match :func:`_moe_capacity`.
    The combine weight is the router probability of each (expert,
    token) pick; a token may be served by several experts or none
    (riding the residual).
    """
    import math
    dt = _compute_dtype(cfg)
    h = _rmsnorm(x, bp["ln2"])
    logits = jnp.einsum("bsd,de->bse", h, bp["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    b, s, d = x.shape
    T, E = b * s, cfg.n_experts
    e_size, e_rank = _size(ax.expert), _index(ax.expert)
    if T % e_size:
        raise ValueError(
            f"expert-choice MoE needs local tokens ({T}) divisible by "
            f"the expert axis ({e_size})")
    T_sh = T // e_size
    off = e_rank * T_sh
    hT = jax.lax.dynamic_slice_in_dim(h.reshape(T, d), off, T_sh)
    pT = jax.lax.dynamic_slice_in_dim(probs.reshape(T, E), off, T_sh)
    C = max(int(math.ceil(cfg.moe_capacity_factor * T_sh / E)), 1)

    wts, idx = jax.lax.top_k(pT.T, min(C, T_sh))       # (E, C) over tokens
    disp = hT[idx].astype(dt)                          # (E, C, d)
    if ax.expert:
        disp = jax.lax.all_to_all(disp, ax.expert, split_axis=0,
                                  concat_axis=1, tiled=True)
    z = jax.nn.relu(jnp.einsum("ecd,edf->ecf", disp,
                               bp["ew1"].astype(dt)))
    y = jnp.einsum("ecf,efd->ecd", z,
                   bp["ew2"].astype(dt)).astype(jnp.float32)
    if ax.expert:
        y = jax.lax.all_to_all(y, ax.expert, split_axis=1,
                               concat_axis=0, tiled=True)
    ytok = jnp.zeros((T_sh, d), jnp.float32).at[idx.reshape(-1)].add(
        y.reshape(-1, d) * wts.reshape(-1)[:, None])
    E_ = cfg.n_experts
    # load is balanced by construction: the aux stats stay zero
    stats = (jnp.zeros(E_, jnp.float32), jnp.zeros(E_, jnp.float32))
    z_stat = jnp.float32(0.0)
    if cfg.moe_zloss_weight > 0:
        lse = jax.nn.logsumexp(
            jax.lax.dynamic_slice_in_dim(logits.reshape(T, E), off, T_sh),
            axis=-1)
        z_stat = _pmean_token_axes(jnp.mean(jnp.square(lse)),
                                   (ax.data, ax.seq, ax.expert))
    full = jnp.zeros((T, d), jnp.float32)
    full = jax.lax.dynamic_update_slice_in_dim(full, ytok, off, axis=0)
    return _psum_if(full, ax.expert).reshape(b, s, d), (*stats, z_stat)


def _moe(bp, x, cfg: TransformerConfig, ax: _Axes):
    """Top-1 MoE, experts sharded over ``expert``: each rank runs its
    local experts on its local tokens; psum over the axis combines (the
    gate selects exactly one expert somewhere on the axis). Dense
    dispatch by default; ``cfg.moe_capacity_factor > 0`` switches to
    the capacity-based all_to_all dispatch (:func:`_moe_capacity`).
    Returns ``(y, aux)`` — the load-balancing aux scalar is 0 unless
    ``cfg.moe_aux_weight > 0``."""
    if cfg.moe_router == "expert_choice":
        if cfg.moe_capacity_factor <= 0:
            raise ValueError("moe_router='expert_choice' needs "
                             "moe_capacity_factor > 0 (defines C)")
        return _moe_expert_choice(bp, x, cfg, ax)
    if cfg.moe_router != "token":
        raise ValueError(f"unknown moe_router {cfg.moe_router!r}")
    if cfg.moe_capacity_factor > 0:
        return _moe_capacity(bp, x, cfg, ax)
    dt = _compute_dtype(cfg)
    h = _rmsnorm(x, bp["ln2"])
    # router stays f32 (softmax + routing decisions); the expert
    # matmuls — the MoE's dominant FLOPs — run in cfg.dtype
    logits = jnp.einsum("bsd,de->bse", h, bp["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    wts, experts = _route_top_k(probs, cfg.moe_top_k)    # [b, s, k]
    e_size, e_rank = _size(ax.expert), _index(ax.expert)
    e_local = cfg.n_experts // e_size
    h_c = h.astype(dt)
    y = jnp.zeros_like(x)
    for e in range(e_local):
        gid = e_rank * e_local + e
        sel = jnp.sum((experts == gid).astype(jnp.float32) * wts,
                      axis=-1)                           # [b, s]
        z = jax.nn.relu(jnp.einsum("bsd,df->bsf", h_c,
                                   bp["ew1"][e].astype(dt)))
        z = jnp.einsum("bsf,fd->bsd", z,
                       bp["ew2"][e].astype(dt)).astype(jnp.float32)
        y = y + z * sel[..., None]
    E = cfg.n_experts
    f_stat = (jnp.zeros(E, jnp.float32), jnp.zeros(E, jnp.float32))
    if cfg.moe_aux_weight > 0:
        # tokens are REPLICATED over the expert axis here, so only the
        # data/seq axes hold distinct tokens; the aux counts the FIRST
        # choice (the Switch definition), whatever k is
        f_stat = _router_stats(probs.reshape(-1, E),
                               experts[..., 0].reshape(-1), E,
                               (ax.data, ax.seq))
    z_stat = jnp.float32(0.0)
    if cfg.moe_zloss_weight > 0:
        lse = jax.nn.logsumexp(logits, axis=-1)
        z_stat = _pmean_token_axes(jnp.mean(jnp.square(lse)),
                                   (ax.data, ax.seq))
    return _psum_if(y, ax.expert), (*f_stat, z_stat)


def _stage(stage_blocks, x, cfg: TransformerConfig, ax: _Axes, pos):
    """One pipeline stage = ``layers_per_stage`` transformer blocks.
    Returns ``(x, f_stack, P_stack, z_stack)``: per-block [n_blocks, E]
    routing statistics for the load-balancing aux plus the per-block
    z-loss scalars [n_blocks] (zeros when dense-MLP or the regularizers
    are disabled) — kept as linear stats so microbatches can be averaged
    before the aux's nonlinear product (see ``local_loss``)."""
    fs, Ps, zs = [], [], []
    for bp in stage_blocks:
        x = x + _attention(bp, x, cfg, ax, pos)
        if cfg.n_experts:
            with jax.named_scope("moe"):
                y, (f, P, z) = _moe(bp, x, cfg, ax)
            x = x + y
            fs.append(f)
            Ps.append(P)
            zs.append(z)
        else:
            x = x + _mlp(bp, x, ax, cfg)
    if not fs:
        z = jnp.zeros((len(stage_blocks), max(cfg.n_experts, 1)),
                      jnp.float32)
        return x, z, z, jnp.zeros(len(stage_blocks), jnp.float32)
    return x, jnp.stack(fs), jnp.stack(Ps), jnp.stack(zs)


def local_loss(params, tokens, labels, mask, cfg: TransformerConfig,
               ax: _Axes):
    """Per-device mean-CE loss over the full mesh (replicated scalar).

    GPipe schedule: rank 0 ingests a microbatch per tick, activations
    rotate over ``pipe`` each tick, the last rank collects outputs after
    the ``n_stages - 1``-tick fill; loss is psum'd over pipe+data+seq.
    """
    p_size, p_rank = _size(ax.pipe), _index(ax.pipe)
    m = cfg.microbatches
    b_loc, s_loc = tokens.shape
    if b_loc % m:
        raise ValueError(f"local batch {b_loc} not divisible by "
                         f"microbatches {m}")
    mb = b_loc // m
    pos = _index(ax.seq) * s_loc + jnp.arange(s_loc)     # global positions
    # my stage's blocks: pipe-sharded leaves arrive [1, ...]
    stage_blocks = [{k: v[0] for k, v in bp.items()} for bp in
                    params["blocks"]]
    tok_mb = tokens.reshape(m, mb, s_loc)

    state = jnp.zeros((mb, s_loc, cfg.d_model), jnp.float32)
    out = jnp.zeros((m, mb, s_loc, cfg.d_model), jnp.float32)
    perm = [(i, (i + 1) % p_size) for i in range(p_size)]
    n_blk = len(stage_blocks)
    F_acc = jnp.zeros((n_blk, max(cfg.n_experts, 1)), jnp.float32)
    P_acc = jnp.zeros_like(F_acc)
    Z_acc = jnp.zeros(n_blk, jnp.float32)
    for t in range(m + p_size - 1):
        if t < m:
            with jax.named_scope("embed"):
                inp = params["embed"][tok_mb[t]]         # [mb, S_loc, D]
            state = jnp.where(p_rank == 0, inp, state)
        state, f_t, p_t, z_t = _stage(stage_blocks, state, cfg, ax, pos)
        if cfg.n_experts and (cfg.moe_aux_weight > 0
                              or cfg.moe_zloss_weight > 0):
            # accumulate only ticks where REAL data flows through this
            # rank (fill/drain ticks carry garbage activations); the
            # stats are linear, so averaging them over microbatches then
            # taking the product equals the full-batch aux exactly
            real = ((p_rank <= t) & (t < p_rank + m)).astype(jnp.float32)
            F_acc = F_acc + f_t * real
            P_acc = P_acc + p_t * real
            Z_acc = Z_acc + z_t * real
        o_idx = t - (p_size - 1)
        if o_idx >= 0:
            out = out.at[o_idx].set(
                jnp.where(p_rank == p_size - 1, state, out[o_idx]))
        if p_size > 1 and t < m + p_size - 2:
            state = jax.lax.ppermute(state, ax.pipe, perm)

    h = _rmsnorm(out.reshape(b_loc, s_loc, cfg.d_model), params["final_norm"])
    dt = _compute_dtype(cfg)
    ce_impl = cfg.ce_impl
    if ce_impl == "auto":
        from mmlspark_tpu.ops.fused_ce import fused_ce_available
        ce_impl = ("fused" if fused_ce_available(
            b_loc * s_loc, cfg.d_model, cfg.vocab,
            itemsize=jnp.dtype(dt).itemsize) else "xla")
    if ce_impl in ("fused", "fused_interpret"):
        # the Pallas streaming CE: logit tiles stay in VMEM, d_logits
        # never reaches HBM, and the only large write is one
        # compute-dtype logits copy for the backward (ops/fused_ce.py)
        from mmlspark_tpu.ops.fused_ce import fused_softmax_xent
        with jax.named_scope("ce"):     # the head is inside the kernel
            ce = fused_softmax_xent(
                h.reshape(b_loc * s_loc, cfg.d_model), params["head"],
                labels.reshape(b_loc * s_loc), compute_dtype=dt,
                interpret=ce_impl == "fused_interpret",
            ).reshape(b_loc, s_loc)
    else:
        # the vocab head is a third of a small LM's forward FLOPs: run
        # the matmul with bf16 inputs + f32 MXU accumulation. The logits
        # COME OUT f32 (preferred_element_type), so there is no separate
        # upcast pass over [b, s, vocab] — the trap that made a plain
        # bf16 head slower
        with jax.named_scope("head"):
            if dt != jnp.float32:
                logits = jnp.einsum("bsd,dv->bsv", h.astype(dt),
                                    params["head"].astype(dt),
                                    preferred_element_type=jnp.float32)
            else:
                logits = jnp.einsum("bsd,dv->bsv", h, params["head"])
        # fused CE: logsumexp - gold logit. log_softmax would
        # materialize a second [b, s, vocab] array (logp) just to gather
        # one column — at 32k vocab that is a gigabyte of pure HBM
        # traffic per step
        with jax.named_scope("ce"):
            lse = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, labels[..., None],
                                       axis=-1)[..., 0]
            ce = lse - gold
    is_last = (p_rank == p_size - 1).astype(jnp.float32)
    loss_sum = jnp.sum(ce * mask) * is_last
    count = jnp.sum(mask) * is_last
    axes = tuple(a for a in (ax.pipe, ax.data, ax.seq) if a)
    if axes:
        loss_sum = jax.lax.psum(loss_sum, axes)
        count = jax.lax.psum(count, axes)
    loss = loss_sum / jnp.maximum(count, 1.0)
    if cfg.n_experts and cfg.moe_aux_weight > 0:
        # per-layer aux from microbatch-averaged (f, P), summed over
        # this rank's layers, then over all stages (each pipe rank
        # holds different layers)
        aux = cfg.n_experts * jnp.sum((F_acc / m) * (P_acc / m))
        if ax.pipe:
            aux = jax.lax.psum(aux, ax.pipe)
        loss = loss + cfg.moe_aux_weight * aux
    if cfg.n_experts and cfg.moe_zloss_weight > 0:
        # z-loss is already token-linear; microbatch average then sum
        # over this rank's layers and all stages
        zterm = jnp.sum(Z_acc / m)
        if ax.pipe:
            zterm = jax.lax.psum(zterm, ax.pipe)
        loss = loss + cfg.moe_zloss_weight * zterm
    return loss


# ---------------------------------------------------------------------------
# reference (unsharded) forward — golden model for the SPMD tests


def _reference_ec(bp, h, cfg: TransformerConfig, ec_groups: int):
    """Unsharded expert-choice MoE matching the sharded rule: expert
    choice runs WITHIN each token group (a rank's token shard in the
    SPMD step — pass the number of token shards as ``ec_groups``)."""
    import math
    b, s, d = h.shape
    T, E = b * s, cfg.n_experts
    hf = h.reshape(T, d)
    logits = jnp.einsum("td,de->te", hf, bp["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    Tg = T // ec_groups
    C = max(int(math.ceil(cfg.moe_capacity_factor * Tg / E)), 1)
    y = jnp.zeros((T, d), jnp.float32)
    for g in range(ec_groups):
        pg = probs[g * Tg:(g + 1) * Tg]                # (Tg, E)
        hg = hf[g * Tg:(g + 1) * Tg]
        wts, idx = jax.lax.top_k(pg.T, min(C, Tg))     # (E, C)
        z = jax.nn.relu(jnp.einsum("ecd,edf->ecf", hg[idx], bp["ew1"]))
        out = jnp.einsum("ecf,efd->ecd", z, bp["ew2"])
        yg = jnp.zeros((Tg, d), jnp.float32).at[idx.reshape(-1)].add(
            out.reshape(-1, d) * wts.reshape(-1)[:, None])
        y = y.at[g * Tg:(g + 1) * Tg].add(yg)
    lse = jax.nn.logsumexp(logits, axis=-1)
    return y.reshape(b, s, d), jnp.mean(jnp.square(lse))


def _reference_forward(params, tokens, cfg: TransformerConfig,
                       ec_groups: int = 1):
    """Unsharded forward: ``(logits, aux_total, z_total)``."""
    x = params["embed"][tokens]
    pos = jnp.arange(tokens.shape[1])
    aux_total = jnp.float32(0.0)
    z_total = jnp.float32(0.0)
    for s in range(cfg.n_stages):
        for bp_all in params["blocks"]:
            bp = {k: v[s] for k, v in bp_all.items()}
            h = _rmsnorm(x, bp["ln1"])
            q = _rope(jnp.einsum("bsd,dhk->bshk", h, bp["wq"]), pos)
            k = _rope(jnp.einsum("bsd,dhk->bshk", h, bp["wk"]), pos)
            v = jnp.einsum("bsd,dhk->bshk", h, bp["wv"])
            a = dense_attention(q, k, v, causal=True)
            x = x + jnp.einsum("bshk,hkd->bsd", a, bp["wo"])
            h = _rmsnorm(x, bp["ln2"])
            if cfg.n_experts and cfg.moe_router == "expert_choice":
                y, z_layer = _reference_ec(bp, h, cfg, ec_groups)
                x = x + y
                if cfg.moe_zloss_weight > 0:
                    z_total = z_total + z_layer
            elif cfg.n_experts:
                logits = jnp.einsum("bsd,de->bse", h, bp["router"])
                probs = jax.nn.softmax(logits, axis=-1)
                wts, experts = _route_top_k(probs, cfg.moe_top_k)
                y = jnp.zeros_like(x)
                for e in range(cfg.n_experts):
                    sel = jnp.sum((experts == e).astype(jnp.float32)
                                  * wts, axis=-1)
                    z = jax.nn.relu(jnp.einsum("bsd,df->bsf", h, bp["ew1"][e]))
                    z = jnp.einsum("bsf,fd->bsd", z, bp["ew2"][e])
                    y = y + z * sel[..., None]
                x = x + y
                if cfg.moe_aux_weight > 0:
                    f, P = _router_stats(
                        probs.reshape(-1, cfg.n_experts),
                        experts[..., 0].reshape(-1), cfg.n_experts, ())
                    aux_total = aux_total + cfg.n_experts * jnp.sum(f * P)
                if cfg.moe_zloss_weight > 0:
                    lse_r = jax.nn.logsumexp(logits, axis=-1)
                    z_total = z_total + jnp.mean(jnp.square(lse_r))
            else:
                z = jax.nn.relu(
                    jnp.einsum("bsd,df->bsf", h, bp["w1"]) + bp["b1"])
                x = x + jnp.einsum("bsf,fd->bsd", z, bp["w2"]) + bp["b2"]
    h = _rmsnorm(x, params["final_norm"])
    logits = jnp.einsum("bsd,dv->bsv", h, params["head"])
    return logits, aux_total, z_total


def reference_logits(params, tokens, cfg: TransformerConfig):
    """Per-position next-token logits ``[b, s, vocab]`` on one device —
    the scoring entry for sequence-labeling / generation consumers (the
    era analogue of scoring a pretrained BiLSTM tagger, `notebooks/
    samples/DeepLearning - BiLSTM Medical Entity Extraction.ipynb`)."""
    return _reference_forward(params, tokens, cfg)[0]


def reference_loss(params, tokens, labels, mask, cfg: TransformerConfig,
                   ec_groups: int = 1):
    """Same math as the SPMD step on one device: dense attention, dense
    MoE, no pipeline — the golden model for the sharded tests.
    ``ec_groups``: for expert-choice routing, the number of token
    groups the SPMD step shards tokens into (expert choice is
    group-wise; see :func:`_reference_ec`)."""
    logits, aux_total, z_total = _reference_forward(params, tokens, cfg,
                                                    ec_groups)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    ce = lse - gold
    loss = jnp.sum(ce * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return (loss + cfg.moe_aux_weight * aux_total
            + cfg.moe_zloss_weight * z_total)


# ---------------------------------------------------------------------------
# train step


def _validate_mesh_config(cfg: TransformerConfig, mesh) -> "_Axes":
    """The shared build-time checks of BOTH train-step formulations
    (manual shard_map and pjit): every mesh/config mismatch fails
    loudly at build, never as a cryptic XLA partitioning error."""
    ax = _Axes.of(mesh)
    _check_default_recipe(cfg, "the train step")
    if ax.pipe and mesh.shape[ax.pipe] != cfg.n_stages:
        raise ValueError(
            f"n_stages={cfg.n_stages} != pipe axis size {mesh.shape[ax.pipe]}")
    if not ax.pipe and cfg.n_stages != 1:
        raise ValueError("n_stages > 1 requires a 'pipe' mesh axis")
    if ax.model and cfg.n_heads % mesh.shape[ax.model]:
        raise ValueError("n_heads must divide over the model axis")
    if ax.model and cfg.d_ff % mesh.shape[ax.model]:
        raise ValueError("d_ff must divide over the model axis")
    if ax.expert and cfg.n_experts and cfg.n_experts % mesh.shape[ax.expert]:
        raise ValueError("n_experts must divide over the expert axis")
    if cfg.n_experts and not 1 <= cfg.moe_top_k <= cfg.n_experts:
        raise ValueError(
            f"moe_top_k={cfg.moe_top_k} must be in [1, n_experts="
            f"{cfg.n_experts}]")
    return ax


def build_spmd_train_step(cfg: TransformerConfig, mesh,
                          learning_rate: float = 0.1,
                          momentum: float = 0.9,
                          donate: bool = True,
                          check_vma: bool = True,
                          impl: str = "auto"):
    """Jitted full train step over ``mesh``: fwd + bwd + per-leaf grad
    psum + momentum-SGD update.

    Two interchangeable formulations exist (``impl``):

    * ``"shard_map"`` — the manual per-device program (explicit
      psum/ppermute/all_to_all; maps 1:1 onto ICI); its backward
      relies on vma types to insert the replicated-parameter grad
      psums. ``"auto"`` is this one.
    * ``"pjit"`` — the same math as ONE global GSPMD program
      (:func:`build_pjit_train_step`): XLA inserts every collective
      from the ``NamedSharding`` annotations. Fixed-seed parity
      between the two is test-pinned; choosing one is ROADMAP C1.

    ``check_vma=False`` is test-only (see the warning below) and
    belongs to the shard_map path, whose documented under-reduction
    boundary is itself pinned by tests.

    Returns ``step(params, velocity, tokens, labels, mask) ->
    (params, velocity, loss)`` where params/velocity are device arrays
    laid out per :func:`param_specs`. Replaces the reference's
    mpirun/BrainScript data-parallel SGD chain (`CommandBuilders.scala`)
    with one compiled program; adds tp/pp/sp/ep the reference never had.

    .. warning:: With ``donate=True`` (the default) the ``params`` and
       ``velocity`` arguments are **donated**: their buffers are reused
       for the outputs, and the input arrays are invalidated after the
       call *on TPU/GPU* (CPU ignores donation, so misuse only surfaces
       on accelerator backends). Always rebind, ``params, velocity,
       loss = step(params, velocity, ...)``; callers that must reuse the
       pre-step state (warm-up probes, pre/post diffing) should pass
       ``donate=False``.
    """
    from jax.sharding import PartitionSpec as P

    if impl not in ("auto", "shard_map", "pjit"):
        raise ValueError(f"unknown train-step impl {impl!r}")
    if impl == "pjit":
        return build_pjit_train_step(cfg, mesh, learning_rate, momentum,
                                     donate=donate)

    ax = _validate_mesh_config(cfg, mesh)
    specs = param_specs(cfg, mesh)
    data_spec = P(ax.data, ax.seq)

    def local_step(params, velocity, tokens, labels, mask):
        loss, grads = jax.value_and_grad(local_loss)(
            params, tokens, labels, mask, cfg, ax)
        with jax.named_scope("optimizer"):
            velocity = jax.tree.map(lambda v, g: momentum * v + g,
                                    velocity, grads)
            params = jax.tree.map(lambda p, v: p - learning_rate * v,
                                  params, velocity)
        return params, velocity, loss

    # check_vma=False exists ONLY for interpret-mode Pallas kernels in
    # CPU tests (the HLO interpreter re-runs the kernel body with
    # vma-typed values, where kernel-internal iota/scratch constants
    # cannot be matched). It is sound only on single-device meshes:
    # without vma types the shard_map transpose does NOT insert the
    # cross-shard psums for replicated-parameter gradients (embed/head),
    # so a real multi-shard mesh silently under-reduces them —
    # tests/test_fused_ce.py pins this boundary from both sides.
    sharded = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(specs, specs, data_spec, data_spec, data_spec),
        out_specs=(specs, specs, P()), check_vma=check_vma)
    # donate params+velocity: the optimizer update happens in place in
    # HBM instead of allocating (and copying into) a second full copy
    # of the model state every step
    return jax.jit(sharded, donate_argnums=(0, 1) if donate else ())


# ---------------------------------------------------------------------------
# pjit (GSPMD) train step — the global-array formulation
#
# The manual shard_map program above expresses every collective
# explicitly; this one expresses NONE: the same math is written over
# the global arrays, params/batch arrive with NamedSharding layouts
# (the identical `param_specs` tree), and XLA/GSPMD inserts the grad
# allreduces and TP/EP collectives from the annotations. The one semantic
# subtlety is capacity-factor MoE: the manual step computes its
# capacity C and drops overflow *per rank's token shard*, so the
# global formulation reproduces that grouping exactly (tokens split
# into data x seq x expert contiguous groups — `_token_groups`), which
# keeps the two formulations bit-comparable drop-for-drop.


def _token_groups(h, D: int, Q: int):
    """Global ``[B, S, ...]`` -> rank-local token blocks
    ``[D*Q, T_local, ...]`` in exactly the manual step's order (batch
    sharded over ``data``, sequence over ``seq``, rows flattened
    batch-major within a rank)."""
    B, S = h.shape[0], h.shape[1]
    rest = h.shape[2:]
    g = h.reshape(D, B // D, Q, S // Q, *rest)
    g = jnp.moveaxis(g, 2, 1)                  # [D, Q, B/D, S/Q, ...]
    return g.reshape(D * Q, (B // D) * (S // Q), *rest)


def _ungroup_tokens(g, D: int, Q: int, B: int, S: int):
    """Inverse of :func:`_token_groups`."""
    rest = g.shape[2:]
    g = g.reshape(D, Q, B // D, S // Q, *rest)
    g = jnp.moveaxis(g, 1, 2)                  # [D, B/D, Q, S/Q, ...]
    return g.reshape(B, S, *rest)


def _pjit_moe_grouped(bp, x, cfg: TransformerConfig, D: int, Q: int,
                      E_ax: int):
    """Capacity-factor token-choice MoE, group-wise: the global twin of
    :func:`_moe_capacity`. Each of the ``D*Q*E_ax`` token groups
    builds its own capacity queues (same engines, same overflow
    drops); expert FFNs run on the full queue set — numerically what
    the manual step's all_to_all round-trip computes."""
    import math
    dt = _compute_dtype(cfg)
    h = _rmsnorm(x, bp["ln2"])
    logits = jnp.einsum("bsd,de->bse", h, bp["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    B, S, d = x.shape
    T, E = B * S, cfg.n_experts
    n_rank = D * Q
    if T % n_rank:
        raise ValueError(f"tokens ({T}) must divide over data x seq "
                         f"({n_rank})")
    T_local = T // n_rank
    if T_local % E_ax:
        raise ValueError(
            f"capacity MoE dispatch needs local tokens ({T_local}) "
            f"divisible by the expert axis ({E_ax})")
    T_sh = T_local // E_ax
    k = cfg.moe_top_k
    C = max(int(math.ceil(cfg.moe_capacity_factor * T_sh * k / E)), 1)
    hg = _token_groups(h, D, Q)                # [n_rank, T_local, d]
    pg = _token_groups(probs, D, Q)            # [n_rank, T_local, E]
    engine = (_sorted_capacity_queues if cfg.moe_dispatch == "sort"
              else _scatter_capacity_queues)
    ew1, ew2 = bp["ew1"], bp["ew2"]
    if cfg.moe_dispatch not in ("sort", "scatter"):
        raise ValueError(f"unknown moe_dispatch {cfg.moe_dispatch!r}")
    out_groups = []
    for g in range(n_rank):
        wts, experts = _route_top_k(pg[g], k)  # [T_local, k]
        parts = []
        for er in range(E_ax):
            sl = slice(er * T_sh, (er + 1) * T_sh)
            top = experts[sl].reshape(T_sh * k)
            wf = wts[sl].reshape(T_sh * k)
            disp, combine = engine(
                jnp.repeat(hg[g][sl].astype(dt), k, axis=0),
                top, wf, E, C, dt)
            z = jax.nn.relu(jnp.einsum("ecd,edf->ecf", disp,
                                       ew1.astype(dt)))
            y = jnp.einsum("ecf,efd->ecd", z,
                           ew2.astype(dt)).astype(jnp.float32)
            yflat = combine(y)                 # [T_sh*k, d]
            parts.append(jnp.sum(yflat.reshape(T_sh, k, d), axis=1))
        out_groups.append(jnp.concatenate(parts, axis=0))
    ytok = jnp.stack(out_groups)               # [n_rank, T_local, d]
    y = _ungroup_tokens(ytok, D, Q, B, S)
    # aux statistics are token-LINEAR, so the global means equal the
    # manual step's pmean-over-token-axes exactly (equal-size groups)
    E_ = cfg.n_experts
    f_stat = (jnp.zeros(E_, jnp.float32), jnp.zeros(E_, jnp.float32))
    if cfg.moe_aux_weight > 0:
        _, exp_all = _route_top_k(probs.reshape(T, E), k)
        f_stat = _router_stats(probs.reshape(T, E), exp_all[:, 0], E, ())
    z_stat = jnp.float32(0.0)
    if cfg.moe_zloss_weight > 0:
        lse = jax.nn.logsumexp(logits.reshape(T, E), axis=-1)
        z_stat = jnp.mean(jnp.square(lse))
    return y, (*f_stat, z_stat)


def _pjit_moe_expert_choice(bp, x, cfg: TransformerConfig, D: int,
                            Q: int, E_ax: int):
    """Expert-choice routing, group-wise: the global twin of
    :func:`_moe_expert_choice` (experts pick their top-C tokens WITHIN
    each rank-shaped token group)."""
    import math
    dt = _compute_dtype(cfg)
    h = _rmsnorm(x, bp["ln2"])
    logits = jnp.einsum("bsd,de->bse", h, bp["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    B, S, d = x.shape
    T, E = B * S, cfg.n_experts
    n_rank = D * Q
    T_local = T // n_rank
    if T_local % E_ax:
        raise ValueError(
            f"expert-choice MoE needs local tokens ({T_local}) "
            f"divisible by the expert axis ({E_ax})")
    T_sh = T_local // E_ax
    C = max(int(math.ceil(cfg.moe_capacity_factor * T_sh / E)), 1)
    hg = _token_groups(h, D, Q).reshape(n_rank * E_ax, T_sh, d)
    pg = _token_groups(probs, D, Q).reshape(n_rank * E_ax, T_sh, E)
    ew1, ew2 = bp["ew1"], bp["ew2"]
    outs = []
    for g in range(n_rank * E_ax):
        wts, idx = jax.lax.top_k(pg[g].T, min(C, T_sh))  # (E, C)
        disp = hg[g][idx].astype(dt)
        z = jax.nn.relu(jnp.einsum("ecd,edf->ecf", disp,
                                   ew1.astype(dt)))
        y = jnp.einsum("ecf,efd->ecd", z,
                       ew2.astype(dt)).astype(jnp.float32)
        outs.append(jnp.zeros((T_sh, d), jnp.float32)
                    .at[idx.reshape(-1)]
                    .add(y.reshape(-1, d) * wts.reshape(-1)[:, None]))
    ytok = jnp.stack(outs).reshape(n_rank, T_local, d)
    y = _ungroup_tokens(ytok, D, Q, B, S)
    E_ = cfg.n_experts
    stats = (jnp.zeros(E_, jnp.float32), jnp.zeros(E_, jnp.float32))
    z_stat = jnp.float32(0.0)
    if cfg.moe_zloss_weight > 0:
        lse = jax.nn.logsumexp(logits.reshape(T, E), axis=-1)
        z_stat = jnp.mean(jnp.square(lse))
    return y, (*stats, z_stat)


def _pjit_moe_dense(bp, x, cfg: TransformerConfig):
    """Dense-dispatch token-choice MoE over the global batch — the
    global twin of :func:`_moe`'s default branch (identical to the
    reference math)."""
    dt = _compute_dtype(cfg)
    h = _rmsnorm(x, bp["ln2"])
    logits = jnp.einsum("bsd,de->bse", h, bp["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    wts, experts = _route_top_k(probs, cfg.moe_top_k)
    h_c = h.astype(dt)
    y = jnp.zeros_like(x)
    for e in range(cfg.n_experts):
        sel = jnp.sum((experts == e).astype(jnp.float32) * wts, axis=-1)
        z = jax.nn.relu(jnp.einsum("bsd,df->bsf", h_c,
                                   bp["ew1"][e].astype(dt)))
        z = jnp.einsum("bsf,fd->bsd", z,
                       bp["ew2"][e].astype(dt)).astype(jnp.float32)
        y = y + z * sel[..., None]
    E = cfg.n_experts
    f_stat = (jnp.zeros(E, jnp.float32), jnp.zeros(E, jnp.float32))
    if cfg.moe_aux_weight > 0:
        f_stat = _router_stats(probs.reshape(-1, E),
                               experts[..., 0].reshape(-1), E, ())
    z_stat = jnp.float32(0.0)
    if cfg.moe_zloss_weight > 0:
        lse = jax.nn.logsumexp(logits, axis=-1)
        z_stat = jnp.mean(jnp.square(lse))
    return y, (*f_stat, z_stat)


def _pjit_moe(bp, x, cfg: TransformerConfig, D: int, Q: int, E_ax: int):
    """MoE branch selection mirroring :func:`_moe`, global form."""
    if cfg.moe_router == "expert_choice":
        if cfg.moe_capacity_factor <= 0:
            raise ValueError("moe_router='expert_choice' needs "
                             "moe_capacity_factor > 0 (defines C)")
        return _pjit_moe_expert_choice(bp, x, cfg, D, Q, E_ax)
    if cfg.moe_router != "token":
        raise ValueError(f"unknown moe_router {cfg.moe_router!r}")
    if cfg.moe_capacity_factor > 0:
        return _pjit_moe_grouped(bp, x, cfg, D, Q, E_ax)
    return _pjit_moe_dense(bp, x, cfg)


def _pjit_attention(bp, x, cfg: TransformerConfig, pos):
    """Global-batch attention with the manual step's mixed-precision
    flow (heavy matmuls in ``cfg.dtype``, rope/softmax/residuals f32).
    Always the XLA dense engine: the Pallas kernels are per-device
    programs and stay with the shard_map formulation."""
    dt = _compute_dtype(cfg)
    mm_dt = dt if dt != jnp.float32 else None
    h = _rmsnorm(x, bp["ln1"])
    with jax.named_scope("attn.qkv"):
        h = h.astype(dt)
        q = jnp.einsum("bsd,dhk->bshk", h,
                       bp["wq"].astype(dt)).astype(jnp.float32)
        k = jnp.einsum("bsd,dhk->bshk", h,
                       bp["wk"].astype(dt)).astype(jnp.float32)
        v = jnp.einsum("bsd,dhk->bshk", h,
                       bp["wv"].astype(dt)).astype(jnp.float32)
        q, k = _rope(q, pos), _rope(k, pos)
    with jax.named_scope("attn.core"):
        a = dense_attention(q, k, v, causal=True, compute_dtype=mm_dt)
    with jax.named_scope("attn.out"):
        return jnp.einsum("bshk,hkd->bsd", a.astype(dt),
                          bp["wo"].astype(dt)).astype(jnp.float32)


def _pjit_loss(params, tokens, labels, mask, cfg: TransformerConfig,
               groups: "Tuple[int, int, int]", ce_impl: str):
    """The global-array loss: identical math to ``local_loss`` (same
    CE, same aux/z-loss formulas, group-faithful capacity dispatch)
    with the pipeline schedule flattened to a sequential stage loop —
    a pure perf schedule, not a semantic one, so the loss is
    unchanged."""
    D, Q, E_ax = groups
    B, S = tokens.shape
    with jax.named_scope("embed"):
        x = params["embed"][tokens]
    pos = jnp.arange(S)
    aux_total = jnp.float32(0.0)
    z_total = jnp.float32(0.0)
    for s in range(cfg.n_stages):
        for bp_all in params["blocks"]:
            bp = {k: v[s] for k, v in bp_all.items()}
            x = x + _pjit_attention(bp, x, cfg, pos)
            if cfg.n_experts:
                with jax.named_scope("moe"):
                    y, (f, P_, z) = _pjit_moe(bp, x, cfg, D, Q, E_ax)
                x = x + y
                if cfg.moe_aux_weight > 0:
                    aux_total = aux_total + cfg.n_experts * jnp.sum(f * P_)
                if cfg.moe_zloss_weight > 0:
                    z_total = z_total + z
            else:
                dt = _compute_dtype(cfg)
                h = _rmsnorm(x, bp["ln2"])
                with jax.named_scope("ffn"):
                    h = h.astype(dt)
                    z = jax.nn.relu(
                        jnp.einsum("bsd,df->bsf", h, bp["w1"].astype(dt))
                        + bp["b1"].astype(dt))
                    y = jnp.einsum("bsf,fd->bsd", z,
                                   bp["w2"].astype(dt)).astype(jnp.float32)
                x = x + y + bp["b2"]
    h = _rmsnorm(x, params["final_norm"])
    dt = _compute_dtype(cfg)
    if ce_impl in ("fused", "fused_interpret"):
        from mmlspark_tpu.ops.fused_ce import fused_softmax_xent
        with jax.named_scope("ce"):
            ce = fused_softmax_xent(
                h.reshape(B * S, cfg.d_model), params["head"],
                labels.reshape(B * S), compute_dtype=dt,
                interpret=ce_impl == "fused_interpret").reshape(B, S)
    else:
        with jax.named_scope("head"):
            if dt != jnp.float32:
                logits = jnp.einsum("bsd,dv->bsv", h.astype(dt),
                                    params["head"].astype(dt),
                                    preferred_element_type=jnp.float32)
            else:
                logits = jnp.einsum("bsd,dv->bsv", h, params["head"])
        with jax.named_scope("ce"):
            lse = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, labels[..., None],
                                       axis=-1)[..., 0]
            ce = lse - gold
    loss = jnp.sum(ce * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    if cfg.n_experts and cfg.moe_aux_weight > 0:
        loss = loss + cfg.moe_aux_weight * aux_total
    if cfg.n_experts and cfg.moe_zloss_weight > 0:
        loss = loss + cfg.moe_zloss_weight * z_total
    return loss


def build_pjit_train_step(cfg: TransformerConfig, mesh,
                          learning_rate: float = 0.1,
                          momentum: float = 0.9,
                          donate: bool = True):
    """The train step as ONE global GSPMD program (pjit): same
    signature, layouts (:func:`param_specs`), and math as the
    shard_map formulation — XLA inserts every collective from the
    ``NamedSharding`` annotations. Fixed-seed parity between the
    formulations is pinned in tests/test_transformer.py.

    The Pallas attention/CE kernels are per-device programs: this
    formulation uses the XLA engines except on a single-device mesh,
    where an explicitly requested fused CE still runs (the ``auto``
    resolution matches ``local_loss``'s gates there)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    ax = _validate_mesh_config(cfg, mesh)
    n_dev = int(mesh.devices.size)
    specs = param_specs(cfg, mesh)
    is_spec = lambda s: isinstance(s, P)  # noqa: E731
    p_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=is_spec)
    data_sh = NamedSharding(mesh, P(ax.data, ax.seq))
    repl = NamedSharding(mesh, P())
    groups = (mesh.shape.get(ax.data, 1) if ax.data else 1,
              mesh.shape.get(ax.seq, 1) if ax.seq else 1,
              mesh.shape.get(ax.expert, 1) if ax.expert else 1)
    ce_impl = cfg.ce_impl
    if ce_impl == "auto":
        # the fused-CE kernel is a per-device program; "auto" under the
        # global formulation resolves to the XLA path (explicit
        # requests still run it on a single-device mesh, where no
        # partitioning exists to break it)
        ce_impl = "xla"
    elif ce_impl in ("fused", "fused_interpret") and n_dev > 1:
        import warnings
        warnings.warn(
            f"ce_impl={cfg.ce_impl!r} is a per-device Pallas kernel; "
            f"the pjit train-step formulation on a {n_dev}-device mesh "
            f"uses the XLA CE path instead (the shard_map formulation "
            f"runs the kernel per shard)", stacklevel=2)
        ce_impl = "xla"

    def step(params, velocity, tokens, labels, mask):
        loss, grads = jax.value_and_grad(_pjit_loss)(
            params, tokens, labels, mask, cfg, groups, ce_impl)
        with jax.named_scope("optimizer"):
            velocity = jax.tree.map(lambda v, g: momentum * v + g,
                                    velocity, grads)
            params = jax.tree.map(lambda p, v: p - learning_rate * v,
                                  params, velocity)
        return params, velocity, loss

    return jax.jit(
        step,
        in_shardings=(p_sh, p_sh, data_sh, data_sh, data_sh),
        out_shardings=(p_sh, p_sh, repl),
        donate_argnums=(0, 1) if donate else ())


def shard_params(params, cfg: TransformerConfig, mesh):
    """Device-put a host param pytree with the canonical layout."""
    from jax.sharding import NamedSharding

    specs = param_specs(cfg, mesh)
    return jax.tree.map(
        lambda p, s: jax.device_put(p, NamedSharding(mesh, s)),
        params, specs)


# ---------------------------------------------------------------------------
# checkpoint / resume


def save_train_state(path: str, params, velocity, step: int,
                     max_to_keep: int = 3) -> None:
    """Checkpoint the SPMD training state (params + velocity) at
    ``step``. Sharded arrays are written shard-by-shard (the native
    sharded store in :mod:`mmlspark_tpu.io.checkpoint` — no host
    gather); the on-disk format is mesh-layout independent, so a
    resume may use a different mesh (fewer/more chips, different axis
    split) than the run that saved it, and the digest manifest written
    last keeps every step flip-eligible for the rollout plane.
    """
    from mmlspark_tpu.io import checkpoint as _ckpt
    mngr = _ckpt.manager(path, max_to_keep)
    mngr.save(step, {"params": params, "velocity": velocity})
    mngr.wait_until_finished()
    mngr.close()


def restore_train_state(path: str, cfg: TransformerConfig, mesh,
                        step: Optional[int] = None):
    """Restore ``(params, velocity, step)`` directly onto ``mesh``'s
    canonical shardings (:func:`param_specs`: each device shard is
    assembled from only the saved files that overlap it) — the resume
    half of :func:`save_train_state`, valid across mesh layouts.
    ``step=None`` restores the latest checkpoint."""
    from jax.sharding import NamedSharding
    from mmlspark_tpu.io import checkpoint as _ckpt
    from mmlspark_tpu.io import fs as _fs
    if not _fs.exists(path):
        raise FileNotFoundError(f"no checkpoint under {path!r}")
    mngr = _ckpt.manager(path, create=False)
    target = step if step is not None else mngr.latest_step()
    if target is None:
        raise FileNotFoundError(f"no checkpoint under {path!r}")
    template = jax.eval_shape(lambda: init_params(cfg, seed=0))
    specs = param_specs(cfg, mesh)
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                             is_leaf=lambda x: isinstance(
                                 x, jax.sharding.PartitionSpec))
    state_template = {"params": template, "velocity": template}
    state_shardings = {"params": shardings, "velocity": shardings}
    restored = mngr.restore(target, state_template,
                            shardings=state_shardings)
    mngr.close()
    return restored["params"], restored["velocity"], target


def make_batch(rng: np.random.Generator, cfg: TransformerConfig,
               batch: int, seq: int):
    """Synthetic next-token batch (tokens, labels, mask) for tests/bench."""
    toks = rng.integers(0, cfg.vocab, size=(batch, seq + 1), dtype=np.int64)
    tokens = jnp.asarray(toks[:, :-1].astype(np.int32))
    labels = jnp.asarray(toks[:, 1:].astype(np.int32))
    mask = jnp.ones((batch, seq), jnp.float32)
    return tokens, labels, mask


# ---------------------------------------------------------------------------
# autoregressive decode: slot-indexed KV-cache pool
#
# The serving-side decode path. Shapes are FIXED at build time
# ([n_slots, ...] for the single-token step, a bucketed prompt ladder
# for prefill), the cache is one preallocated pool donated through
# every call (cache-in buffers are reused for cache-out — zero
# steady-state HBM allocations), and requests address it by SLOT: a
# request claims a free slot, prefill fills rows [0, len) of that
# slot's lane in every layer, each decode step appends one row at its
# position, and freeing the slot is just returning the index — the
# next occupant's prefill overwrites the lane. Dense-MLP and
# token-choice MoE configs (dense dispatch — see _decode_ffn);
# expert-choice routing is refused (it couples slots).
# Replicated per worker by default; under tensor parallelism
# (``decode_param_specs`` + ``decode_cache_spec``) ONE model and ONE
# pool span the mesh — heads and the MLP hidden shard over ``model``,
# each device's cache holds its heads' lanes, and the same jitted
# prefill/step run as sharded computations (XLA inserts the fan-in
# collectives; shapes, donation, and the compile-once contract are
# unchanged).


def _decode_block_params(params, cfg: TransformerConfig
                         ) -> List[Dict[str, Any]]:
    """Per-layer param dicts in reference order (stage-major), with
    the leading ``n_stages`` dim indexed away."""
    out = []
    for s in range(cfg.n_stages):
        for bp_all in params["blocks"]:
            out.append({k: v[s] for k, v in bp_all.items()})
    return out


def _rope_at(x, pos, recipe: BlockRecipe = BlockRecipe()):
    """Rotary embedding for mid-sequence tokens: ``x`` [..., H, Dh] at
    positions ``pos`` matching the leading dims (``[N]`` for the
    single-token step, ``[N, W]`` for the speculative verify step —
    each slot is mid-sequence at its own depth, the batched analogue
    of :func:`_rope` at short S). ``recipe`` gives the base and which
    columns pair up."""
    dh = x.shape[-1]
    freqs = 1.0 / (recipe.rope_base ** (jnp.arange(0, dh, 2) / dh))
    ang = pos[..., None].astype(jnp.float32) * freqs      # [..., Dh/2]
    cos = jnp.cos(ang)[..., None, :]                      # [..., 1, Dh/2]
    sin = jnp.sin(ang)[..., None, :]
    if recipe.rope_layout == "half":
        x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               axis=-1)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    r1 = x1 * cos - x2 * sin
    r2 = x1 * sin + x2 * cos
    return jnp.stack([r1, r2], axis=-1).reshape(x.shape)


def _check_default_recipe(cfg: TransformerConfig, what: str) -> None:
    """``what`` implements the default recipe and no other: refuse by
    name instead of running another block under this one's sizes."""
    ours = BlockRecipe()
    odd = [f"{f.name}={getattr(cfg.recipe, f.name)!r}"
           for f in dataclasses.fields(BlockRecipe)
           if getattr(cfg.recipe, f.name) != getattr(ours, f.name)]
    if odd:
        raise NotImplementedError(
            f"{what} implements the default block recipe only, not "
            f"{', '.join(odd)}: the paged decode programs "
            f"(build_paged_prefill / _prefix_prefill / _decode_step) "
            f"read the recipe")


def _check_decode_config(cfg: TransformerConfig, looped: bool = True
                         ) -> None:
    """Refuse what the decode programs do not implement, by name.
    ``looped=False``: the unpaged draft programs and the verify step,
    which run one pass and hold no pass-keyed rows."""
    r = cfg.recipe
    if cfg.n_experts and cfg.moe_router == "expert_choice":
        raise NotImplementedError(
            "expert-choice MoE has no decode form: each expert picks "
            "its top tokens ACROSS the batch, so slots would couple — "
            "the property continuous batching forbids. Token-choice "
            "MoE decodes via dense dispatch (_decode_ffn).")
    if cfg.n_experts and r.ffn != "relu_bias":
        raise NotImplementedError(
            f"recipe ffn={r.ffn!r} with n_experts={cfg.n_experts}: the "
            f"decode MoE is the ReLU expert pair (_decode_ffn)")
    if cfg.dtype not in ("float32", "bfloat16"):
        raise NotImplementedError(
            f"decode compute dtype {cfg.dtype!r}: float32 or bfloat16")
    if r.looped and r.exit_threshold < 1.0:
        raise NotImplementedError(
            f"exit_threshold={r.exit_threshold} < 1: a token that "
            f"leaves the loop before its last pass is not built (a "
            f"step whose cost differs by slot, later tokens wanting "
            f"rows of passes an earlier token never ran: ROADMAP B12); "
            f"the published threshold 1.0 runs every pass")
    if r.looped and not looped:
        raise NotImplementedError(
            f"n_loops={r.n_loops}: the unpaged lanes (the speculation "
            f"draft's) and the verify step hold one row a (layer, "
            f"position); a looped stack has no draft yet (a model of "
            f"fewer passes: ROADMAP B12)")


def _q_matmul(x, w_q, w_s, act_dtype=jnp.bfloat16):
    """int8-weight matmul for the quantized decode FFN: ``x`` [T, I]
    f32, ``w_q`` [I, O] int8, ``w_s`` [O] f32 per-output-channel
    scales. The activation and the (exactly representable) int8
    weights meet as ``act_dtype`` on the MXU with f32 accumulation
    (``preferred_element_type``), and the scales fold into the f32
    accumulator AFTER the contraction — one multiply per output
    element, full scale precision. Returns f32 [T, O]."""
    acc = jax.lax.dot_general(
        x.astype(act_dtype), w_q.astype(act_dtype),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return acc * w_s


def quantize_decode_ffn(params, cfg: TransformerConfig,
                        scale_multiplier: float = 1.0):
    """Per-channel int8 quantization of the decode FFN weights —
    computed ONCE (rollout stage time), served forever.

    For every stage's ``w1`` [s, D, F] / ``w2`` [s, F, D], symmetric
    per-output-channel scales ``amax(|w|, axis=input) / 127`` (f32,
    zero-channels guard to 1.0), weights rounded into ``w1_q``/
    ``w2_q`` int8 with ``w1_s``/``w2_s`` scale vectors alongside; the
    f32 originals are dropped from the returned tree (the HBM win —
    biases and everything outside the FFN stay f32: rope, softmax,
    attention, and the residual stream keep the reference numerics,
    mirroring the ``cfg.dtype`` flow in the train path). MoE configs
    are refused — dense dispatch re-runs every expert per token, so
    there is no hot single matmul to win on yet.

    ``scale_multiplier`` deliberately corrupts the stored scales when
    != 1.0 — the chaos knob the rollout-verify tests use to prove a
    broken quantized config fails parity and never flips."""
    _check_decode_config(cfg)
    if cfg.n_experts or cfg.recipe.ffn != "relu_bias":
        raise NotImplementedError(
            "quantized decode FFN supports dense ReLU-MLP configs only")
    out = dict(params)
    blocks = []
    for bp_all in params["blocks"]:
        b = {k: v for k, v in bp_all.items()
             if k not in ("w1", "w2")}
        for name, axis in (("w1", 1), ("w2", 1)):
            w = jnp.asarray(bp_all[name], jnp.float32)  # [s, I, O]
            s = jnp.max(jnp.abs(w), axis=axis) / 127.0  # [s, O]
            s = jnp.where(s > 0, s, 1.0)
            q = jnp.clip(jnp.round(w / s[:, None, :]),
                         -127, 127).astype(jnp.int8)
            b[name + "_q"] = q
            b[name + "_s"] = (s * float(scale_multiplier)
                              ).astype(jnp.float32)
        blocks.append(b)
    out["blocks"] = blocks
    return out


def _decode_ffn(bp, h, cfg: TransformerConfig):
    """The decode paths' FFN over post-``ln2`` activations ``h``
    ([..., D] — [1, S, D] prefill, [N, D] step, [N, W, D] verify).

    Dense-MLP configs run the plain two-matmul FFN. MoE configs run
    token-choice routing with **dense dispatch**: at decode the batch
    is one token per slot, so capacity queues degenerate (C would be
    0 or 1 and dropping a routing truncates a LIVE sequence) — every
    expert runs on every token and the top-k router weights combine,
    which is exactly :func:`_reference_forward`'s MoE math (the decode
    parity golden). Compute scales with ``n_experts``, acceptable at
    decode's tiny token counts; ``moe_capacity_factor`` is ignored
    here by design."""
    with jax.named_scope("moe" if cfg.n_experts else "ffn"):
        return _decode_ffn_body(bp, h, cfg)


def _dmm(a, w, dt):
    """A decode program's matrix product ``a @ w``: operands in the
    compute dtype ``dt``, float32 out."""
    return jnp.matmul(a.astype(dt), w.astype(dt),
                      preferred_element_type=jnp.float32)


def _dproj(spec: str, a, w, dt):
    """:func:`_dmm` for the head-shaped projections (an einsum)."""
    return jnp.einsum(spec, a.astype(dt), w.astype(dt),
                      preferred_element_type=jnp.float32)


def _heads(a, w, cfg: TransformerConfig):
    """``a [..., D]`` through ``w [D, H, Dh]`` -> float32 ``[..., H,
    Dh]``. In a looped stack (``recipe.looped``) the weights are a
    loop's carry, whose layout XLA is free to pick: for this product it
    wants the heads outermost and copies every q, k and v matrix to get
    them there, once a step (1.2 GB of temporaries and 2.4 GB of
    traffic at 48 layers of 2048 x 16 x 128, by the AOT compiler's
    memory analysis for the v5e; the two forms' step times were not
    measured against each other on the chip: PERF.md section 7). So
    there it is ONE product over ``w`` seen as ``[D, H * Dh]``, the
    layout the weights arrive in, and the barrier keeps the reshape
    behind it from being folded back in. The condition is the carry,
    not the dtype: an unlooped stack keeps the einsum in either."""
    dt = _compute_dtype(cfg)
    if not cfg.recipe.looped:
        return _dproj("...d,dhk->...hk", a, w, dt)
    d, h, dh = w.shape
    y = jax.lax.optimization_barrier(_dmm(a, w.reshape(d, h * dh), dt))
    return y.reshape(a.shape[:-1] + (h, dh))


def _decode_ffn_body(bp, h, cfg: TransformerConfig):
    shape = h.shape
    hf = h.reshape(-1, shape[-1])
    if cfg.n_experts:
        logits = hf @ bp["router"]                        # [T, E]
        probs = jax.nn.softmax(logits, axis=-1)
        wts, experts = _route_top_k(probs, cfg.moe_top_k)
        y = jnp.zeros_like(hf)
        for e in range(cfg.n_experts):
            sel = jnp.sum((experts == e).astype(jnp.float32) * wts,
                          axis=-1)
            z = jax.nn.relu(hf @ bp["ew1"][e])
            y = y + (z @ bp["ew2"][e]) * sel[:, None]
        return y.reshape(shape)
    if "w1_q" in bp:
        # int8-compute FFN (quantize_decode_ffn): int8 weights meet
        # bf16 activations on the MXU, f32 accumulate, per-channel
        # dequant on the accumulator; biases and the residual add
        # stay f32
        z = jax.nn.relu(_q_matmul(hf, bp["w1_q"], bp["w1_s"])
                        + bp["b1"])
        return (_q_matmul(z, bp["w2_q"], bp["w2_s"])
                + bp["b2"]).reshape(shape)
    dt = _compute_dtype(cfg)
    if cfg.recipe.ffn == "gated_silu":
        z = jax.nn.silu(_dmm(hf, bp["w_gate"], dt)) * _dmm(hf, bp["w_up"],
                                                           dt)
        return _dmm(z, bp["w_down"], dt).reshape(shape)
    z = jax.nn.relu(_dmm(hf, bp["w1"], dt) + bp["b1"])
    return (_dmm(z, bp["w2"], dt) + bp["b2"]).reshape(shape)


def decode_param_specs(cfg: TransformerConfig, mesh,
                       quantized_ffn: bool = False) -> Dict[str, Any]:
    """PartitionSpec tree for the decode path's params under tensor
    parallelism: attention heads and the MLP hidden shard over the
    ``model`` axis (the Megatron split — each device holds its heads'
    K/V lanes and its hidden slice; XLA inserts the out-proj/MLP
    fan-in collectives), embed/head/norms replicated. Requires
    ``n_heads`` and ``d_ff`` divisible by the model-axis size.
    ``quantized_ffn`` describes a :func:`quantize_decode_ffn` tree:
    the int8 weights take their f32 originals' split and each scale
    vector shards with its matmul's OUTPUT channels (``w1_s`` over the
    hidden like ``b1``, ``w2_s`` replicated like ``b2``)."""
    from jax.sharding import PartitionSpec as P

    _check_decode_config(cfg)
    _check_default_recipe(cfg, "tensor-parallel decode "
                               "(decode_param_specs)")
    model = AXIS_MODEL if AXIS_MODEL in mesh.axis_names else None
    tp = mesh.shape.get(AXIS_MODEL, 1)
    if model and cfg.n_heads % tp:
        raise ValueError(f"n_heads={cfg.n_heads} must divide over the "
                         f"model axis ({tp})")
    if model and cfg.d_ff % tp:
        raise ValueError(f"d_ff={cfg.d_ff} must divide over the "
                         f"model axis ({tp})")
    specs: Dict[str, Any] = {"embed": P(), "head": P(), "final_norm": P()}
    blocks = []
    for _ in range(cfg.layers_per_stage):
        b = {
            "ln1": P(), "ln2": P(),
            "wq": P(None, None, model, None),
            "wk": P(None, None, model, None),
            "wv": P(None, None, model, None),
            "wo": P(None, model, None, None),
        }
        if cfg.n_experts:
            # MoE decode (dense dispatch): router replicated, expert
            # FFNs Megatron-split over the hidden dim — the same
            # fan-in psum the dense MLP split relies on
            b["router"] = P()
            b["ew1"] = P(None, None, None, model)
            b["ew2"] = P(None, None, model, None)
        elif quantized_ffn:
            b["w1_q"] = P(None, None, model)
            b["w1_s"] = P(None, model)
            b["b1"] = P(None, model)
            b["w2_q"] = P(None, model, None)
            b["w2_s"] = P()
            b["b2"] = P()
        else:
            b["w1"] = P(None, None, model)
            b["b1"] = P(None, model)
            b["w2"] = P(None, model, None)
            b["b2"] = P()
        blocks.append(b)
    specs["blocks"] = blocks
    return specs


def decode_cache_spec(mesh):
    """The KV pool's sharding under tensor parallelism: the head dim
    over the ``model`` axis — axis 2 of every leaf of the paged pool
    (one ``[n_pages, page_size, H, Dh]`` array a layer) — so each
    device's cache holds exactly its heads' lanes and the pool's HBM
    footprint splits across the mesh."""
    from jax.sharding import PartitionSpec as P
    model = AXIS_MODEL if AXIS_MODEL in mesh.axis_names else None
    return P(None, None, model, None)


def init_kv_cache(cfg: TransformerConfig, n_slots: int, max_len: int
                  ) -> Dict[str, jax.Array]:
    """The preallocated slot-indexed KV pool: ``{"k", "v"}`` arrays of
    shape ``[n_layers, n_slots, max_len, n_heads, d_head]`` (f32 — the
    decode path mirrors the reference forward's numerics so greedy
    decode matches the full-context argmax token-for-token). Allocated
    ONCE; every prefill/decode call donates it back in."""
    _check_decode_config(cfg, looped=False)
    shape = (cfg.n_layers, int(n_slots), int(max_len),
             cfg.n_heads, cfg.d_head)
    return {"k": jnp.zeros(shape, jnp.float32),
            "v": jnp.zeros(shape, jnp.float32)}


def _jit_decode(fn, donate: bool, cache_sharding=None,
                n_replicated: int = 2, looped: bool = False):
    """The decode builders' jit epilogue: ``fn(params, cache, ...) ->
    (cache, *outs)`` under its own ``__name__`` (``jit_step``,
    ``jit_prefill``; a looped stack's are ``jit_looped_step``,
    ``jit_looped_prefill``: what traces and the benchmark find it by),
    the cache donated. Under tensor parallelism the output layout is
    pinned: the cache keeps its canonical head sharding through every
    donated call (otherwise XLA may pick a different layout for the
    prefill's output than the step expects — one silent retrace per
    transition), the ``n_replicated`` outputs behind it (tokens,
    logits, scores) come back replicated (they are host-fetched
    anyway)."""
    kw = {}
    if cache_sharding is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        repl = NamedSharding(cache_sharding.mesh, P())
        kw["out_shardings"] = (
            {"k": cache_sharding, "v": cache_sharding},
        ) + (repl,) * n_replicated
    if looped:
        fn.__name__ = "looped_" + fn.__name__
    return jax.jit(fn, donate_argnums=(1,) if donate else (), **kw)


def _attn_kernel(kernel, attn_impl: str, cache_sharding, ranks, **kw):
    """A Pallas attention kernel of ``parallel/pallas_attention`` made
    ready for a decode program, or None under ``attn_impl="dense"``
    (XLA's softmax path); ``"pallas_interpret"`` interprets it, for
    CPU parity. Under a TP mesh the dispatch is sharding-aware: heads
    are independent in attention, so each model-axis shard runs the
    SAME kernel on its own head slice (q ``[N, H/t, Dh]``, pool
    ``[pages, page, H/t, Dh]``) with page tables and positions
    replicated — per-shard head-slice grids, no collective in either
    direction. ``ranks`` names each operand: its rank where it is
    ``[..., H, Dh]``, 0 where replicated; the output is shaped like
    the first. check_vma is irrelevant here (forward-only, nothing
    replicated is produced); False lets the interpret-mode parity
    tests run (see build_spmd_train_step on interpret + vma)."""
    if attn_impl not in ("dense", "pallas", "pallas_interpret"):
        raise ValueError(f"unknown attn_impl {attn_impl!r}")
    if attn_impl == "dense":
        return None
    kernel = functools.partial(
        kernel, interpret=attn_impl == "pallas_interpret", **kw)
    if cache_sharding is None \
            or cache_sharding.mesh.shape.get(AXIS_MODEL, 1) <= 1:
        return kernel
    from jax.sharding import PartitionSpec as P
    specs = tuple(P(*[None] * (r - 2), AXIS_MODEL, None) if r else P()
                  for r in ranks)
    return jax.shard_map(kernel, mesh=cache_sharding.mesh, in_specs=specs,
                         out_specs=specs[0], check_vma=False)


def _inflight_attention(cfg: TransformerConfig, attn_impl: str,
                        cache_sharding):
    """The cold prefills' ``attend``: attention over the ``[S, H, Dh]``
    q/k/v a prefill just computed (no cache is read). ``"dense"`` is
    the softmax path (the [S, S] score matrix materializes),
    ``"pallas"`` the streaming flash kernel
    (:func:`~mmlspark_tpu.parallel.pallas_attention.
    flash_prefill_attention` — no [S, S] intermediate),
    ``"pallas_interpret"`` the kernel interpreted for CPU parity."""
    from mmlspark_tpu.parallel.pallas_attention import (
        flash_prefill_attention)
    dt = _compute_dtype(cfg)
    core = _attn_kernel(flash_prefill_attention, attn_impl, cache_sharding,
                        (4, 4, 4), scale=cfg.d_head ** -0.5) \
        or functools.partial(dense_attention, causal=True,
                             compute_dtype=None if dt == jnp.float32 else dt)

    def attend(l, q, k, v):
        # both engines take a batch: a prefill is a batch of one prompt
        with jax.named_scope("attn.core"):
            return core(q[None], k[None], v[None])[0]

    return attend


def _lane_attention(q, lk, lv, qpos):
    """Softmax attention over gathered lanes: queries ``q [N, H, Dh]``
    (one a slot: the single-token step) or ``[N, W, H, Dh]`` (a
    verify's window; a prefix prefill is N = 1) at virtual rows
    ``qpos [N]`` / ``[N, W]`` over each slot's lane ``lk``/``lv [N, V,
    H, Dh]``. A query reads ``index <= qpos``, so rows not yet
    overwritten (padding tails, the last occupant's leftovers, the
    scratch page) are dead by construction."""
    w = "w" if q.ndim == 4 else ""
    mask = jnp.arange(lk.shape[1]) <= qpos[..., None, None]
    s = jnp.einsum(f"n{w}hk,nshk->n{w}hs", q, lk,
                   preferred_element_type=jnp.float32) * q.shape[-1] ** -0.5
    s = jnp.where(mask, s, -1e30)                  # [N, (W,) 1, V] bcast
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum(f"n{w}hs,nshk->n{w}hk", p.astype(lv.dtype), lv,
                      preferred_element_type=jnp.float32)


def _attend_pages(k_l, v_l, q, tables, qpos, kernel=None, kernel_pos=None):
    """Attention over virtual lanes of one layer's pools ``k_l``/``v_l
    [n_pages, page_size, H, Dh]``: by ``kernel`` (the pool goes to it
    whole: the page table names the pages that are read, and no lane-
    or score-shaped value enters the jaxpr) or by gathering each
    slot's lane from its pages."""
    if kernel is not None:
        with jax.named_scope("attn.core"):
            return kernel(q, k_l, v_l, tables, kernel_pos)
    with jax.named_scope("kv.gather"):
        # [N, P, page, H, Dh] -> [N, virtual_len, H, Dh]
        lane = (tables.shape[0], -1) + q.shape[-2:]
        lk, lv = k_l[tables].reshape(lane), v_l[tables].reshape(lane)
    with jax.named_scope("attn.core"):
        return _lane_attention(q, lk, lv, qpos)


class _CacheView(NamedTuple):
    """What one decode program states about its cache, and all it
    states (the rest of a program is :func:`_decode_layers`): the
    pool it returns, how a layer's new K/V rows reach it (``write(l,
    k, v)``), and what attention reads (``attend(l, q, k, v)``, which
    opens ``kv.gather`` / ``attn.core`` itself; a cold prefill's is
    :func:`_inflight_attention`: the q/k/v in flight, never the
    pool)."""
    cache: Dict[str, Any]
    write: Callable
    attend: Callable


def _lanes(cache, slot, pos=None, inflight=None) -> _CacheView:
    """The unpaged pool (the speculation draft's): K and V one stacked
    ``[L, n_slots, max_len, H, Dh]`` array each. Rows go to ``[l,
    slot, pos]``: a prefill's ``[S, H, Dh]`` are rows ``[0, S)`` of ONE
    slot's lane (a scalar ``slot``: one ``dynamic_update_slice``), a
    step's ``[N, H, Dh]`` one row a slot, which then attends its own
    lane masked to ``index <= pos``."""
    pool = dict(cache)

    def write(l, k, v):
        if jnp.ndim(slot) == 0:
            put = lambda c, x: jax.lax.dynamic_update_slice(  # noqa: E731
                c, x[None, None], (l, slot, 0, 0, 0))
        else:
            put = lambda c, x: c.at[l, slot, pos].set(x)  # noqa: E731
        pool["k"], pool["v"] = put(pool["k"], k), put(pool["v"], v)

    def attend(l, q, k, v):
        with jax.named_scope("kv.gather"):
            lk, lv = pool["k"][l], pool["v"][l]
        with jax.named_scope("attn.core"):
            return _lane_attention(q, lk, lv, pos)

    return _CacheView(pool, write, inflight or attend)


def _table_pages(cache, page_table, hit_len, pos=None, inflight=None,
                 kernel=None) -> _CacheView:
    """Pages through ONE slot's table (the paged prefills): the rows
    ``[S, H, Dh]`` of virtual positions ``pos = hit_len + [0, S)`` go
    through ``page_table`` from entry ``hit_len // page_size`` on
    (:func:`_write_pages`; a cold prefill is ``hit_len = 0``). Behind
    a prefix hit attention reads the WHOLE virtual lane: shared prefix
    rows straight from their pages, suffix rows just written."""
    ck, cv = list(cache["k"]), list(cache["v"])

    def write(l, k, v):
        start = hit_len // ck[l].shape[1]
        ck[l] = _write_pages(ck[l], k, page_table, start)
        cv[l] = _write_pages(cv[l], v, page_table, start)

    def attend(l, q, k, v):
        if kernel is not None:
            return _attend_pages(ck[l], cv[l], q, page_table, None,
                                 kernel, hit_len)
        return _attend_pages(ck[l], cv[l], q[None], page_table[None],
                             pos[None])[0]

    return _CacheView({"k": ck, "v": cv}, write, inflight or attend)


def _slot_pages(cache, page_tables, qpos, pg, kernel=None) -> _CacheView:
    """Pages through EVERY slot's table (step and verify): the row of
    query position ``qpos`` (``[N]``, or ``[N, W]`` in a verify) goes
    to page ``pg``, row ``qpos % page_size``, and attention reads each
    slot's virtual lane masked to ``index <= qpos``."""
    ck, cv = list(cache["k"]), list(cache["v"])
    row = qpos % ck[0].shape[1]

    def write(l, k, v):
        ck[l], cv[l] = ck[l].at[pg, row].set(k), cv[l].at[pg, row].set(v)

    def attend(l, q, k, v):
        return _attend_pages(ck[l], cv[l], q, page_tables, qpos, kernel,
                             qpos)

    return _CacheView({"k": ck, "v": cv}, write, attend)


def _shifted(pages, shift):
    """Page ids ``pages`` as pass ``shift // n_pages`` of a looped
    stack addresses them (``_decode_layers``); the first and only pass
    of an unlooped one names them as they are."""
    return pages if isinstance(shift, int) and shift == 0 \
        else pages + shift


def _decode_pass(blocks, cfg: TransformerConfig, x, pos, view: _CacheView):
    """One pass of the stack, the ONE statement of a softmax decode
    layer: the residual stream ``x [..., D]`` (float32) at positions
    ``pos`` through every layer of ``blocks``, as ``cfg.recipe`` says a
    layer is and in ``cfg.dtype`` (weights, matmul operands and K/V rows
    in it; accumulation, the stream, norms, rotary and softmax
    float32)."""
    r, dt = cfg.recipe, _compute_dtype(cfg)
    sandwich = r.norms == "sandwich"
    for l, bp in enumerate(blocks):
        h = _rmsnorm(x, bp["ln1"], r.norm_eps)
        with jax.named_scope("attn.qkv"):
            q = _rope_at(_heads(h, bp["wq"], cfg), pos, r).astype(dt)
            k = _rope_at(_heads(h, bp["wk"], cfg), pos, r).astype(dt)
            v = _heads(h, bp["wv"], cfg).astype(dt)
        with jax.named_scope("kv.write"):
            view.write(l, k, v)
        a = view.attend(l, q, k, v)
        with jax.named_scope("attn.out"):
            o = _dproj("...hk,hkd->...d", a, bp["wo"], dt)
            x = x + (_rmsnorm(o, bp["ln1_post"], r.norm_eps)
                     if sandwich else o)
        m = _decode_ffn(bp, _rmsnorm(x, bp["ln2"], r.norm_eps), cfg)
        x = x + (_rmsnorm(m, bp["ln2_post"], r.norm_eps)
                 if sandwich else m)
    return x


def _decode_layers(params, cfg: TransformerConfig, tokens, pos, cache,
                   view_of: Callable):
    """The softmax stack as the decode programs run it: ``tokens`` at
    positions ``pos`` (one shape: ``[S]`` in a prefill, ``[N]`` in a
    step, ``[N, W]`` in a verify) through ``recipe.n_loops`` passes of
    every layer -> ``(cache, h [..., D], lam)``, ``h`` final-normed.
    Programs differ in ``view_of(cache, shift)``, their cache view.

    A looped stack (``n_loops > 1``) runs the SAME layers every pass,
    the final norm between passes (scope ``loop.norm``: the normalised
    state feeds the next pass), and reads the exit gate after each
    (``loop.gate``): ``lam [n_loops, ...]``, else None. Each layer's
    pool holds ``n_loops`` x ``n_pages`` pages and pass ``t`` addresses
    page ``id + t * n_pages`` (``shift``): its OWN K/V rows at every
    position, the same page ids in every pass, page 0 of each pass its
    scratch page. The passes are a ``fori_loop`` whose carry is the
    pool: the program holds one body a layer, not ``n_loops``."""
    r = cfg.recipe
    blocks = _decode_block_params(params, cfg)
    with jax.named_scope("embed"):
        x = params["embed"][tokens].astype(jnp.float32)    # [..., D]
    if not r.looped:
        view = view_of(cache, 0)
        x = _decode_pass(blocks, cfg, x, pos, view)
        return view.cache, _rmsnorm(x, params["final_norm"],
                                    r.norm_eps), None
    n_pages = cache["k"][0].shape[0] // r.n_loops

    def one_pass(t, carry):
        pool, x, lam = carry
        view = view_of(pool, t * n_pages)
        x = _decode_pass(blocks, cfg, x, pos, view)
        with jax.named_scope("loop.norm"):
            h = _rmsnorm(x, params["final_norm"], r.norm_eps)
        with jax.named_scope("loop.gate"):
            # a D -> 1 linear, on the VPU: float32 to the last bit
            gate = jax.nn.sigmoid(
                jnp.sum(h * params["exit_w"].astype(jnp.float32), -1)
                + params["exit_b"].astype(jnp.float32))
        return view.cache, h, lam.at[t].set(gate)

    lam = jnp.zeros((r.n_loops,) + x.shape[:-1], jnp.float32)
    return jax.lax.fori_loop(0, r.n_loops, one_pass, (cache, x, lam))


def exit_distribution(lam):
    """The looped stack's exit distribution from its gates ``lam [T,
    ...]``: ``p_t = lam_t prod_{j<t} (1 - lam_j)`` for ``t < T`` and
    ``p_T = prod_{j<T} (1 - lam_j)``, so that it sums to one. A token
    leaves at the first pass whose cumulated ``p`` reaches the
    configuration's threshold; at the published 1.0 that is pass
    ``T``."""
    stay = jnp.cumprod(1.0 - lam, axis=0)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]])
    return jnp.concatenate([(lam * before)[:-1], before[-1:]])


def expected_exit_pass(lam):
    """``sum_t t p_t`` over passes counted from 1: ``[...]`` float32."""
    p = exit_distribution(lam)
    t = jnp.arange(1, p.shape[0] + 1, dtype=jnp.float32)
    return jnp.tensordot(t, p, axes=1)


def _greedy_head(params, cfg: TransformerConfig, h, lam=None, last=None):
    """The vocab head over final-normed ``h [..., D]`` -> ``(greedy
    tokens int32, float32 logits)``. A prefill names ``last``, the one
    row of its ``[S, D]`` anybody reads (the prompt's last position).
    A looped stack hands its gates ``lam``: everything the host reads
    back is then ONE int32 vector, ``[tokens | expected exit pass]``
    (the float32's bits: :func:`split_fetched`)."""
    with jax.named_scope("head"):
        if last is not None:
            h = jax.lax.dynamic_index_in_dim(h, last, axis=0,
                                             keepdims=False)
        logits = _dmm(h, params["head"], _compute_dtype(cfg))
        tokens = jnp.argmax(logits, -1).astype(jnp.int32)
        if lam is None:
            return tokens, logits
        if last is not None:
            lam = jax.lax.dynamic_index_in_dim(lam, last, axis=1,
                                               keepdims=False)
        exits = jax.lax.bitcast_convert_type(expected_exit_pass(lam),
                                             jnp.int32)
        return jnp.concatenate([tokens.reshape(-1),
                                exits.reshape(-1)]), logits


def split_fetched(fetched: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """A looped program's one fetch, on the host: ``(tokens int32 [n],
    expected exit pass float32 [n])``."""
    tokens, exits = np.split(np.asarray(fetched), 2)
    return tokens, exits.view(np.float32)


def build_prefill(cfg: TransformerConfig, donate: bool = True,
                  cache_sharding=None, attn_impl: str = "dense"):
    """Jitted ``prefill(params, cache, tokens, slot, length) ->
    (cache, next_token, last_logits)``.

    ``tokens`` is ONE bucket-padded prompt ``[S_pad]`` (one compile per
    bucket — the prompt ladder is the serving shape set), ``slot`` the
    claimed cache lane, ``length`` the true prompt length. Every
    layer's K/V rows land in ``cache[...][layer, slot, :S_pad]``; rows
    past ``length`` hold padding-token garbage, but the decode step's
    position mask never reads an index it has not yet overwritten, so
    they are dead by construction. The cache is donated: prefill
    writes in place, no second pool exists.

    ``next_token`` is the greedy argmax at position ``length - 1`` —
    the first generated token. ``attn_impl`` picks the in-flight
    attention engine (see :func:`_inflight_attention`)."""
    _check_decode_config(cfg, looped=False)
    inflight = _inflight_attention(cfg, attn_impl, cache_sharding)

    def prefill(params, cache, tokens, slot, length):
        cache, h, _ = _decode_layers(
            params, cfg, tokens, jnp.arange(tokens.shape[0]), cache,
            lambda c, _: _lanes(c, slot, inflight=inflight))
        return (cache,) + _greedy_head(params, cfg, h, last=length - 1)

    return _jit_decode(prefill, donate, cache_sharding)


def build_decode_step(cfg: TransformerConfig, n_slots: int,
                      max_len: int, donate: bool = True,
                      cache_sharding=None):
    """Jitted ``step(params, cache, tokens, pos) -> (cache,
    next_tokens, logits)`` — ONE token for every slot at once.

    All shapes are fixed at build time (``tokens``/``pos`` are
    ``[n_slots]`` int32), so the step compiles exactly once however
    requests join and leave; the cache is donated, so a warm loop
    allocates nothing on device. Each slot writes its new K/V row at
    ``pos[slot]`` then attends over its own lane masked to
    ``index <= pos`` — slots are fully independent, which is what lets
    the scheduler splice a freshly prefilled request into a running
    batch between steps. Free slots ride along with ``token 0 @ pos
    0`` (their lane row 0 is rewritten by the next prefill); their
    outputs are garbage the host never reads."""
    _check_decode_config(cfg, looped=False)
    rows = jnp.arange(int(n_slots))

    def step(params, cache, tokens, pos):
        cache, h, _ = _decode_layers(params, cfg, tokens, pos, cache,
                                     lambda c, _: _lanes(c, rows, pos))
        return (cache,) + _greedy_head(params, cfg, h)

    return _jit_decode(step, donate, cache_sharding)


# ---------------------------------------------------------------------------
# paged KV cache: block-table layout
#
# The dense pool above reserves ``max_len`` rows per slot, so a short
# sequence wastes most of its lane — concurrency per device is capped
# by WORST-CASE length. The paged layout breaks the lane into fixed
# ``page_size``-row pages drawn from one shared pool: ONE array a
# layer, ``[n_pages, page_size, H, Dh]``, in a list (the layout
# ``models/evabyte.init_cache`` has). A per-slot **page table**
# (int32 page indices, virtual row r lives at
# ``pages[table[r // page_size], r % page_size]``) maps each slot's
# virtual lane onto whatever pages it has claimed, so HBM is spent on
# rows sequences actually occupy and the same pool holds
# ``~max_len / mean_len`` times more concurrent sessions. All shapes
# stay fixed (tables are ``[pages_per_slot]`` dense int arrays), the
# pool is donated leaf by leaf through every call, and the
# compile-once contract is unchanged. Why a list and not one stacked
# array: a ``pallas_call`` operand must be a buffer of its own, so a
# layer sliced out of a stack is a copy of that layer's pool (48 a
# step), while a layer's own array goes to the kernel whole and takes
# the rows or pages a program names in place.
# Page index 0 is the SCRATCH page by convention: unclaimed
# table entries point at it, so writes past a slot's claimed region
# (bucket-padding tails, speculative overshoot, free slots riding the
# step) land harmlessly there and the position mask never reads them.


def init_paged_kv_cache(cfg: TransformerConfig, n_pages: int,
                        page_size: int) -> Dict[str, List[jax.Array]]:
    """The shared page pool: ``{"k", "v"}``, each a LIST of one
    ``[n_loops * n_pages, page_size, n_heads, d_head]`` array a layer,
    in ``cfg.dtype`` (float32 or bfloat16, as the programs' K/V rows
    are). Allocated once and donated through every prefill/step/verify
    call. Page 0 is the scratch page (see module section comment); a
    pool of ``n_pages`` therefore holds ``n_pages - 1`` claimable
    pages. A looped stack keeps a row a (pass, layer, position): pass
    ``t``'s rows of page ``id`` lie in page ``id + t * n_pages`` of the
    layer's array, so a page id names a position's rows in every pass
    and whoever counts pages counts a position once."""
    _check_decode_config(cfg)
    shape = (cfg.recipe.n_loops * int(n_pages), int(page_size),
             cfg.n_heads, cfg.d_head)
    return {name: [jnp.zeros(shape, _compute_dtype(cfg))
                   for _ in range(cfg.n_layers)] for name in ("k", "v")}


def _write_pages(c_l, x, page_table, start_page):
    """A prefill's rows ``x [S, H, Dh]`` into one layer's pool ``c_l
    [n_pages, page_size, H, Dh]`` through the slot's ``page_table``,
    from table entry ``start_page`` on (0 in a cold prefill, the first
    private page behind a prefix hit: the hit is page-aligned, so
    chunk c fills page ``table[start_page + c]`` exactly)."""
    page_size = c_l.shape[1]
    pages_per_slot = page_table.shape[0]
    S = x.shape[0]
    if S <= page_size:
        # one page or a part of one: rows [0, S) of the first page, as
        # ONE dynamic_update_slice (a one-chunk scatter is answered
        # with a relayout of the whole operand). ``hit_len < length
        # <= max_len``, so ``start_page`` is inside the table.
        pg = jax.lax.dynamic_index_in_dim(page_table, start_page,
                                          keepdims=False)
        return jax.lax.dynamic_update_slice(c_l, x[None], (pg, 0, 0, 0))
    # The bucket can overshoot the lane end (start_page + n_chunks >
    # pages_per_slot when hit_len + S_pad > max_len) — a clamped
    # dynamic_slice would silently re-aim those chunks at EARLIER
    # table entries, i.e. write padding over the SHARED prefix pages,
    # so overflow chunks route to the scratch page instead (the verify
    # step's overshoot convention).
    n_chunks = S // page_size
    cpos = start_page + jnp.arange(n_chunks)
    pgs = jnp.where(cpos < pages_per_slot,
                    page_table[jnp.minimum(cpos, pages_per_slot - 1)], 0)
    return c_l.at[pgs].set(x.reshape(n_chunks, page_size, *x.shape[1:]))


def build_paged_prefill(cfg: TransformerConfig, page_size: int,
                        pages_per_slot: int, donate: bool = True,
                        cache_sharding=None, attn_impl: str = "dense"):
    """Jitted ``prefill(params, cache, tokens, page_table, length) ->
    (cache, next_token, last_logits)`` — the paged analogue of
    :func:`build_prefill`.

    ``tokens`` is one bucket-padded prompt ``[S_pad]`` (one compile
    per bucket), ``page_table`` the slot's ``[pages_per_slot]`` table.
    Every layer's K/V rows land in the slot's claimed pages through
    the table: buckets over ``page_size`` scatter whole page-shaped
    chunks, a bucket of one page or less is one
    ``dynamic_update_slice`` into the first claimed page. Chunks past
    the claimed page count ride the scratch-page convention (table
    entry 0), so bucket padding never corrupts another slot's pages.
    ``attn_impl`` picks the in-flight attention engine (the cold
    prefill attends over the q/k/v it just computed, not the pool —
    see :func:`_inflight_attention`)."""
    _check_decode_config(cfg)
    inflight = _inflight_attention(cfg, attn_impl, cache_sharding)

    def prefill(params, cache, tokens, page_table, length):
        cache, h, lam = _decode_layers(
            params, cfg, tokens, jnp.arange(tokens.shape[0]), cache,
            lambda c, shift: _table_pages(c, _shifted(page_table, shift),
                                          0, inflight=inflight))
        return (cache,) + _greedy_head(params, cfg, h, lam, length - 1)

    return _jit_decode(prefill, donate, cache_sharding,
                       looped=cfg.recipe.looped)


def build_paged_prefix_prefill(cfg: TransformerConfig, page_size: int,
                               pages_per_slot: int, donate: bool = True,
                               cache_sharding=None,
                               attn_impl: str = "dense"):
    """Jitted ``prefill(params, cache, tokens, page_table, length,
    hit_len) -> (cache, next_token, last_logits)`` — the **partial /
    offset** prefill behind the cross-request prefix cache
    (docs/serving.md "Prefix cache").

    When the radix index matched a prompt's first ``hit_len`` tokens
    (page-aligned) to cached pages, only the uncached suffix needs
    compute: ``tokens`` is the suffix ``prompt[hit_len:]`` padded to a
    bucket ``[S_pad]`` (one compile per SUFFIX bucket — the same pow2
    ladder as cold prefill), ``page_table`` the slot's full table whose
    first ``hit_len // page_size`` entries are the SHARED prefix pages
    and the rest the slot's private pages. Each suffix position ``j``
    embeds/ropes at virtual position ``hit_len + j`` (``hit_len`` is a
    traced scalar — hit depth is data, not shape), writes its K/V row
    through the table at that virtual row (hit_len is page-aligned, so
    suffix chunks start on a page boundary), and attends over the
    WHOLE virtual lane — prefix rows come straight from the shared
    pages, never recomputed — masked causally to ``index <= hit_len +
    j``. Exact, not approximate: the lane holds the same K/V a cold
    prefill would have produced (the shared pages ARE a previous cold
    prefill's output), so greedy/sampled/speculative decode from an
    offset prefill is token-for-token the cold path (test-pinned).

    Shared pages are READ-only here by construction: every write lands
    at virtual row ``>= hit_len``, i.e. pages ``>= hit_len //
    page_size`` — the immutability invariant the scheduler's sharing
    model rests on. ``next_token`` is the greedy argmax at virtual
    position ``length - 1`` (suffix row ``length - 1 - hit_len``;
    the cache layer caps ``hit_len < length``, so the last prompt
    position is always computed, never cached).

    ``attn_impl`` picks the virtual-lane attention engine: ``"dense"``
    gathers the whole lane through the table and softmaxes the [S, V]
    score matrix; ``"pallas"`` runs the fused block-table kernel
    (:func:`~mmlspark_tpu.parallel.pallas_attention.
    paged_prefix_prefill_attention` — page DMAs aimed by scalar
    prefetch, streaming softmax over (q-tile, page) steps, neither the
    gathered lane nor the [S, V] scores ever reach HBM);
    ``"pallas_interpret"`` is the CPU parity mode. Same scratch-page
    overshoot semantics on every engine."""
    _check_decode_config(cfg)
    from mmlspark_tpu.parallel.pallas_attention import (
        paged_prefix_prefill_attention)
    kernel = _attn_kernel(
        paged_prefix_prefill_attention, attn_impl, cache_sharding,
        (3, 4, 4, 0, 0), scale=cfg.d_head ** -0.5, page_size=int(page_size))

    def prefill(params, cache, tokens, page_table, length, hit_len):
        pos = hit_len + jnp.arange(tokens.shape[0])    # virtual rows
        cache, h, lam = _decode_layers(
            params, cfg, tokens, pos, cache,
            lambda c, shift: _table_pages(c, _shifted(page_table, shift),
                                          hit_len, pos, kernel=kernel))
        return (cache,) + _greedy_head(params, cfg, h, lam,
                                       length - 1 - hit_len)

    return _jit_decode(prefill, donate, cache_sharding,
                       looped=cfg.recipe.looped)


def build_paged_decode_step(cfg: TransformerConfig, n_slots: int,
                            page_size: int, pages_per_slot: int,
                            donate: bool = True, cache_sharding=None,
                            attn_impl: str = "dense"):
    """Jitted ``step(params, cache, tokens, pos, page_tables) ->
    (cache, next_tokens, logits)`` — one token for every slot through
    the block-table layout (the paged :func:`build_decode_step`). A
    looped stack's is ``(cache, fetched, logits, next_tokens)``:
    ``fetched`` packs the exit passes behind the tokens
    (:func:`split_fetched`).

    Each slot writes its new K/V row at page
    ``page_tables[slot, pos // page_size]``, row ``pos % page_size``,
    then attends over its virtual lane masked to ``index <= pos``.
    ``page_tables`` is ``[n_slots, pages_per_slot]`` int32 — fixed
    shape, so occupancy churn and page churn alike reuse ONE
    executable. Free slots ride at token 0 / pos 0 with an all-scratch
    table.

    ``attn_impl`` picks the gather engine: ``"dense"`` (the
    CPU/fallback path — materialize each slot's lane via
    ``c_l[page_tables]`` then one masked attention), ``"pallas"``
    (the fused block-table kernel —
    :func:`~mmlspark_tpu.parallel.pallas_attention.
    paged_decode_attention`: the page table aims each page's DMA via
    scalar prefetch, streaming softmax in VMEM, no lane intermediate
    in HBM), or ``"pallas_interpret"`` (the kernel interpreted, for
    CPU parity tests). Token-for-token parity between the two is
    test-pinned."""
    _check_decode_config(cfg)
    page_size = int(page_size)
    rows = jnp.arange(int(n_slots))
    from mmlspark_tpu.parallel.pallas_attention import (
        paged_decode_attention)
    kernel = _attn_kernel(
        paged_decode_attention, attn_impl, cache_sharding,
        (3, 4, 4, 0, 0), scale=cfg.d_head ** -0.5, page_size=page_size)

    def step(params, cache, tokens, pos, page_tables):
        pg = page_tables[rows, pos // page_size]
        cache, h, lam = _decode_layers(
            params, cfg, tokens, pos, cache,
            lambda c, shift: _slot_pages(c, _shifted(page_tables, shift),
                                         pos, _shifted(pg, shift), kernel))
        fetched, logits = _greedy_head(params, cfg, h, lam)
        if lam is None:
            return cache, fetched, logits
        # a looped stack's one fetch packs the exit passes behind the
        # tokens: the tokens are an output of their own as well, which
        # a step dispatched before this one is fetched takes as it lies
        # on the device (nobody copies it back)
        return cache, fetched, logits, fetched[:rows.shape[0]]

    return _jit_decode(step, donate, cache_sharding,
                       n_replicated=3 if cfg.recipe.looped else 2,
                       looped=cfg.recipe.looped)


# ---------------------------------------------------------------------------
# speculative decoding: draft propose + target verify
#
# A small draft model proposes ``k`` tokens per slot (one fused device
# program — k chained single-token steps, one host round-trip instead
# of k), then ONE width-k verify step of the target model scores every
# proposal; the host accepts the longest agreeing prefix (exact argmax
# match for greedy slots, Leviathan rejection sampling for sampled
# slots — both in serving/decode.py). Per emitted token that's
# ~(1 draft + 1 verify) / m dispatches at acceptance m instead of one
# full target step each, which is where the tokens/s comes from; the
# verify's K/V writes for rejected positions are repaired for free by
# the next round's writes (every position is (re)written by the round
# that consumes its token — the same invariant as the single step).


def verify_ce_engine(cfg: TransformerConfig, n_slots: int, width: int,
                     sharded: bool = False) -> str:
    """Resolve the verify/score CE engine for ``cfg.ce_impl``:
    ``"fused"`` = the streaming Pallas CE kernel scores proposals
    straight off the hidden states (``ops/fused_ce.py`` — no second
    ``[N*W, vocab]`` log-prob materialization and a ``[N, W]`` fetch
    instead of ``[N, W, vocab]``), ``"xla"`` = logsumexp-minus-gold
    over the logits the verify computes anyway. ``"auto"`` picks fused
    exactly when the kernel is eligible (TPU backend, lane-aligned
    d_model, enough tokens to fill a tile) and the head is not
    mesh-sharded (the kernel is not partition-aware — XLA partitions
    the einsum path instead)."""
    impl = cfg.ce_impl
    if impl == "auto":
        from mmlspark_tpu.ops.fused_ce import fused_ce_available
        t = int(n_slots) * max(int(width) - 1, 1)
        # the VMEM budget is a compute-dtype question: an f32 model's
        # logit tiles are twice a bf16 model's (same guard the train
        # path applies at its call site)
        itemsize = jnp.dtype(_compute_dtype(cfg)).itemsize
        impl = ("fused" if not sharded
                and fused_ce_available(t, cfg.d_model, cfg.vocab,
                                       itemsize=itemsize)
                else "xla")
    return impl


def build_paged_verify_step(cfg: TransformerConfig, n_slots: int,
                            width: int, page_size: int,
                            pages_per_slot: int, donate: bool = True,
                            cache_sharding=None,
                            with_scores: bool = False,
                            ce_impl: Optional[str] = None):
    """Jitted ``verify(params, cache, tokens, pos, page_tables) ->
    (cache, greedy_tokens, logits[, scores])`` — the target model's
    batched scoring of ``width`` draft positions per slot over the
    paged cache.

    ``tokens`` is ``[n_slots, width]`` (column 0 = the slot's current
    input token, columns 1.. = draft proposals), ``pos`` the per-slot
    start positions: query ``j`` sits at ``pos + j``, writes its K/V
    row through the page table there, and attends its virtual lane
    masked causally to ``index <= pos + j``. Returns the greedy argmax
    ``[n_slots, width]`` (token at ``pos + j + 1`` per the target) and
    the full logits ``[n_slots, width, vocab]`` (fetched only when a
    sampled slot needs rejection sampling).

    ``with_scores`` adds a fourth output: ``[n_slots, width-1]`` f32
    target log-probs of the PROPOSED tokens (``tokens[:, j+1]`` scored
    by query ``j``) — the per-proposal acceptance-quality signal. The
    engine is :func:`verify_ce_engine`'s pick (override via
    ``ce_impl``: ``"fused"``/``"fused_interpret"``/``"xla"``): fused
    scores come off the hidden states through the streaming CE kernel
    (``log p = -ce``), the XLA path reuses the verify's own logits.
    Both are f32-accumulated and parity-pinned in
    tests/test_transformer.py."""
    _check_decode_config(cfg, looped=False)
    n_slots, width = int(n_slots), int(width)
    page_size, pages_per_slot = int(page_size), int(pages_per_slot)
    V = page_size * pages_per_slot
    rows = jnp.arange(n_slots)
    offs = jnp.arange(width)
    if ce_impl is None:
        ce_impl = verify_ce_engine(cfg, n_slots, width,
                                   sharded=cache_sharding is not None)
    if ce_impl not in ("fused", "fused_interpret", "xla"):
        raise ValueError(f"unknown verify ce_impl {ce_impl!r}")

    def verify(params, cache, tokens, pos, page_tables):
        qpos = pos[:, None] + offs[None, :]            # [N, W]
        # a slot whose lane ends inside the window (pos + W > V — e.g.
        # a non-speculative slot riding the round near its lane end)
        # must not wrap its writes onto its own live pages: overflow
        # positions route to the scratch page instead
        pg = jnp.where(
            qpos < V,
            page_tables[rows[:, None],
                        jnp.minimum(qpos // page_size,
                                    pages_per_slot - 1)], 0)  # [N, W]
        cache, h, _ = _decode_layers(
            params, cfg, tokens, qpos, cache,
            lambda c, _: _slot_pages(c, page_tables, qpos, pg))  # [N, W, D]
        toks, logits = _greedy_head(params, cfg, h)    # [N, W, vocab]
        if not with_scores:
            return cache, toks, logits
        with jax.named_scope("ce"):
            return (cache, toks, logits,
                    score_proposals(tokens, h, logits, params["head"]))

    def score_proposals(tokens, h, logits, head):
        labels = tokens[:, 1:].reshape(-1)             # proposals
        if ce_impl in ("fused", "fused_interpret"):
            # score straight off the hidden states: the streaming CE
            # kernel computes lse - gold per token with logit tiles in
            # VMEM — log p(proposal) = -ce, f32-accumulated
            from mmlspark_tpu.ops.fused_ce import fused_softmax_xent
            ce = fused_softmax_xent(
                h[:, :-1].reshape(-1, cfg.d_model), head,
                labels, interpret=ce_impl == "fused_interpret")
            return -ce.reshape(n_slots, width - 1)
        lg = logits[:, :-1].astype(jnp.float32)        # [N, W-1, V]
        lse = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(
            lg, tokens[:, 1:, None], axis=-1)[..., 0]
        return gold - lse

    return _jit_decode(verify, donate, cache_sharding,
                       n_replicated=3 if with_scores else 2)


def build_draft_propose(cfg: TransformerConfig, n_slots: int,
                        max_len: int, width: int, donate: bool = True):
    """Jitted ``propose(params, cache, tokens, pos) -> (cache,
    proposals)`` — ``width`` greedy draft steps chained INSIDE one
    device program (each step's argmax feeds the next), over the
    draft's dense slot-lane cache.

    One host round-trip proposes the whole block — the draft-side
    half of the speculative dispatch saving. Greedy only: sampled
    slots need per-step draft distributions on host, so the scheduler
    falls back to ``width`` separate draft steps when one is active."""
    _check_decode_config(cfg, looped=False)
    rows = jnp.arange(int(n_slots))

    def propose(params, cache, tokens, pos):
        props = []
        for j in range(int(width)):
            cache, h, _ = _decode_layers(
                params, cfg, tokens, pos + j, cache,
                lambda c, _, j=j: _lanes(c, rows, pos + j))
            tokens, _ = _greedy_head(params, cfg, h)
            props.append(tokens)
        return cache, jnp.stack(props, axis=1)

    return _jit_decode(propose, donate, n_replicated=1)


def layer_truncated_draft(params, cfg: TransformerConfig,
                          layers: int):
    """A self-speculative draft: the target's FIRST ``layers`` blocks
    with the shared embed/final-norm/head (LayerSkip-style early
    exit). The draft's step costs ``layers / n_layers`` of the
    target's while sharing its representation space — residual blocks
    refine, not replace, the embedding stream, so the early exit's
    argmax agrees with the full model's often enough to pay for
    verification. Returns ``(draft_params, draft_cfg)``; the params
    ALIAS the target's leaves (no copy — one set of weights serves
    both models)."""
    if cfg.n_stages != 1:
        raise ValueError("layer-truncated drafts need n_stages == 1 "
                         "(decode configs are single-stage)")
    if not 1 <= layers <= cfg.layers_per_stage:
        raise ValueError(f"draft layers must be in "
                         f"[1, {cfg.layers_per_stage}]")
    dcfg = dataclasses.replace(cfg, layers_per_stage=int(layers))
    dparams = {"embed": params["embed"], "head": params["head"],
               "final_norm": params["final_norm"],
               "blocks": params["blocks"][:int(layers)]}
    return dparams, dcfg
