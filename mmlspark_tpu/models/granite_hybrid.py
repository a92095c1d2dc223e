"""Granite-hybrid: Mamba-2 layers beside a few grouped-query attention
layers, a routed mixture of gated experts with a shared expert behind
every mixer.

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
* **Attention layer**: ``n_heads`` query heads over ``n_kv_heads`` K/V
  heads, no positional encoding, softmax scale ``attention_multiplier``.
* **Experts** (:func:`moe`): the router scores ALL ``n_experts`` and
  keeps ``top_k`` a token, renormalised; this chip computes the part of
  the experts it HOLDS (``experts_held``), and a routing to an expert
  it does not hold contributes nothing here (in the deployment the
  chip that holds it adds it; on one chip the layer runs without that
  exchange and nothing stands in for it). No routing is dropped: a
  tile's routings are sorted by expert into one grouped product
  (``jax.lax.ragged_dot``, no capacity); a decode step runs every held
  expert over every slot, weighted by the router (zero where a slot
  was not routed): at 16 slots x 10 routings some nine in ten held
  experts are touched anyway, and the masked form is exact.

**Precision** (``cfg.dtype``, bfloat16 as served): weights, K/V rows and
matmul operands in ``dtype``; accumulation, the residual stream, the
recurrent state, the convolution, the router and the logits in float32.

**The cache has two parts** (:func:`init_cache`). A slot's *state*: per
Mamba layer ``ssm[n_slots, H, P, N]`` float32 and ``conv[n_slots,
ssm_conv - 1, H P + 2 N]`` float32, overwritten by every step and by
every tile, never appended to; a request's first tile (``pos0 == 0``)
starts from zeros whatever the slot held. *Rows a position*: per
attention layer one K and one V page pool ``[n_pages, page_size,
n_kv_heads, d_head]`` in ``dtype`` (page 0 the scratch page). Every
array is a buffer of its own, donated through both programs:

``build_hybrid_prefill``  one tile of a prompt (``tile`` tokens at
    ``pos0``, ``length`` of them real): carries the slot's state and
    conv tail in and out, appends the tile's K/V rows to the slot's
    pages and attends over the lane so far. Padded positions leave the
    state as it was (``dt = 0``, no conv shift).
``build_hybrid_step``     one token for every slot.

``forward_logits`` is the plain forward (no cache).
"""

from __future__ import annotations

import dataclasses
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

    #: what the serving plane reads to pick the decoder
    block_kind = "granite_hybrid"

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        object.__setattr__(self, "experts_held", tuple(self.experts_held))
        bad = set(self.layer_types) - {"mamba", "attention"}
        if bad:
            raise ValueError(f"unknown layer types {sorted(bad)}")
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
        """From a ``granitemoehybrid`` ``config.json`` (its HF keys);
        ``experts_held`` defaults to all ``num_local_experts``."""
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

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

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
    position and a thousand, so it neither dies at once nor grows."""
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
              "norm2": jnp.ones((d,), jnp.float32),
              "router": matrix(ks[0], (d, cfg.n_experts), d,
                               dtype=jnp.float32),
              "w_in_e": matrix(ks[1], (e, d, 2 * cfg.d_expert), d),
              "w_out_e": matrix(ks[2], (e, cfg.d_expert, d), cfg.d_expert),
              "w_in_s": matrix(ks[3], (d, 2 * cfg.d_shared), d),
              "w_out_s": matrix(ks[4], (cfg.d_shared, d), cfg.d_shared)}
        if kind == "attention":
            hd = cfg.n_heads * cfg.d_head
            bp.update(
                wq=matrix(ks[5], (d, cfg.n_heads, cfg.d_head), d, QK_GAIN),
                wk=matrix(ks[6], (d, cfg.n_kv_heads, cfg.d_head), d,
                          QK_GAIN),
                wv=matrix(ks[7], (d, cfg.n_kv_heads, cfg.d_head), d),
                wo=matrix(ks[8], (cfg.n_heads, cfg.d_head, d), hd))
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
    return {"embed": embed.astype(dt_),
            "final_norm": jnp.ones((d,), jnp.float32), "blocks": blocks}


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


def _gated(u, w_in, w_out, dtype):
    gv = _mm("td,df->tf", u, w_in)
    f = gv.shape[-1] // 2
    return _mm("tf,fd->td",
               (jax.nn.silu(gv[:, :f]) * gv[:, f:]).astype(dtype), w_out)


def moe(bp, h, cfg: GraniteHybridConfig, grouped: bool, live=None):
    """The expert layer's share on this chip: ``h`` [T, D] float32 ->
    ``(routed + shared [T, D], routings int32[len(experts_held)],
    touched int32)``: the router over all ``n_experts``, ``top_k`` a
    token renormalised, the held experts' gated products weighted by
    the router and summed, the shared expert once. A routing to an
    expert that is not held, or of a token that is not ``live`` (bool
    [T]: padding, a free slot), computes and counts nothing.
    ``routings`` are the routings each held expert received, ``touched``
    how many of them received any.

    ``grouped`` sorts the routings by expert into one grouped product
    (a prompt's tile); otherwise every held expert runs over every
    token with the router's weight, zero where it was not routed (a
    decode step's few tokens). Both are exact: no routing is dropped."""
    dt_, f = cfg.compute_dtype, cfg.d_expert
    e = len(cfg.experts_held)
    u32 = _norm(h, bp["norm2"], cfg)
    u = u32.astype(dt_)
    with jax.named_scope("moe.route"):
        logits = jnp.einsum("td,de->te", u32, bp["router"],
                            precision=jax.lax.Precision.HIGHEST)
        top, idx = jax.lax.top_k(logits, cfg.top_k)
        w = jax.nn.softmax(top, axis=-1)
        # a held expert's place among the held; ``e`` for one that is not
        place = np.full(cfg.n_experts, e, np.int32)
        place[list(cfg.experts_held)] = np.arange(e)
        local = jnp.asarray(place)[idx]                     # [T, k]
        if live is not None:
            local = jnp.where(live[:, None], local, e)
        w = jnp.where(local < e, w, 0.0)
        routings = jnp.zeros((e + 1,), jnp.int32).at[
            local.reshape(-1)].add(1)[:e]
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
            wt = w.reshape(-1)[order][:, None]
            # rows past the last group belong to no expert
            routed = jnp.zeros_like(h).at[tok].add(
                jnp.where(wt > 0, y * wt, 0.0))
        else:
            weight = jnp.zeros((h.shape[0], e + 1), jnp.float32).at[
                jnp.arange(h.shape[0])[:, None], local].add(w)[:, :e]
            gv = _mm("td,edf->etf", u, bp["w_in_e"])
            act = jax.nn.silu(gv[..., :f]) * gv[..., f:]
            routed = _mm("etf,efd->td",
                         (act * weight.T[:, :, None]).astype(dt_),
                         bp["w_out_e"])
    return routed + shared, routings, jnp.sum(routings > 0).astype(jnp.int32)


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


def _qkv(bp, u, cfg: GraniteHybridConfig):
    dt_ = cfg.compute_dtype
    u = u.astype(dt_)
    with jax.named_scope("attn.qkv"):
        return (_mm("td,dhk->thk", u, bp["wq"]).astype(dt_),
                _mm("td,dhk->thk", u, bp["wk"]).astype(dt_),
                _mm("td,dhk->thk", u, bp["wv"]).astype(dt_))


def _attn_out(bp, a, cfg: GraniteHybridConfig):
    with jax.named_scope("attn.out"):
        return _mm("thk,hkd->td", a.astype(cfg.compute_dtype), bp["wo"])


def _embed(params, tokens, cfg: GraniteHybridConfig):
    with jax.named_scope("embed"):
        return cfg.embedding_multiplier * params["embed"][tokens].astype(
            jnp.float32)


def _head(params, x, cfg: GraniteHybridConfig):
    """``x`` [T, D] float32 -> float32 logits [T, vocab] (tied)."""
    h = _norm(x, params["final_norm"], cfg).astype(cfg.compute_dtype)
    with jax.named_scope("head"):
        return _mm("td,vd->tv", h, params["embed"]) / cfg.logits_scaling


# ---------------------------------------------------------------------------
# the plain forward: no cache


def forward_logits(params, tokens, cfg: GraniteHybridConfig):
    """``tokens`` [S] -> float32 logits [S, vocab] of the whole
    sequence: every Mamba layer from a zero state, the attention dense
    over the sequence's own rows."""
    s_len = tokens.shape[0]
    rm = cfg.residual_multiplier
    x = _embed(params, tokens, cfg)
    tail = jnp.zeros((cfg.ssm_conv - 1, cfg.d_conv_in), jnp.float32)
    state = jnp.zeros((cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
                      jnp.float32)
    for kind, bp in zip(cfg.layer_types, params["blocks"]):
        u = _norm(x, bp["norm1"], cfg)
        if kind == "attention":
            q, k, v = _qkv(bp, u, cfg)
            with jax.named_scope("attn.core"):
                a = attention_dense(q, k, v, jnp.arange(s_len),
                                    cfg.attention_multiplier)
            mixed = _attn_out(bp, a, cfg)
        else:
            mixed, _, _ = mamba_tile(bp, u, tail, state, s_len, cfg)
        x = x + rm * mixed
        x = x + rm * moe(bp, x, cfg, grouped=True)[0]
    return _head(params, x, cfg)


# ---------------------------------------------------------------------------
# the cache and the two programs over it


def init_cache(cfg: GraniteHybridConfig, n_slots: int, n_pages: int,
               page_size: int) -> Dict[str, List[jax.Array]]:
    """``{"ssm", "conv"}``: one array a Mamba layer, a slot's recurrent
    state and the rows its convolution still needs; ``{"k", "v"}``: one
    page pool an attention layer (page 0 the scratch page). Lists, so
    that every array is a buffer of its own."""
    n_mamba = sum(k == "mamba" for k in cfg.layer_types)
    n_attn = cfg.n_layers - n_mamba
    pool = (int(n_pages), int(page_size), cfg.n_kv_heads, cfg.d_head)
    return {
        "ssm": [jnp.zeros((n_slots, cfg.ssm_heads, cfg.ssm_head_dim,
                           cfg.ssm_state), jnp.float32)
                for _ in range(n_mamba)],
        "conv": [jnp.zeros((n_slots, cfg.ssm_conv - 1, cfg.d_conv_in),
                           jnp.float32) for _ in range(n_mamba)],
        "k": [jnp.zeros(pool, cfg.compute_dtype) for _ in range(n_attn)],
        "v": [jnp.zeros(pool, cfg.compute_dtype) for _ in range(n_attn)]}


def _lists(cache):
    return {name: list(arrays) for name, arrays in cache.items()}


def build_hybrid_prefill(cfg: GraniteHybridConfig, page_size: int,
                         donate: bool = True, attn_impl: str = "dense"):
    """Jitted ``hybrid_prefill(params, cache, tokens, page_table, slot,
    pos0, length) -> (cache, next_token, logits)``.

    ``tokens`` [T] is one tile of a prompt at positions ``pos0 +
    arange(T)`` (``pos0`` a multiple of ``T``, ``T`` a multiple of
    ``page_size``); ``length`` of them are real. Slot ``slot``'s state
    and conv tail are read (zeros when ``pos0 == 0``: a request's first
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
                q, k, v = _qkv(bp, u, cfg)
                with jax.named_scope("kv.write"):
                    pages = jax.lax.dynamic_slice_in_dim(
                        page_table, pos0 // page_size, n_chunks)
                    shape = (n_chunks, page_size, cfg.n_kv_heads,
                             cfg.d_head)
                    c["k"][i] = c["k"][i].at[pages].set(k.reshape(shape))
                    c["v"][i] = c["v"][i].at[pages].set(v.reshape(shape))
                with jax.named_scope("attn.core"):
                    rows = (-1, cfg.n_kv_heads, cfg.d_head)
                    lane_k = c["k"][i][page_table].reshape(rows)
                    lane_v = c["v"][i][page_table].reshape(rows)
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
            else:
                tail = jnp.where(fresh, 0.0, c["conv"][i][slot])
                state = jnp.where(fresh, 0.0, c["ssm"][i][slot])
                mixed, tail, state = mamba_tile(bp, u, tail, state, length,
                                                cfg)
                c["conv"][i] = c["conv"][i].at[slot].set(tail)
                c["ssm"][i] = c["ssm"][i].at[slot].set(state)
            x = x + rm * mixed
            x = x + rm * moe(bp, x, cfg, grouped=True, live=live)[0]
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
    ``pos`` of the slot's lane. Free slots ride along at position 0
    with an all-scratch table; their routings are not counted (a live
    slot's position is at least one). ``routings`` int32[len(
    experts_held)] are the routings each held expert received over the
    layers, ``touched`` how many (layer, held expert) pairs received
    any: the expert weights the step had to read."""
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
                q, k, v = _qkv(bp, u, cfg)
                with jax.named_scope("kv.write"):
                    c["k"][i] = c["k"][i].at[page, row].set(k)
                    c["v"][i] = c["v"][i].at[page, row].set(v)
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
                mixed = _attn_out(bp, a, cfg)
            else:
                mixed, c["conv"][i], c["ssm"][i] = mamba_step(
                    bp, u, c["conv"][i], c["ssm"][i], cfg)
            x = x + rm * mixed
            out, r, tch = moe(bp, x, cfg, grouped=False, live=live)
            x = x + rm * out
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
