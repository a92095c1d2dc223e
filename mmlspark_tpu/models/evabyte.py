"""EvaByte: a byte-level decoder whose attention keeps one exact window
and one summary row per chunk of every earlier window.

A second block kind beside ``models/transformer.py``'s softmax block
(``EvaByteConfig.block_kind == "eva"``; the serving plane picks its
decoder from that, ``serving.decode.decoder_for``). The block, as the
source's ``config.json`` fixes it and ``benchmark/configs/
evabyte-6.5b.json`` lists what is assumed beyond it:

* ``norm(x) = x * rsqrt(mean(x^2) + eps) * (1 + g)`` (RMSNorm with a
  unit offset), half-split RoPE over the whole head at base
  ``rope_theta``, a gated SiLU feed-forward of three matrices, no
  biases, ``n_pred_heads`` prediction heads of ``vocab`` columns each
  (columns ``[0, vocab)`` are the next byte's logits);
* **EVA attention**: positions fall into fixed windows ``[W j, W j +
  W)`` and chunks of ``C``. A query attends exactly over the keys of
  its own window up to itself, and over ONE summary row for every chunk
  of every window wholly behind its own, all in one softmax. A chunk's
  summary is a softmax pooling of its keys by a learned ``phi``
  (``ks = sum_m a_m k_m + mu``, ``vs = sum_m a_m v_m``, ``a =
  softmax_m(phi . k_m)``; ``phi``, ``mu`` per head and layer). With one
  window this is causal softmax attention.

**Precision** (``cfg.dtype``, bfloat16 as the source states): weights,
cache rows and matmul operands in ``dtype``; accumulation, RoPE,
softmax statistics, the residual stream (``fp32_skip_add``) and the
logits (``fp32_logits``) in float32.

**The cache** is one page pool per layer (``init_cache``: a list, so
that a layer's pool is a buffer of its own and no program slices it
out of a stacked array), holding rows of ONE shape ``[H, Dh]`` for two
kinds of row. A slot's page table lists its summary pages first, then
its window pages: ``W / C`` summaries a window are whole pages, so the
live rows are a prefix of the slot's virtual lane (``n_summary_rows +
pos % W + 1`` of them) and the decode step is ``paged_decode_attention``
over that lane, the softmax block's kernel. Three programs:

``build_eva_prefill``  one window's tile of a prompt (a bucket of at
    most ``W`` tokens at ``pos0``): writes the window's rows, attends
    over the tile causally and over the summaries before it.
``build_eva_step``     one token a slot (as many slots as it is handed).
``build_eva_compact``  a finished window's ``W`` rows -> ``W / C``
    summary rows in the slot's next summary pages; the scheduler then
    gives the window's pages back.

``forward_logits`` is the plain forward (no cache), window by window.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List

import jax
import jax.numpy as jnp

_NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class EvaByteConfig:
    vocab: int = 320
    d_model: int = 4096
    n_heads: int = 32
    d_head: int = 128
    d_ff: int = 11008
    n_layers: int = 32
    window: int = 2048
    chunk: int = 16
    n_pred_heads: int = 8
    rope_theta: float = 100000.0
    norm_eps: float = 1e-5
    init_std: float = 0.01275
    dtype: str = "bfloat16"

    #: what the serving plane reads to pick the decoder
    block_kind = "eva"

    def __post_init__(self):
        if self.window % self.chunk:
            raise ValueError(f"chunk={self.chunk} must divide "
                             f"window={self.window}")
        if self.d_head % 2:
            raise ValueError("half-split RoPE needs an even d_head")

    @property
    def summaries_per_window(self) -> int:
        return self.window // self.chunk

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)


def init_params(cfg: EvaByteConfig, seed: int = 0) -> Dict[str, Any]:
    """Random weights in ``cfg.dtype`` (norm gains float32, zero):
    normal ``init_std`` for matrices and the embedding, ``phi`` and
    ``mu`` normal with std ``d_head ** -0.5``."""
    dt = cfg.compute_dtype
    d, h, dh, f = cfg.d_model, cfg.n_heads, cfg.d_head, cfg.d_ff

    def normal(key, shape, std):
        return (std * jax.random.normal(key, shape, jnp.float32)).astype(dt)

    root = jax.random.PRNGKey(int(seed))
    blocks = []
    for layer in range(cfg.n_layers):
        ks = jax.random.split(jax.random.fold_in(root, layer + 2), 9)
        blocks.append({
            "ln1": jnp.zeros((d,), jnp.float32),
            "wq": normal(ks[0], (d, h, dh), cfg.init_std),
            "wk": normal(ks[1], (d, h, dh), cfg.init_std),
            "wv": normal(ks[2], (d, h, dh), cfg.init_std),
            "wo": normal(ks[3], (h, dh, d), cfg.init_std),
            "ln2": jnp.zeros((d,), jnp.float32),
            "w_gate": normal(ks[4], (d, f), cfg.init_std),
            "w_up": normal(ks[5], (d, f), cfg.init_std),
            "w_down": normal(ks[6], (f, d), cfg.init_std),
            "phi": normal(ks[7], (h, dh), dh ** -0.5),
            "mu": normal(ks[8], (h, dh), dh ** -0.5),
        })
    return {"embed": normal(jax.random.fold_in(root, 0),
                            (cfg.vocab, d), cfg.init_std),
            "head": normal(jax.random.fold_in(root, 1),
                           (d, cfg.n_pred_heads * cfg.vocab), cfg.init_std),
            "final_norm": jnp.zeros((d,), jnp.float32),
            "blocks": blocks}


# ---------------------------------------------------------------------------
# the block's parts


def _norm(x, g, cfg: EvaByteConfig):
    """float32 in, ``cfg.dtype`` out (a matmul operand)."""
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + cfg.norm_eps)
            * (1.0 + g)).astype(cfg.compute_dtype)


def _rope(x, pos, theta: float):
    """Half-split rotary embedding over the whole head: ``x`` [..., H,
    Dh] float32 at positions ``pos`` matching the leading dims."""
    dh = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = pos[..., None].astype(jnp.float32) * freqs       # [..., Dh/2]
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def _mm(spec: str, a, b):
    return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)


def _qkv(bp, h, pos, cfg: EvaByteConfig):
    """``h`` [T, D] -> q, k, v [T, H, Dh] in ``cfg.dtype`` (q, k with
    RoPE at ``pos`` [T], applied in float32)."""
    dt = cfg.compute_dtype
    with jax.named_scope("attn.qkv"):
        q = _rope(_mm("td,dhk->thk", h, bp["wq"]), pos, cfg.rope_theta)
        k = _rope(_mm("td,dhk->thk", h, bp["wk"]), pos, cfg.rope_theta)
        v = _mm("td,dhk->thk", h, bp["wv"])
    return q.astype(dt), k.astype(dt), v.astype(dt)


def _ffn(bp, x, cfg: EvaByteConfig):
    """``x`` [T, D] float32 -> the gated SiLU feed-forward's output,
    float32."""
    h = _norm(x, bp["ln2"], cfg)
    with jax.named_scope("ffn.gate_up"):
        g = _mm("td,df->tf", h, bp["w_gate"])
        u = _mm("td,df->tf", h, bp["w_up"])
        z = (jax.nn.silu(g) * u).astype(cfg.compute_dtype)
    with jax.named_scope("ffn.down"):
        return _mm("tf,fd->td", z, bp["w_down"])


def summarize(k, v, phi, mu, chunk: int):
    """Chunk summaries of whole chunks: ``k``, ``v`` [T, H, Dh] (``T``
    a multiple of ``chunk``) -> ``ks``, ``vs`` [T / chunk, H, Dh] in
    the rows' dtype. Pooling weights and sums in float32."""
    with jax.named_scope("eva.summarize"):
        t, h, dh = k.shape
        kf = k.astype(jnp.float32).reshape(t // chunk, chunk, h, dh)
        vf = v.astype(jnp.float32).reshape(t // chunk, chunk, h, dh)
        s = jnp.einsum("cmhd,hd->cmh", kf, phi.astype(jnp.float32))
        a = jax.nn.softmax(s, axis=1)
        ks = jnp.einsum("cmh,cmhd->chd", a, kf) + mu.astype(jnp.float32)
        vs = jnp.einsum("cmh,cmhd->chd", a, vf)
        return ks.astype(k.dtype), vs.astype(v.dtype)


def eva_attention_dense(q, k, v, ks, vs, n_summary, scale: float):
    """One window's attention in plain XLA: queries ``q`` [S, H, Dh]
    over the tile's own keys causally and over the first ``n_summary``
    of the summary rows ``ks``/``vs`` [M, H, Dh], one softmax. Returns
    float32 [S, H, Dh]."""
    s_len, m_len = q.shape[0], ks.shape[0]
    sw = _mm("qhd,khd->hqk", q, k) * scale
    causal = jnp.arange(s_len)[:, None] >= jnp.arange(s_len)[None, :]
    sw = jnp.where(causal[None], sw, _NEG_INF)
    ss = _mm("qhd,khd->hqk", q, ks) * scale
    ss = jnp.where((jnp.arange(m_len) < n_summary)[None, None], ss,
                   _NEG_INF)
    p = jax.nn.softmax(jnp.concatenate([ss, sw], axis=-1), axis=-1)
    p = p.astype(v.dtype)
    return (_mm("hqk,khd->qhd", p[..., :m_len], vs)
            + _mm("hqk,khd->qhd", p[..., m_len:], v))


def _window_attention(q, k, v, ks, vs, n_summary, cfg: EvaByteConfig,
                      attn_impl: str):
    scale = cfg.d_head ** -0.5
    with jax.named_scope("eva.attn"):
        if attn_impl == "dense":
            return eva_attention_dense(q, k, v, ks, vs, n_summary, scale)
        from mmlspark_tpu.parallel.pallas_attention import (
            eva_prefill_attention)
        return eva_prefill_attention(
            q, k, v, ks, vs, n_summary, scale,
            interpret=attn_impl == "pallas_interpret")


def _head(params, x, cfg: EvaByteConfig):
    """``x`` [T, D] float32 -> float32 logits [T, n_pred_heads * V]."""
    h = _norm(x, params["final_norm"], cfg)
    with jax.named_scope("head"):
        return _mm("td,dv->tv", h, params["head"])


# ---------------------------------------------------------------------------
# the plain forward: no cache, window by window


def forward_logits(params, tokens, cfg: EvaByteConfig,
                   attn_impl: str = "dense"):
    """``tokens`` [S] -> float32 logits [S, n_pred_heads * vocab] of the
    whole sequence. ``S`` need not be a multiple of the window; a
    window's summaries become visible to the windows after it."""
    s_len = tokens.shape[0]
    w_len, c = cfg.window, cfg.chunk
    x = params["embed"][tokens].astype(jnp.float32)
    starts = list(range(0, s_len, w_len))
    no_rows = jnp.zeros((c, cfg.n_heads, cfg.d_head), cfg.compute_dtype)
    for bp in params["blocks"]:
        h = _norm(x, bp["ln1"], cfg)
        q, k, v = _qkv(bp, h, jnp.arange(s_len), cfg)
        outs, ks_all, vs_all = [], [], []
        for s0 in starts:
            s1 = min(s0 + w_len, s_len)
            ks = jnp.concatenate(ks_all) if ks_all else no_rows
            vs = jnp.concatenate(vs_all) if vs_all else no_rows
            outs.append(_window_attention(
                q[s0:s1], k[s0:s1], v[s0:s1], ks, vs,
                ks.shape[0] if ks_all else 0, cfg, attn_impl))
            if s1 - s0 == w_len:
                ks_w, vs_w = summarize(k[s0:s1], v[s0:s1], bp["phi"],
                                       bp["mu"], c)
                ks_all.append(ks_w)
                vs_all.append(vs_w)
        a = jnp.concatenate(outs).astype(cfg.compute_dtype)
        with jax.named_scope("attn.out"):
            x = x + _mm("thk,hkd->td", a, bp["wo"])
        x = x + _ffn(bp, x, cfg)
    return _head(params, x, cfg)


# ---------------------------------------------------------------------------
# the cache and the three programs over it


def init_cache(cfg: EvaByteConfig, n_pages: int, page_size: int
               ) -> Dict[str, List[jax.Array]]:
    """The page pool: ``{"k", "v"}``, each a LIST of one ``[n_pages,
    page_size, H, Dh]`` array a layer in ``cfg.dtype``. Page 0 is the
    scratch page (unclaimed table entries aim at it)."""
    shape = (int(n_pages), int(page_size), cfg.n_heads, cfg.d_head)
    return {name: [jnp.zeros(shape, cfg.compute_dtype)
                   for _ in range(cfg.n_layers)] for name in ("k", "v")}


def summary_params(params) -> List[Dict[str, jax.Array]]:
    """What ``build_eva_compact`` needs of the weights."""
    return [{"phi": bp["phi"], "mu": bp["mu"]} for bp in params["blocks"]]


def build_eva_prefill(cfg: EvaByteConfig, page_size: int,
                      donate: bool = True, attn_impl: str = "dense"):
    """Jitted ``eva_prefill(params, cache, tokens, sum_table, win_table,
    pos0, length) -> (cache, next_token, logits, further)``.

    ``tokens`` [S] is one window's tile of a prompt, padded to a bucket
    ``S <= W`` that ``page_size`` divides, at positions ``pos0 +
    arange(S)`` (``pos0`` a multiple of ``W``); ``length`` of them are
    real. Every layer's K/V rows go to the window's pages
    (``win_table`` [W / page_size]; entries past the claimed pages aim
    at the scratch page), and the tile attends over itself causally and
    over the ``pos0 / C`` summary rows already in ``sum_table``'s pages.
    ``logits`` [vocab] are the next byte's at the last real row,
    ``further`` the other prediction heads' columns."""
    page_size = int(page_size)
    per_window = cfg.summaries_per_window

    def eva_prefill(params, cache, tokens, sum_table, win_table, pos0,
                    length):
        s_len = tokens.shape[0]
        n_chunks = s_len // page_size
        with jax.named_scope("embed"):
            x = params["embed"][tokens].astype(jnp.float32)
        pos = pos0 + jnp.arange(s_len)
        n_summary = (pos0 // cfg.window) * per_window
        ck, cv = list(cache["k"]), list(cache["v"])
        for l, bp in enumerate(params["blocks"]):
            h = _norm(x, bp["ln1"], cfg)
            q, k, v = _qkv(bp, h, pos, cfg)
            with jax.named_scope("kv.write"):
                shape = (n_chunks, page_size, cfg.n_heads, cfg.d_head)
                ck[l] = ck[l].at[win_table[:n_chunks]].set(k.reshape(shape))
                cv[l] = cv[l].at[win_table[:n_chunks]].set(v.reshape(shape))
            with jax.named_scope("eva.summary_rows"):
                rows = (-1, cfg.n_heads, cfg.d_head)
                ks = ck[l][sum_table].reshape(rows)
                vs = cv[l][sum_table].reshape(rows)
            a = _window_attention(q, k, v, ks, vs, n_summary, cfg,
                                  attn_impl).astype(cfg.compute_dtype)
            with jax.named_scope("attn.out"):
                x = x + _mm("thk,hkd->td", a, bp["wo"])
            x = x + _ffn(bp, x, cfg)
        last = jax.lax.dynamic_index_in_dim(x, length - 1, axis=0,
                                            keepdims=True)
        logits = _head(params, last, cfg)[0]
        served = logits[:cfg.vocab]
        return ({"k": ck, "v": cv},
                jnp.argmax(served, -1).astype(jnp.int32), served,
                logits[cfg.vocab:])

    return jax.jit(eva_prefill, donate_argnums=(1,) if donate else ())


def build_eva_step(cfg: EvaByteConfig, page_size: int,
                   donate: bool = True, attn_impl: str = "dense"):
    """Jitted ``eva_step(params, cache, tokens, pos, page_tables) ->
    (cache, next_tokens, logits, further)``: one token for every slot.

    ``pos`` [N] are absolute positions; a slot's table lists its
    ``(pos // W) * W / C / page_size`` summary pages, then its window
    pages, so its live rows are the first ``n_summary + pos % W + 1``
    of its virtual lane. The new K/V row goes to virtual row
    ``n_summary + pos % W``; free slots ride along at position 0 with
    an all-scratch table."""
    page_size = int(page_size)
    scale = cfg.d_head ** -0.5

    def eva_step(params, cache, tokens, pos, page_tables):
        n = tokens.shape[0]
        with jax.named_scope("embed"):
            x = params["embed"][tokens].astype(jnp.float32)
        vpos = ((pos // cfg.window) * cfg.summaries_per_window
                + pos % cfg.window)
        page = page_tables[jnp.arange(n), vpos // page_size]
        row = vpos % page_size
        ck, cv = list(cache["k"]), list(cache["v"])
        for l, bp in enumerate(params["blocks"]):
            h = _norm(x, bp["ln1"], cfg)
            q, k, v = _qkv(bp, h, pos, cfg)
            with jax.named_scope("kv.write"):
                ck[l] = ck[l].at[page, row].set(k)
                cv[l] = cv[l].at[page, row].set(v)
            with jax.named_scope("eva.attn"):
                if attn_impl == "dense":
                    a = _lane_attention(q, ck[l], cv[l], page_tables, vpos,
                                        scale)
                else:
                    from mmlspark_tpu.parallel.pallas_attention import (
                        paged_decode_attention)
                    a = paged_decode_attention(
                        q, ck[l], cv[l], page_tables, vpos, scale,
                        page_size,
                        interpret=attn_impl == "pallas_interpret")
            with jax.named_scope("attn.out"):
                x = x + _mm("thk,hkd->td", a.astype(cfg.compute_dtype),
                            bp["wo"])
            x = x + _ffn(bp, x, cfg)
        logits = _head(params, x, cfg)
        served = logits[:, :cfg.vocab]
        return ({"k": ck, "v": cv},
                jnp.argmax(served, -1).astype(jnp.int32), served,
                logits[:, cfg.vocab:])

    return jax.jit(eva_step, donate_argnums=(1,) if donate else ())


def _lane_attention(q, c_k, c_v, page_tables, vpos, scale: float):
    """The dense-gather twin of ``paged_decode_attention``: every
    slot's virtual lane materialised, one masked softmax over it."""
    n, h, dh = q.shape
    lane_k = c_k[page_tables].reshape(n, -1, h, dh)
    lane_v = c_v[page_tables].reshape(n, -1, h, dh)
    s = _mm("nhd,nkhd->nhk", q, lane_k) * scale
    live = jnp.arange(lane_k.shape[1])[None] <= vpos[:, None]
    s = jnp.where(live[:, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(lane_v.dtype)
    return _mm("nhk,nkhd->nhd", p, lane_v)


def build_eva_compact(cfg: EvaByteConfig, page_size: int,
                      donate: bool = True):
    """Jitted ``eva_compact(summ, cache, win_table, sum_pages) ->
    cache``: one slot's finished window (its ``W`` rows in
    ``win_table``'s pages, every layer) becomes ``W / C`` summary rows
    written to ``sum_pages`` (``W / C / page_size`` page ids). ``summ``
    is :func:`summary_params`."""
    page_size = int(page_size)
    if cfg.summaries_per_window % page_size:
        raise ValueError(
            f"a window's {cfg.summaries_per_window} summary rows must "
            f"fill whole pages of {page_size}")

    def eva_compact(summ, cache, win_table, sum_pages):
        ck, cv = list(cache["k"]), list(cache["v"])
        rows = (cfg.window, cfg.n_heads, cfg.d_head)
        pages = (-1, page_size, cfg.n_heads, cfg.d_head)
        for l, sp in enumerate(summ):
            ks, vs = summarize(ck[l][win_table].reshape(rows),
                               cv[l][win_table].reshape(rows),
                               sp["phi"], sp["mu"], cfg.chunk)
            with jax.named_scope("kv.write"):
                ck[l] = ck[l].at[sum_pages].set(ks.reshape(pages))
                cv[l] = cv[l].at[sum_pages].set(vs.reshape(pages))
        return {"k": ck, "v": cv}

    return jax.jit(eva_compact, donate_argnums=(1,) if donate else ())
