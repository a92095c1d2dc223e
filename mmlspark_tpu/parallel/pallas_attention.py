"""Pallas flash-attention kernel for the ring-attention block step.

Drop-in replacement for ``ring_attention._block_attn`` (same
``(m, l, o)`` streaming-softmax partials contract) that never
materializes the (Sq × Sk) score matrix in HBM: the KV dimension is the
innermost grid axis, with the running max / normalizer / unnormalized
accumulator carried in VMEM scratch across KV tiles (the canonical TPU
flash pattern — see the pallas guide's grid/scratch sections). QK^T and
P·V run on the MXU per (tq × tk) tile; :func:`flash_tiles` picks the
tile from the lengths, the head_dim and the operand dtype (1024 × 1024
for 2048 bf16 tokens at head_dim 64, 128 × 128 for a 128-token prefill
bucket), because a grid step has a fixed cost that a 128 × 128 tile's
work does not cover.

Masking uses *global position* operands rather than block indices so the
one kernel serves every ring step: each device's local Q block carries
its global positions, the rotating KV block carries the origin rank's,
and the causal rule ``q_pos >= k_pos`` reproduces full visibility /
no visibility / the diagonal automatically. Sequence padding rides the
same mechanism (padded keys get the INT32-max sentinel position, masked
out even in bidirectional mode).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Q_TILE = 128         # the unit the lengths are padded to, and the floor
KV_TILE = 128        # of :func:`flash_tiles` and :func:`flash_bwd_tiles`
LANE = 128           # lanes of a vector register: a block's VMEM width
_NEG_INF = -1e30
_PAD_POS = np.iinfo(np.int32).max  # sentinel: padded key, always masked

# what one grid step of the forward kernel may hold in VMEM by
# :func:`_flash_vmem_bytes`' count (the backward's by
# :func:`_flash_bwd_vmem_bytes`'), and the kernel's scoped-VMEM limit
# (the v5e's default is 16 MiB of its 128). Mosaic's own count for
# 1024 x 1024 tiles, compiled ahead of time for the v5e: 9-12 MiB at
# head_dim 64 in bf16 and at 128 in f32 (the count here says 11 and
# 14.5), 17-20 at 256 in f32 (19)
_FLASH_VMEM_BUDGET = 20 * 2**20
_FLASH_VMEM_LIMIT = 32 * 2**20
_MAX_TILE = 1024     # the longest tile measured on the chip


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _flash_vmem_bytes(tq: int, tk: int, d: int, itemsize: int) -> int:
    """VMEM one forward grid step holds: the q/k/v blocks and the f32
    output block double-buffered, the accumulator and statistics, and
    the (tq x tk) scores in f32 and the probabilities in the operands'
    dtype. A block's last dimension takes whole 128-lane registers
    whatever ``d`` is."""
    d_l = _round_up(d, LANE)
    blocks = 2 * (tq + 2 * tk) * d_l * itemsize + 2 * tq * d_l * 4
    scratch = tq * d_l * 4 + 4 * tq * LANE * 4
    scores = tq * tk * (4 + itemsize)
    return blocks + scratch + scores


def _tile_choices(s: int):
    """Multiples of 128 that divide ``s`` padded to 128, largest first:
    the sequence is never padded further than to 128."""
    n = _round_up(s, Q_TILE) // Q_TILE
    return [Q_TILE * m for m in range(n, 0, -1)
            if n % m == 0 and Q_TILE * m <= _MAX_TILE]


def _largest_tiles(sq: int, sk: int, vmem_bytes) -> tuple:
    """Of the ``(tq, tk)`` that divide the lengths padded to 128 and
    whose ``vmem_bytes(tq, tk)`` fits :data:`_FLASH_VMEM_BUDGET`, the
    pair with the most score elements a grid step, ties to the longer
    KV tile; the floor is ``(Q_TILE, KV_TILE)``."""
    fits = [(tq * tk, tk, tq) for tq in _tile_choices(sq)
            for tk in _tile_choices(sk)
            if vmem_bytes(tq, tk) <= _FLASH_VMEM_BUDGET]
    if not fits:
        return Q_TILE, KV_TILE
    _, tk, tq = max(fits)
    return tq, tk


def flash_tiles(sq: int, sk: int, d: int, dtype) -> tuple:
    """``(tq, tk)`` of the forward kernel for these lengths, head_dim
    and operand dtype: of the pairs that divide the lengths padded to
    128 and fit :data:`_FLASH_VMEM_BUDGET`, the one with the most score
    elements a grid step. A grid step has a fixed cost (about a third
    of a microsecond) and every step rescales the accumulator, so on
    the v5e 64 heads of 2048 causal bf16 tokens at head_dim 64 take
    9.8 ms in 128 x 128 tiles, 1.56 in 512 x 512, 1.00 in 512 x 1024
    and 0.90 in 1024 x 1024 (PERF.md, PR 27). Ties go to the longer KV
    tile (1024 x 512 took 1.33). The floor is ``(Q_TILE, KV_TILE)``."""
    itemsize = jnp.dtype(dtype).itemsize
    return _largest_tiles(
        sq, sk, lambda tq, tk: _flash_vmem_bytes(tq, tk, d, itemsize))


def _tile_live(qpos, kpos, causal: bool):
    """False when the whole (q-tile x kv-tile) is masked out: all-padding
    keys, or (causal) every key strictly in every query's future."""
    kmin = jnp.min(kpos)
    live = kmin != _PAD_POS
    if causal:
        live = live & (jnp.max(qpos) >= kmin)
    return live


def _tile_full(qpos, kpos, causal: bool):
    """True when no (query, key) pair of the tile is masked: no padded
    key, and (causal) every key at or before every query."""
    kmax = jnp.max(kpos)
    full = kmax != _PAD_POS
    if causal:
        full = full & (jnp.min(qpos) >= kmax)
    return full


def _vma(x):
    """Varying-manual-axes of ``x`` (empty outside shard_map)."""
    return getattr(jax.typeof(x), "vma", frozenset()) or frozenset()


def _flash_kernel(qpos_ref, kpos_ref, q_ref, k_ref, v_ref, *refs,
                  scale: float, causal: bool, normalize: bool):
    """One (batch*head, q-tile, kv-tile) step of streaming attention.
    Outputs ``(o, lse)`` normalised when ``normalize``, else the ring's
    partials ``(o, m, l)``."""
    out_refs, (acc, m_scr, l_scr) = refs[:-3], refs[-3:]
    kv_idx = pl.program_id(2)

    @pl.when(kv_idx == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    qpos = qpos_ref[0]                                 # (TQ,)
    kpos = kpos_ref[0]                                 # (TK,)

    def step(masked: bool):
        s = jax.lax.dot_general(q_ref[0], k_ref[0],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if masked:
            if causal:
                # a padded key's sentinel lies past every query
                mask = qpos[:, None] >= kpos[None, :]
            else:
                mask = (kpos != _PAD_POS)[None, :]
            s = jnp.where(mask, s, _NEG_INF)
        m_prev = m_scr[:]                              # (TQ, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # a row with no live key yet keeps m == -1e30, and exp(s - m)
        # would be exp(0) for its masked entries: subtract 0 there, so
        # they underflow to 0, l stays 0 and the ring merge sees
        # "no data"
        m_sub = jnp.where(m_new <= _NEG_INF, 0.0, m_new) if masked else m_new
        p = jnp.exp(s - m_sub)
        alpha = jnp.exp(m_prev - m_new)                # (TQ, 1)
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        # P·V in the inputs' dtype (bf16 inputs keep the MXU fast path),
        # f32 accumulation via preferred_element_type
        acc[:] = acc[:] * alpha + jnp.dot(
            p.astype(v_ref.dtype), v_ref[0],
            preferred_element_type=jnp.float32)
        m_scr[:] = m_new

    # a tile whose every key is padding, or (causal) in the future of
    # every query, contributes nothing and is skipped (6 of 16 tile
    # pairs at 512 x 512 over 2048 causal tokens); a tile with nothing
    # to mask (another 6, off the diagonal) runs without the mask
    live = _tile_live(qpos, kpos, causal)
    full = _tile_full(qpos, kpos, causal)

    @pl.when(live & full)
    def _():
        step(masked=False)

    @pl.when(live & jnp.logical_not(full))
    def _():
        step(masked=True)

    @pl.when(kv_idx == pl.num_programs(2) - 1)
    def _():
        l = l_scr[:]
        if normalize:
            o_ref, lse_ref = out_refs
            l_safe = jnp.maximum(l, 1e-30)
            o_ref[0] = acc[:] / l_safe
            # lse = m + log l reconstructs p = exp(s - lse) tile-locally
            # in the backward; fully-masked rows get +BIG so their p
            # (and grads) are 0
            lse_ref[0] = jnp.where(l > 0, m_scr[:] + jnp.log(l_safe), 1e30)
        else:
            o_ref, m_ref, l_ref = out_refs
            o_ref[0] = acc[:]                          # unnormalized
            m_ref[0] = m_scr[:]                        # (TQ, 1)
            l_ref[0] = l


@functools.partial(jax.jit,
                   static_argnames=("scale", "causal", "interpret", "tiles",
                                    "normalize", "arange_pos"))
def _flash_call(q, k, v, q_pos, k_pos, scale: float, causal: bool,
                interpret: bool, tiles: tuple = (Q_TILE, KV_TILE),
                normalize: bool = False, arange_pos: bool = False):
    """q (BH, Sq, D), k/v (BH, Sk, D), positions (1, S*) int32, all
    padded to ``tiles`` = (tq, tk); D is the head_dim as it is (a block
    whose last dimension is the array's is legal: nothing pads the head
    to 128 lanes, and the matmuls contract and produce D columns).
    Returns f32 ``(o, m, l)`` partials, or ``(o, lse)`` normalised.
    ``arange_pos`` says the positions are ``arange`` (padding apart), so
    which causal tiles are dead is known here: their K/V index repeats
    the row's last live block and the pipeline fetches nothing."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    tq, tk = tiles
    grid = (bh, sq // tq, sk // tk)
    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               normalize=normalize)
    if causal and arange_pos:
        def kv_map(b, i, j):
            return (b, jnp.minimum(j, ((i + 1) * tq - 1) // tk), 0)
    else:
        def kv_map(b, i, j):
            return (b, j, 0)
    # stats as (.., TQ, 1) blocks: a trailing dim equal to the full array
    # dim satisfies the TPU (8, 128) tiling rule
    stat_spec = pl.BlockSpec((1, tq, 1), lambda b, i, j: (b, i, 0))
    # propagate the varying-manual-axes type so the kernel also composes
    # inside VMA-checked shard_map (the ring body)
    stat_shape = jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32, vma=_vma(q))
    n_stats = 1 if normalize else 2
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tq), lambda b, i, j: (0, i)),
            pl.BlockSpec((1, tk), lambda b, i, j: (0, j)),
            pl.BlockSpec((1, tq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, tk, d), kv_map),
            pl.BlockSpec((1, tk, d), kv_map),
        ],
        out_specs=[pl.BlockSpec((1, tq, d), lambda b, i, j: (b, i, 0))]
        + [stat_spec] * n_stats,
        out_shape=[jax.ShapeDtypeStruct((bh, sq, d), jnp.float32,
                                        vma=_vma(q))]
        + [stat_shape] * n_stats,
        scratch_shapes=[
            # acc / running-max / normalizer live across KV tiles
            pltpu.VMEM((tq, d), jnp.float32),
            pltpu.VMEM((tq, 1), jnp.float32),
            pltpu.VMEM((tq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_FLASH_VMEM_LIMIT),
        interpret=interpret,
    )(q_pos, k_pos, q, k, v)


def _to_bh(x, s_pad: int):
    """(B, S, H, D) -> (B*H, S_pad, D); keeps dtype and head_dim."""
    b, s, h, d = x.shape
    x = jnp.swapaxes(x, 1, 2).reshape(b * h, s, d)
    return jnp.pad(x, ((0, 0), (0, s_pad - s), (0, 0)))


def _padded_positions(q_pos, k_pos, sq_p: int, sk_p: int):
    """(1, S_pad) int32 position rows: padded queries sit at 0, padded
    keys at the sentinel no query sees."""
    q_pos, k_pos = (jnp.asarray(x, jnp.int32) for x in (q_pos, k_pos))
    return (jnp.pad(q_pos, (0, sq_p - q_pos.shape[0]))[None],
            jnp.pad(k_pos, (0, sk_p - k_pos.shape[0]),
                    constant_values=_PAD_POS)[None])


def flash_block_attn(q, k, v, scale, q_pos, k_pos, causal: bool,
                     interpret: bool = False):
    """``_block_attn`` twin: returns (m (B,H,Sq), l (B,H,Sq),
    o (B,Sq,H,Dh) unnormalized) for the online-softmax ring merge.

    q (B, Sq, H, Dh); k, v (B, Sk, H, Dh); *_pos (S*,) int32 global
    positions. Handles arbitrary (unaligned) Sq/Sk by padding to 128,
    a multiple of the tiles :func:`flash_tiles` picks; padded keys
    carry a sentinel position and can never contribute. The positions
    are operands (a ring step's are traced), so every tile is a grid
    step and the kernel decides from them which it skips.
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    sq_p, sk_p = _round_up(sq, Q_TILE), _round_up(sk, KV_TILE)
    qpos_p, kpos_p = _padded_positions(q_pos, k_pos, sq_p, sk_p)
    o, m, l = _flash_call(_to_bh(q, sq_p), _to_bh(k, sk_p), _to_bh(v, sk_p),
                          qpos_p, kpos_p, float(scale), causal, interpret,
                          tiles=flash_tiles(sq, sk, d, q.dtype))
    o = o[:, :sq].reshape(b, h, sq, d).swapaxes(1, 2)      # (B,Sq,H,Dh)
    m = m[:, :sq, 0].reshape(b, h, sq)
    l = l[:, :sq, 0].reshape(b, h, sq)
    return m.astype(q.dtype), l.astype(q.dtype), o.astype(q.dtype)


def flash_available() -> bool:
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# Full flash attention with a Pallas backward (custom VJP)
# ---------------------------------------------------------------------------
#
# The ring path above streams (m, l, o) partials and is forward-only; this
# is the standalone differentiable kernel for the un-ring-sharded (dense)
# attention path in models/transformer.py — the path the single-chip train
# cell measures. Forward reuses _flash_call; backward is the
# FlashAttention-2 recipe: save q, k, v in the kernels' (B*H, S, D)
# layout with out and the lse, recompute each score tile in VMEM, and
# accumulate dk/dv and dq in float32 scratch. No (S x S) matrix ever
# reaches HBM in either direction.

# the backward kernel's scoped-VMEM limit (of the v5e's 128 MiB): its
# tiles fit :data:`_FLASH_VMEM_BUDGET` by :func:`_flash_bwd_vmem_bytes`'
# count, but the dq of the whole sequence stays in VMEM, 1 KiB a query
# row at head_dim <= 128 in bf16 (so the kernel ends near 50,000 tokens)
_FLASH_BWD_VMEM_LIMIT = 64 * 2**20


def _flash_bwd_vmem_bytes(tq: int, tk: int, d: int, itemsize: int,
                          sq_p: int) -> int:
    """VMEM one backward grid step holds: the q/do/k/v blocks
    double-buffered; the dk/dv blocks and the whole-sequence dq block
    double-buffered, and their f32 accumulators; and of the (tq x tk)
    tile two f32 temporaries (scores/probabilities, ``dp``/``ds``) and
    ``p`` and ``ds`` in the operands' dtype. Mosaic's own count,
    compiled ahead of time for the v5e at head_dim 64 in bf16 over 2048
    tokens: PERF.md, PR 32 (the count here says 18 MiB for 1024 x 1024
    tiles)."""
    d_l = _round_up(d, LANE)
    blocks = 2 * (2 * tq + 2 * tk) * d_l * itemsize
    outs = 2 * (2 * tk + sq_p) * d_l * itemsize
    scratch = (2 * tk + sq_p) * d_l * 4
    scores = tq * tk * (2 * 4 + 2 * itemsize)
    return blocks + outs + scratch + scores


def flash_bwd_tiles(sq: int, sk: int, d: int, dtype) -> tuple:
    """``(tq, tk)`` of the backward kernel: :func:`flash_tiles`' rule
    over the backward's own VMEM count. On the v5e the backward of 64
    heads of 2048 causal bf16 tokens at head_dim 64 takes 11.9 ms in
    128 x 128 tiles, 4.31 in 256 x 256, 2.30 in 512 x 512, 2.24 in
    512 x 1024, 2.23 in 1024 x 512 and 2.09 in 1024 x 1024 (PERF.md,
    PR 32; XLA's einsums over the whole ``[b,H,S,S]`` took 7.12)."""
    itemsize = jnp.dtype(dtype).itemsize
    sq_p = _round_up(sq, Q_TILE)
    return _largest_tiles(
        sq, sk,
        lambda tq, tk: _flash_bwd_vmem_bytes(tq, tk, d, itemsize, sq_p))


def _first_live_q(j, tq: int, tk: int):
    """Index of the first q tile that sees kv tile ``j`` under the
    causal rule with ``arange`` positions."""
    return (j * tk) // tq


def _flash_bwd_kernel(qpos_ref, kpos_ref, q_ref, k_ref, v_ref, do_ref,
                      lse_ref, delta_ref, dq_ref, dk_ref, dv_ref,
                      dq_acc, dk_acc, dv_acc, *, scale: float, causal: bool):
    """One (batch*head, kv-tile, q-tile) step, q innermost: dk/dv of the
    kv tile accumulate over the q tiles, dq of the WHOLE sequence stays
    in VMEM over both axes, so a tile's scores are recomputed once (five
    tile products, and one pass over the scores, for the seven and two
    of separate dq and dk/dv kernels: 2.09 ms against 2.80 on the chip).
    ``lse`` and ``delta = sum(do * out)`` arrive as lane-dense (1, TQ)
    rows; ``p`` and ``ds`` are rounded to the operands' dtype before
    their products, every accumulation is f32."""
    j, i = pl.program_id(1), pl.program_id(2)
    last_j, last_i = pl.num_programs(1) - 1, pl.num_programs(2) - 1
    tq = q_ref.shape[1]

    @pl.when((j == 0) & (i == 0))
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    @pl.when(i == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    qpos, kpos = qpos_ref[0], kpos_ref[0]

    def step(masked: bool):
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        # a row with no visible key carries lse = +BIG: its p is 0
        p = jnp.exp(s - lse_ref[0, 0][:, None])            # (TQ, TK)
        if masked:
            if causal:
                # a padded key's sentinel lies past every query
                mask = qpos[:, None] >= kpos[None, :]
            else:
                mask = (kpos != _PAD_POS)[None, :]
            p = jnp.where(mask, p, 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[0, 0][:, None])).astype(q.dtype)
        dv_acc[:] += jax.lax.dot_general(                   # p^T @ do
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc[:] += jax.lax.dot_general(                   # ds^T @ q
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        rows = pl.ds(pl.multiple_of(i * tq, tq), tq)
        dq_acc[rows, :] += jnp.dot(ds, k,
                                   preferred_element_type=jnp.float32)

    # dead and unmasked tiles as in the forward kernel
    live = _tile_live(qpos, kpos, causal)
    full = _tile_full(qpos, kpos, causal)

    @pl.when(live & full)
    def _():
        step(masked=False)

    @pl.when(live & jnp.logical_not(full))
    def _():
        step(masked=True)

    @pl.when(i == last_i)
    def _():
        dk_ref[0] = (dk_acc[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)

    @pl.when((j == last_j) & (i == last_i))
    def _():
        dq_ref[0] = (dq_acc[:] * scale).astype(dq_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("scale", "causal", "interpret", "tiles"))
def _flash_bwd_call(q, k, v, do, lse, delta, q_pos, k_pos,
                    scale: float, causal: bool, interpret: bool,
                    tiles: tuple):
    """q/k/v/do (BH, S_pad, D) in their own dtype; lse and delta
    (BH, 1, Sq_pad) f32, a lane-dense row a head (a (.., S, 1) array
    takes 128 lanes a row in HBM: 67 MB at the train cell's shape);
    positions (1, S_pad) int32, ``arange`` but for the padding; S_pad a
    multiple of ``tiles`` = (tq, tk). Returns (dq, dk, dv) in the
    operands' dtype and layout. Under the causal rule a dead tile's
    q-side blocks repeat the kv tile's first live q block, so the
    pipeline fetches nothing for it."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    tq, tk = tiles
    vma = _vma(q)

    def q_idx(j, i):
        return jnp.maximum(i, _first_live_q(j, tq, tk)) if causal else i

    q_spec = pl.BlockSpec((1, tq, d), lambda b, j, i: (b, q_idx(j, i), 0))
    kv_spec = pl.BlockSpec((1, tk, d), lambda b, j, i: (b, j, 0))
    stat_spec = pl.BlockSpec((1, 1, tq), lambda b, j, i: (b, 0, q_idx(j, i)))
    return pl.pallas_call(
        functools.partial(_flash_bwd_kernel, scale=scale, causal=causal),
        grid=(bh, sk // tk, sq // tq),
        in_specs=[
            pl.BlockSpec((1, tq), lambda b, j, i: (0, i)),
            pl.BlockSpec((1, tk), lambda b, j, i: (0, j)),
            q_spec, kv_spec, kv_spec, q_spec,               # q, k, v, do
            stat_spec, stat_spec,                           # lse, delta
        ],
        out_specs=[pl.BlockSpec((1, sq, d), lambda b, j, i: (b, 0, 0)),
                   kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct((bh, sq, d), q.dtype, vma=vma),
                   jax.ShapeDtypeStruct((bh, sk, d), k.dtype, vma=vma),
                   jax.ShapeDtypeStruct((bh, sk, d), v.dtype, vma=vma)],
        scratch_shapes=[pltpu.VMEM((sq, d), jnp.float32),
                        pltpu.VMEM((tk, d), jnp.float32),
                        pltpu.VMEM((tk, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            # dq accumulates over the kv axis too
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_FLASH_BWD_VMEM_LIMIT),
        interpret=interpret,
    )(q_pos, k_pos, q, k, v, do, lse, delta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, causal: bool = True, scale=None,
                    interpret: bool = False):
    """Differentiable flash attention, [B, S, H, Dh] in/out.

    Forward = the streaming kernel above (normalized, saves the
    log-sum-exp). Backward = one Pallas kernel (``_flash_bwd_call``)
    that recomputes ``p = exp(s - lse)`` tile by tile in VMEM and
    applies the FlashAttention-2 gradient algebra: ``p`` and ``ds`` are
    rounded to the operands' dtype before their products, every
    accumulation is float32, and nothing (S x S) reaches HBM. Its tiles
    come from the shape (:func:`flash_bwd_tiles`), it skips and does not
    fetch the dead causal tiles, and it masks only the tiles that need
    it, as the forward does.

    Numerically equivalent to :func:`ring_attention.dense_attention` in
    value and gradients to f32 tolerance (tests/test_transformer.py).
    ``interpret=True`` runs the kernels interpreted for CPU tests.
    """
    out, _ = _flash_fwd(q, k, v, causal, scale, interpret)
    return out


def _bh_rows(x):
    """(B, S, H, D) -> (B*H, S, D); keeps dtype and head_dim."""
    return _to_bh(x, x.shape[1])


def _pad_rows(x, s_pad: int):
    return jnp.pad(x, ((0, 0), (0, s_pad - x.shape[1]), (0, 0)))


def _flash_fwd(q, k, v, causal, scale, interpret):
    b, sq, h, d = q.shape
    sk = k.shape[1]
    sq_p, sk_p = _round_up(sq, Q_TILE), _round_up(sk, KV_TILE)
    qpos, kpos = _padded_positions(jnp.arange(sq), jnp.arange(sk),
                                   sq_p, sk_p)
    scale_f = float(scale) if scale is not None else d ** -0.5
    tq, tk = flash_tiles(sq, sk, d, q.dtype)
    # the relayout around the kernel and the kernel (the jitted
    # ``_flash_call``, named by itself in a trace, under a scope that
    # says which tiles ran) under names of their own, inside the
    # caller's ``attn.core``
    with jax.named_scope("flash.layout"):
        qb, kb, vb = _bh_rows(q), _bh_rows(k), _bh_rows(v)
    with jax.named_scope(f"flash.t{tq}x{tk}"):
        # normalized f32 (BH, Sq_p, D) and lse (BH, Sq_p, 1)
        out_bh, lse_bh = _flash_call(
            _pad_rows(qb, sq_p), _pad_rows(kb, sk_p), _pad_rows(vb, sk_p),
            qpos, kpos, scale_f, causal, interpret,
            tiles=(tq, tk), normalize=True, arange_pos=True)
    with jax.named_scope("flash.layout"):
        out = out_bh[:, :sq].reshape(b, h, sq, d).swapaxes(1, 2)
        # the residuals: q, k, v in the kernels' layout, unpadded (their
        # shapes carry the lengths), so the backward transposes nothing
        # again; the lse as one lane-dense row a head (a (.., S, 1)
        # array takes 128 lanes a row in HBM)
        return out.astype(q.dtype), (qb, kb, vb, out,
                                     lse_bh.reshape(b * h, 1, sq_p))


def _flash_bwd(causal, scale, interpret, res, dout):
    qb, kb, vb, out, lse = res
    b, sq, h, d = dout.shape
    sk = kb.shape[1]
    sq_p, sk_p = lse.shape[2], _round_up(sk, KV_TILE)
    qpos, kpos = _padded_positions(jnp.arange(sq), jnp.arange(sk),
                                   sq_p, sk_p)
    scale_f = float(scale) if scale is not None else d ** -0.5
    tq, tk = flash_bwd_tiles(sq, sk, d, qb.dtype)
    with jax.named_scope("flash.layout"):
        do_bh = _to_bh(dout, sq_p)
        # delta = sum(do * out), one row a head like the lse
        delta = jnp.sum(dout.astype(jnp.float32) * out, axis=-1)
        delta = jnp.pad(delta.swapaxes(1, 2).reshape(b * h, 1, sq),
                        ((0, 0), (0, 0), (0, sq_p - sq)))
    with jax.named_scope(f"flash.bwd_t{tq}x{tk}"):
        dq, dk, dv = _flash_bwd_call(
            _pad_rows(qb, sq_p), _pad_rows(kb, sk_p), _pad_rows(vb, sk_p),
            do_bh, lse, delta, qpos, kpos, scale_f, causal, interpret,
            tiles=(tq, tk))

    def from_bh(x, s):
        return x[:, :s].reshape(b, h, s, d).swapaxes(1, 2)

    with jax.named_scope("flash.layout"):
        return from_bh(dq, sq), from_bh(dk, sk), from_bh(dv, sk)


flash_attention.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# Folded (feature-major) flash kernels — the short-head-dim regime
# ---------------------------------------------------------------------------
#
# The kernels above put head_dim on the LANE axis: at head_dim 64 a block
# fills half of each 128-lane register and every d-output matmul uses
# half the MXU's columns. The folded layout has no short lane axis:
#
#   q, k, v, o:  (B, H*Dh, S)   — heads*features on the SUBLANE axis
#                                  (8-multiple, no 128 constraint),
#                                  sequence tiles on the lane axis
#   per head:    X[h*Dh:(h+1)*Dh, :] — a cheap sublane slice
#
# Every matmul runs in transposed form — s^T = k_h . q_h (contract the
# feature sublanes), o_h = v_h . p^T — so no operand or output ever has
# fewer than 128 live lanes, whatever Dh is (Dh % 8 == 0). The softmax
# runs over the SUBLANE axis of s^T with (1, TQ) running stats. One grid
# step processes every head of a (q-tile, kv-tile) block, so K/V tiles
# are DMA'd once per q-tile, not once per head.

F_TILE = 512   # q/kv tile edge (clamped to S; S must divide by it)


def _fold_tile(s: int) -> int:
    for t in (F_TILE, 256, 128):
        if s % t == 0:
            return t
    return 0


# VMEM the folded kernels' largest pass (dk/dv backward) may request:
# q/k/v/do blocks double-buffered + two f32 output blocks + two f32
# accumulator scratches, all (H*Dh, tile) — ~40 bytes per (H*Dh x tile)
# element. 14 MB keeps a healthy margin under the ~16 MB v5e VMEM.
_FOLDED_VMEM_BUDGET = 14 * 2**20


def _folded_shape_ok(sq: int, sk: int, d: int,
                     h: Optional[int] = None) -> bool:
    """Same-length self-attention, tileable S, sublane-aligned head —
    the shape half of the folded-kernel eligibility (backend-agnostic:
    interpret mode runs these shapes on CPU too). Pass ``h`` to also
    bound the folded (H*Dh, tile) working set against VMEM: every
    buffer in these kernels carries ALL heads, so wide-head configs
    (large H*Dh) can exceed VMEM even at short head dims — the auto
    policies must fall back rather than fail the Mosaic compile
    (r4 advisor)."""
    ok = sq == sk and d % 8 == 0 and _fold_tile(sq) > 0
    if ok and h is not None:
        ok = h * d * _fold_tile(sq) * 40 <= _FOLDED_VMEM_BUDGET
    return ok


def folded_available(sq: int, sk: int, d: int,
                     h: Optional[int] = None) -> bool:
    return _folded_shape_ok(sq, sk, d, h) and \
        jax.default_backend() == "tpu"


def _causal_mask_t(i, j, tq: int, tk: int):
    """Mask for the TRANSPOSED score tile s^T (TK, TQ): key pos <= q pos."""
    qpos = i * tq + jax.lax.broadcasted_iota(jnp.int32, (tk, tq), 1)
    kpos = j * tk + jax.lax.broadcasted_iota(jnp.int32, (tk, tq), 0)
    return kpos <= qpos


def _ffwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_scr, l_scr,
                 *, scale: float, causal: bool, h: int, d: int,
                 tq: int, tk: int):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    live = (j * tk <= i * tq + tq - 1) if causal else (j >= 0)

    @pl.when(live)
    def _():
        mask = _causal_mask_t(i, j, tq, tk) if causal else None
        for hh in range(h):
            sl = slice(hh * d, (hh + 1) * d)
            st = jax.lax.dot_general(                      # (TK, TQ)
                k_ref[0, sl, :], q_ref[0, sl, :],
                (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if causal:
                st = jnp.where(mask, st, _NEG_INF)
            m_prev = m_scr[hh]                             # (1, TQ)
            m_new = jnp.maximum(m_prev,
                                jnp.max(st, axis=0, keepdims=True))
            pt = jnp.exp(st - m_new)                       # (TK, TQ)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[hh] = l_scr[hh] * alpha + jnp.sum(pt, axis=0,
                                                    keepdims=True)
            acc[sl, :] = acc[sl, :] * alpha + jax.lax.dot_general(
                v_ref[0, sl, :], pt.astype(v_ref.dtype),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[hh] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        for hh in range(h):
            sl = slice(hh * d, (hh + 1) * d)
            l_safe = jnp.maximum(l_scr[hh], 1e-30)         # (1, TQ)
            o_ref[0, sl, :] = (acc[sl, :] / l_safe).astype(o_ref.dtype)
            lse_ref[0, hh] = (m_scr[hh] + jnp.log(l_safe))[0]


def _fdq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dq_ref, dq_acc, *, scale: float, causal: bool, h: int,
                d: int, tq: int, tk: int):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    live = (j * tk <= i * tq + tq - 1) if causal else (j >= 0)

    @pl.when(live)
    def _():
        mask = _causal_mask_t(i, j, tq, tk) if causal else None
        for hh in range(h):
            sl = slice(hh * d, (hh + 1) * d)
            kh, qh = k_ref[0, sl, :], q_ref[0, sl, :]
            st = jax.lax.dot_general(
                kh, qh, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if causal:
                st = jnp.where(mask, st, _NEG_INF)
            lse = lse_ref[0, hh].reshape(1, tq)
            pt = jnp.exp(st - lse)                         # (TK, TQ)
            dpt = jax.lax.dot_general(                     # do . v
                v_ref[0, sl, :], do_ref[0, sl, :],
                (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dst = pt * (dpt - delta_ref[0, hh].reshape(1, tq))
            dq_acc[sl, :] += jax.lax.dot_general(          # (D, TQ)
                kh, dst.astype(kh.dtype), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * scale

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _fdkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                 dk_ref, dv_ref, dk_acc, dv_acc, *, scale: float,
                 causal: bool, h: int, d: int, tq: int, tk: int):
    j, i = pl.program_id(1), pl.program_id(2)

    @pl.when(i == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    live = (j * tk <= i * tq + tq - 1) if causal else (j >= 0)

    @pl.when(live)
    def _():
        mask = _causal_mask_t(i, j, tq, tk) if causal else None
        for hh in range(h):
            sl = slice(hh * d, (hh + 1) * d)
            qh, doh = q_ref[0, sl, :], do_ref[0, sl, :]
            st = jax.lax.dot_general(
                k_ref[0, sl, :], qh, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if causal:
                st = jnp.where(mask, st, _NEG_INF)
            pt = jnp.exp(st - lse_ref[0, hh].reshape(1, tq))
            dv_acc[sl, :] += jax.lax.dot_general(          # do . p
                doh, pt.astype(doh.dtype), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            dpt = jax.lax.dot_general(
                v_ref[0, sl, :], doh, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dst = (pt * (dpt - delta_ref[0, hh].reshape(1, tq))
                   ).astype(qh.dtype)
            dk_acc[sl, :] += jax.lax.dot_general(          # (D, TK)
                qh, dst, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale

    @pl.when(i == pl.num_programs(2) - 1)
    def _():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=("h", "scale", "causal",
                                             "interpret"))
def _ffwd_call(qf, kf, vf, h: int, scale: float, causal: bool,
               interpret: bool):
    """qf/kf/vf (B, H*D, S) -> (o (B, H*D, S), lse (B, H, S) f32)."""
    b, hd, s = qf.shape
    d = hd // h
    t = _fold_tile(s)
    grid = (b, s // t, s // t)
    kernel = functools.partial(_ffwd_kernel, scale=scale, causal=causal,
                               h=h, d=d, tq=t, tk=t)
    seq_spec = pl.BlockSpec((1, hd, t), lambda b_, i, j: (b_, 0, i))
    kv_spec = pl.BlockSpec((1, hd, t), lambda b_, i, j: (b_, 0, j))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[seq_spec, kv_spec, kv_spec],
        out_specs=[seq_spec,
                   pl.BlockSpec((1, h, t), lambda b_, i, j: (b_, 0, i))],
        out_shape=[
            jax.ShapeDtypeStruct((b, hd, s), qf.dtype, vma=_vma(qf)),
            jax.ShapeDtypeStruct((b, h, s), jnp.float32, vma=_vma(qf)),
        ],
        scratch_shapes=[pltpu.VMEM((hd, t), jnp.float32),
                        pltpu.VMEM((h, 1, t), jnp.float32),
                        pltpu.VMEM((h, 1, t), jnp.float32)],
        interpret=interpret,
    )(qf, kf, vf)


@functools.partial(jax.jit, static_argnames=("h", "scale", "causal",
                                             "interpret"))
def _fbwd_call(qf, kf, vf, dof, lse, delta, h: int, scale: float,
               causal: bool, interpret: bool):
    """Folded backward: all (B, H*D, S); lse/delta (B, H, S) f32."""
    b, hd, s = qf.shape
    d = hd // h
    t = _fold_tile(s)
    n = s // t
    f32 = jnp.float32

    q_spec = pl.BlockSpec((1, hd, t), lambda b_, i, j: (b_, 0, i))
    kv_spec = pl.BlockSpec((1, hd, t), lambda b_, i, j: (b_, 0, j))
    st_spec = pl.BlockSpec((1, h, t), lambda b_, i, j: (b_, 0, i))
    dq = pl.pallas_call(
        functools.partial(_fdq_kernel, scale=scale, causal=causal,
                          h=h, d=d, tq=t, tk=t),
        grid=(b, n, n),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, st_spec, st_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, hd, s), f32, vma=_vma(qf)),
        scratch_shapes=[pltpu.VMEM((hd, t), f32)],
        interpret=interpret,
    )(qf, kf, vf, dof, lse, delta)

    # dk/dv accumulate across q tiles -> q innermost; note the index
    # maps swap (b, j, i)
    q_spec2 = pl.BlockSpec((1, hd, t), lambda b_, j, i: (b_, 0, i))
    kv_spec2 = pl.BlockSpec((1, hd, t), lambda b_, j, i: (b_, 0, j))
    st_spec2 = pl.BlockSpec((1, h, t), lambda b_, j, i: (b_, 0, i))
    dk, dv = pl.pallas_call(
        functools.partial(_fdkv_kernel, scale=scale, causal=causal,
                          h=h, d=d, tq=t, tk=t),
        grid=(b, n, n),
        in_specs=[q_spec2, kv_spec2, kv_spec2, q_spec2, st_spec2, st_spec2],
        out_specs=[kv_spec2, kv_spec2],
        out_shape=[jax.ShapeDtypeStruct((b, hd, s), f32, vma=_vma(qf)),
                   jax.ShapeDtypeStruct((b, hd, s), f32, vma=_vma(qf))],
        scratch_shapes=[pltpu.VMEM((hd, t), f32),
                        pltpu.VMEM((hd, t), f32)],
        interpret=interpret,
    )(qf, kf, vf, dof, lse, delta)
    return dq, dk, dv


def _to_folded(x):
    """(B, S, H, D) -> (B, H*D, S)."""
    b, s, h, d = x.shape
    return x.transpose(0, 2, 3, 1).reshape(b, h * d, s)


def _from_folded(x, h: int):
    """(B, H*D, S) -> (B, S, H, D)."""
    b, hd, s = x.shape
    return x.reshape(b, h, hd // h, s).transpose(0, 3, 1, 2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention_folded(q, k, v, causal: bool = True, scale=None,
                           interpret: bool = False):
    """Differentiable folded flash attention, [B, S, H, Dh] in/out.

    The short-head-dim twin of :func:`flash_attention`: same streaming
    algorithm and FA-2 backward algebra, feature-major kernels (heads on
    the sublane axis — see the section comment). Use when
    :func:`folded_available`; numerics match ``dense_attention`` to f32
    tolerance (tests/test_transformer.py).
    """
    out, _ = _ffold_fwd(q, k, v, causal, scale, interpret)
    return out


def _ffold_fwd(q, k, v, causal, scale, interpret):
    b, s, h, d = q.shape
    scale_f = float(scale) if scale is not None else d ** -0.5
    qf, kf, vf = _to_folded(q), _to_folded(k), _to_folded(v)
    of, lse = _ffwd_call(qf, kf, vf, h, scale_f, causal, interpret)
    return _from_folded(of, h).astype(q.dtype), (qf, kf, vf, of, lse)


def _ffold_bwd(causal, scale, interpret, res, dout):
    qf, kf, vf, of, lse = res
    b, hd, s = qf.shape
    h = lse.shape[1]
    d = hd // h
    scale_f = float(scale) if scale is not None else d ** -0.5
    dof = _to_folded(dout).astype(qf.dtype)
    # delta_h = sum_d do * out, per (b, h, s) — cast BEFORE the product
    # so bf16 inputs multiply in f32 (matching _flash_bwd's numerics)
    delta = jnp.sum((dof.astype(jnp.float32) * of.astype(jnp.float32))
                    .reshape(b, h, d, s), axis=2)          # (B, H, S)
    dq, dk, dv = _fbwd_call(qf, kf, vf, dof, lse, delta, h, scale_f,
                            causal, interpret)
    return (_from_folded(dq, h).astype(qf.dtype),
            _from_folded(dk, h).astype(kf.dtype),
            _from_folded(dv, h).astype(vf.dtype))


flash_attention_folded.defvjp(_ffold_fwd, _ffold_bwd)


# ---------------------------------------------------------------------------
# Folded ring-block kernel (position-aware, forward-only)
# ---------------------------------------------------------------------------
#
# The :func:`flash_block_attn` twin in the feature-major layout: the ring
# path's per-step block attention for short head dims. Positions are
# kernel operands — the query block's as a lane-oriented (1, S) row, the
# rotating KV block's as a sublane-oriented (S, 1) column (the transposed
# score tile s^T (TK, TQ) masks with kpos on sublanes, qpos on lanes) —
# so one kernel serves every ring step: full / diagonal / no visibility
# fall out of ``k_pos <= q_pos``, padded keys carry the sentinel.
# Returns the ring merge's (m, l, o-unnormalized) partials contract.


def _fring_kernel(qpos_ref, kpos_ref, q_ref, k_ref, v_ref,
                  o_ref, m_ref, l_ref, acc, m_scr, l_scr,
                  *, scale: float, causal: bool, h: int, d: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    qpos = qpos_ref[0]                                  # (TQ,) lanes
    kpos = kpos_ref[:, 0:1]                             # (TK, 1) sublanes
    kmin = jnp.min(kpos)
    live = kmin != _PAD_POS
    if causal:
        live = live & (jnp.max(qpos) >= kmin)

    @pl.when(live)
    def _():
        mask = kpos != _PAD_POS                         # (TK, 1)
        if causal:
            mask = mask & (kpos <= qpos[None, :])       # (TK, TQ)
        for hh in range(h):
            sl = slice(hh * d, (hh + 1) * d)
            st = jax.lax.dot_general(                   # (TK, TQ)
                k_ref[0, sl, :], q_ref[0, sl, :],
                (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            st = jnp.where(mask, st, _NEG_INF)
            m_prev = m_scr[hh]                          # (1, TQ)
            m_new = jnp.maximum(m_prev,
                                jnp.max(st, axis=0, keepdims=True))
            # fully-masked columns: m_new == -1e30 makes exp(st - m_new)
            # = exp(0); kill those so l stays 0 (ring merge: "no data")
            pt = jnp.where(mask, jnp.exp(st - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[hh] = l_scr[hh] * alpha + jnp.sum(pt, axis=0,
                                                    keepdims=True)
            acc[sl, :] = acc[sl, :] * alpha + jax.lax.dot_general(
                v_ref[0, sl, :], pt.astype(v_ref.dtype),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[hh] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        for hh in range(h):
            sl = slice(hh * d, (hh + 1) * d)
            o_ref[0, sl, :] = acc[sl, :].astype(o_ref.dtype)  # UNnormalized
            m_ref[0, hh] = m_scr[hh][0]
            l_ref[0, hh] = l_scr[hh][0]


@functools.partial(jax.jit, static_argnames=("h", "scale", "causal",
                                             "interpret"))
def _fring_call(qf, kf, vf, qpos, kpos_t, h: int, scale: float,
                causal: bool, interpret: bool):
    """qf/kf/vf (B, H*D, S); qpos (1, S); kpos_t (S, 1) int32."""
    b, hd, s = qf.shape
    d = hd // h
    t = _fold_tile(s)
    grid = (b, s // t, s // t)
    seq_spec = pl.BlockSpec((1, hd, t), lambda b_, i, j: (b_, 0, i))
    kv_spec = pl.BlockSpec((1, hd, t), lambda b_, i, j: (b_, 0, j))
    st_spec = pl.BlockSpec((1, h, t), lambda b_, i, j: (b_, 0, i))
    return pl.pallas_call(
        functools.partial(_fring_kernel, scale=scale, causal=causal,
                          h=h, d=d),
        grid=grid,
        in_specs=[pl.BlockSpec((1, t), lambda b_, i, j: (0, i)),
                  pl.BlockSpec((t, 1), lambda b_, i, j: (j, 0)),
                  seq_spec, kv_spec, kv_spec],
        out_specs=[seq_spec, st_spec, st_spec],
        out_shape=[
            # the UNNORMALIZED accumulator stays f32 whatever the input
            # dtype: the ring merge rescales it across n steps, and
            # quantizing each step's partial to bf16 would compound
            # (the dense ring keeps f32 partials too)
            jax.ShapeDtypeStruct((b, hd, s), jnp.float32, vma=_vma(qf)),
            jax.ShapeDtypeStruct((b, h, s), jnp.float32, vma=_vma(qf)),
            jax.ShapeDtypeStruct((b, h, s), jnp.float32, vma=_vma(qf)),
        ],
        scratch_shapes=[pltpu.VMEM((hd, t), jnp.float32),
                        pltpu.VMEM((h, 1, t), jnp.float32),
                        pltpu.VMEM((h, 1, t), jnp.float32)],
        interpret=interpret,
    )(qpos, kpos_t, qf, kf, vf)


# same eligibility as the differentiable folded kernel (the ring's
# local blocks are same-length by construction)
folded_block_available = folded_available


def _frdq_kernel(qpos_ref, kpos_ref, q_ref, k_ref, v_ref, do_ref,
                 lse_ref, delta_ref, dq_ref, dq_acc,
                 *, scale: float, causal: bool, h: int, d: int):
    """Position-aware folded dq for one ring block pair (kv inner)."""
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    qpos = qpos_ref[0]
    kpos = kpos_ref[:, 0:1]
    kmin = jnp.min(kpos)
    live = kmin != _PAD_POS
    if causal:
        live = live & (jnp.max(qpos) >= kmin)

    @pl.when(live)
    def _():
        mask = kpos != _PAD_POS
        if causal:
            mask = mask & (kpos <= qpos[None, :])
        for hh in range(h):
            sl = slice(hh * d, (hh + 1) * d)
            kh, qh = k_ref[0, sl, :], q_ref[0, sl, :]
            st = jax.lax.dot_general(
                kh, qh, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            st = jnp.where(mask, st, _NEG_INF)
            # lse rows with no visible key carry the +BIG sentinel, so
            # exp(-inf - BIG) underflows to exactly 0 — no garbage flows
            pt = jnp.exp(st - lse_ref[0, hh].reshape(1, -1))
            dpt = jax.lax.dot_general(
                v_ref[0, sl, :], do_ref[0, sl, :],
                (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dst = pt * (dpt - delta_ref[0, hh].reshape(1, -1))
            dq_acc[sl, :] += jax.lax.dot_general(
                kh, dst.astype(kh.dtype), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * scale

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _frdkv_kernel(qpos_ref, kpos_ref, q_ref, k_ref, v_ref, do_ref,
                  lse_ref, delta_ref, dk_ref, dv_ref, dk_acc, dv_acc,
                  *, scale: float, causal: bool, h: int, d: int):
    """Position-aware folded dk/dv for one ring block pair (q inner)."""
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    qpos = qpos_ref[0]
    kpos = kpos_ref[:, 0:1]
    kmin = jnp.min(kpos)
    live = kmin != _PAD_POS
    if causal:
        live = live & (jnp.max(qpos) >= kmin)

    @pl.when(live)
    def _():
        mask = kpos != _PAD_POS
        if causal:
            mask = mask & (kpos <= qpos[None, :])
        for hh in range(h):
            sl = slice(hh * d, (hh + 1) * d)
            qh, doh = q_ref[0, sl, :], do_ref[0, sl, :]
            st = jax.lax.dot_general(
                k_ref[0, sl, :], qh, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            st = jnp.where(mask, st, _NEG_INF)
            pt = jnp.exp(st - lse_ref[0, hh].reshape(1, -1))
            dv_acc[sl, :] += jax.lax.dot_general(
                doh, pt.astype(doh.dtype), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            dpt = jax.lax.dot_general(
                v_ref[0, sl, :], doh, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dst = (pt * (dpt - delta_ref[0, hh].reshape(1, -1))
                   ).astype(qh.dtype)
            dk_acc[sl, :] += jax.lax.dot_general(
                qh, dst, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale

    @pl.when(i == pl.num_programs(2) - 1)
    def _():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=("h", "scale", "causal",
                                             "interpret"))
def _fring_bwd_call(qf, kf, vf, dof, lse, delta, qpos, kpos_t,
                    h: int, scale: float, causal: bool, interpret: bool):
    """Folded ring-block backward: one (q-block, kv-block) pair.
    qf/kf/vf/dof (B, H*D, S); lse/delta (B, H, S) f32 (lse carries +BIG
    on no-visibility rows); qpos (1, S); kpos_t (S, 1) int32."""
    b, hd, s = qf.shape
    d = hd // h
    t = _fold_tile(s)
    n = s // t
    f32 = jnp.float32

    q_spec = pl.BlockSpec((1, hd, t), lambda b_, i, j: (b_, 0, i))
    kv_spec = pl.BlockSpec((1, hd, t), lambda b_, i, j: (b_, 0, j))
    st_spec = pl.BlockSpec((1, h, t), lambda b_, i, j: (b_, 0, i))
    dq = pl.pallas_call(
        functools.partial(_frdq_kernel, scale=scale, causal=causal,
                          h=h, d=d),
        grid=(b, n, n),
        in_specs=[pl.BlockSpec((1, t), lambda b_, i, j: (0, i)),
                  pl.BlockSpec((t, 1), lambda b_, i, j: (j, 0)),
                  q_spec, kv_spec, kv_spec, q_spec, st_spec, st_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, hd, s), f32, vma=_vma(qf)),
        scratch_shapes=[pltpu.VMEM((hd, t), f32)],
        interpret=interpret,
    )(qpos, kpos_t, qf, kf, vf, dof, lse, delta)

    q_spec2 = pl.BlockSpec((1, hd, t), lambda b_, j, i: (b_, 0, i))
    kv_spec2 = pl.BlockSpec((1, hd, t), lambda b_, j, i: (b_, 0, j))
    st_spec2 = pl.BlockSpec((1, h, t), lambda b_, j, i: (b_, 0, i))
    dk, dv = pl.pallas_call(
        functools.partial(_frdkv_kernel, scale=scale, causal=causal,
                          h=h, d=d),
        grid=(b, n, n),
        in_specs=[pl.BlockSpec((1, t), lambda b_, j, i: (0, i)),
                  pl.BlockSpec((t, 1), lambda b_, j, i: (j, 0)),
                  q_spec2, kv_spec2, kv_spec2, q_spec2, st_spec2,
                  st_spec2],
        out_specs=[kv_spec2, kv_spec2],
        out_shape=[jax.ShapeDtypeStruct((b, hd, s), f32, vma=_vma(qf)),
                   jax.ShapeDtypeStruct((b, hd, s), f32, vma=_vma(qf))],
        scratch_shapes=[pltpu.VMEM((hd, t), f32),
                        pltpu.VMEM((hd, t), f32)],
        interpret=interpret,
    )(qpos, kpos_t, qf, kf, vf, dof, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Paged decode attention: the block-table gather kernel
# ---------------------------------------------------------------------------
#
# The decode plane's paged KV cache (models/transformer.py) reads each
# slot's K/V through a per-slot page table. The XLA path materializes
# every slot's FULL virtual lane per layer per step
# (``c_l[page_tables]`` — an [N, pages_per_slot, page, H, Dh] gather
# written back to HBM) before one masked attention over it: at decode
# the op is bandwidth-bound, and that intermediate doubles the bytes
# every step moves. This kernel fuses gather + streaming-softmax
# attention: the pools stay in HBM, the page table and the positions
# ride SCALAR PREFETCH, and the kernel itself copies the pages a slot's
# table names into VMEM, :func:`paged_fetch_pages` of them a fetch, the
# next fetch (the next slot's first, at a slot's end) in flight while
# this one is summed. A slot's walk covers its LIVE entries and nothing
# else: the first ``pos // page_size + 1`` of its row. Entries past
# them (every unclaimed one aims at the scratch page) cost no grid
# step, no copy and no arithmetic, and rows past ``pos`` in the last
# live page are removed with ``where``, whatever they hold. Scores and
# the running (m, l, acc) stats live in VMEM; nothing lane-shaped ever
# lands in HBM.
#
# The dense gather stays the CPU/interpret fallback with token-for-
# token parity pinned (tests/test_transformer.py TestPagedAttnKernel).

# what the K and the V pages of two fetches (the one summed, the one in
# flight) may hold in VMEM (the kernel asks for no scoped-VMEM limit of
# its own: the v5e's default is 16 MiB), and the most pages a fetch may
# name
_PAGED_VMEM_BUDGET = 4 * 2**20
_PAGED_MAX_FETCH = 32


def _paged_page_vmem_bytes(page_size: int, h_kv: int, d: int,
                           dtype) -> int:
    """VMEM one ``(page_size, h_kv, d)`` page takes: its last two
    dimensions in whole tiles of 8 x 128 elements (Mosaic keeps 8 heads
    of bfloat16 in ``T(8,128)(2,1)`` tiles, unpadded: compiled ahead of
    time for the v5e, 250 such pages a fetch fit 32 MiB, 300 do not)."""
    return (page_size * _round_up(h_kv, 8) * _round_up(d, LANE)
            * jnp.dtype(dtype).itemsize)


def paged_fetch_pages(pages_per_slot: int, page_size: int, h_kv: int,
                      d: int, dtype) -> int:
    """How many consecutive table entries of a slot
    :func:`paged_decode_attention` copies at a time: as many as fit
    :data:`_PAGED_VMEM_BUDGET` with K and V double-buffered, at most
    :data:`_PAGED_MAX_FETCH`, at least 2, and never more than the
    table has. A fetch has a fixed cost (its copies' latency is hidden
    behind the fetch before it, its bookkeeping is not): on the v5e
    one 16-row page a grid step ran at 6-37% of the HBM roofline, and
    a call over 690 live bfloat16 pages of 32 heads x 128 takes 0.376 /
    0.355 / 0.354 / 0.356 ms at 2 / 4 / 8 / 16 pages a fetch, one over
    190 float32 pages of 16 heads 0.095 / 0.096 / 0.086 / 0.092
    (PERF.md, PR 34)."""
    page = _paged_page_vmem_bytes(page_size, h_kv, d, dtype)
    fit = _PAGED_VMEM_BUDGET // (4 * page)
    return min(pages_per_slot, max(2, min(fit, _PAGED_MAX_FETCH)))


def paged_walk(pos, page_size: int):
    """Number of entries of its table a slot at position ``pos`` reads:
    entries ``[0, pos // page_size]``, the pages that hold rows
    ``index <= pos``."""
    return pos // page_size + 1


def _paged_fetch_live(walk, i, fetch: int):
    """Live entries in fetch ``i`` of a walk of ``walk`` entries,
    ``fetch`` at a time: all but a slot's last fetch are whole."""
    return jnp.minimum(fetch, walk - i * fetch)


def _paged_init(acc, m_scr, l_scr):
    acc[:] = jnp.zeros_like(acc)
    m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)


def _paged_page(q_ref, k, v, acc, m_scr, l_scr, *, scale: float,
                groups: int, live=None):
    """Sum one page ``k``/``v`` (page, H_kv, Dh) into the streaming-
    softmax stats ``acc`` (H, Dh), ``m_scr``/``l_scr`` (groups, H_kv, 1)
    of the slot's query ``q_ref`` (1, H, Dh). With ``groups`` > 1
    (grouped queries) the q block holds its heads group-major,
    ``groups`` runs of ``H_kv`` rows, and the page serves each run in
    turn. ``live`` (page, 1, 1) marks the rows ``index <= pos`` of a
    slot's LAST page, the only one that has others: they are removed
    from the scores and from the products alike, because a dead row may
    hold anything; with ``live`` None every row counts."""
    f32 = jnp.float32
    h_kv = k.shape[1]
    k, v = k.astype(f32), v.astype(f32)
    if live is not None:
        v = jnp.where(live, v, 0.0)
    for g in range(groups):
        rows = pl.ds(g * h_kv, h_kv)
        q = q_ref[0, rows].astype(f32)              # (H_kv, Dh)
        # per-head scores by multiply and reduce over the lanes (the
        # float32 cell's products have to stay float32-exact, and one
        # MXU pass is bfloat16), kept (page, H_kv, 1): heads stay on
        # the sublanes from the page to the accumulator, so nothing is
        # relaid out
        s = jnp.sum(k * q[None], axis=2, keepdims=True) * scale
        if live is not None:
            s = jnp.where(live, s, _NEG_INF)
        m_prev = m_scr[g]                           # (H_kv, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
        pw = jnp.exp(s - m_new[None])               # (page, H_kv, 1)
        alpha = jnp.exp(m_prev - m_new)             # (H_kv, 1)
        l_scr[g] = l_scr[g] * alpha + jnp.sum(pw, axis=0)
        acc[rows] = acc[rows] * alpha + jnp.sum(pw * v, axis=0)
        m_scr[g] = m_new


def _paged_live_rows(pos, page_size: int):
    """(page, 1, 1) mask of the rows ``index <= pos`` in the page that
    holds row ``pos``."""
    return jax.lax.broadcasted_iota(
        jnp.int32, (page_size, 1, 1), 0) <= pos % page_size


def _paged_finish(o_ref, acc, l_scr):
    groups, h_kv, _ = l_scr.shape
    for g in range(groups):
        rows = pl.ds(g * h_kv, h_kv)
        l_safe = jnp.maximum(l_scr[g], 1e-30)       # (H_kv, 1)
        o_ref[0, rows] = (acc[rows] / l_safe).astype(o_ref.dtype)


def _paged_walk_kernel(tbl_ref, pos_ref, q_ref, k_hbm, v_hbm, o_ref,
                       k_buf, v_buf, sem, acc, m_scr, l_scr, n_fetched,
                       *, page_size: int, fetch: int, **page_kw):
    """One slot, by the kernel's own copies: its query ``q_ref``
    (1, H, Dh) against the rows ``index <= pos`` of its virtual lane,
    ``fetch`` pages of (page, H_kv, Dh) at a time from the pools in HBM
    into one of two VMEM buffers each for K and V (``k_buf``/``v_buf``
    (2, fetch, page, H_kv, Dh)). ``n_fetched`` (SMEM) counts the
    fetches of the slots before this one: the buffers alternate across
    slots, because a slot's last fetch starts the next slot's first."""
    n = pl.program_id(0)

    def walk(s):
        """Entries slot ``s`` reads; never past its table's row, so no
        position aims a copy outside the pool."""
        return jnp.minimum(paged_walk(pos_ref[s], page_size),
                           tbl_ref.shape[1])

    def n_pages(s, i):
        """Live entries in fetch ``i`` of slot ``s``."""
        return _paged_fetch_live(walk(s), i, fetch)

    def each_copy(s, i, b, act):
        """``act`` on the K and the V copy of every live entry of fetch
        ``i`` of slot ``s`` into buffer ``b``."""
        def one(j, carry):
            page = tbl_ref[s, i * fetch + j]
            act(pltpu.make_async_copy(k_hbm.at[page], k_buf.at[b, j],
                                      sem.at[b]))
            act(pltpu.make_async_copy(v_hbm.at[page], v_buf.at[b, j],
                                      sem.at[b]))
            return carry
        jax.lax.fori_loop(0, n_pages(s, i), one, 0)

    def start(s, i, b):
        each_copy(s, i, b, lambda c: c.start())

    @pl.when(n == 0)
    def _():
        n_fetched[0] = 0
        start(0, 0, 0)

    _paged_init(acc, m_scr, l_scr)
    pos = pos_ref[n]
    n_fetch = pl.cdiv(walk(n), fetch)
    first = n_fetched[0]

    def page(b, j, live=None):
        _paged_page(q_ref, k_buf[b, j], v_buf[b, j], acc, m_scr, l_scr,
                    live=live, **page_kw)

    def fetch_body(i, carry):
        b = (first + i) % 2
        last = i + 1 == n_fetch

        @pl.when(jnp.logical_not(last))
        def _():
            start(n, i + 1, 1 - b)

        @pl.when(last & (n + 1 < pl.num_programs(0)))
        def _():
            start(n + 1, 0, 1 - b)

        each_copy(n, i, b, lambda c: c.wait())
        # every page but the slot's last is live in every row
        full = n_pages(n, i) - last.astype(jnp.int32)

        def full_page(j, c):
            page(b, j)
            return c
        jax.lax.fori_loop(0, full, full_page, 0)

        @pl.when(last)
        def _():
            page(b, full, _paged_live_rows(pos, page_size))
        return carry

    jax.lax.fori_loop(0, n_fetch, fetch_body, 0)
    n_fetched[0] = first + n_fetch
    _paged_finish(o_ref, acc, l_scr)


def _paged_grid_kernel(tbl_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                       acc, m_scr, l_scr, *, page_size: int, **page_kw):
    """One (slot, table entry) grid step, by the ``BlockSpec``
    pipeline: the feed for a head_dim that is not whole 128-lane
    registers (Mosaic slices no such memref, so the kernel cannot aim
    its own copies there). An entry past the slot's walk names the
    walk's last page again, so nothing is fetched for it, and is
    skipped; it is still a grid step."""
    n, p = pl.program_id(0), pl.program_id(1)

    @pl.when(p == 0)
    def _():
        _paged_init(acc, m_scr, l_scr)

    pos = pos_ref[n]
    last = paged_walk(pos, page_size) - 1

    def page(live=None):
        _paged_page(q_ref, k_ref[0], v_ref[0], acc, m_scr, l_scr,
                    live=live, **page_kw)

    pl.when(p < last)(page)

    @pl.when(p == last)
    def _():
        page(_paged_live_rows(pos, page_size))

    @pl.when(p == pl.num_programs(1) - 1)
    def _():
        _paged_finish(o_ref, acc, l_scr)


@functools.partial(jax.jit, static_argnames=("scale", "page_size",
                                             "interpret"))
def paged_decode_attention(q, k_pages, v_pages, page_tables, pos,
                           scale: float, page_size: int,
                           interpret: bool = False):
    """Fused paged-attention for one decode step of one layer.

    ``q`` (N, H, Dh) — each slot's single query (rope applied);
    ``k_pages``/``v_pages`` (n_pages, page_size, H_kv, Dh) — the layer's
    shared page pool AFTER this step's K/V write; ``page_tables``
    (N, pages_per_slot) int32; ``pos`` (N,) int32. Returns the
    normalized attention output (N, H, Dh) — numerically the paged
    dense-gather path (softmax over ``index <= pos`` of the virtual
    lane), computed without ever materializing the lane.

    **The work follows the live entries**: slot ``n`` reads entries
    ``[0, pos[n] // page_size]`` of its row (:func:`paged_walk`),
    :func:`paged_fetch_pages` of them a fetch, and no entry past them
    is looked at; a free slot (position 0, an all-scratch table) reads
    one page and returns a finite row that nobody reads. (With a
    head_dim that is not a multiple of 128 the pages come through the
    ``BlockSpec`` pipeline, one a grid step, dead entries skipped:
    :func:`_paged_grid_kernel`.)

    **Grouped queries**: ``H_kv`` may divide ``H`` (query head ``j``
    reads K/V head ``j // (H / H_kv)``). The pool keeps ``H_kv`` heads,
    a page is fetched once and serves its ``H / H_kv`` query heads
    there; nothing is repeated in HBM."""
    n, h, d = q.shape
    h_kv = k_pages.shape[2]
    if h % h_kv:
        raise ValueError(f"{h} query heads over {h_kv} K/V heads")
    groups = h // h_kv
    page_size = int(page_size)
    pps = page_tables.shape[1]
    if groups > 1:
        # group-major rows: run g holds query heads g, g + G, ... so
        # that row i of a run reads K/V head i
        q = q.reshape(n, h_kv, groups, d).swapaxes(1, 2).reshape(n, h, d)
    kw = dict(scale=float(scale), page_size=page_size, groups=groups)
    stats = [pltpu.VMEM((h, d), jnp.float32),            # acc
             pltpu.VMEM((groups, h_kv, 1), jnp.float32),  # running max
             pltpu.VMEM((groups, h_kv, 1), jnp.float32)]  # normalizer
    if d % LANE == 0:
        fetch = paged_fetch_pages(pps, page_size, h_kv, d, k_pages.dtype)
        buf = (2, fetch, page_size, h_kv, d)
        kernel = functools.partial(_paged_walk_kernel, fetch=fetch, **kw)
        q_spec = pl.BlockSpec((1, h, d), lambda n_, tbl, ps_: (n_, 0, 0))
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n,),
            # the pools stay where they are: the kernel's own copies,
            # aimed by the scalar-prefetched table, are the paged gather
            in_specs=[q_spec, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=q_spec,
            scratch_shapes=[pltpu.VMEM(buf, k_pages.dtype),
                            pltpu.VMEM(buf, v_pages.dtype),
                            pltpu.SemaphoreType.DMA((2,)), *stats,
                            pltpu.SMEM((1,), jnp.int32)])
    else:
        def live_page(n_, p_, tbl, ps_):
            last = paged_walk(ps_[n_], page_size) - 1
            return tbl[n_, jnp.minimum(p_, last)], 0, 0, 0
        kernel = functools.partial(_paged_grid_kernel, **kw)
        q_spec = pl.BlockSpec((1, h, d),
                              lambda n_, p_, tbl, ps_: (n_, 0, 0))
        page_spec = pl.BlockSpec((1, page_size, h_kv, d), live_page)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n, pps),
            in_specs=[q_spec, page_spec, page_spec],
            out_specs=q_spec, scratch_shapes=stats)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, h, d), q.dtype),
        interpret=interpret,
    )(page_tables.astype(jnp.int32), pos.astype(jnp.int32),
      q, k_pages, v_pages)
    if groups > 1:
        out = out.reshape(n, groups, h_kv, d).swapaxes(1, 2).reshape(n, h, d)
    return out


def paged_attention_available() -> bool:
    """Whether the fused paged-attention kernel can run compiled on
    this backend (TPU); everywhere else the dense gather is the
    fallback and ``interpret=True`` serves the parity tests."""
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# Flash prefill: streaming-softmax attention for the prefill builders
# ---------------------------------------------------------------------------
#
# All three prefill builders in models/transformer.py historically
# materialized the full score matrix through ``jax.nn.softmax`` — [S, S]
# for the in-flight builders, [S, V] (V = pages_per_slot * page_size)
# for the offset/prefix builder's whole-virtual-lane attention. At long
# prompt buckets that intermediate dominates prefill HBM traffic the
# same way the dense lane gather dominated decode. Two engines replace
# it behind ``attn_impl``:
#
# * in-flight prefill (build_prefill / build_paged_prefill) attends
#   over the q/k/v it just computed — :func:`flash_prefill_attention`,
#   the (m, l, acc) streaming kernel above, normalized, forward-only;
# * the prefix prefill attends over the slot's PAGED virtual lane —
#   :func:`paged_prefix_prefill_attention` extends the
#   ``paged_decode_attention`` scalar-prefetch idiom along the query
#   axis: grid (q-tile, page), each page's DMA aimed by the table,
#   running stats carried in VMEM scratch across pages, causal mask
#   ``virtual_index <= hit_len + row`` — the scratch-page overshoot
#   convention (dead pages skip compute; unclaimed entries aim at
#   page 0 and are always dead) is preserved exactly.


def flash_prefill_attention(q, k, v, scale=None,
                            interpret: bool = False, q_offset=None):
    """Normalized causal flash self-attention for the in-flight
    prefill path: ``q``/``k``/``v`` [B, S, H, Dh] -> [B, S, H, Dh],
    forward-only, no [S, S] score matrix in HBM. Numerics match
    ``dense_attention(q, k, v, causal=True)`` (same default
    ``Dh**-0.5`` scale, f32 accumulation) to streaming-softmax
    reassociation tolerance; token-for-token argmax parity is
    test-pinned.

    **Grouped queries and a tile of a longer lane**: ``k``/``v`` may be
    [B, S_k, H_kv, Dh] with ``H_kv`` dividing ``H`` (query head ``j``
    reads K/V head ``j // (H / H_kv)``) and, with ``q_offset`` (a traced
    scalar), ``S_k >= S`` rows at positions ``arange(S_k)`` that the
    queries, at ``q_offset + arange(S)``, see causally. A K/V head's
    ``H / H_kv`` query heads are folded into the kernel's query rows, so
    each K/V tile is fetched once for all of them and nothing is
    repeated in HBM. With ``H_kv == H`` and no offset this is the
    program this function always built."""
    if q_offset is None and k.shape[2] == q.shape[2]:
        return flash_attention(q, k, v, True, scale, interpret)
    b, s, h, d = q.shape
    sk, h_kv = k.shape[1], k.shape[2]
    if h % h_kv:
        raise ValueError(f"{h} query heads over {h_kv} K/V heads")
    g = h // h_kv
    s_p, sk_p = _round_up(s, Q_TILE), _round_up(sk, KV_TILE)
    q0 = 0 if q_offset is None else q_offset
    q_pos = jnp.tile(jnp.pad(q0 + jnp.arange(s, dtype=jnp.int32),
                             (0, s_p - s)), g)
    qpos_p, kpos_p = _padded_positions(q_pos, jnp.arange(sk), g * s_p, sk_p)
    scale_f = float(scale) if scale is not None else d ** -0.5
    tiles = flash_tiles(g * s_p, sk, d, q.dtype)
    with jax.named_scope("flash.layout"):
        # (B, S, H_kv, G, D) -> (B * H_kv, G * S_p, D): a K/V head's
        # query heads one after the other
        qf = jnp.pad(q.reshape(b, s, h_kv, g, d),
                     ((0, 0), (0, s_p - s), (0, 0), (0, 0), (0, 0)))
        qf = qf.transpose(0, 2, 3, 1, 4).reshape(b * h_kv, g * s_p, d)
    with jax.named_scope(f"flash.t{tiles[0]}x{tiles[1]}"):
        o, _ = _flash_call(qf, _to_bh(k, sk_p), _to_bh(v, sk_p), qpos_p,
                           kpos_p, scale_f, True, interpret, tiles=tiles,
                           normalize=True)
    with jax.named_scope("flash.layout"):
        o = o.reshape(b, h_kv, g, s_p, d)[:, :, :, :s]
        return o.transpose(0, 3, 1, 2, 4).reshape(b, s, h, d).astype(q.dtype)


def _paged_prefix_kernel(tbl_ref, hit_ref, q_ref, k_ref, v_ref, o_ref,
                         acc, m_scr, l_scr, *, scale: float,
                         page_size: int, s_real: int, q_tile: int):
    """One (q-tile, page) step of prefix-prefill attention: queries
    (H, TQ, Dh) at virtual positions ``hit_len + row`` against the
    slot's p-th table page, streaming-softmax stats carried in VMEM
    scratch across the page axis (the innermost grid dim)."""
    i, p = pl.program_id(0), pl.program_id(1)

    @pl.when(p == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    hit = hit_ref[0]
    # bucket-pad rows past the real suffix clamp to the LAST real row:
    # they become harmless duplicates (sliced off outside) and the
    # dead-page liveness bound below stays exactly hit + s_real - 1 —
    # padding never drags extra pages live
    row = jnp.minimum(
        i * q_tile + jax.lax.broadcasted_iota(jnp.int32,
                                              (1, q_tile, 1), 1),
        s_real - 1)
    qpos = hit + row                                    # (1, TQ, 1)
    base = p * page_size

    # dead-page skip: the whole page starts past every query's
    # position (every unclaimed scratch-aimed entry does) — the DMA
    # was free-running but the compute is skipped
    @pl.when(base <= hit + s_real - 1)
    def _():
        q = q_ref[:]                                    # (H, TQ, Dh)
        k = k_ref[0]                                    # (page, H, Dh)
        v = v_ref[0]
        # per-head MXU scores: contract Dh, batch H -> (H, TQ, page)
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (1,))),
            preferred_element_type=jnp.float32) * scale
        idx = base + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, page_size), 2)            # (1, 1, page)
        mask = idx <= qpos                              # (1, TQ, page)
        s = jnp.where(mask, s, _NEG_INF)
        m_prev = m_scr[:]                               # (H, TQ, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        pw = jnp.where(mask, jnp.exp(s - m_new), 0.0)   # (H, TQ, page)
        alpha = jnp.exp(m_prev - m_new)                 # (H, TQ, 1)
        l_scr[:] = l_scr[:] * alpha + jnp.sum(pw, axis=2,
                                              keepdims=True)
        # P·V: contract the page axis, batch H -> (H, TQ, Dh)
        acc[:] = acc[:] * alpha + jax.lax.dot_general(
            pw, v.astype(jnp.float32), (((2,), (0,)), ((0,), (1,))),
            preferred_element_type=jnp.float32)
        m_scr[:] = m_new

    @pl.when(p == pl.num_programs(1) - 1)
    def _():
        l_safe = jnp.maximum(l_scr[:], 1e-30)           # (H, TQ, 1)
        o_ref[:] = (acc[:] / l_safe).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "page_size",
                                             "interpret"))
def paged_prefix_prefill_attention(q, k_pages, v_pages, page_table,
                                   hit_len, scale: float,
                                   page_size: int,
                                   interpret: bool = False):
    """Fused prefix-prefill attention for one layer of one slot.

    ``q`` (S, H, Dh) — the suffix queries (rope applied at virtual
    positions ``hit_len + j``); ``k_pages``/``v_pages``
    (n_pages, page_size, H, Dh) — the layer's shared page pool AFTER
    the suffix K/V scatter; ``page_table`` (pages_per_slot,) int32 —
    the slot's full table (shared prefix pages first, then private
    pages; unclaimed entries aim at scratch page 0); ``hit_len`` a
    TRACED int32 scalar (hit depth is data, not shape). Returns the
    normalized attention output (S, H, Dh) — numerically the dense
    whole-virtual-lane gather+softmax path of
    ``build_paged_prefix_prefill``, computed without ever
    materializing the [S, V] score matrix or the gathered lane."""
    s, h, d = q.shape
    pps = page_table.shape[0]
    # q tiles on the sublane axis: 128 for MXU-sized buckets, the
    # 8-aligned minimum for short suffix buckets (Dh rides the lane
    # axis unpadded, the decode kernel's convention)
    q_tile = min(Q_TILE, _round_up(s, 8))
    s_pad = _round_up(s, q_tile)
    qt = jnp.pad(q.astype(jnp.float32), ((0, s_pad - s), (0, 0),
                                         (0, 0))).transpose(1, 0, 2)
    kernel = functools.partial(
        _paged_prefix_kernel, scale=float(scale),
        page_size=int(page_size), s_real=s, q_tile=q_tile)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s_pad // q_tile, pps),
        in_specs=[
            pl.BlockSpec((h, q_tile, d),
                         lambda i_, p_, tbl, hl_: (0, i_, 0)),
            # the paged gather: each page DMA aimed by the
            # scalar-prefetched table, exactly the decode kernel's idiom
            pl.BlockSpec((1, page_size, h, d),
                         lambda i_, p_, tbl, hl_: (tbl[p_], 0, 0, 0)),
            pl.BlockSpec((1, page_size, h, d),
                         lambda i_, p_, tbl, hl_: (tbl[p_], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((h, q_tile, d),
                               lambda i_, p_, tbl, hl_: (0, i_, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, q_tile, d), jnp.float32),    # acc
            pltpu.VMEM((h, q_tile, 1), jnp.float32),    # running max
            pltpu.VMEM((h, q_tile, 1), jnp.float32),    # normalizer
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((h, s_pad, d), q.dtype),
        interpret=interpret,
    )(page_table.astype(jnp.int32),
      jnp.reshape(hit_len, (1,)).astype(jnp.int32),
      qt, k_pages, v_pages)
    return out.transpose(1, 0, 2)[:s]


def eva_prefill_attention(q, k, v, ks, vs, n_summary, scale: float,
                          interpret: bool = False):
    """One window's EVA attention (``models/evabyte.py``): queries
    ``q`` [S, H, Dh] over the tile's own ``k``/``v`` [S, H, Dh] causally
    and over the first ``n_summary`` (traced) of the summary rows
    ``ks``/``vs`` [M, H, Dh], all in ONE softmax: the forward flash
    kernel over the concatenated keys, the visibility carried by the
    position operands (a live summary sits at position -1, before every
    query; a dead one at the padding sentinel). Returns float32
    [S, H, Dh], normalised."""
    s, h, d = q.shape
    m = ks.shape[0]
    k_pos = jnp.concatenate([
        jnp.where(jnp.arange(m) < n_summary, -1, _PAD_POS).astype(jnp.int32),
        jnp.arange(s, dtype=jnp.int32)])
    sq_p, sk_p = _round_up(s, Q_TILE), _round_up(m + s, KV_TILE)
    qpos_p, kpos_p = _padded_positions(jnp.arange(s), k_pos, sq_p, sk_p)
    tiles = flash_tiles(s, m + s, d, q.dtype)
    with jax.named_scope(f"flash.t{tiles[0]}x{tiles[1]}"):
        o, _ = _flash_call(
            _to_bh(q[None], sq_p),
            _to_bh(jnp.concatenate([ks, k])[None], sk_p),
            _to_bh(jnp.concatenate([vs, v])[None], sk_p),
            qpos_p, kpos_p, float(scale), True, interpret, tiles=tiles,
            normalize=True)
    return o[:, :s].swapaxes(0, 1)


def flash_prefill_available() -> bool:
    """Whether the flash prefill kernels can run compiled on this
    backend (TPU); everywhere else the dense-softmax paths are the
    fallback and ``interpret=True`` serves the parity tests."""
    return jax.default_backend() == "tpu"


def folded_block_attn(q, k, v, scale, q_pos, k_pos, causal: bool,
                      interpret: bool = False):
    """:func:`flash_block_attn` twin in the folded layout: returns
    (m (B,H,Sq), l (B,H,Sq), o (B,Sq,H,Dh) unnormalized) for the
    online-softmax ring merge. Requires
    :func:`folded_block_available` shapes (the ring's local blocks are
    same-length by construction)."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    if not _folded_shape_ok(sq, sk, d, h):
        # the flash twin pads arbitrary shapes; this layout cannot —
        # fail with the rule, not a ZeroDivisionError inside the trace
        raise ValueError(
            f"folded_block_attn needs same-length blocks (sq={sq}, "
            f"sk={sk}), head_dim % 8 == 0 (got {d}), a 128-tileable "
            f"sequence, and an (H*Dh x tile) working set inside the "
            f"VMEM budget (H*Dh={h * d}); use block_impl='flash' (or "
            f"'auto') for other shapes")
    qf, kf, vf = _to_folded(q), _to_folded(k), _to_folded(v)
    qpos = jnp.asarray(q_pos, jnp.int32)[None]            # (1, S)
    kpos_t = jnp.asarray(k_pos, jnp.int32)[:, None]       # (S, 1)
    o, m, l = _fring_call(qf, kf, vf, qpos, kpos_t, h, float(scale),
                          causal, interpret)
    return (m.astype(q.dtype), l.astype(q.dtype),
            _from_folded(o, h).astype(q.dtype))
