"""Multi-head latent attention's decode step over a paged latent cache.

An ``"mla"`` layer of the hybrid block (``models/granite_hybrid.py``
:func:`~mmlspark_tpu.models.granite_hybrid.mla_step`) keeps ONE row a
position, shared by all its heads: ``[c_kv | k_pe]``, the normed latent
(``kv_lora_rank`` lanes) and the turned rotary key (``d_rope``). A
decode step folds ``W_UK`` into each head's query, so that a head's
score of row ``t`` is ``scale * ([q_lat | q_pe] . row_t)``, and sums the
softmax-weighted LATENTS, ``o_h = sum_t p_h(t) c_kv(t)``; the caller
applies ``W_UV`` to ``o_h`` after. Both are exact rewrites of the
expanded layer's ``q . k`` and ``p . v``.

:func:`paged_latent_decode_attention` walks a slot's live pages once for
all the heads: the pool stays in HBM, and the kernel's own copies, aimed
by the scalar-prefetched page table, bring :func:`latent_fetch_pages`
pages at a time into one of two VMEM buffers (the next fetch's copies,
and at a slot's last fetch the next slot's first, are started before
this fetch's are waited for). A full fetch's copies go out as
straight-line code, one block a buffer so that each destination is a
constant, their pages read from the table laid flat, and one wait takes
all their bytes: a 20 KiB page's copy costs the scalar unit about as
long to start as it takes to move, and a loop of copies and waits took
two thirds of a fetch's bundles. A slot's partial last fetch copies and
waits a page a loop trip. A fetch of ``R`` rows is two MXU products,
``[H, lanes] x [lanes, R]`` scores and ``[H, R] x [R, kv_lora_rank]``
latents, and one online-softmax update. Rows past ``pos`` in a slot's
last fetch (its last page's later rows, the buffer's pages past the
walk) may hold anything: only the last fetch is masked, its scores
replaced and its rows selected away.

:func:`latent_decode_attention_dense` is the dense-gather twin: every
slot's lane materialised, one softmax (``attn_impl="dense"``).

:func:`latent_tile_attention` is a prefill tile's EXPANDED form (the
tile's many queries make the absorbed one's 576-lane scores dearer than
expanding the rows): one call a layer over the slot's lane, each chunk
of rows brought up through ``W_UK`` / ``W_UV`` and scored a few heads
at a time in VMEM, where the XLA loop it replaces wrote and read back
four [H, T, T] float32 arrays a chunk.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
_NEG_INF = -1e30
# what the two fetch buffers (the one summed, the one in flight) may hold
# in VMEM, and the most pages a fetch may name
_LATENT_VMEM_BUDGET = 4 * 2**20
_LATENT_MAX_FETCH = 32


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def latent_pool_lanes(lanes: int) -> int:
    """The lanes a latent pool that the kernel walks keeps a row in:
    ``lanes`` zero-padded to whole 128-lane registers (Kimi-K2's 512 +
    64 to 640). The device keeps a narrower row padded alike in HBM,
    and the kernel cannot aim its copies at a row slice that is not
    whole registers, so the pad costs no memory and makes the walk
    possible; a query padded alike gives the same scores."""
    return _round_up(lanes, LANE)


def latent_fetch_pages(pages_per_slot: int, page_size: int, lanes: int,
                       dtype) -> int:
    """How many consecutive table entries of a slot
    :func:`paged_latent_decode_attention` copies at a time: as many as
    two buffers of fit :data:`_LATENT_VMEM_BUDGET` (a row takes its
    lanes in whole 128-lane registers), at most
    :data:`_LATENT_MAX_FETCH`, at least one, never more than the table
    has."""
    page = page_size * _round_up(lanes, LANE) * jnp.dtype(dtype).itemsize
    fit = _LATENT_VMEM_BUDGET // (2 * page)
    return min(pages_per_slot, max(1, min(fit, _LATENT_MAX_FETCH)))


def _latent_walk_kernel(tbl_ref, pos_ref, q_ref, pool_hbm, o_ref, buf, sem,
                        acc, m_scr, l_scr, n_fetched, *, page_size: int,
                        pages_per_slot: int, scale: float, d_lat: int):
    """One slot: its query ``q_ref`` (1, H, lanes) against the rows
    ``index <= pos`` of its virtual lane, ``fetch`` pages at a time into
    ``buf`` (2, fetch, page, lanes). ``tbl_ref`` is the page table laid
    flat, slot ``s``'s entries from ``s * pages_per_slot`` on.
    ``n_fetched`` (SMEM) counts the fetches of the slots before this
    one: the buffers alternate across slots, because a slot's last
    fetch starts the next slot's first."""
    f32 = jnp.float32
    n = pl.program_id(0)
    fetch = buf.shape[1]
    rows_f = fetch * page_size

    def walk(s):
        """Entries slot ``s`` reads; never past its table's row."""
        return jnp.minimum(pos_ref[s] // page_size + 1, pages_per_slot)

    def start(s, i, b):
        """Start the copies of fetch ``i`` of slot ``s`` into buffer
        ``b``. A full fetch's go out as straight-line code, one block a
        buffer, so that each copy's destination is a constant; a
        partial fetch's (a slot's last) one live entry a loop trip."""
        e0 = s * pages_per_slot + i * fetch
        live = walk(s) - i * fetch

        def copy(j, b):
            return pltpu.make_async_copy(pool_hbm.at[tbl_ref[e0 + j]],
                                         buf.at[b, j], sem.at[b])

        for b_ in range(2):
            if isinstance(b, int) and b != b_:     # the kernel's first
                continue

            @pl.when((live >= fetch) & (b == b_))
            def _():
                for j in range(fetch):
                    copy(j, b_).start()

        @pl.when(live < fetch)
        def _():
            def one(j, carry):
                copy(j, b).start()
                return carry
            jax.lax.fori_loop(0, live, one, 0)

    def wait(b, live):
        """Wait for the copies of a fetch of ``live`` entries into
        buffer ``b``: a full fetch's with one wait for all its bytes."""
        @pl.when(live >= fetch)
        def _():
            pltpu.make_async_copy(pool_hbm.at[pl.ds(0, fetch)], buf.at[b],
                                  sem.at[b]).wait()

        @pl.when(live < fetch)
        def _():
            def one(j, carry):
                pltpu.make_async_copy(pool_hbm.at[0], buf.at[b, 0],
                                      sem.at[b]).wait()
                return carry
            jax.lax.fori_loop(0, live, one, 0)

    @pl.when(n == 0)
    def _():
        n_fetched[0] = 0
        start(0, 0, 0)

    pos = pos_ref[n]
    n_walk = walk(n)
    n_fetch = pl.cdiv(n_walk, fetch)
    first = n_fetched[0]
    q = q_ref[0]
    m_scr[...] = jnp.full(m_scr.shape, _NEG_INF, f32)
    l_scr[...] = jnp.zeros(l_scr.shape, f32)
    acc[...] = jnp.zeros(acc.shape, f32)

    def update(rows, live=None):
        """Sum ``rows`` (R, lanes) into the running softmax; ``live``:
        how many of them count (the last fetch's)."""
        if live is not None:
            keep = jax.lax.broadcasted_iota(jnp.int32, (rows_f, 1), 0) < live
            rows = jnp.where(keep, rows.astype(f32), 0.0).astype(rows.dtype)
        s = jax.lax.dot_general(q, rows, (((1,), (1,)), ((), ())),
                                preferred_element_type=f32) * scale
        if live is not None:
            col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(col < live, s, _NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        acc[...] = alpha * acc[...] + jnp.dot(
            p.astype(rows.dtype), rows[:, :d_lat], preferred_element_type=f32)
        m_scr[...] = m_new

    def fetch_body(i, carry):
        b = (first + i) % 2
        last = i + 1 == n_fetch
        # the next fetch: this slot's, or at its last the next slot's
        # first
        s_next = jnp.where(last, n + 1, n)

        @pl.when(s_next < pl.num_programs(0))
        def _():
            start(s_next, jnp.where(last, 0, i + 1), 1 - b)

        wait(b, n_walk - i * fetch)

        @pl.when(jnp.logical_not(last))
        def _():
            update(buf[b].reshape(rows_f, -1))

        @pl.when(last)
        def _():
            update(buf[b].reshape(rows_f, -1), pos + 1 - i * rows_f)

        return carry

    jax.lax.fori_loop(0, n_fetch, fetch_body, 0)
    n_fetched[0] = first + n_fetch
    o_ref[0] = (acc[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "page_size", "d_lat",
                                             "interpret"))
def paged_latent_decode_attention(q, pool, page_tables, pos, scale: float,
                                  page_size: int, d_lat: int,
                                  interpret: bool = False):
    """Latent attention for one decode step of one ``"mla"`` layer.

    ``q`` (N, H, lanes): each slot's absorbed query ``[W_UK^T q_nope |
    q_pe]`` in the pool's dtype; ``pool`` (n_pages, page_size, lanes):
    the layer's latent rows ``[c_kv | k_pe]`` AFTER this step's write;
    ``page_tables`` (N, pages_per_slot) int32; ``pos`` (N,) int32.
    Returns float32 (N, H, d_lat): ``sum_t p_h(t) c_kv(t)`` over the rows
    ``index <= pos`` of each slot's virtual lane, ``p_h`` the softmax of
    ``scale * q_h . row_t``. Slot ``n`` reads entries ``[0, pos[n] //
    page_size]`` of its row and no other; a free slot (position 0, an
    all-scratch table) reads one row and returns a finite output that
    nobody reads. ``interpret``: True, or a ``pltpu.InterpretParams``
    for the TPU interpreter, which makes each copy when it is waited
    for."""
    n, h, lanes = q.shape
    pps = page_tables.shape[1]
    fetch = latent_fetch_pages(pps, int(page_size), lanes, pool.dtype)
    kernel = functools.partial(_latent_walk_kernel, page_size=int(page_size),
                               pages_per_slot=pps, scale=float(scale),
                               d_lat=int(d_lat))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n,),
        in_specs=[pl.BlockSpec((1, h, lanes), lambda n_, tbl, ps_: (n_, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, h, int(d_lat)),
                               lambda n_, tbl, ps_: (n_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, fetch, int(page_size), lanes), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((h, int(d_lat)), jnp.float32),     # acc
            pltpu.VMEM((h, 1), jnp.float32),              # running max
            pltpu.VMEM((h, 1), jnp.float32),              # normalizer
            pltpu.SMEM((1,), jnp.int32)])
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, h, int(d_lat)), jnp.float32),
        # the slots run in order: a slot's last fetch starts the next's
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(page_tables.astype(jnp.int32).reshape(-1), pos.astype(jnp.int32), q,
      pool)


def latent_decode_attention_dense(q, pool, page_tables, pos, scale: float,
                                  d_lat: int):
    """The dense-gather twin of :func:`paged_latent_decode_attention`:
    every slot's whole lane gathered from the pool, one masked softmax
    over the rows ``index <= pos``, the weights rounded to the pool's
    dtype for the sum of latents."""
    n = q.shape[0]
    lane = pool[page_tables].reshape(n, -1, pool.shape[-1])
    s = jnp.einsum("nhl,nsl->nhs", q, lane,
                   preferred_element_type=jnp.float32) * scale
    seen = jnp.arange(lane.shape[1])[None, :] <= pos[:, None]
    p = jax.nn.softmax(jnp.where(seen[:, None], s, _NEG_INF), axis=-1)
    return jnp.einsum("nhs,nsc->nhc", p.astype(lane.dtype),
                      lane[..., :d_lat], preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# a prefill tile's expanded attention over the lane so far

#: query heads a grid step of :func:`latent_tile_attention` takes, and
#: its scoped-VMEM limit: the heads' queries, ``W_UK`` / ``W_UV``, the
#: chunk of rows and the output double-buffered, the running softmax
#: and a head's scores come to 22.6 MiB at Kimi-K2's 512-row tile by
#: Mosaic's count (ahead of time, for the v5e), over the default 16
_TILE_HEADS = 8
_TILE_VMEM_LIMIT = 32 * 2**20


def _latent_tile_kernel(n_ref, qn_ref, qp_ref, wk_ref, wv_ref, lane_ref,
                        o_ref, m_scr, l_scr, acc, *, scale: float, d_lat: int,
                        d_rope: int):
    """Heads ``g * hb ...`` of one tile of ``T`` queries against chunk
    ``j`` of ``T`` lane rows: each head's keys ``c_kv W_UK[h]`` and
    values ``c_kv W_UV[h]`` made here from the chunk's latents, the
    rotary key shared, one online-softmax update. Chunks at or past
    ``n_ref[0]`` are skipped (their block index repeats the last live
    one, so nothing is copied); the last live one is this tile's own
    rows, masked causally."""
    f32 = jnp.float32
    j, n = pl.program_id(1), n_ref[0]
    hb, t = qn_ref.shape[0], qn_ref.shape[1]

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full(m_scr.shape, _NEG_INF, f32)
        l_scr[...] = jnp.zeros(l_scr.shape, f32)
        acc[...] = jnp.zeros(acc.shape, f32)

    def update(masked: bool):
        lat = lane_ref[...]
        c, k_pe = lat[:, :d_lat], lat[:, d_lat:d_lat + d_rope]
        nt = (((1,), (1,)), ((), ()))

        def head(h, carry):
            k = jnp.dot(c, wk_ref[h], preferred_element_type=f32)
            v = jnp.dot(c, wv_ref[h], preferred_element_type=f32)
            s = (jax.lax.dot_general(qn_ref[h], k.astype(lat.dtype), nt,
                                     preferred_element_type=f32)
                 + jax.lax.dot_general(qp_ref[h], k_pe, nt,
                                       preferred_element_type=f32)) * scale
            if masked:
                row = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
                col = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
                s = jnp.where(row >= col, s, _NEG_INF)
            m_prev = m_scr[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[h] = alpha * l_scr[h] + jnp.sum(p, axis=1, keepdims=True)
            acc[h] = alpha * acc[h] + jnp.dot(
                p.astype(lat.dtype), v.astype(lat.dtype),
                preferred_element_type=f32)
            m_scr[h] = m_new
            return carry

        # a loop, not unrolled: the eight heads' straight-line code
        # took Mosaic 10 s to compile for the v5e (a head a trip: 1.6 s)
        # for ~13% fewer bundles
        jax.lax.fori_loop(0, hb, head, 0)

    @pl.when(j + 1 < n)
    def _():
        update(False)

    @pl.when(j + 1 == n)
    def _():
        update(True)

    @pl.when(j + 1 == pl.num_programs(1))
    def _():
        o_ref[...] = (acc[...] / l_scr[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def latent_tile_attention(q_nope, q_pe, lane, w_uk, w_uv, n_chunks,
                          scale: float, interpret: bool = False):
    """The expanded latent attention of one prefill tile of ``T`` queries
    at positions ``(n_chunks - 1) T + arange(T)``, in one kernel.

    ``q_nope`` (H, T, d_nope) and the turned ``q_pe`` (H, T, d_rope) in
    the lane's dtype; ``lane`` (S, lanes): the slot's latent rows ``[c_kv
    | k_pe | pad]`` in position order, this tile's own already written,
    ``S`` a multiple of ``T``; ``w_uk`` (H, d_lat, d_nope), ``w_uv`` (H,
    d_lat, d_v); ``n_chunks`` int32: the chunks of ``T`` rows up to and
    including the tile's own. Returns (H, T, d_v) in the lane's dtype:
    a head's softmax of ``scale * (q_nope . W_UK c_kv + q_pe . k_pe)``
    causally over the rows, times ``W_UV c_kv``, the same arithmetic as
    the XLA form (keys and values rounded to the dtype, float32 scores,
    the probabilities rounded for the sum). Rows past the tile's own
    chunk are not read."""
    h, t, d_nope = q_nope.shape
    d_rope = q_pe.shape[2]
    d_lat, d_v = w_uv.shape[1], w_uv.shape[2]
    lanes = lane.shape[1]
    hb = next(b for b in range(min(_TILE_HEADS, h), 0, -1) if h % b == 0)
    kernel = functools.partial(_latent_tile_kernel, scale=float(scale),
                               d_lat=d_lat, d_rope=d_rope)

    def heads(*tail):
        return pl.BlockSpec((hb,) + tail, lambda g, j, n_: (g, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(h // hb, lane.shape[0] // t),
        in_specs=[heads(t, d_nope), heads(t, d_rope), heads(d_lat, d_nope),
                  heads(d_lat, d_v),
                  pl.BlockSpec((t, lanes), lambda g, j, n_: (
                      jnp.minimum(j, n_[0] - 1), 0))],
        out_specs=heads(t, d_v),
        scratch_shapes=[pltpu.VMEM((hb, t, 1), jnp.float32),
                        pltpu.VMEM((hb, t, 1), jnp.float32),
                        pltpu.VMEM((hb, t, d_v), jnp.float32)])
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((h, t, d_v), lane.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_TILE_VMEM_LIMIT),
        interpret=interpret,
    )(jnp.reshape(n_chunks, (1,)).astype(jnp.int32), q_nope, q_pe, w_uk,
      w_uv, lane)
