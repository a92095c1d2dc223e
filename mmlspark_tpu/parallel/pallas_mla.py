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
this fetch's are waited for). A fetch of ``R`` rows is two MXU products,
``[H, lanes] x [lanes, R]`` scores and ``[H, R] x [R, kv_lora_rank]``
latents, and one online-softmax update. Rows past ``pos`` in a slot's
last fetch (its last page's later rows, the buffer's pages past the
walk) may hold anything: only the last fetch is masked, its scores
replaced and its rows selected away.

:func:`latent_decode_attention_dense` is the dense-gather twin: every
slot's lane materialised, one softmax (``attn_impl="dense"``).
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
                        fetch: int, scale: float, d_lat: int):
    """One slot: its query ``q_ref`` (1, H, lanes) against the rows
    ``index <= pos`` of its virtual lane, ``fetch`` pages at a time into
    ``buf`` (2, fetch * page, lanes). ``n_fetched`` (SMEM) counts the
    fetches of the slots before this one: the buffers alternate across
    slots, because a slot's last fetch starts the next slot's first."""
    f32 = jnp.float32
    n = pl.program_id(0)
    rows_f = buf.shape[1]

    def walk(s):
        """Entries slot ``s`` reads; never past its table's row."""
        return jnp.minimum(pos_ref[s] // page_size + 1, tbl_ref.shape[1])

    def each_copy(s, i, b, act):
        """``act`` on the copy of every live entry of fetch ``i`` of
        slot ``s`` into buffer ``b``."""
        def one(j, carry):
            page = tbl_ref[s, i * fetch + j]
            act(pltpu.make_async_copy(
                pool_hbm.at[page], buf.at[b, pl.ds(j * page_size, page_size)],
                sem.at[b]))
            return carry
        jax.lax.fori_loop(0, jnp.minimum(fetch, walk(s) - i * fetch), one, 0)

    @pl.when(n == 0)
    def _():
        n_fetched[0] = 0
        each_copy(0, 0, 0, lambda c: c.start())

    pos = pos_ref[n]
    n_fetch = pl.cdiv(walk(n), fetch)
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

        @pl.when(jnp.logical_not(last))
        def _():
            each_copy(n, i + 1, 1 - b, lambda c: c.start())

        @pl.when(last & (n + 1 < pl.num_programs(0)))
        def _():
            each_copy(n + 1, 0, 1 - b, lambda c: c.start())

        each_copy(n, i, b, lambda c: c.wait())

        @pl.when(jnp.logical_not(last))
        def _():
            update(buf[b])

        @pl.when(last)
        def _():
            update(buf[b], pos + 1 - i * rows_f)

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
    nobody reads."""
    n, h, lanes = q.shape
    pps = page_tables.shape[1]
    fetch = latent_fetch_pages(pps, int(page_size), lanes, pool.dtype)
    rows = fetch * int(page_size)
    kernel = functools.partial(_latent_walk_kernel, page_size=int(page_size),
                               fetch=fetch, scale=float(scale),
                               d_lat=int(d_lat))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n,),
        in_specs=[pl.BlockSpec((1, h, lanes), lambda n_, tbl, ps_: (n_, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, h, int(d_lat)),
                               lambda n_, tbl, ps_: (n_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, rows, lanes), pool.dtype),
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
    )(page_tables.astype(jnp.int32), pos.astype(jnp.int32), q, pool)


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
