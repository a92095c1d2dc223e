"""The decode step's expert products over the experts its slots routed
to, and no others.

A decode step of the hybrid block (``models/granite_hybrid.py``
:func:`~mmlspark_tpu.models.granite_hybrid.moe` with ``grouped=False``)
computes, for its few tokens, ``sum_e weight[:, e] * W_out_e (silu(g_e)
* v_e)`` with ``[g_e | v_e] = u W_in_e`` over the experts the chip
holds, ``weight`` the router's (zero where a token was not routed to
``e``). The masked form runs every held expert over every token and lets
the zeros remove what was not routed: exact, and it reads every held
expert's weights whatever was touched. :func:`touched_experts` reads
only the experts some token was routed to: the caller lists them first
(``ids``, ``n_touched`` of them), and the kernel walks that list with
its own copies, as ``paged_decode_attention`` walks a slot's live pages.

**The walk.** The weights stay in HBM. An expert is copied in
:func:`expert_chunk` columns of its width ``F`` at a time: the chunk's
columns of ``g`` and of ``v`` in ``W_in`` ([D, chunk] each) and its rows
of ``W_out`` ([chunk, D]), three copies into one of two VMEM buffers,
the next chunk's copies started before this one's are waited for. A
chunk's part is whole on its own (the gate is elementwise in ``F``), so
a chunk is multiplied as it lands: ``g``, ``v`` in float32 from ``u``,
``act = silu(g) * v * weight[:, e]`` rounded to the weights' dtype, and
``act @ W_out`` summed into a float32 [T, D] output. These are the
masked form's operands and precision; only the order of the float32
sums differs. ``n_touched == 0`` copies nothing and returns zeros.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
# what two chunks' weights (the one multiplied, the one in flight) may
# hold in VMEM, and the scoped-VMEM limit the kernel asks for (the
# v5e's default is 16 MiB): the buffers, u and the output beside them
_MOE_VMEM_BUDGET = 24 * 2**20
_MOE_VMEM_LIMIT = 40 * 2**20


def expert_chunk(d: int, f: int, itemsize: int) -> int:
    """Columns of an expert's width ``f`` that one copy of the walk
    carries: the widest multiple of 128 that divides ``f`` and whose two
    buffers of ``W_in``'s ``g`` and ``v`` columns and ``W_out``'s rows
    (``2 x 3 x d x chunk`` elements) fit :data:`_MOE_VMEM_BUDGET`; a
    width that is not whole 128-lane registers is copied whole. At the
    cells' shapes (LFM2's D 2048, F 1536; granite's D 4096, F 768, both
    bfloat16) a chunk is 768 / 384 columns: three copies of 3.1 MB."""
    if f % LANE:
        return f
    per_column = 2 * 3 * d * itemsize
    fits = [c for c in range(LANE, f + 1, LANE)
            if f % c == 0 and c * per_column <= _MOE_VMEM_BUDGET]
    return max(fits, default=LANE)


def _touched_kernel(ids_ref, n_ref, u_ref, w_ref, w_in_hbm, w_out_hbm,
                    o_ref, g_buf, v_buf, out_buf, sem, *, chunk: int,
                    f: int):
    """The walk over the first ``n_ref[0]`` experts of ``ids_ref``,
    ``f // chunk`` chunks an expert, flattened into one loop whose
    step ``k`` multiplies chunk ``k % n_chunks`` of expert ``ids_ref[k
    // n_chunks]`` out of buffer ``k % 2`` while step ``k + 1``'s copies
    land in the other."""
    f32 = jnp.float32
    n_chunks = f // chunk
    total = n_ref[0] * n_chunks

    def copies(k, b):
        e = ids_ref[k // n_chunks]
        c0 = pl.multiple_of((k % n_chunks) * chunk, chunk)
        return (pltpu.make_async_copy(w_in_hbm.at[e, :, pl.ds(c0, chunk)],
                                      g_buf.at[b], sem.at[b]),
                pltpu.make_async_copy(
                    w_in_hbm.at[e, :, pl.ds(f + c0, chunk)], v_buf.at[b],
                    sem.at[b]),
                pltpu.make_async_copy(w_out_hbm.at[e, pl.ds(c0, chunk)],
                                      out_buf.at[b], sem.at[b]))

    @pl.when(total > 0)
    def _():
        for c in copies(0, 0):
            c.start()

    o_ref[...] = jnp.zeros_like(o_ref)
    lane = jax.lax.broadcasted_iota(jnp.int32, w_ref.shape, 1)

    def body(k, carry):
        b = k % 2

        @pl.when(k + 1 < total)
        def _():
            for c in copies(k + 1, 1 - b):
                c.start()

        for c in copies(k, b):
            c.wait()
        e = ids_ref[k // n_chunks]
        # the router's weight of expert e for every token, [T, 1]
        w = jnp.sum(jnp.where(lane == e, w_ref[...], 0.0), axis=1,
                    keepdims=True)
        u = u_ref[...]
        g = jnp.dot(u, g_buf[b], preferred_element_type=f32)
        v = jnp.dot(u, v_buf[b], preferred_element_type=f32)
        act = (jax.nn.silu(g) * v * w).astype(out_buf.dtype)
        o_ref[...] += jnp.dot(act, out_buf[b], preferred_element_type=f32)
        return carry

    jax.lax.fori_loop(0, total, body, 0)


@functools.partial(jax.jit, static_argnames=("interpret", "chunk"))
def touched_experts(u, weight, w_in_e, w_out_e, ids, n_touched,
                    interpret: bool = False, chunk: int = None):
    """The experts' part of a decode step, reading only the touched
    experts' weights.

    ``u`` [T, D] — the tokens' normed input in the weights' dtype;
    ``weight`` [T, E] float32 — the router's weight of each held expert
    for each token, zero where it was not routed (or the token is not
    live); ``w_in_e`` [E, D, 2F] (``[g | v]``) and ``w_out_e`` [E, F, D]
    — the held experts, left where they lie; ``ids`` int32[E] — held
    experts' places, the ``n_touched`` (int32 scalar) that some token
    was routed to first. Returns ``sum_e weight[:, e] * (silu(g_e) *
    v_e) W_out_e`` [T, D] float32 over those ``n_touched`` experts; an
    expert past them is never copied, whatever its weights hold.
    ``chunk`` (default :func:`expert_chunk`) divides ``F``."""
    t, d = u.shape
    n_held, _, f2 = w_in_e.shape
    f = f2 // 2
    dtype = w_in_e.dtype
    chunk = chunk or expert_chunk(d, f, jnp.dtype(dtype).itemsize)
    if f % chunk:
        raise ValueError(f"a chunk of {chunk} does not divide F={f}")

    def whole(shape):
        return pl.BlockSpec(shape, lambda i, ids_, n_: (0,) * len(shape))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(1,),
        # the experts stay where they are: the kernel's own copies,
        # aimed by the scalar-prefetched ids, read the touched ones
        in_specs=[whole((t, d)), whole((t, n_held)),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=whole((t, d)),
        scratch_shapes=[pltpu.VMEM((2, d, chunk), dtype),
                        pltpu.VMEM((2, d, chunk), dtype),
                        pltpu.VMEM((2, chunk, d), w_out_e.dtype),
                        pltpu.SemaphoreType.DMA((2,))])
    return pl.pallas_call(
        functools.partial(_touched_kernel, chunk=chunk, f=f),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_MOE_VMEM_LIMIT),
        interpret=interpret,
    )(ids.astype(jnp.int32), jnp.reshape(n_touched, (1,)).astype(jnp.int32),
      u, weight.astype(jnp.float32), w_in_e, w_out_e)
