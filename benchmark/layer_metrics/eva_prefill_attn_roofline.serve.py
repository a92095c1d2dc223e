"""``eva_prefill_attn_roofline.serve``: the least time of the prefill
attention calls the traced slices hold (each window tile's causal pairs
plus its queries against the summary rows before it, QK^T and PV at the
bf16 peak, or its bytes at the HBM peak where they bind:
``flops_evabyte.prefill_attention``), against the device time of the
flash kernel's calls in the trace (``_flash_call``, one a layer and
tile, over the concatenated summary rows and tile).

Which tiles ran is read from the ``decode.prefill`` spans that overlap
a slice; a prompt's walk may straddle a slice's end, so the least time
is scaled by the calls the trace holds over the calls those prompts
make."""

import flops
import flops_evabyte as F
import trace_reduce
from layer_metrics import eva_cell

KERNEL = r"_flash_call"


def read(reduced, counters, ctx):
    m = eva_cell.model(ctx)
    if reduced is None or ctx.peak is None or m is None:
        return None
    seconds, calls = trace_reduce.op_seconds(reduced, KERNEL)
    tiles = eva_cell.traced_prefill_tiles(ctx, counters)
    if not calls or not tiles:
        return None
    costs = (F.prefill_attention(m, t, s) for t, s in tiles)
    least = m.n_layers * sum(
        flops.roofline(c["flops"], c["bytes"], ctx.peak)["seconds"]
        for c in costs)
    expected = len(tiles) * m.n_layers
    return 100.0 * least * (calls / expected) / seconds
