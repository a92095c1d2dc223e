"""``looped_decode_attn_roofline.serve``: the K/V bytes one call of
``paged_decode_attention`` had to read under the looped step (the live
positions of the traced steps, a step's mean, times one (pass, layer)'s
K and V row: ``flops_ouro.kv_row_bytes``, 8 KiB in bfloat16) over the
HBM peak, against the kernel's device time a call in the trace
(``n_loops`` x ``n_layers`` calls a step). Memory-bound by construction
(one query row a slot)."""

import flops_ouro as F
import trace_reduce
from layer_metrics import decode_loop, looped_cell

KERNEL = r"paged_decode_attention"


def read(reduced, counters, ctx):
    if reduced is None or ctx.peak is None:
        return None
    m = looped_cell.model(ctx)
    steps = looped_cell.traced_steps(ctx, counters)
    seconds, calls = trace_reduce.op_seconds(reduced, KERNEL)
    if not steps or not calls:
        return None
    rows = decode_loop.mean(p["window_rows"] for p in steps)
    least = rows * F.kv_row_bytes(m) / ctx.peak["hbm_bytes_per_s"]
    return 100.0 * least / (seconds / calls)
