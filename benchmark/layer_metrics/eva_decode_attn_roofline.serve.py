"""``eva_decode_attn_roofline.serve``: the cache rows a decode step had
to read (the live rows of BOTH kinds that the traced slices' bytes'
queries read, a step's mean, all layers, bfloat16 K and V:
``flops_evabyte.decode_attention_bytes``) over the HBM peak, against the
device time of one step's decode-attention kernel calls in the trace
(``paged_decode_attention`` over a page table that lists summary pages,
then window pages). Memory-bound by construction (one query a slot)."""

import flops_evabyte as F
import trace_reduce
from layer_metrics import eva_cell

KERNEL = r"paged_decode_attention"


def read(reduced, counters, ctx):
    m = eva_cell.model(ctx)
    rows = eva_cell.rows_a_step(counters)
    if reduced is None or ctx.peak is None or m is None or rows is None:
        return None
    seconds, calls = trace_reduce.op_seconds(reduced, KERNEL)
    if not calls:
        return None
    steps_seen = calls / m.n_layers
    least = (steps_seen * F.decode_attention_bytes(m, sum(rows))
             / ctx.peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds
