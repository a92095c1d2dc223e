"""``eva_compact_roofline.serve``: what turning a finished window into
its summaries had to move (read the window's rows, write its summary
rows, every layer, bfloat16: ``flops_evabyte.summaries``) over the HBM
peak, against the compaction program's (``jit_eva_compact``) device time
per execution in the trace. Nothing where the traced slices hold no
compaction."""

import flops_evabyte as F
import trace_reduce
from layer_metrics import eva_cell

PROGRAM = r"^jit_eva_compact$"


def read(reduced, counters, ctx):
    m = eva_cell.model(ctx)
    if reduced is None or ctx.peak is None or m is None:
        return None
    seconds, calls = trace_reduce.module_seconds(reduced, PROGRAM)
    if not calls:
        return None
    least = (m.n_layers * F.summaries(m)["bytes"]
             / ctx.peak["hbm_bytes_per_s"])
    return 100.0 * least / (seconds / calls)
