"""``eva_step_roofline.serve``: what a decode step had to read (every
matmul weight once in bfloat16, plus the live cache rows of both kinds,
a step's mean over the traced slices: ``flops_evabyte.step_bytes``) over
the HBM peak, against the step program's (``jit_eva_step``) device time
per execution in the trace."""

import flops_evabyte as F
import trace_reduce
from layer_metrics import eva_cell

PROGRAM = r"^jit_eva_step$"


def read(reduced, counters, ctx):
    m = eva_cell.model(ctx)
    rows = eva_cell.rows_a_step(counters)
    if reduced is None or ctx.peak is None or m is None or rows is None:
        return None
    seconds, calls = trace_reduce.module_seconds(reduced, PROGRAM)
    if not calls:
        return None
    least = F.step_bytes(m, sum(rows)) / ctx.peak["hbm_bytes_per_s"]
    return 100.0 * least / (seconds / calls)
