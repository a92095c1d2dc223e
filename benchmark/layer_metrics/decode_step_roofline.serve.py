"""``decode_step_roofline.serve``: what a decode step had to read (every
matmul weight once, plus the K/V rows of the live lengths, a step's
mean over the traced slices: ``flops.decode_step_bytes``) over the HBM
peak, against the step program's device time per execution in the
trace."""

import flops
import trace_reduce

PROGRAM = r"^jit_step$"


def read(reduced, counters, ctx):
    if reduced is None or ctx.peak is None:
        return None
    seconds, calls = trace_reduce.module_seconds(reduced, PROGRAM)
    if not calls or not counters.get("traced_steps"):
        return None
    rows_a_step = counters["traced_positions"] / counters["traced_steps"]
    least = (flops.decode_step_bytes(ctx.model, rows_a_step)
             / ctx.peak["hbm_bytes_per_s"])
    return 100.0 * least / (seconds / calls)
