"""``hybrid_step_roofline.serve``: what a decode step had to move (the
bfloat16 weights outside the routed experts and the head once, the held
experts the step touched, the live slots' recurrent state read and
written, the live K/V rows: ``flops_granite.step_bytes``, a step's mean
over the traced slices, from what the program stamps on its passes) over
the HBM peak, against the step program's (``jit_hybrid_step``) device
time per execution in the trace."""

import flops_granite as F
import trace_reduce
from layer_metrics import decode_loop, hybrid_cell

PROGRAM = r"^jit_hybrid_step$"


def read(reduced, counters, ctx):
    if reduced is None or ctx.peak is None:
        return None
    m = hybrid_cell.model(ctx)
    passes = hybrid_cell.traced_passes(ctx, counters)
    steps = [p for p in passes or () if "experts_touched" in p]
    seconds, calls = trace_reduce.module_seconds(reduced, PROGRAM)
    if not steps or not calls:
        return None
    least = F.step_bytes(
        m, decode_loop.mean(p["experts_touched"] for p in steps),
        decode_loop.mean(p["state_slots"] for p in steps),
        decode_loop.mean(p["window_rows"] for p in steps)
    ) / ctx.peak["hbm_bytes_per_s"]
    return 100.0 * least / (seconds / calls)
