"""``kimi_step_roofline.serve``: the least a decode step of Kimi-K2's cut
had to move (the weights outside the routed experts once, the routers
float32, the (layer, held expert) pairs the step touched, and the live
latent rows of the six layers at their UNPADDED 1,152 B:
``flops_kimi.step_bytes``, a step's mean over the traced slices, from
what the program stamps on its passes) over the HBM peak, against the
step program's (``jit_hybrid_step``) device time per execution."""

import flops_kimi as F
import trace_reduce
from layer_metrics import decode_loop, kimi_cell

PROGRAM = r"^jit_hybrid_step$"


def read(reduced, counters, ctx):
    if reduced is None or ctx.peak is None:
        return None
    m = kimi_cell.model(ctx)
    steps = kimi_cell.traced_steps(ctx, counters)
    seconds, calls = trace_reduce.module_seconds(reduced, PROGRAM)
    if m is None or not steps or not calls:
        return None
    least = F.step_bytes(
        m, decode_loop.mean(p["experts_touched"] for p in steps),
        decode_loop.mean(p["window_rows"] for p in steps)
    ) / ctx.peak["hbm_bytes_per_s"]
    return 100.0 * least / (seconds / calls)
