"""``looped_step_roofline.serve``: what a decode step of the looped
stack had to move (``flops_ouro.step_bytes``: the layers' bfloat16
weights ``n_loops`` times, the head once, the live positions' K/V rows
of every (pass, layer) read and the live slots' new rows written; a
step's mean over the traced slices, from what ``decode.prepare``
stamps) over the HBM peak, against the step program's
(``jit_looped_step``) device time per execution in the trace."""

import flops_ouro as F
import trace_reduce
from layer_metrics import decode_loop, looped_cell

PROGRAM = r"^jit_looped_step$"


def read(reduced, counters, ctx):
    if reduced is None or ctx.peak is None:
        return None
    m = looped_cell.model(ctx)
    steps = looped_cell.traced_steps(ctx, counters)
    seconds, calls = trace_reduce.module_seconds(reduced, PROGRAM)
    if not steps or not calls:
        return None
    least = F.step_bytes(
        m, decode_loop.mean(p["active"] for p in steps),
        decode_loop.mean(p["window_rows"] for p in steps)
    ) / ctx.peak["hbm_bytes_per_s"]
    return 100.0 * least / (seconds / calls)
