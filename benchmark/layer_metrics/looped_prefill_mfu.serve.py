"""``looped_prefill_mfu.serve``: the FLOPs of the prompts the traced
slices prefilled (``flops_ouro.prefill_flops`` of each prompt's real
tokens, every pass) over the prefill programs' (``jit_looped_prefill``,
every bucket) device time in the trace times the bf16 peak.

Which prompts ran is read from the ``decode.prefill`` spans of the
passes that overlap a slice; one may straddle a slice's end, so the
FLOPs are scaled by the program executions the trace holds over the
prefills those spans are."""

import flops_ouro as F
import trace_reduce
from layer_metrics import looped_cell

PROGRAM = r"^jit_looped_prefill$"


def read(reduced, counters, ctx):
    if reduced is None or ctx.peak is None:
        return None
    m = looped_cell.model(ctx)
    passes = looped_cell.traced_passes(ctx, counters)
    if not passes:
        return None
    ran = []
    for p in passes:
        s0, s1 = p["slice"]
        for q in p["prefills"]:
            a = p["t0"] + q["start_ms"] * 1e-3
            if a < s1 and a + q["ms"] * 1e-3 > s0 \
                    and q.get("loops", 1) > 1:
                ran.append(q)
    seconds, calls = trace_reduce.module_seconds(reduced, PROGRAM)
    if not calls or not ran:
        return None
    flops = sum(F.prefill_flops(m, q["prompt_tokens"]) for q in ran)
    return (100.0 * flops * (calls / len(ran))
            / (seconds * ctx.peak["bf16_flops_per_s"]))
