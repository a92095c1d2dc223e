"""``hybrid_prefill_mfu.serve``: the FLOPs of the prompts whose tiles
the traced slices hold (``flops_granite.prefill_flops`` of each
prompt's real tokens, this chip's share of the experts) over the tile
program's (``jit_hybrid_prefill``) device time in the trace times the
bf16 peak.

Which prompts ran is read from the ``decode.prefill`` spans of the
passes that overlap a slice; a prompt's walk may straddle a slice's
end, so the FLOPs are scaled by the tile executions the trace holds
over the tiles those prompts are."""

import flops_granite as F
import trace_reduce
from layer_metrics import hybrid_cell

PROGRAM = r"^jit_hybrid_prefill$"


def read(reduced, counters, ctx):
    if reduced is None or ctx.peak is None:
        return None
    m = hybrid_cell.model(ctx)
    passes = hybrid_cell.traced_passes(ctx, counters)
    if not passes:
        return None
    walks = []
    for p in passes:
        s0, s1 = p["slice"]
        for q in p["prefills"]:
            a = p["t0"] + q["start_ms"] * 1e-3
            if a < s1 and a + q["ms"] * 1e-3 > s0 and q.get("tiles"):
                walks.append(q)
    seconds, calls = trace_reduce.module_seconds(reduced, PROGRAM)
    tiles = sum(q["tiles"] for q in walks)
    if not calls or not tiles:
        return None
    held = counters.get("held_per_token_layer") or F.expected_held(m)
    flops = sum(F.prefill_flops(m, q["prompt_tokens"], held)
                for q in walks)
    return (100.0 * flops * (calls / tiles)
            / (seconds * ctx.peak["bf16_flops_per_s"]))
