"""``kimi_tile_roofline.serve``: the least time of the prompt tiles the
traced slices hold (``flops_kimi.prompt_least_s``: each tile's larger of
its FLOPs at the bf16 peak, the lane's rows expanded through ``W_UK`` /
``W_UV`` and the causal pairs included, and its bytes at the HBM peak,
the weights once plus the experts its tokens touch under a uniform
router and the lane's latent rows) against the tile program's
(``jit_hybrid_prefill``) device time.

Which prompts ran is read from the ``decode.prefill`` spans of the
passes that overlap a slice; a prompt's walk may straddle a slice's
end, so the least time is scaled by the tile executions the trace holds
over the tiles those prompts are."""

import flops_kimi as F
import trace_reduce
from layer_metrics import kimi_cell

PROGRAM = r"^jit_hybrid_prefill$"


def read(reduced, counters, ctx):
    if reduced is None or ctx.peak is None:
        return None
    m = kimi_cell.model(ctx)
    passes = kimi_cell.traced_passes(ctx, counters)
    if m is None or not passes:
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
    tile = int(ctx.config["serve"]["prefill_tile"])
    held = counters.get("held_per_token_layer") or m.top_k_held
    least = sum(F.prompt_least_s(m, q["prompt_tokens"], tile, held,
                                 ctx.peak) for q in walks)
    return 100.0 * least * (calls / tiles) / seconds
