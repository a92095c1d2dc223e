"""``mfu.train``: the whole step's share of the chip's bf16 peak — the
traced part of the window's own tokens per second (host clock, steps
that ran wholly inside it) times the model's FLOPs per token
(``flops.train_flops_per_token``: 6 x matmul weights + causal
attention, no recompute)."""

import flops


def read(reduced, counters, ctx):
    if ctx.peak is None or not counters.get("traced_steps"):
        return None
    tokens_per_s = (counters["traced_steps"] * counters["tokens_per_step"]
                    / counters["traced_s"])
    per_token = flops.train_flops_per_token(ctx.model,
                                            int(ctx.traffic["seq"]))
    return 100.0 * tokens_per_s * per_token / ctx.peak["bf16_flops_per_s"]
