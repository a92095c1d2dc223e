"""``expert_load_max_over_mean.serve``: over the window's passes that
ran a step, the mean of the busiest held expert's routings over the
held experts' mean (``expert_load_max`` and ``expert_routings``, the
layers summed, which the decoder stamps on the step's ``decode.fetch``
span): 1 is an even load; the deployment's expert-parallel exchange
waits for the busiest. A program that stamps no routings reads as
nothing."""

from layer_metrics import decode_loop


def read(reduced, counters, ctx):
    return decode_loop.mean(
        p["expert_load_max"] * len(p["expert_routings"])
        / float(sum(p["expert_routings"]))
        for p in decode_loop.step_passes(ctx)
        if p.get("expert_routings") and sum(p["expert_routings"]))
