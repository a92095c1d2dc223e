"""``loop_stall_share.serve``: the share of the window lost to stalls
of the decode loop: over the passes that dispatched or fetched a step,
the time by which a pass (less its prefill and compact children)
exceeds ``SLOW_PASS_MULTIPLE`` times the window's median of the same,
summed, over the window; 0.0 where no pass does."""

from layer_metrics import loop_account


def read(reduced, counters, ctx):
    ps = loop_account.passes(ctx)
    if ps is None:
        return None
    return 100.0 * sum(s["over_ms"] for s in loop_account.stalls(ps)) \
        * 1e-3 / ctx.seconds
