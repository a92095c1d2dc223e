"""``device_starved_share.serve``: the share of the window in which the
decode loop had nothing of its own queued on the device: the sum of
the window's passes' ``starved_ms`` (admit less its prefills, prepare,
dispatch and emit that ran with no step in flight) over the window.
The host's account of ``device_idle_share.serve``, over all 51 s and
with no profiler session: it leaves out the copy back at the tail of a
fetch and the device's gaps inside a prefill walk, and counts the tail
of a dispatch after its program was queued."""

from layer_metrics import loop_account


def read(reduced, counters, ctx):
    ps = loop_account.passes(ctx)
    if ps is None:
        return None
    return 100.0 * loop_account.starved_ms(ps) * 1e-3 / ctx.seconds
