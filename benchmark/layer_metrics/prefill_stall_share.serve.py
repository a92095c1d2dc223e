"""``prefill_stall_share.serve``: the share of the window in which the
loop sat inside a ``decode.prefill`` while at least one other slot was
active (``others_active`` on the span): time in which those slots'
next tokens waited for somebody else's prompt."""

from layer_metrics import decode_loop


def read(reduced, counters, ctx):
    passes = decode_loop.passes(ctx)
    if not passes:
        return None
    stalled = sum(q["ms"] for p in passes for q in p["prefills"]
                  if q.get("others_active"))
    return 100.0 * stalled * 1e-3 / ctx.seconds
