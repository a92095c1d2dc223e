"""``loop_stall_blocked_share.serve``: the part of
``loop_stall_share.serve`` in passes whose ``cpu_ms`` and
``proc_cpu_ms`` are both under half their length (the program's
``stall_word`` says ``blocked``): neither the loop's thread nor any
other thread of the process ran, so the time went to the runtime's
wait or to a machine that stood still, not to this program's host
code; 0.0 where no stall is of that kind."""

from layer_metrics import loop_account


def read(reduced, counters, ctx):
    ps = loop_account.passes(ctx)
    if ps is None:
        return None
    return 100.0 * sum(s["over_ms"] for s in loop_account.stalls(ps)
                       if s["stall"] == "blocked") * 1e-3 / ctx.seconds
