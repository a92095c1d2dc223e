"""``host_loop_ms.serve``: the host time the device waits for, per step:
the mean, over the window's ``decode.pass`` spans that ran a step, of
the pass's length less its ``decode.fetch`` (the wait for the device
and the copy back) and less its ``decode.prefill`` children (device
work of another program). What is left is admit, prepare, dispatch,
emit and the Python between them."""

from layer_metrics import decode_loop


def read(reduced, counters, ctx):
    return decode_loop.mean(
        p["ms"] - p["phases_ms"].get("fetch", 0.0) - p["prefill_ms"]
        for p in decode_loop.step_passes(ctx))
