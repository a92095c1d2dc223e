"""``loop_dispatch_ms.serve``: mean ``decode.dispatch`` per step: the
host-to-device copies of tokens, positions and page tables and the call
of the step program until it returns, over the window's ``decode.pass``
spans that ran a step."""

from layer_metrics import decode_loop


def read(reduced, counters, ctx):
    return decode_loop.mean(p["phases_ms"]["dispatch"]
                            for p in decode_loop.step_passes(ctx))
