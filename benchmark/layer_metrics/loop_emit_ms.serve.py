"""``loop_emit_ms.serve``: mean ``decode.emit`` per step: the per-slot
loop after the step (sampling, one SSE event a token handed to the HTTP
frontend, retiring finished requests), over the window's
``decode.pass`` spans that ran a step."""

from layer_metrics import decode_loop


def read(reduced, counters, ctx):
    return decode_loop.mean(p["phases_ms"].get("emit", 0.0)
                            for p in decode_loop.step_passes(ctx))
