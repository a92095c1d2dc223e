"""``prefill_call_ms.serve``: mean ``decode.prefill`` over all prefills
of the window (about 65 in ``chat-closed``): the ``prefill_logits``
call through ``int(nxt)``, on the host's clock, whatever the bucket.
This is what ``prefill_device_ms.serve`` could not be from the 3-4
prefills a traced slice holds."""

from layer_metrics import decode_loop


def read(reduced, counters, ctx):
    return decode_loop.mean(p["ms"] for p in decode_loop.prefills(ctx))
