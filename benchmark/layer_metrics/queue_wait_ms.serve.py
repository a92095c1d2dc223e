"""``queue_wait_ms.serve``: mean wait between ``submit`` and the slot
claim (the request's ``queue_wait`` span, stamped on its
``decode.prefill`` as ``queue_wait_ms``) over the requests admitted in
the window. Near 0 with as many closed-loop clients as slots; the
number an open-loop cell needs."""

from layer_metrics import decode_loop


def read(reduced, counters, ctx):
    return decode_loop.mean(p["queue_wait_ms"]
                            for p in decode_loop.prefills(ctx)
                            if "queue_wait_ms" in p)
