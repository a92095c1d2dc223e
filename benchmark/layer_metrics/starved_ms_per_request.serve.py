"""``starved_ms_per_request.serve``: the window's starved milliseconds
(``device_starved_share.serve``'s sum) over the requests admitted in
it, one ``decode.prefill`` span each: what a request's end and the
next one's admission cost the device, whatever the step's length."""

from layer_metrics import loop_account


def read(reduced, counters, ctx):
    ps = loop_account.passes(ctx)
    n = sum(len(p["prefills"]) for p in ps or ())
    if not n:
        return None
    return loop_account.starved_ms(ps) / n
