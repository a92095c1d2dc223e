"""``kv_page_occupancy.serve``: mean, over the window's ``decode.pass``
spans that ran a step, of the pages live requests hold over the
claimable pages (``pages_in_use`` / ``n_pages``, stamped on each pass
after the step's pages were claimed): how full the K/V pool really
is."""

from layer_metrics import decode_loop


def read(reduced, counters, ctx):
    return decode_loop.mean(
        100.0 * p["pages_in_use"] / p["n_pages"]
        for p in decode_loop.step_passes(ctx) if p.get("n_pages"))
