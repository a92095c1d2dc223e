"""``steps_ahead_share.serve``: of the window's passes that dispatched
a step, the share whose ``decode.prepare`` says ``order == "ahead"``:
the step went out behind a step still in flight, so the host's turn
ran beside the device (``n_steps_ahead`` / ``n_steps`` of
``/decode/stats``, over the window and from the spans)."""

from layer_metrics import loop_account


def read(reduced, counters, ctx):
    ps = loop_account.passes(ctx)
    stepped = [p for p in ps or () if "dispatch" in p["phases_ms"]]
    if not stepped:
        return None
    return 100.0 * sum(p["order"] == "ahead" for p in stepped) \
        / len(stepped)
