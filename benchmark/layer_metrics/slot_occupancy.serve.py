"""``slot_occupancy.serve``: tokens the decode steps generated over the
steps run times the slots, from ``/decode/stats`` read at the window's
two ends (``n_tokens`` less the first tokens, which prefills emit, over
``n_steps`` x ``n_slots``)."""


def read(reduced, counters, ctx):
    steps = counters.get("window_steps")
    if not steps:
        return None
    tokens = counters["window_server_tokens"] - counters["window_prefills"]
    return 100.0 * tokens / (steps * counters["n_slots"])
