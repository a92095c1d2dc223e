"""``device_idle_share``: 1 - the union of the device-op intervals over
the traced window (first op's start to last op's end), averaged over
the chips used."""


def read(reduced, counters, ctx):
    if reduced is None or reduced["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])
