"""``gap_p50_ms.serve``: the median of the pooled client-side gaps
between consecutive streamed tokens — the steady statistic beside the
end-to-end ``gap_p95_ms``."""


def read(reduced, counters, ctx):
    return counters.get("gap_p50_ms")
