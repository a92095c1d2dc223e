"""``ttft_p95_ms.serve``: the 95th percentile of send -> first streamed
token over the requests sent in the window — the tail beside the
end-to-end ``ttft_p50_ms``. At the 65 requests a window of
``chat-closed`` holds it is the fourth-longest, and it flips between
two prefill buckets from run to run (PERF.md, section 2), so it
carries no bound."""


def read(reduced, counters, ctx):
    return counters.get("ttft_p95_ms")
