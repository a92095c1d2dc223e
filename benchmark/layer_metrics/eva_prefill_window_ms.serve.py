"""``eva_prefill_window_ms.serve``: the host-clock time of a prompt's
walk, a window tile walked: over the window's ``decode.prefill`` spans,
their milliseconds over the ``windows`` they carry (a decoder that
walks a prompt window by window stamps how many tiles the prompt is:
each full one is written, attended and compacted before the next
starts). Every slot's step waits while a walk runs, so this is what the
window-by-window prefill and its in-prefill compaction cost the cell's
``gen_tokens_per_s``; the cell's TTFT is this times the prompt's tiles.
A program whose prefill spans carry no ``windows`` reads as nothing."""

from layer_metrics import decode_loop


def read(reduced, counters, ctx):
    walks = [q for q in decode_loop.prefills(ctx) if q.get("windows")]
    tiles = sum(q["windows"] for q in walks)
    return sum(q["ms"] for q in walks) / tiles if tiles else None
