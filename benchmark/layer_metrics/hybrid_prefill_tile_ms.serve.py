"""``hybrid_prefill_tile_ms.serve``: the host-clock time of a prompt's
walk, a tile walked: over the window's ``decode.prefill`` spans, their
milliseconds over the ``tiles`` they carry (a decoder that walks a
prompt tile by tile with its recurrent state carried stamps how many
tiles the prompt is). Every slot's step waits while a tile runs, so
this is what a tile holds the cell's ``gen_tokens_per_s`` for. A
program whose prefill spans carry no ``tiles`` reads as nothing."""

from layer_metrics import decode_loop


def read(reduced, counters, ctx):
    walks = [q for q in decode_loop.prefills(ctx) if q.get("tiles")]
    tiles = sum(q["tiles"] for q in walks)
    return sum(q["ms"] for q in walks) / tiles if tiles else None
