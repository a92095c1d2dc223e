"""``eva_summary_row_share.serve``: of all the cache rows the window's
decode steps read, the share that are summary rows: the sums, over the
window's ``decode.pass`` spans that ran a step, of ``summary_rows`` and
``window_rows`` as ``decode.prepare`` stamped them (the live slots' rows
of each kind at their positions, as the decoder counts them). The
mechanism's footprint: one row a position would read every row as a
window row."""

from layer_metrics import decode_loop


def read(reduced, counters, ctx):
    n_sum = n_win = 0
    for p in decode_loop.step_passes(ctx):
        n_sum += p.get("summary_rows") or 0
        n_win += p.get("window_rows") or 0
    if not n_sum:
        # a program that keeps one row a position has no such rows
        return None
    return 100.0 * n_sum / (n_sum + n_win)
