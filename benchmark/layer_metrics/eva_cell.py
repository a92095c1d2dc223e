"""What the ``eva_*`` readers share: the configuration's sizes, the rows
a traced step read, and the prefill calls that ran inside the traced
slices.

The driver (``drivers/generate_http_evabyte.py``) counts, from the
clients' records, the rows of each kind that the steps whose bytes
arrived inside a traced slice read (``traced_summary_rows``,
``traced_window_rows``, over ``traced_steps``), and hands over the
slices' bounds on its own clock (``traced_slices``), which is the
clock of the program's ``decode.pass`` spans (``decode_loop.py``). A
``decode.prefill`` span carries its prompt's length, so the window
tiles it ran are known: ``flops_evabyte.prompt_windows``.

A program without this block kind, or a run without a trace, reads as
nothing: every function returns ``None``.
"""

from typing import Any, Dict, List, Optional, Tuple

import flops_evabyte as F
import reference_evabyte as RE


def model(ctx) -> Optional[RE.Model]:
    try:
        return RE.Model.from_config(ctx.config)
    except KeyError:          # a configuration of another block kind
        return None


def rows_a_step(counters: Dict[str, Any]) -> Optional[Tuple[float, float]]:
    """Mean ``(summary_rows, window_rows)`` a traced step read, all its
    slots together."""
    steps = counters.get("traced_steps")
    if not steps or "traced_window_rows" not in counters:
        return None
    return (counters["traced_summary_rows"] / steps,
            counters["traced_window_rows"] / steps)


def traced_prefill_tiles(ctx, counters: Dict[str, Any]
                         ) -> Optional[List[Tuple[int, int]]]:
    """``(tile, n_summary)`` of every window tile of every prompt whose
    ``decode.prefill`` span overlaps a traced slice."""
    m = model(ctx)
    slices = counters.get("traced_slices")
    if m is None or not slices:
        return None
    from mmlspark_tpu.core.tracing import TRACER
    try:
        from mmlspark_tpu.serving.decode import pass_view
    except ImportError:
        return None
    scan = getattr(TRACER.recorder, "scan", None)
    if scan is None:
        return None
    tiles: List[Tuple[int, int]] = []
    for t0, t1 in slices:
        # a pass is recorded when it ends: one that holds a long
        # prefill may have started seconds before the slice
        for sp in scan("decode.pass", t0 - 10.0, t1):
            for q in pass_view(sp.attrs["phases"])["prefills"]:
                a = sp.t0 + q["start_ms"] * 1e-3
                if a < t1 and a + q["ms"] * 1e-3 > t0:
                    tiles += F.prompt_windows(m, q["prompt_len"])
    return tiles
