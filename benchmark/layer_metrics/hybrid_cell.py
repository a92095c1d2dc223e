"""What the ``hybrid_*`` readers share: the configuration's sizes and
the ``decode.pass`` spans that lie in the traced slices.

The driver (``drivers/generate_http_granite.py``) hands over the traced
slices' bounds on its own clock (``traced_slices``), which is the clock
of the program's ``decode.pass`` spans (``decode_loop.py``). A pass that
ran a step carries what the step moved: ``state_slots`` and
``window_rows`` (stamped by ``decode.prepare``: the slots whose
recurrent state the step advances, the K/V rows it reads) and
``experts_touched`` (stamped by the decoder on ``decode.fetch``: the
(layer, held expert) pairs that received a routing). A
``decode.prefill`` span carries ``tiles`` and ``prompt_tokens``.

A program without this block kind, or a run without a trace, reads as
nothing: every function returns ``None``.
"""

from typing import Any, Dict, List, Optional

import reference_granite as RG


def model(ctx) -> Optional[RG.Model]:
    try:
        return RG.Model.from_config(ctx.config)
    except KeyError:          # a configuration of another block kind
        return None


def traced_passes(ctx, counters: Dict[str, Any]
                  ) -> Optional[List[Dict[str, Any]]]:
    """The views of the ``decode.pass`` spans that overlap a traced
    slice, each with ``t0`` and ``t1`` (seconds, the driver's clock)
    and ``slice``, the bounds of the slice it overlaps."""
    slices = counters.get("traced_slices")
    if model(ctx) is None or not slices:
        return None
    from mmlspark_tpu.core.tracing import TRACER
    try:
        from mmlspark_tpu.serving.decode import pass_view
    except ImportError:
        return None
    scan = getattr(TRACER.recorder, "scan", None)
    if scan is None:
        return None
    out: List[Dict[str, Any]] = []
    for t0, t1 in slices:
        # a pass is recorded when it ends: one that holds a long
        # prefill may have started seconds before the slice
        for sp in scan("decode.pass", t0 - 10.0, t1):
            if sp.t0 < t1 and sp.t1 > t0:
                out.append(dict(pass_view(sp.attrs["phases"]), t0=sp.t0,
                                t1=sp.t1, slice=(t0, t1)))
    return out
