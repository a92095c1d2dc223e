"""What the ``kimi_*`` readers share: the configuration's sizes and the
``decode.pass`` spans that lie in the traced slices.

The driver (``drivers/generate_http_kimi.py``) hands over the traced
slices' bounds on its own clock (``traced_slices``), which is the clock
of the program's ``decode.pass`` spans. A pass that ran a step carries
what the step moved: ``window_rows`` (stamped by ``decode.prepare``: the
latent rows the step reads in each layer) and ``experts_touched``
(stamped by the decoder on ``decode.fetch``: the (layer, held expert)
pairs that received a routing). A ``decode.prefill`` span carries
``tiles``, ``prompt_tokens`` and ``latent_rows_expanded``.

A configuration of another kind (its ``model_type`` is not
``kimi_k2``), a program without these spans, or a run without a trace,
reads as nothing: every function returns ``None``.
"""

from typing import Any, Dict, List, Optional

import reference_kimi as RK


def model(ctx) -> Optional[RK.Model]:
    try:
        return RK.Model.from_config(ctx.config)
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


def traced_steps(ctx, counters) -> List[Dict[str, Any]]:
    """The traced passes that ran a step (its ``decode.fetch`` carries
    ``experts_touched``)."""
    return [p for p in traced_passes(ctx, counters) or ()
            if "experts_touched" in p]
