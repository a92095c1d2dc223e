"""What the ``looped_*`` readers share: the configuration's sizes and
the ``decode.pass`` spans that lie in the traced slices.

The driver (``drivers/generate_http_ouro.py``) hands over the traced
slices' bounds on its own clock (``traced_slices``), which is the clock
of the program's ``decode.pass`` spans. A pass that ran a step carries
what the step read: ``window_rows`` (live positions) and ``loops`` (the
passes each is read in), stamped by ``decode.prepare``, and ``active``
(the live slots). A ``decode.prefill`` span carries ``prompt_tokens``,
``bucket`` and ``loops``.

A program without a looped stack (a configuration of another block
kind, or the parent commit), or a run without a trace, reads as
nothing: every function returns ``None``.
"""

from typing import Any, Dict, List, Optional

import reference_ouro as RO


def model(ctx) -> Optional[RO.Model]:
    try:
        return RO.Model.from_config(ctx.config)
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
        # a pass is recorded when it ends: it may have started before
        # the slice
        for sp in scan("decode.pass", t0 - 10.0, t1):
            if sp.t0 < t1 and sp.t1 > t0:
                out.append(dict(pass_view(sp.attrs["phases"]), t0=sp.t0,
                                t1=sp.t1, slice=(t0, t1)))
    return out


def traced_steps(ctx, counters) -> List[Dict[str, Any]]:
    """The traced passes that ran a step of a looped stack (its
    ``decode.fetch`` carries ``exit_pass_mean``)."""
    return [p for p in traced_passes(ctx, counters) or ()
            if "exit_pass_mean" in p]
