"""What the seven ``*.serve`` readers of the decode loop share: the
``decode.pass`` spans of the measured window, from the program's own
in-memory flight recorder.

``DecodeScheduler`` records every pass of its loop once, as one
``decode.pass`` span, into ``mmlspark_tpu.core.tracing.TRACER``, which
``ServingServer`` uses by default and which outlives the server: the
readers run in the driver's process after the server has stopped. A
pass carries ``phases``, the ``decode.<phase>`` spans of the pass as
they closed, which the program's ``serving.decode.pass_view`` spells
out: ``phases_ms`` (milliseconds in admit, prefill, prepare, dispatch,
fetch, emit, idle), ``prefills`` (one entry per ``decode.prefill``:
``ms``, ``bucket``, ``others_active``, ``queue_wait_ms``, ...),
``pages_in_use`` and ``n_pages``. Span times are seconds on
``time.monotonic``, which is ``time.perf_counter``'s clock on Linux:
the driver's clock.

The window opens ``traffic["ramp_s"]`` seconds after ``ctx.t_start +
ctx.phases["warm_request"]`` and lasts ``ctx.seconds``: the driver's
own window to within the one ``/decode/stats`` call it makes between
the ramp and its ``t0`` (a few milliseconds against 51 s).

A program without these spans (no ``pass_view``, a recorder without
``scan``, or no ``decode.pass`` in it) reads as nothing: every reader
returns ``None``.
"""

from typing import Any, Dict, List, Optional


def window(ctx) -> "tuple[float, float]":
    t_open = (ctx.t_start + ctx.phases["warm_request"]
              + float(ctx.traffic["ramp_s"]))
    return t_open, t_open + ctx.seconds


def passes(ctx) -> List[Dict[str, Any]]:
    """The ``decode.pass`` spans that START inside the window, each as
    its view with ``ms``, the pass's length."""
    if "warm_request" not in ctx.phases:
        return []
    from mmlspark_tpu.core.tracing import TRACER
    try:
        from mmlspark_tpu.serving.decode import pass_view
    except ImportError:
        return []
    scan = getattr(TRACER.recorder, "scan", None)
    if scan is None:
        return []
    return [dict(pass_view(sp.attrs["phases"]),
                 ms=(sp.t1 - sp.t0) * 1e3)
            for sp in scan("decode.pass", *window(ctx))]


def step_passes(ctx) -> List[Dict[str, Any]]:
    """The window's passes that ran a step, with ``prefill_ms``, their
    prefills' sum."""
    return [dict(p, prefill_ms=sum(q["ms"] for q in p["prefills"]))
            for p in passes(ctx) if "dispatch" in p["phases_ms"]]


def prefills(ctx) -> List[Dict[str, Any]]:
    """Every ``decode.prefill`` of the window's passes."""
    return [q for p in passes(ctx) for q in p["prefills"]]


def mean(values) -> Optional[float]:
    values = list(values)
    return sum(values) / len(values) if values else None
