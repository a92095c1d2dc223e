"""What the five readers of the decode loop's own account share: the
window's ``decode.pass`` spans WITH the attributes the pass itself
carries, which ``decode_loop.passes`` drops.

Since PR 37 the program's loop keeps an account of the time it left
the device without work (``serving.decode.DecodeScheduler._record_pass``
states what it counts and what it leaves out). Every pass that reached
its ``decode.prepare`` says there in which ``order`` it ran (``ahead``,
``start``, ``in_turn``, ``fetch_only``, ``spec_round``) and, where the
rule held it to today's order, ``held_by``; the ``decode.pass`` span
carries ``starved_ms`` (milliseconds by phase in which nothing of the
loop's was queued on the device), ``cpu_ms`` and ``proc_cpu_ms`` (the
loop thread's and the process's CPU time over the pass). All of it is
read from ``core.tracing.TRACER`` after the server has stopped, in a
plain run as in a traced one: no profiler session is needed.

A program that records no ``order`` (the parent of PR 37, or one
without the spans at all) reads as nothing: :func:`passes` returns
``None`` and so does every reader.
"""

from typing import Any, Dict, List, Optional

from layer_metrics import decode_loop


def passes(ctx, t0: Optional[float] = None, t1: Optional[float] = None
           ) -> Optional[List[Dict[str, Any]]]:
    """The ``decode.pass`` spans that START inside the window (or
    inside ``[t0, t1)``, seconds on the driver's clock), each as its
    view with ``ms``, ``t0``, ``prefill_ms`` / ``compact_ms`` (its
    children of another program) and the span's own ``starved_ms``,
    ``cpu_ms``, ``proc_cpu_ms``; ``None`` where no pass says its
    ``order``."""
    if "warm_request" not in ctx.phases:
        return None
    from mmlspark_tpu.core.tracing import TRACER
    try:
        from mmlspark_tpu.serving.decode import pass_view
    except ImportError:
        return None
    scan = getattr(TRACER.recorder, "scan", None)
    if scan is None:
        return None
    lo, hi = decode_loop.window(ctx)
    out = []
    for sp in scan("decode.pass", lo if t0 is None else t0,
                   hi if t1 is None else t1):
        view = pass_view(sp.attrs["phases"])
        view.update(
            ms=(sp.t1 - sp.t0) * 1e3, t0=sp.t0,
            prefill_ms=sum(q["ms"] for q in view["prefills"]),
            compact_ms=view["phases_ms"].get("compact", 0.0),
            starved_ms=sp.attrs.get("starved_ms") or {},
            cpu_ms=sp.attrs.get("cpu_ms"),
            proc_cpu_ms=sp.attrs.get("proc_cpu_ms"))
        out.append(view)
    if not any("order" in p for p in out):
        return None
    return out


def starved_ms(ps: List[Dict[str, Any]]) -> float:
    """All the passes' starved milliseconds, every phase."""
    return sum(sum(p["starved_ms"].values()) for p in ps)


def stalls(ps: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """The passes that dispatched or fetched a step and whose own time
    (the pass less its prefill and compact children, device work of
    another program) is over ``SLOW_PASS_MULTIPLE`` times the median
    of the same over ``ps``: each with ``over_ms`` (the time past that
    line), ``stall`` (the program's ``stall_word`` over the whole pass:
    ``on_cpu``, ``contended``, ``blocked``) and ``held``, the phase
    that took most of it."""
    from mmlspark_tpu.serving import decode
    stepped = [p for p in ps if "dispatch" in p["phases_ms"]
               or "fetch" in p["phases_ms"]]
    if not stepped:
        return []
    own = sorted(p["ms"] - p["prefill_ms"] - p["compact_ms"]
                 for p in stepped)
    line = decode.SLOW_PASS_MULTIPLE * own[len(own) // 2]
    out = []
    for p in stepped:
        over = p["ms"] - p["prefill_ms"] - p["compact_ms"] - line
        if over > 0:
            rest = {k: v for k, v in p["phases_ms"].items()
                    if k not in ("prefill", "compact")}
            rest["admit"] = rest.get("admit", 0.0) - p["prefill_ms"]
            out.append(dict(
                p, over_ms=over, held=max(rest, key=rest.get),
                stall=decode.stall_word(p["ms"], p["cpu_ms"],
                                        p["proc_cpu_ms"])))
    return out
