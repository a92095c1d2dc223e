"""Profiling hooks: stage timing + device traces.

The reference's observability is wall-clock stage timing (`Timer` stage,
`pipeline-stages/Timer.scala:14-90`; suite timing in `TestBase.scala`).
The TPU build keeps that parity (the ``Timer`` stage in
``stages/basic.py``) and adds what the platform does natively: XLA
device traces viewable in TensorBoard/Perfetto via the jax profiler.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Callable, Dict, Iterator, Optional


@contextlib.contextmanager
def device_trace(log_dir: str) -> Iterator[None]:
    """Capture a jax profiler trace (TensorBoard/Perfetto) around a block::

        with device_trace("/tmp/trace"):
            model.transform(df)
    """
    import jax
    with jax.profiler.trace(log_dir):
        yield


_SINK = threading.local()        # .spans: the list its thread's owner reads
_TRACE_ANNOTATION = None


def _trace_annotation():
    # jax is imported on first use: this module stays importable (and
    # StageTimings usable) on hosts that never touch a device
    global _TRACE_ANNOTATION
    if _TRACE_ANNOTATION is None:
        from jax.profiler import TraceAnnotation
        _TRACE_ANNOTATION = TraceAnnotation
    return _TRACE_ANNOTATION


class span:
    """One named unit of host work, on the profiler's clock and ours.

    ``with span("decode.emit", slot=3) as sp:`` opens a
    ``jax.profiler.TraceAnnotation(name, **attrs)`` while a profiler
    session runs (``jax.profiler.trace``, ``POST /profile``, the
    benchmark's ``--trace 1``), so the span lies in the device trace
    beside the ops it waited for, on that trace's clock; with no session
    the annotation costs one atomic check. Either way it reads
    ``time.perf_counter_ns()`` on entry and on exit and appends ``(name,
    t0_ns, t1_ns, attrs)`` to the list its thread's owner opened with
    :func:`collect`; outside any owner the tuple is dropped (the times
    stay on ``sp.t0``/``sp.t1``). ``sp.attrs`` may be replaced inside
    the block with what is only known at its end. No log line, no lock.

    ``time.perf_counter`` and ``time.monotonic``, which
    :class:`~mmlspark_tpu.core.tracing.Tracer` reads, are the same clock
    on Linux (``CLOCK_MONOTONIC``): a span's nanoseconds times 1e-9 are
    seconds on the tracer's clock, and the serving loop hands them over
    as such.
    """

    __slots__ = ("name", "attrs", "t0", "t1", "_ann")

    def __init__(self, name: str, **attrs):
        self.name = name
        self.attrs = attrs or None
        self.t0 = self.t1 = 0
        self._ann = None

    def __enter__(self) -> "span":
        ann = _TRACE_ANNOTATION or _trace_annotation()
        if ann.is_enabled():
            self._ann = ann(self.name, **(self.attrs or {}))
            self._ann.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.t1 = time.perf_counter_ns()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
            self._ann = None
        spans = getattr(_SINK, "spans", None)
        if spans is not None:
            spans.append((self.name, self.t0, self.t1, self.attrs))
        return False

    @property
    def seconds(self) -> float:
        return (self.t1 - self.t0) * 1e-9


class collect:
    """Own the spans this thread closes inside the block: ``with
    collect() as spans`` yields the list every :class:`span` exit on
    this thread appends to, whatever module opened it (the decode loop
    owns a pass this way, and the decoder's dispatch and fetch land in
    it with no argument threaded through). Owners nest; the outer one
    is restored on exit."""

    __slots__ = ("spans", "_outer")

    def __enter__(self) -> list:
        self._outer = getattr(_SINK, "spans", None)
        self.spans = _SINK.spans = []
        return self.spans

    def __exit__(self, exc_type, exc, tb) -> bool:
        _SINK.spans = self._outer
        return False


class StageTimings:
    """Thread-safe per-stage wall-clock accumulator for hot loops.

    Where :class:`span` records one interval, this aggregates millions:
    each ``span(name)`` adds one sample to the named stage's running
    count/total, and :meth:`snapshot` returns a JSON-able summary —
    the backing store for the serving data plane's per-stage timings in
    ``GET /stats``. Pure python (no jax import) so it costs nothing on
    hosts that never touch a device, and cheap enough (~1 us/span) to
    leave on in production.

    Since the unified-telemetry work this is a thin view over a
    :class:`mmlspark_tpu.core.telemetry.MetricsRegistry` histogram (one
    child per stage name, millisecond log-scale buckets): the SAME
    samples back both the ``GET /stats`` snapshot and the Prometheus
    ``GET /metrics`` exposition. Pass ``registry`` to land the spans in
    a shared registry (the serving plane passes its per-server one);
    the default is a private registry, preserving the standalone
    behavior.
    """

    def __init__(self, clock: Callable[[], float] = time.perf_counter,
                 registry=None, metric: str = "stage_duration_ms"):
        from mmlspark_tpu.core.telemetry import MetricsRegistry
        self._clock = clock
        self.registry = registry if registry is not None \
            else MetricsRegistry()
        self._hist = self.registry.histogram(
            metric, "Per-stage wall-clock spans.", labels=("stage",))
        self._children: Dict[str, object] = {}   # stage -> histogram child

    def _child(self, name: str):
        child = self._children.get(name)     # atomic under the GIL
        if child is None:
            child = self._children[name] = self._hist.labels(name)
        return child

    @contextlib.contextmanager
    def span(self, name: str) -> Iterator[None]:
        t0 = self._clock()
        try:
            yield
        finally:
            self._child(name).observe((self._clock() - t0) * 1000.0)

    def snapshot(self) -> Dict[str, Dict[str, float]]:
        """``{stage: {count, total_ms, mean_ms, last_ms, max_ms}}``,
        JSON-able."""
        out: Dict[str, Dict[str, float]] = {}
        for key, child in self._hist.children():
            s = child.stats()
            n = s["count"]
            out[key[0]] = {
                "count": n,
                "total_ms": round(s["sum"], 3),
                "mean_ms": round(s["sum"] / n, 4) if n else 0.0,
                "last_ms": round(s["last"], 3),
                "max_ms": round(s["max"], 3),
            }
        return out

    def reset(self) -> None:
        """Zero every stage's accumulators (chaos drills diff snapshots
        across restarts; a long-soak harness resets between phases)."""
        for _, child in self._hist.children():
            child.reset()


# -- process vitals (exported via GET /stats so chaos drills can spot
# leaks and confirm restarts) ------------------------------------------------

_PROCESS_START_MONO = time.monotonic()


def process_uptime_s() -> float:
    """Seconds since this module first loaded — effectively process
    uptime; a restarted worker's counter visibly resets."""
    return time.monotonic() - _PROCESS_START_MONO


def process_rss_bytes() -> Optional[int]:
    """Current resident set size. Linux reads ``/proc/self/status``
    (current RSS); elsewhere falls back to ``ru_maxrss`` (PEAK RSS —
    still monotone evidence for leak spotting) or None."""
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1]) * 1024
    except (OSError, ValueError, IndexError):
        pass
    try:
        import resource
        import sys
        peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        return int(peak if sys.platform == "darwin" else peak * 1024)
    except Exception:  # noqa: BLE001 — vitals are best-effort
        return None


# ---------------------------------------------------------------------------
# On-demand device profiling + always-on compute accounting (ISSUE 18)
# ---------------------------------------------------------------------------

def device_memory_stats() -> Dict[str, int]:
    """HBM accounting straight from the runtime allocator of local
    device 0: live bytes, the high-water mark since process start, and
    the allocator's limit. Empty dict on backends that do not expose
    ``memory_stats`` (CPU) — callers gauge 0s, they never fail."""
    try:
        import jax
        stats = jax.local_devices()[0].memory_stats()
    except Exception:  # noqa: BLE001 — vitals are best-effort
        return {}
    if not stats:
        return {}
    return {"bytes_in_use": int(stats.get("bytes_in_use", 0)),
            "peak_bytes": int(stats.get("peak_bytes_in_use", 0)),
            "bytes_limit": int(stats.get("bytes_limit", 0))}


class ProfilerBusy(RuntimeError):
    """A capture window is already running (one at a time, by design:
    concurrent jax profiler sessions abort the process)."""


class DeviceProfiler:
    """Guarded one-at-a-time ``jax.profiler`` capture windows.

    ``start_window`` kicks off a background daemon thread that opens a
    trace, sleeps the requested window, and closes it — the caller
    (a ``POST /profile`` handler on the event loop) returns
    immediately with the target directory. A second request while a
    window is open raises :class:`ProfilerBusy` (the route 409s).
    Output loads in TensorBoard / Perfetto / XProf.
    """

    def __init__(self, base_dir: Optional[str] = None):
        import os
        import tempfile
        import threading
        self.base_dir = base_dir or os.path.join(
            tempfile.gettempdir(), "mmlspark_tpu_profiles")
        self._lock = threading.Lock()
        self._active: Optional[Dict[str, object]] = None
        self.last: Optional[Dict[str, object]] = None
        self.n_captures = 0
        self.n_errors = 0

    def start_window(self, duration_s: float = 1.0,
                     log_dir: Optional[str] = None) -> Dict[str, object]:
        """Begin one capture window; returns ``{log_dir, duration_s,
        started_unix}``. Raises :class:`ProfilerBusy` while a prior
        window is open."""
        import os
        import threading
        duration_s = float(duration_s)
        with self._lock:
            if self._active is not None:
                raise ProfilerBusy(
                    f"capture already running: {self._active}")
            if log_dir is None:
                log_dir = os.path.join(
                    self.base_dir,
                    time.strftime("%Y%m%d-%H%M%S"))
            info: Dict[str, object] = {
                "log_dir": log_dir, "duration_s": duration_s,
                "started_unix": time.time()}
            self._active = info
        t = threading.Thread(target=self._run, args=(info,),
                             daemon=True, name="device-profile")
        t.start()
        return dict(info)

    def _run(self, info: Dict[str, object]) -> None:
        try:
            import jax
            jax.profiler.start_trace(str(info["log_dir"]))
            try:
                time.sleep(float(info["duration_s"]))  # the window
            finally:
                jax.profiler.stop_trace()
            info["ok"] = True
            with self._lock:
                self.n_captures += 1
        except Exception as exc:  # noqa: BLE001 — report, don't die
            info["ok"] = False
            info["error"] = f"{type(exc).__name__}: {exc}"
            with self._lock:
                self.n_errors += 1
            from mmlspark_tpu.core.logs import get_logger
            get_logger("profiling").warning(
                "device trace capture failed", exc_info=True)
        finally:
            info["finished_unix"] = time.time()
            with self._lock:
                self.last = dict(info)
                self._active = None

    @property
    def busy(self) -> bool:
        with self._lock:
            return self._active is not None

    def status(self) -> Dict[str, object]:
        with self._lock:
            return {"busy": self._active is not None,
                    "active": dict(self._active) if self._active else None,
                    "last": dict(self.last) if self.last else None,
                    "n_captures": self.n_captures,
                    "n_errors": self.n_errors,
                    "base_dir": self.base_dir}


class CompileLedger:
    """Bounded ring of compile events (a new dispatch shape = a jit
    retrace). One ``note()`` per retrace — by construction off the
    steady-state hot path, since steady state means zero retraces."""

    def __init__(self, cap: int = 64):
        import collections
        import threading
        self._events: "collections.deque" = collections.deque(
            maxlen=int(cap))
        self._lock = threading.Lock()
        self.n_events = 0

    def note(self, kind: str, shape: str, duration_ms: float,
             **extra: object) -> None:
        ev = {"kind": kind, "shape": shape,
              "duration_ms": round(float(duration_ms), 3),
              "at_unix": round(time.time(), 3)}
        ev.update(extra)
        with self._lock:
            self._events.append(ev)
            self.n_events += 1

    def events(self) -> list:
        with self._lock:
            return list(self._events)

    def snapshot(self) -> Dict[str, object]:
        with self._lock:
            return {"n_events": self.n_events,
                    "events": list(self._events)}


class MfuMeter:
    """Always-on per-bucket MFU estimation.

    ``note(bucket, seconds, flops)`` accumulates dispatch wall-clock
    per shape bucket and, when the model exposes a flops count for the
    bucket (``dispatch_flops(df)`` hook or ``cost_analysis``), keeps an
    EWMA of achieved flops/s and its ratio to the chip's peak. Without
    flops it still reports per-bucket seconds — the time side of the
    accounting is never conditional on the model cooperating.
    """

    def __init__(self, peak_tflops: Optional[float] = None,
                 alpha: float = 0.2):
        import threading
        self._lock = threading.Lock()
        self.alpha = float(alpha)
        self.peak_flops: Optional[float] = (
            peak_tflops * 1e12 if peak_tflops is not None else None)
        self.device_kind: Optional[str] = None
        if peak_tflops is None:
            # the chip's published peak (None on a CPU host: flops/s
            # without a ratio); an accelerator kind the table does not
            # know raises — no MFU against a guessed denominator
            import jax
            from mmlspark_tpu.core.environment import device_peaks
            dev = jax.devices()[0]
            self.device_kind = dev.device_kind
            peaks = device_peaks(dev.device_kind, dev.platform)
            if peaks is not None:
                self.peak_flops = peaks["bf16_tflops"] * 1e12
        self._buckets: Dict[object, Dict[str, float]] = {}

    def note(self, bucket: object, seconds: float,
             flops: Optional[float] = None) -> None:
        with self._lock:
            row = self._buckets.get(bucket)
            if row is None:
                row = self._buckets[bucket] = {
                    "count": 0, "seconds": 0.0, "flops_per_s": None}
            row["count"] += 1
            row["seconds"] += float(seconds)
            if flops and seconds > 0:
                achieved = float(flops) / float(seconds)
                prev = row["flops_per_s"]
                row["flops_per_s"] = (
                    achieved if prev is None
                    else prev + self.alpha * (achieved - prev))

    def snapshot(self) -> Dict[str, object]:
        with self._lock:
            buckets = {}
            for bucket, row in self._buckets.items():
                out = {"count": int(row["count"]),
                       "seconds": round(row["seconds"], 4)}
                fps = row["flops_per_s"]
                if fps is not None:
                    out["tflops_per_s"] = round(fps / 1e12, 3)
                    if self.peak_flops:
                        out["mfu"] = round(fps / self.peak_flops, 4)
                buckets[str(bucket)] = out
            return {"device_kind": self.device_kind,
                    "peak_tflops": (round(self.peak_flops / 1e12, 1)
                                    if self.peak_flops else None),
                    "buckets": buckets}
