"""From a profiler trace to numbers: the one reduction every PR uses.

``load(xplane_path)`` reads JAX's ``.xplane.pb`` with nothing but JAX
and keeps, per device plane, the op events (line ``XLA Ops``), the
program executions (line ``XLA Modules``) and the benchmark's own host
annotations (``bench.*``). ``reduce(trace)`` gives the busy union, the
traced window, per-name device time and the idle gaps. The readers in
``layer_metrics/`` work on that reduced form, which is plain data: a
small recorded one is kept in ``tests/data`` and checks this file.
"""

from __future__ import annotations

import glob
import os
import re
import shutil
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")

Event = Tuple[str, int, int]          # name, start_ns, duration_ns


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def short_name(name: str) -> str:
    """An op event is named by its whole HLO instruction,
    ``%fusion.12 = f32[...] fusion(...)``: keep ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def kind(name: str) -> str:
    """``jvp_jit__flash_call__.25`` -> ``jvp_jit__flash_call__``: the
    instruction's name without its number, which for a Pallas call is
    the jitted kernel's name and for a fusion XLA's summary of it."""
    return re.sub(r"(\.\d+)+$", "", name)


def profile_options():
    """The profiler's options for a traced window: device and host
    annotations, no Python tracer (it slows the host it measures)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def load(xplane_path: str) -> Dict[str, Any]:
    """``{"devices": {ordinal: {"ops": [Event], "modules": [Event],
    "labels": {op name: label}}}, "host": [Event], "lines": {...}}``.
    An op is named by its HLO instruction's name; its label is the head
    of the instruction's text (an op event carries nothing else but its
    timing), kept once per name."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane_path)
    devices: Dict[int, Dict[str, Any]] = {}
    host: List[Event] = []
    lines: Dict[str, Dict[str, int]] = {}
    for plane in data.planes:
        dev = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            n = 0
            if dev and line.name in (OPS_LINE, MODULES_LINE):
                d = devices.setdefault(int(dev.group(1)), {
                    "ops": [], "modules": [], "labels": {}})
                into = d["ops"] if line.name == OPS_LINE else d["modules"]
                ops = line.name == OPS_LINE
                for ev in line.events:
                    n += 1
                    name = short_name(ev.name) if ops else ev.name
                    into.append((name, int(ev.start_ns),
                                 int(ev.duration_ns)))
                    if ops and name not in d["labels"]:
                        d["labels"][name] = ev.name[:200]
            else:
                for ev in line.events:
                    n += 1
                    if ev.name.startswith("bench."):
                        host.append((ev.name, int(ev.start_ns),
                                     int(ev.duration_ns)))
            lines.setdefault(plane.name, {})[line.name] = n
    for d in devices.values():
        d["ops"].sort(key=lambda e: e[1])
        d["modules"].sort(key=lambda e: e[1])
    host.sort(key=lambda e: e[1])
    return {"devices": devices, "host": host, "lines": lines}


def union_ns(events: Iterable[Event]) -> int:
    """Length of the union of the events' intervals (sorted by start)."""
    total, end = 0, None
    for _, s, d in events:
        e = s + d
        if end is None or s > end:
            total += d
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def extent_ns(events: Sequence[Event]) -> Tuple[int, int]:
    return (min(e[1] for e in events), max(e[1] + e[2] for e in events))


def time_by_name(events: Iterable[Event]) -> Dict[str, Tuple[int, int]]:
    """``{name: (total ns, calls)}``."""
    out: Dict[str, List[int]] = {}
    for name, _, d in events:
        t = out.setdefault(name, [0, 0])
        t[0] += d
        t[1] += 1
    return {k: (v[0], v[1]) for k, v in out.items()}


def gaps(events: Sequence[Event]) -> List[Tuple[int, int, str, str]]:
    """Idle gaps between device events: ``(length ns, start ns, the op
    before, the op after)``, longest first."""
    out, end, last = [], None, ""
    for name, s, d in events:
        if end is not None and s > end:
            out.append((s - end, end, last, name))
        if end is None or s + d > end:
            end, last = s + d, name
    out.sort(reverse=True)
    return out


def _host_at(host: Sequence[Event], t0: int, t1: int) -> Optional[str]:
    """The benchmark's own annotation that covers most of ``[t0, t1]``."""
    best, best_ns = None, 0
    for name, s, d in host:
        o = min(t1, s + d) - max(t0, s)
        if o > best_ns:
            best, best_ns = name, o
    return best


def module_name(name: str) -> str:
    """``jit_step(123456789)`` -> ``jit_step``."""
    return re.sub(r"\(\d+\)$", "", name)


def segments(events: Sequence[Event], hole_ns: Optional[int]
             ) -> List[List[Event]]:
    """The events split where nothing ran for longer than ``hole_ns``:
    the device's trace buffer is finite, and where it overflowed the
    trace has a hole that is no idleness (``None``: never split)."""
    if hole_ns is None or not events:
        return [list(events)]
    out, cur, end = [], [], None
    for ev in events:
        if end is not None and ev[1] - end > hole_ns:
            out.append(cur)
            cur = []
        cur.append(ev)
        end = ev[1] + ev[2] if end is None else max(end, ev[1] + ev[2])
    out.append(cur)
    return out


def _top_kinds(ops: Dict[str, Tuple[int, int]]) -> List[List[Any]]:
    kinds: Dict[str, int] = {}
    for n, (t, _) in ops.items():
        kinds[kind(n)] = kinds.get(kind(n), 0) + t
    by_time = sorted(kinds.items(), key=lambda kv: -kv[1])
    return [[n, t / 1e9] for n, t in by_time[:10]]


def reduce(trace: Dict[str, Any], hole_s: Optional[float] = None
           ) -> Dict[str, Any]:
    """Busy union and traced window, averaged over the devices that ran
    anything; per-name op and program time; the longest idle gaps, named
    by the benchmark's own annotation that covers them or else by the
    ops on either side. With ``hole_s`` the window is the sum of the
    trace's covered segments (see :func:`segments`)."""
    devs = {k: d for k, d in trace["devices"].items() if d["ops"]}
    if not devs:
        raise ValueError("the trace holds no device operation")
    hole_ns = None if hole_s is None else int(hole_s * 1e9)
    busy, window, holes = [], [], 0
    for d in devs.values():
        segs = segments(d["ops"], hole_ns)
        holes += len(segs) - 1
        busy.append(sum(union_ns(sg) for sg in segs) / 1e9)
        window.append(sum(extent_ns(sg)[1] - extent_ns(sg)[0]
                          for sg in segs) / 1e9)
    first = devs[min(devs)]
    ops = time_by_name(first["ops"])
    mods = time_by_name((module_name(n), s, d)
                        for n, s, d in first["modules"])
    found = sorted((g for sg in segments(first["ops"], hole_ns)
                    for g in gaps(sg)), reverse=True)[:10]
    top_gaps = []
    for length, start, before, after in found:
        host = _host_at(trace["host"], start, start + length)
        top_gaps.append([host or f"{kind(before)}->{kind(after)}",
                         length / 1e9])
    return {"busy_s": sum(busy) / len(busy),
            "window_s": sum(window) / len(window),
            "n_devices": len(devs), "holes": holes,
            "ops": ops, "modules": mods,
            "device_ops": _top_kinds(ops), "idle_gaps": top_gaps}


def combine(parts: Sequence[Dict[str, Any]]) -> Dict[str, Any]:
    """Several traced slices of one window as one reduced trace."""
    def merged(key: str) -> Dict[str, Tuple[int, int]]:
        out: Dict[str, Tuple[int, int]] = {}
        for p in parts:
            for n, (t, c) in p[key].items():
                t0, c0 = out.get(n, (0, 0))
                out[n] = (t0 + t, c0 + c)
        return out
    ops = merged("ops")
    return {"busy_s": sum(p["busy_s"] for p in parts),
            "window_s": sum(p["window_s"] for p in parts),
            "n_devices": parts[0]["n_devices"],
            "holes": sum(p["holes"] for p in parts),
            "ops": ops, "modules": merged("modules"),
            "device_ops": _top_kinds(ops),
            "idle_gaps": sorted((g for p in parts for g in p["idle_gaps"]),
                                key=lambda g: -g[1])[:10]}


def read_and_remove(log_dir: str, on_chip: bool,
                    hole_s: Optional[float] = None
                    ) -> Optional[Dict[str, Any]]:
    """Load and reduce the trace under ``log_dir``, then delete it (a
    run writes little to disk); ``BENCH_KEEP_TRACE=1`` keeps it for the
    look by hand. Off the chip a trace holds no device plane: nothing."""
    try:
        trace = load(find_xplane(log_dir))
    finally:
        if os.environ.get("BENCH_KEEP_TRACE") != "1":
            shutil.rmtree(log_dir, ignore_errors=True)
    if not on_chip and not any(d["ops"] for d in trace["devices"].values()):
        return None
    return reduce(trace, hole_s)


def op_seconds(reduced: Dict[str, Any], pattern: str) -> Tuple[float, int]:
    """Device seconds and calls of the ops whose NAME matches."""
    rx = re.compile(pattern)
    total, calls = 0, 0
    for name, (t, n) in reduced["ops"].items():
        if rx.search(name):
            total += t
            calls += n
    return total / 1e9, calls


def module_seconds(reduced: Dict[str, Any], pattern: str
                   ) -> Tuple[float, int]:
    """Device seconds and executions of the programs whose name matches."""
    rx = re.compile(pattern)
    total, calls = 0, 0
    for name, (t, n) in reduced["modules"].items():
        if rx.search(name):
            total += t
            calls += n
    return total / 1e9, calls


def dump(trace: Dict[str, Any], top: int = 40,
         hole_s: Optional[float] = None) -> str:
    """What a trace holds, for the one look by hand."""
    out = ["lines (events):"]
    for plane, ls in trace["lines"].items():
        for line, n in ls.items():
            out.append(f"  {plane} | {line}: {n}")
    for k, d in trace["devices"].items():
        red = reduce({"devices": {k: d}, "host": trace["host"]}, hole_s) \
            if d["ops"] else None
        if red is None:
            continue
        out.append(f"device {k}: busy {red['busy_s']:.4f}s of "
                   f"{red['window_s']:.4f}s, {red['holes']} holes; "
                   f"{len(d['ops'])} op events over "
                   f"{(extent_ns(d['ops'])[1] - extent_ns(d['ops'])[0]) / 1e9:.3f}s")
        out.append(" kinds:")
        for n, t in red["device_ops"]:
            out.append(f"  {t:10.6f}s {n}")
        out.append(" programs:")
        for n, (t, c) in sorted(red["modules"].items(),
                                key=lambda kv: -kv[1][0])[:top]:
            out.append(f"  {t / 1e9:10.6f}s {c:6d}x {n}")
        out.append(" ops:")
        for n, (t, c) in sorted(red["ops"].items(),
                                key=lambda kv: -kv[1][0])[:top]:
            out.append(f"  {t / 1e9:10.6f}s {c:6d}x "
                       f"{d['labels'].get(n, n)[:260]}")
        out.append(" gaps:")
        for what, s in red["idle_gaps"]:
            out.append(f"  {s:10.6f}s {what[:200]}")
    out.append(f"host annotations: {len(trace['host'])}")
    return "\n".join(out)


def record(trace: Dict[str, Any], seconds: float = 1.4,
           skip: float = 0.2) -> Dict[str, Any]:
    """A short cut of a trace as plain data (for ``tests/data``): the
    events of ``seconds`` seconds, ``skip`` seconds into the trace."""
    out: Dict[str, Any] = {"devices": {}, "host": [], "lines": {}}
    for k, d in trace["devices"].items():
        if not d["ops"]:
            continue
        t0 = extent_ns(d["ops"])[0] + int(skip * 1e9)
        t1 = t0 + int(seconds * 1e9)
        cut = lambda evs: [list(e) for e in evs          # noqa: E731
                           if e[1] >= t0 and e[1] + e[2] <= t1]
        ops = cut(d["ops"])
        out["devices"][str(k)] = {
            "ops": ops, "modules": cut(d["modules"]),
            "labels": {n: d["labels"][n][:80] for n in {e[0] for e in ops}}}
        out["host"] = [list(e) for e in trace["host"]
                       if e[1] >= t0 and e[1] + e[2] <= t1]
    return out


def from_record(rec: Dict[str, Any]) -> Dict[str, Any]:
    return {"devices": {int(k): {"ops": [tuple(e) for e in d["ops"]],
                                 "modules": [tuple(e) for e in d["modules"]],
                                 "labels": d["labels"]}
                        for k, d in rec["devices"].items()},
            "host": [tuple(e) for e in rec["host"]], "lines": {}}


if __name__ == "__main__":
    import argparse
    import gzip
    import json
    ap = argparse.ArgumentParser(description="look at a trace by hand")
    ap.add_argument("log_dir")
    ap.add_argument("--dump", help="write the listing here")
    ap.add_argument("--record", help="write a short cut (.json.gz) here")
    ap.add_argument("--hole-s", type=float, default=None)
    a = ap.parse_args()
    tr = load(find_xplane(a.log_dir))
    text = dump(tr, hole_s=a.hole_s)
    if a.dump:
        with open(a.dump, "w") as f:
            f.write(text + "\n")
    else:
        print(text)
    if a.record:
        with gzip.open(a.record, "wt") as f:
            json.dump(record(tr), f)
