#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

run from the root of a checkout on a machine that holds the chips the
cell asks for. Everything that belongs to one cell is found by name, in
a file of its own (see ``benchmark/README.md``):

    BENCHMARK.json                       the cell, its metrics
    benchmark/configs/<config>.json      the sizes, as run
    benchmark/traffic/<traffic>.json     the traffic mix; names its driver
    benchmark/drivers/<driver>.py        build, warm, measure, check
    benchmark/limits/<cell>.json         the limits `correct` holds
    benchmark/layer_metrics/<metric>.py  one reader per per-layer metric

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``compared``: every number `correct` compared,
beside its limit. Without a TPU whose ``device_kind`` is in
``peaks.json``, or with fewer chips than the cell asks for, it exits
non-zero and prints no result. ``--rehearse-cpu`` runs the cells of
``benchmark/rehearsal.json`` (tiny sizes, named engines) on the CPU for
the sandbox, and labels its output ``cpu``.

Never part of a benchmark run, and read on the chip when a limit is set
(`PERF.md` records from what): ``--control`` adds, after the window, the
reference put in the program's place one precision below what the
configuration states (``control`` in the configuration file), and for a
training cell the half-batch fault carried by the reference, each with
the verdict of the driver's own comparison; ``--fault <name>`` breaks
the timed path itself (``drivers/<driver>.py`` ``FAULTS``); ``--seeds
a,b,c`` reads several seeds in one process, one result line each.
"""

from __future__ import annotations

import time

# set-up is counted from here, less the wait for the devices
T_START = time.perf_counter()

import argparse                   # noqa: E402
import dataclasses                # noqa: E402
import importlib.util             # noqa: E402
import json                       # noqa: E402
import os                         # noqa: E402
import sys                        # noqa: E402
from typing import Any, Dict, List, Optional   # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (ROOT, HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)


@dataclasses.dataclass
class Context:
    """What a driver is given."""

    cell: Dict[str, Any]
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, float]
    model: Any                     # reference.Model
    seed: int
    seconds: float
    trace: bool
    t_start: float
    peak: Optional[Dict[str, float]]
    on_chip: bool
    trace_dir: str
    root: str
    control: bool = False          # --control: also read the control
    fault: Optional[str] = None    # --fault / tests: break the path
    # seconds since t_start at which each phase of set-up ended; the
    # drivers add theirs and print all of them as facts.setup_phases_s
    phases: Dict[str, float] = dataclasses.field(default_factory=dict)
    # importing JAX and waiting for the TPU runtime to hand over the
    # devices: not counted in setup_s (t_start is moved past it)
    device_wait_s: float = 0.0

    def mark(self, name: str) -> None:
        self.phases[name] = time.perf_counter() - self.t_start


def load_module(kind: str, name: str):
    path = os.path.join(HERE, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader_of(metric: str):
    """A per-layer metric's reader: ``layer_metrics/<metric>.py``, or,
    where several metrics share one reader, the file named by what
    stands before the metric's last dot (``device_idle_share.py`` reads
    ``device_idle_share.train`` and ``device_idle_share.serve``)."""
    for name in (metric, metric.rpartition(".")[0]):
        if name and os.path.exists(
                os.path.join(HERE, "layer_metrics", f"{name}.py")):
            return load_module("layer_metrics", name)
    raise FileNotFoundError(f"no reader for the per-layer metric "
                            f"{metric!r} under benchmark/layer_metrics/")


def load_json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_benchmark(rehearse: bool) -> Dict[str, Any]:
    bench = load_json(ROOT, "BENCHMARK.json")
    if rehearse:
        # the rehearsal's cells are not cells of the benchmark: they
        # borrow its metrics and bring their own configs and traffic
        reh = load_json(HERE, "rehearsal.json")
        bench = dict(bench, configs=reh["configs"],
                     workloads=reh["workloads"])
        stands_for = reh["stands_for"]
        for group in ("end_to_end", "per_layer"):
            bench[group] = [
                dict(mt, workloads=[w for w, real in stands_for.items()
                                    if real in mt["workloads"]])
                if "workloads" in mt else mt for mt in bench[group]]
    return bench


def metrics_of(bench: Dict[str, Any], group: str, cell: str
               ) -> List[Dict[str, Any]]:
    return [mt for mt in bench[group]
            if "workloads" not in mt or cell in mt["workloads"]]


def cell_of(bench: Dict[str, Any], workload: str) -> Dict[str, Any]:
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        print(f"run.py: no cell {workload!r}; have {sorted(cells)}",
              file=sys.stderr)
        raise SystemExit(2)
    return cells[workload]


def build_context(bench: Dict[str, Any], workload: str, seed: int,
                  seconds: float, trace: bool, on_chip: bool,
                  peak: Optional[Dict[str, float]], t_start: float,
                  **extra) -> Context:
    import reference
    import traffic as traffic_mod
    cell = cell_of(bench, workload)
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == cell["config"])
    config = load_json(ROOT, cfg_entry["file"])
    return Context(
        cell=cell, config=config,
        traffic=traffic_mod.load(cell["traffic"]),
        limits=load_json(HERE, "limits", f"{workload}.json"),
        model=reference.Model.from_config(config), seed=int(seed),
        seconds=float(seconds), trace=bool(trace), t_start=t_start,
        peak=peak, on_chip=on_chip,
        trace_dir=os.path.join(ROOT, ".bench_trace", workload), root=ROOT,
        **extra)


def devices_or_exit(chips: int, rehearse: bool):
    """The devices this run may use, or exit non-zero saying what was
    found. Nothing goes to standard output on refusal.

    The seconds spent in here are the TPU runtime's start-up, which no
    file of the repo can shorten or lengthen and which reads 10 s or
    13 s as the machine pleases (PERF.md, section 2): ``setup_s`` leaves
    them out, and every line's ``facts.device_wait_s`` holds them."""
    try:
        import jax
        devices = jax.devices()
    except Exception as e:  # noqa: BLE001 — no backend at all
        print(f"run.py: JAX found no device: {type(e).__name__}: {e}",
              file=sys.stderr)
        raise SystemExit(2)
    d0 = devices[0]
    want = "cpu" if rehearse else "tpu"
    if d0.platform != want:
        print(f"run.py: found platform={d0.platform} "
              f"(device_kind={d0.device_kind!r}, {len(devices)} device(s)) "
              f"but this invocation measures on {want} only and does not "
              f"fall back", file=sys.stderr)
        raise SystemExit(2)
    if len(devices) < chips:
        print(f"run.py: the cell asks for {chips} chip(s), JAX found "
              f"{len(devices)}", file=sys.stderr)
        raise SystemExit(2)
    return devices


def result_line(bench: Dict[str, Any], ctx: Context, out: Dict[str, Any],
                devices) -> Dict[str, Any]:
    """The contract's object from what the driver returned."""
    name = ctx.cell["name"]
    metrics: Dict[str, Dict[str, Any]] = {}
    if not ctx.trace:
        for mt in metrics_of(bench, "end_to_end", name):
            if mt["name"] not in out["end_to_end"]:
                raise RuntimeError(f"the driver reported no {mt['name']}")
            metrics[mt["name"]] = {"value": out["end_to_end"][mt["name"]],
                                   "unit": mt["unit"]}
    else:
        for mt in metrics_of(bench, "per_layer", name):
            value = reader_of(mt["name"]).read(
                out.get("reduced"), out.get("counters", {}), ctx)
            if value is not None:
                metrics[mt["name"]] = {"value": value, "unit": mt["unit"]}
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    line: Dict[str, Any] = {
        "correct": bool(out["correct"]), "attempted": out["attempted"],
        "failed": out["failed"], "metrics": metrics, "device": device}
    red = out.get("reduced")
    if ctx.trace and red is not None:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        line["breakdown"] = {"device_ops": red["device_ops"],
                             "idle_gaps": red["idle_gaps"]}
    if not ctx.on_chip:
        line["rehearsal"] = "cpu"
    line["facts"] = dict(out.get("facts", {}),
                         device_wait_s=ctx.device_wait_s)
    # --control: the verdict on the control and on each fault the
    # reference carries, from the driver's own comparison
    for key in ("control", "half_batch_in_reference"):
        if key in out:
            line[key] = out[key]
    if ctx.fault:
        line["fault"] = ctx.fault
    line["compared"] = out["compared"]
    return line


def print_compared(compared: Dict[str, Any]) -> None:
    for k, v in compared.items():
        print(f"compared {k}: {v}", file=sys.stderr)
    sys.stderr.flush()


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny cells of rehearsal.json on the CPU "
                         "(control flow only; labelled cpu)")
    ap.add_argument("--control", action="store_true",
                    help="never in a benchmark run: after the window, "
                         "also put the reference in the program's place "
                         "one precision below what the configuration "
                         "states (and the faults a reference can carry) "
                         "and print the verdict on each")
    ap.add_argument("--fault", default=None,
                    help="never in a benchmark run: break the timed "
                         "path (the driver's FAULTS) and see `correct` "
                         "come out false")
    ap.add_argument("--seeds", default=None,
                    help="with --control or --fault: several seeds in "
                         "this one process, one result line each")
    args = ap.parse_args(argv)
    if (args.seed is None) == (args.seeds is None):
        ap.error("give --seed, or with --control / --fault --seeds")
    if args.seeds and not (args.control or args.fault):
        ap.error("--seeds is for --control and --fault only")
    seeds = ([args.seed] if args.seeds is None
             else [int(x) for x in args.seeds.split(",")])

    bench = load_benchmark(args.rehearse_cpu)
    t_wait = time.perf_counter()
    devices = devices_or_exit(int(cell_of(bench, args.workload)["chips"]),
                              args.rehearse_cpu)
    device_wait_s = time.perf_counter() - t_wait
    import flops
    on_chip = not args.rehearse_cpu
    # unknown device_kind: LookupError, non-zero, no result
    peak = flops.peaks(devices[0].device_kind) if on_chip else None

    # past this line the program is needed: in a directory that holds
    # only BENCHMARK.json and benchmark/ the import raises
    import mmlspark_tpu  # noqa: F401 — places the compile cache

    for i, seed in enumerate(seeds):
        first = i == 0
        ctx = build_context(bench, args.workload, seed, args.seconds,
                            bool(args.trace), on_chip, peak,
                            T_START + device_wait_s if first
                            else time.perf_counter(),
                            control=args.control, fault=args.fault,
                            device_wait_s=device_wait_s if first else 0.0)
        ctx.mark("harness")
        driver = load_module("drivers", ctx.traffic["driver"])
        if args.fault and args.fault not in driver.FAULTS:
            raise SystemExit(f"{ctx.traffic['driver']} has no fault "
                             f"{args.fault!r}; it has {driver.FAULTS}")
        line = result_line(bench, ctx, driver.run(ctx), devices)
        print_compared(line["compared"])
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
