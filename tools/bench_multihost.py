#!/usr/bin/env python
"""Multi-device scaling + parity harness — the ``multihost_scaling_v1``
evidence (ISSUE 10).

One self-contained process that builds 1/2/4/8-device meshes (CPU
``--xla_force_host_platform_device_count`` simulation by default; the
same code runs unchanged on real chips) and measures the distributed
execution layer end to end:

* **A/B parity on fixed seeds** — a pjit data x tensor-parallel
  NNLearner fit must reproduce the single-device fit's scores, and the
  tensor-parallel decoder must emit the single-device greedy token
  sequence (``parity``).
* **Devices-vs-throughput curve** — a model-parallel-friendly
  (wide-MLP) train step compiled per mesh size, timed as one scanned
  device program with the long/short slope trick (``curve``). On CPU,
  ``--xla_cpu_multi_thread_eigen=false`` pins each virtual device to
  one worker thread so "devices" are the unit of parallelism — the
  honest simulation of fixed-compute chips.
* **Zero steady-state recompiles in tensor-parallel serving** — a live
  ``ServingServer`` dispatching a ``tensor_parallel=2`` model and a
  TP ``TransformerDecoder`` both hold their post-warmup compile
  counts flat under traffic (``serving``).
* **Sharded-checkpoint topology drill** — train state saved from a
  2x2 mesh restores bit-identically onto 4x1 and a single device,
  digest manifest verified (``checkpoint``).

Usage::

    python tools/bench_multihost.py --smoke     # CI gate: asserts, exits 1 on violation
    python tools/bench_multihost.py --json      # print the evidence JSON (bench.py consumes)
    python tools/bench_multihost.py --devices 8 # simulated device count
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _ensure_devices(n: int) -> None:
    """Must run before the jax backend initializes."""
    from mmlspark_tpu.parallel.topology import use_cpu_devices
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_cpu_multi_thread_eigen" not in flags:
        # one worker thread per virtual device: the devices, not the
        # shared eigen pool, are the unit of parallelism — otherwise a
        # "1-device" baseline silently uses every core and the curve
        # measures nothing
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_cpu_multi_thread_eigen=false").strip()
    # a virtual-CPU-device drill by design: the platform is assigned,
    # not defaulted — bench.py spawns this while it holds the chip
    use_cpu_devices(n)


# ---------------------------------------------------------------------------
# parity
# ---------------------------------------------------------------------------


def _tp_mesh_shape() -> dict:
    """The biggest data x model=2 mesh this host can build (the
    harness must degrade to 2 devices — and report, not crash, on 1)."""
    import jax
    n = len(jax.devices())
    if n >= 4:
        return {"data": 2, "model": 2}
    if n >= 2:
        return {"data": 1, "model": 2}
    return {"data": 1}


def parity_check(steps_epochs: int = 5) -> dict:
    """Sharded-vs-single-device A/B on fixed seeds."""
    import numpy as np
    from mmlspark_tpu.core.dataframe import DataFrame
    from mmlspark_tpu.models.trainer import NNLearner
    from mmlspark_tpu.models import transformer as T
    from mmlspark_tpu.serving.decode import TransformerDecoder
    from mmlspark_tpu.parallel import dist

    rng = np.random.default_rng(42)
    n = 256
    x = np.concatenate([rng.normal(-2.0, size=(n, 4)),
                        rng.normal(2.0, size=(n, 4))]).astype(np.float32)
    y = np.concatenate([np.zeros(n), np.ones(n)]).astype(np.int64)
    perm = rng.permutation(len(x))
    df = DataFrame({"features": x[perm], "label": y[perm]})
    common = dict(arch={"builder": "mlp", "hidden": [16], "num_outputs": 2},
                  optimizer="adam", learning_rate=0.01,
                  epochs=steps_epochs, batch_size=64, log_every=0, seed=3)
    m1 = NNLearner(mesh_shape={"data": 1}, **common).fit(df)
    m2 = NNLearner(mesh_shape=_tp_mesh_shape(), **common).fit(df)
    s1 = m1.transform(df)["scores"]
    s2 = m2.transform(df)["scores"]
    train_diff = float(np.abs(s1 - s2).max())

    cfg = T.TransformerConfig(vocab=128, d_model=32, n_heads=4, d_head=8,
                              d_ff=64, n_stages=1, layers_per_stage=2)
    params = T.init_params(cfg, seed=0)
    prompt = np.asarray([5, 9, 77, 3], np.int32)

    def greedy(dec, n_tokens=10):
        seq = [dec.prefill(0, prompt)]
        toks = np.zeros(dec.n_slots, np.int32)
        pos = np.zeros(dec.n_slots, np.int32)
        toks[0], pos[0] = seq[0], len(prompt)
        for _ in range(n_tokens):
            out = dec.step(toks, pos)
            seq.append(int(out[0]))
            toks[0] = out[0]
            pos[0] += 1
        return seq

    d1 = TransformerDecoder(params, cfg, n_slots=4, max_len=64)
    d1.warmup()
    mesh = dist.train_mesh(_tp_mesh_shape())
    d2 = TransformerDecoder(params, cfg, n_slots=4, max_len=64, mesh=mesh)
    base = d2.warmup()
    t1, t2 = greedy(d1), greedy(d2)
    return {
        "train_score_max_diff": train_diff,
        "train_parity_ok": train_diff < 1e-3,
        "decode_tokens_equal": t1 == t2,
        "decode_tp_recompiles": d2.n_compiles() - base,
        "ok": (train_diff < 1e-3 and t1 == t2
               and d2.n_compiles() == base),
    }


# ---------------------------------------------------------------------------
# scaling curve
# ---------------------------------------------------------------------------


def scaling_curve(counts=(1, 2, 4, 8), d_model: int = 512,
                  d_ff: int = 2048, batch: int = 32,
                  n_long: int = 40, repeats: int = 3) -> list:
    """Steps/s of a model-parallel-friendly train step per device count.

    The step is one jitted fwd+bwd+SGD over a wide MLP with params
    sharded over ``model`` (the dist rule) — the shape whose matmuls
    split cleanly across the axis. Timing is the long/short scanned-
    chain slope (one dispatch, data-dependent iterations), the same
    methodology every device-side bench in bench.py uses."""
    import functools
    import numpy as np
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.parallel import dist

    rng = np.random.default_rng(0)
    params = {"w1": (rng.normal(size=(d_model, d_ff)) * 0.02
                     ).astype(np.float32),
              "w2": (rng.normal(size=(d_ff, d_model)) * 0.02
                     ).astype(np.float32)}
    x = rng.normal(size=(batch, d_model)).astype(np.float32)
    y = rng.normal(size=(batch, d_model)).astype(np.float32)

    def step(p, xb, yb):
        def loss_fn(q):
            h = jax.nn.relu(xb @ q["w1"])
            return jnp.mean((h @ q["w2"] - yb) ** 2)
        l, g = jax.value_and_grad(loss_fn)(p)
        return jax.tree.map(lambda a, b: a - 0.01 * b, p, g), l

    curve = []
    n_avail = len(jax.devices())
    for n_dev in counts:
        if n_dev > n_avail:
            continue
        mesh = dist.train_mesh({"data": 1, "model": n_dev},
                               devices=jax.devices()[:n_dev])
        p = dist.shard_state(params, mesh)
        xb = jax.device_put(x, dist.batch_shardings(mesh))
        yb = jax.device_put(y, dist.batch_shardings(mesh))

        @functools.partial(jax.jit, static_argnames="n")
        def chain(p, n, xb=xb, yb=yb):
            def body(c, _):
                c, l = step(c, xb, yb)
                return c, l
            _, ls = jax.lax.scan(body, p, None, length=n)
            return ls

        chain(p, n=2).block_until_ready()

        def run(k, chain=chain, p=p):
            t0 = time.perf_counter()
            chain(p, n=k).block_until_ready()
            return time.perf_counter() - t0

        t_long = min(run(n_long) for _ in range(repeats))
        t_short = min(run(2) for _ in range(repeats))
        sec = max((t_long - t_short) / (n_long - 2), 1e-9)
        curve.append({"devices": n_dev,
                      "steps_per_s": round(1.0 / sec, 2),
                      "ms_per_step": round(sec * 1000.0, 4)})
    return curve


# ---------------------------------------------------------------------------
# tensor-parallel serving: zero steady-state recompiles
# ---------------------------------------------------------------------------


def serving_recompile_check(n_requests: int = 32) -> dict:
    """Drive a live TP server past warmup; the compile set must not grow."""
    import urllib.request
    import numpy as np
    from mmlspark_tpu.models.function import NNFunction
    from mmlspark_tpu.models.nn import NNModel
    from mmlspark_tpu.serving.server import ServingServer

    import jax
    if len(jax.devices()) < 2:
        return {"skipped": "tensor parallelism needs >= 2 devices",
                "ok": True}
    fn = NNFunction.init({"builder": "mlp", "hidden": [32],
                          "num_outputs": 4}, input_shape=(8,), seed=0)
    model = NNModel(model=fn, input_col="features", batch_size=32,
                    tensor_parallel=2)
    srv = ServingServer(model, max_batch_size=8, max_latency_ms=2.0)
    srv.warmup({"features": [0.0] * 8})
    srv.start()
    rng = np.random.default_rng(0)
    try:
        base = f"http://{srv.host}:{srv.port}"
        rec0 = srv.n_recompiles
        for _ in range(n_requests):
            payload = json.dumps(
                {"features": [float(v) for v in rng.normal(size=8)]}
            ).encode()
            req = urllib.request.Request(
                base + "/predict", data=payload,
                headers={"Content-Type": "application/json"})
            urllib.request.urlopen(req, timeout=10).read()
        stats = json.loads(urllib.request.urlopen(
            base + "/stats", timeout=10).read())
        placement = stats.get("placement") or {}
        return {"post_warmup_recompiles": srv.n_recompiles - rec0,
                "placement_mode": placement.get("mode"),
                "mesh": placement.get("mesh"),
                "n_requests": n_requests,
                "ok": (srv.n_recompiles == rec0
                       and placement.get("mode") == "tensor_parallel")}
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# pipeline-parallel serving (multihost_pipeline_v1)
# ---------------------------------------------------------------------------


def pipeline_check(rows: int = 512, repeats: int = 3,
                   hidden=(256, 256, 256, 256)) -> dict:
    """Pipeline-parallel serving A/B — the ``multihost_pipeline_v1``
    evidence.

    A deep MLP is partitioned into 2 pipeline stages over 2 device
    slices (``NNModel(pipeline_parallel=2)``); the baseline serves the
    SAME model on a single stage's devices (the pinned single-device
    scope — exactly one slice's hardware when the harness runs with 2
    devices). Gates: >= 2 stages actually placed, zero post-warmup
    recompiles through a live ServingServer, bubble fraction measured
    and reported, and >= 1.25x rows/s over the single-stage baseline —
    with an explicit ``speedup_justification`` when the CPU sandbox
    cannot express inter-stage overlap (virtual devices share cores)."""
    import urllib.request
    import numpy as np
    import jax
    from mmlspark_tpu.core.dataframe import DataFrame
    from mmlspark_tpu.models.function import NNFunction
    from mmlspark_tpu.models.nn import NNModel
    from mmlspark_tpu.parallel.topology import single_device_scope
    from mmlspark_tpu.serving.server import ServingServer

    n_dev = len(jax.devices())
    if n_dev < 2:
        return {"skipped": "pipeline parallelism needs >= 2 devices",
                "ok": True}
    pp = 2
    fn = NNFunction.init({"builder": "mlp", "hidden": list(hidden),
                          "num_outputs": 8}, input_shape=(64,), seed=0)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(rows, 64)).astype(np.float32)
    df = DataFrame({"features": x})

    model = NNModel(model=fn, input_col="features",
                    pipeline_parallel=pp, pipeline_microbatches=4)
    ref = NNModel(model=fn, input_col="features")

    # parity first: the staged forward must equal the fused one
    out_pp = model.transform(df)["scores"]
    with single_device_scope():
        out_ref = ref.transform(df)["scores"]
    parity = float(np.abs(out_pp - out_ref).max())

    def best_rows_per_s(run):
        run()                                     # warm
        best = 0.0
        for _ in range(repeats):
            t0 = time.perf_counter()
            run()
            best = max(best, rows / (time.perf_counter() - t0))
        return best

    pp_rps = best_rows_per_s(lambda: model.transform(df))

    def base_run():
        with single_device_scope():
            ref.transform(df)
    base_rps = best_rows_per_s(base_run)
    speedup = pp_rps / max(base_rps, 1e-9)

    report = model.pipeline_report() or {}

    # zero post-warmup recompiles through a LIVE pipelined server,
    # with the /stats pipeline block as evidence
    srv = ServingServer(model, max_batch_size=16, max_latency_ms=2.0)
    srv.warmup({"features": [0.0] * 64})
    srv.start()
    try:
        base = f"http://{srv.host}:{srv.port}"
        rec0 = srv.n_recompiles
        for _ in range(24):
            payload = json.dumps(
                {"features": [float(v) for v in rng.normal(size=64)]}
            ).encode()
            req = urllib.request.Request(
                base + "/predict", data=payload,
                headers={"Content-Type": "application/json"})
            urllib.request.urlopen(req, timeout=10).read()
        stats = json.loads(urllib.request.urlopen(
            base + "/stats", timeout=10).read())
        live_pipe = stats.get("pipeline_parallel") or {}
        recompiles = srv.n_recompiles - rec0
    finally:
        srv.stop()

    on_cpu = jax.default_backend() == "cpu"
    speedup_ok = speedup >= 1.25
    out = {
        "n_stages": report.get("n_stages"),
        "stages": report.get("stages"),
        "bubble_ratio": report.get("bubble_ratio"),
        "parity_max_diff": parity,
        "pipeline_rows_per_s": round(pp_rps, 1),
        "single_stage_rows_per_s": round(base_rps, 1),
        "speedup_vs_single_stage": round(speedup, 3),
        "post_warmup_recompiles": int(recompiles),
        "live_stats_pipeline_block": bool(live_pipe.get("n_stages")),
        "live_bubble_ratio": live_pipe.get("bubble_ratio"),
    }
    if not speedup_ok and on_cpu:
        out["speedup_justification"] = (
            "CPU sandbox: virtual devices share one host's cores, so "
            "inter-stage overlap may not express as wall-clock "
            f"speedup (measured {speedup:.2f}x); the gate rides "
            "parity + staged placement + zero recompiles + measured "
            "bubble. Real-chip numbers land in MULTICHIP_r0*.json.")
    out["ok"] = bool(
        (report.get("n_stages") or 0) >= 2
        and parity < 1e-5
        and recompiles == 0
        and report.get("bubble_ratio") is not None
        and live_pipe.get("n_stages")
        and (speedup_ok or "speedup_justification" in out))
    return out


# ---------------------------------------------------------------------------
# 2-process DCN drill (multiprocess_dcn_v1 — subprocess, opt-in)
# ---------------------------------------------------------------------------


def dcn_drill(timeout: float = 300.0, smoke: bool = True) -> dict:
    """Spawn tools/launch_multiprocess.py: the REAL 2-process drill
    (gloo cross-process psum, fit parity, pipe-stage split, 2-process
    cooperative checkpoint save -> 1-process restore). Subprocess-
    isolated — the drill owns its jax runtimes — with the per-phase
    timeout degrading to a failed metric line, never a hung bench."""
    import subprocess
    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "launch_multiprocess.py")
    cmd = [sys.executable, script, "--json",
           "--timeout", str(int(timeout))]
    if smoke:
        cmd.append("--smoke")
    try:
        p = subprocess.run(cmd, capture_output=True, text=True,
                           env=dict(os.environ, JAX_PLATFORMS="cpu"),
                           timeout=timeout * 3)
    except subprocess.TimeoutExpired as e:
        return {"passed": False,
                "error": f"dcn drill timed out after {e.timeout}s"}
    try:
        return json.loads(p.stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        return {"passed": False, "rc": p.returncode,
                "error": (p.stdout + p.stderr)[-1200:]}


# ---------------------------------------------------------------------------
# sharded-checkpoint topology drill
# ---------------------------------------------------------------------------


def checkpoint_topology_drill() -> dict:
    """Save on 2x2, restore on 4x1 and 1x1; digests strict-verified."""
    import shutil
    import tempfile
    import numpy as np
    import jax
    from mmlspark_tpu.io import checkpoint as ckpt
    from mmlspark_tpu.parallel import dist

    rng = np.random.default_rng(7)
    tree = {"w": rng.normal(size=(64, 32)).astype(np.float32),
            "b": rng.normal(size=(32,)).astype(np.float32)}
    n = len(jax.devices())
    sharded = dist.shard_state(tree, dist.train_mesh(_tp_mesh_shape()))
    path = tempfile.mkdtemp(prefix="ckpt_topo_")
    try:
        mngr = ckpt.manager(path)
        mngr.save(1, sharded)
        ok_digest, _ = ckpt.verify_digest(mngr._step_dir(1), strict=True)
        results = {"digest_verified": bool(ok_digest)}
        shapes = [("1x1", {"data": 1})]
        if n >= 4:
            shapes.insert(0, ("4x1", {"data": 4}))
        elif n >= 2:
            shapes.insert(0, ("2x1", {"data": 2}))
        for label, shape in shapes:
            mesh = dist.train_mesh(shape)
            r = mngr.restore(1, tree,
                             shardings=dist.state_shardings(tree, mesh),
                             strict_digest=True)
            same = all(
                np.array_equal(np.asarray(a), b)
                for a, b in zip(jax.tree.leaves(r), jax.tree.leaves(tree)))
            results[f"restore_{label}_exact"] = bool(same)
        results["ok"] = all(v for v in results.values())
        return results
    finally:
        shutil.rmtree(path, ignore_errors=True)


# ---------------------------------------------------------------------------
# entry
# ---------------------------------------------------------------------------


def _run_phase(name: str, fn, timeout_s: float) -> dict:
    """Run one in-process phase under a watchdog: a hung phase (the
    XLA:CPU collective-rendezvous deadlock class) degrades to a failed
    metric line instead of hanging the whole bench past its caller's
    budget. The worker thread is daemonized — it cannot be killed, but
    the bench reports and moves on (and the process exit reaps it)."""
    import threading
    box: dict = {}

    def work():
        try:
            box["result"] = fn()
        except Exception as e:  # noqa: BLE001 — failed phase = failed line
            box["error"] = f"{type(e).__name__}: {e}"

    t = threading.Thread(target=work, daemon=True, name=f"phase-{name}")
    t0 = time.time()
    t.start()
    t.join(timeout_s)
    if t.is_alive():
        return {"ok": False, "passed": False,
                "error": f"phase {name!r} timed out after {timeout_s}s "
                         f"(thread abandoned)"}
    if "error" in box:
        return {"ok": False, "passed": False, "error": box["error"],
                "elapsed_s": round(time.time() - t0, 1)}
    return box["result"]


def run_all(counts=(1, 2, 4, 8), quick: bool = False,
            phase_timeout: float = 300.0, with_dcn: bool = False) -> dict:
    parity = _run_phase(
        "parity", lambda: parity_check(steps_epochs=3 if quick else 5),
        phase_timeout)
    curve = _run_phase(
        "curve", lambda: scaling_curve(counts=counts,
                                       n_long=20 if quick else 40,
                                       repeats=2 if quick else 3),
        phase_timeout)
    if isinstance(curve, dict):          # timed out / raised
        curve_err, curve = curve, []
    else:
        curve_err = None
    serving = _run_phase(
        "serving",
        lambda: serving_recompile_check(n_requests=16 if quick else 32),
        phase_timeout)
    ckpt = _run_phase("checkpoint", checkpoint_topology_drill,
                      phase_timeout)
    by_n = {c["devices"]: c["steps_per_s"] for c in curve}
    speedup_4x = ((by_n[4] / by_n[1])
                  if (4 in by_n and by_n.get(1)) else None)
    import jax
    on_cpu = jax.default_backend() == "cpu"
    speedup_ok = speedup_4x is not None and speedup_4x >= 1.5
    out = {
        "parity": parity,
        "curve": curve,
        "speedup_4x_vs_1": (round(speedup_4x, 3)
                            if speedup_4x is not None else None),
        "serving": serving,
        "checkpoint": ckpt,
        "backend": jax.default_backend(),
        "n_devices": len(jax.devices()),
    }
    if curve_err is not None:
        out["curve_error"] = curve_err
    if with_dcn:
        # the REAL multi-process story: opt-in (subprocess-heavy), a
        # smoke-mode sub-result so multihost_scaling_v1 carries DCN
        # evidence without blowing the tier-1/bench budget
        # capped well below the caller's outer budget: the drill's
        # graceful phase-group timeouts must all fire (failed metric
        # line) before any outer kill could orphan the gloo workers
        out["dcn"] = dcn_drill(timeout=min(phase_timeout, 150.0),
                               smoke=True)
    if not speedup_ok:
        # the acceptance contract: when the environment can't express
        # (or reach) the 1.5x target, the measured number is REPORTED
        # with an explicit justification instead of crashing or
        # silently gating — the gate then rides parity +
        # zero-recompile + checkpoint topology
        if speedup_4x is None:
            why = (f"host has {len(jax.devices())} device(s): the "
                   f"4-vs-1 point cannot be measured; the curve covers "
                   f"what exists")
        elif on_cpu:
            why = ("CPU simulation: virtual devices share one host's "
                   "cores and memory bandwidth, so partitioned-matmul "
                   "scaling saturates early. Real-chip numbers land "
                   "in MULTICHIP_r0*.json.")
        else:
            why = (f"measured {speedup_4x:.2f}x at 4 devices — below "
                   f"the 1.5x target for this config on this "
                   f"hardware; reported explicitly per the "
                   f"acceptance contract")
        out["speedup_justification"] = why
    out["passed"] = bool(parity.get("ok") and serving.get("ok")
                         and ckpt.get("ok") and curve
                         and (speedup_ok
                              or "speedup_justification" in out)
                         and (not with_dcn
                              or out["dcn"].get("passed")))
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="quick CI gate: asserts, nonzero exit on violation")
    ap.add_argument("--json", action="store_true",
                    help="print the evidence JSON only")
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--phase", default="all",
                    choices=("all", "pipeline", "dcn"),
                    help="all = the multihost_scaling_v1 suite; "
                         "pipeline = the multihost_pipeline_v1 check "
                         "alone; dcn = the 2-process drill alone")
    ap.add_argument("--dcn", action="store_true",
                    help="include the 2-process DCN drill sub-result "
                         "in the full suite")
    ap.add_argument("--phase-timeout", type=float, default=300.0,
                    help="per-phase watchdog: a hung phase becomes a "
                         "failed metric line, not a hung bench")
    args = ap.parse_args()

    if args.phase == "dcn":
        out = dcn_drill(timeout=args.phase_timeout, smoke=args.smoke)
        print(json.dumps(out, indent=None if args.json else 2))
        sys.exit(0 if out.get("passed") else 1)

    _ensure_devices(2 if args.phase == "pipeline" else args.devices)
    if args.phase == "pipeline":
        out = _run_phase(
            "pipeline",
            lambda: pipeline_check(rows=256 if args.smoke else 512,
                                   repeats=2 if args.smoke else 3),
            args.phase_timeout)
        out["passed"] = bool(out.get("ok"))
        print(json.dumps(out, indent=None if args.json else 2))
        sys.exit(0 if out["passed"] else 1)

    counts = tuple(n for n in (1, 2, 4, 8) if n <= args.devices)
    out = run_all(counts=counts, quick=args.smoke,
                  phase_timeout=args.phase_timeout, with_dcn=args.dcn)
    print(json.dumps(out, indent=None if args.json else 2))
    if not out["passed"]:
        sys.exit(1)


if __name__ == "__main__":
    main()
