"""Benchmarks for the five BASELINE configs plus chip utilization —
one JSON line each.

The reference publishes no absolute numbers (BASELINE.md: its only perf
claims are relative — "10-30% faster" GBDT, "sub-millisecond" serving —
and its CIFAR notebook times a transform without committing the result).
Each config therefore carries an explicit GPU-VM/Spark-era *proxy*
baseline, documented per bench below; ``vs_baseline`` >= 1.0 means
at-or-above parity. Wall-clock benches report the MEDIAN of warm passes
(and carry best-of-N alongside; metric names are versioned _v2 since
r01 reported best-of-3 as the headline value).

Configs (BASELINE.md "Target configs"):
  1. gbdt_quantile_fit_v2        — drug-discovery-shape quantile fit wall-clock
  2. adult_census_fit_v2         — census-shape binary fit (data-parallel learner)
  3. cifar10_scoring_v2          — ResNet-20 scoring images/sec/chip (+ device-only)
     cifar10_scoring_u8_v1       — same pipeline on uint8 images, on-device normalize
  4. transfer_learning_e2e_v2    — ImageFeaturizer + TrainClassifier end-to-end
  5. distributed_sgd_step_v2     — sharded train-step throughput (steps/sec)

Plus (no era analogue, utilization/latency evidence):
  6. imagenet_scoring_v1         — ResNet-50 bf16 device scoring + MFU
  7. serving_latency_v1          — serving-stack p50/p99 request latency
  8. transformer_train_v1        — SPMD transformer LM step tokens/sec + MFU
  9. serving_throughput_v1       — serving-stack req/sec under
                                   concurrent keep-alive load, measured
                                   for BOTH socket edges in one run
                                   (eventloop headline, threaded A/B)
 10. transformer_train_long_v1   — same model at seq 4096 (folded flash
                                   attention's long-context regime)
 11. moe_train_v1                — experts-on train step (top-2 capacity
                                   dispatch + balance aux + z-loss)
 12. telemetry_overhead_v1       — metrics-registry hot path (ns per
                                   counter inc / histogram observe; the
                                   cost every serving batch, train step,
                                   and HTTP send now carries)
 13. tracing_overhead_v1         — span start+finish hot path (ns per
                                   recorded span, flight-recorder ring
                                   throughput; the cost every traced
                                   request, stage, and train step adds)
 14. trace_propagation_overhead_v1 — distributed-trace context
                                   inject+extract per egress attempt
                                   (the header tax every cross-process
                                   hop pays; budget 2 us/hop)
 15. serving_concurrency_v1      — 1,000 concurrent keep-alive
                                   connections against one worker
                                   (event-loop frontend headline +
                                   threaded comparison): req/s,
                                   p50/p99, connection-reuse rate,
                                   zero connection-level errors
 16. decode_continuous_v1        — slot-level continuous batching vs
                                   static whole-batch decode at mixed
                                   arrivals: tokens/s ratio + zero
                                   post-warmup recompiles + in-place
                                   KV-pool donation evidence
 17. multihost_scaling_v1        — the load-bearing mesh: pjit
                                   data x tensor-parallel train-step
                                   parity vs single-device on fixed
                                   seeds, devices-vs-throughput curve
                                   (1/2/4/8 simulated devices), zero
                                   post-warmup recompiles in tensor-
                                   parallel serving dispatch, and the
                                   sharded-checkpoint topology drill
                                   (2x2 save -> 4x1/1x1 restore,
                                   digests verified)
 18. retrain_loop_v1             — the retrain->redeploy loop end to
                                   end: live traffic -> capture ->
                                   fit_stream (with an injected crash/
                                   restart of the streaming query,
                                   exactly-once pinned) -> RetrainLoop
                                   -> canary rollout -> coherent fleet
                                   on the retrained version, zero
                                   dropped replies
 19. multihost_pipeline_v1       — pipeline-parallel serving over
                                   mesh slices: >= 2 stages really
                                   placed, row parity with the fused
                                   forward, zero post-warmup
                                   recompiles through a live server,
                                   measured bubble fraction, and
                                   rows/s vs a single stage's devices
                                   (speedup_justification on CPU
                                   sandboxes)
 20. multiprocess_dcn_v1         — the REAL 2-process drill: gloo
                                   cross-process psum through
                                   put_batch, 2-process fit parity
                                   <= 1e-6, pipeline stages split
                                   across processes, cooperative
                                   2-process sharded save restored
                                   bit-exact by 1 process
 21. slo_overhead_v1             — SLO-plane cost: per-token decode
                                   timeline stamping (budget 1 us/
                                   token) + one full burn-rate
                                   evaluate() over an hour of history
                                   (off hot path; scrape-interval
                                   budget)

Every line carries chip metadata (platform/device kind/count) so the
numbers are interpretable across hosts.
"""

from __future__ import annotations

import json
import os
import time
from typing import Optional

import numpy as np


def _chip():
    from mmlspark_tpu.core.environment import environment_info
    info = environment_info()
    chip = {k: info[k] for k in ("platform", "device_kind", "n_devices")}
    mem = info.get("memory")
    if mem and "bytes_limit" in mem:
        chip["hbm_gib"] = round(mem["bytes_limit"] / 2**30, 1)
    return chip


def _peak_bf16_tflops(chip: dict) -> Optional[float]:
    """The MFU denominator for ``_chip()``'s device, from the one peaks
    table (``core/environment.DEVICE_PEAKS``). ``None`` on a CPU host
    (the line then carries no utilization); an accelerator kind the
    table does not know raises."""
    from mmlspark_tpu.core.environment import device_peaks
    peaks = device_peaks(chip["device_kind"], chip["platform"])
    return peaks["bf16_tflops"] if peaks else None


def _timed_passes(fn, n_passes: int = 3):
    """Median + best of ``n_passes`` warm wall-clock runs (fn must block)."""
    times = []
    for _ in range(n_passes):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return float(np.median(times)), float(min(times))

def _chain_slope_seconds(run_chain, n_short: int, n_long: int,
                         repeats: int = 3) -> float:
    """Seconds per iteration from dependent-chain timing.

    ``run_chain(n)`` must execute n data-dependent iterations and block
    on a real value fetch. min-of-N rejects contention hiccups; the
    long/short slope cancels the fixed dispatch+fetch cost. A
    non-positive slope means noise swamped the measurement: fall back
    to the long chain including that fixed cost (conservative) rather
    than manufacturing an absurd rate from a clamp.
    """
    times = {}
    for n in (n_short, n_long):
        run_chain(n)  # warm + compile
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            run_chain(n)
            best = min(best, time.perf_counter() - t0)
        times[n] = best
    slope = (times[n_long] - times[n_short]) / (n_long - n_short)
    return slope if slope > 0 else times[n_long] / n_long



def bench_gbdt_quantile():
    """Config 1: LightGBMRegressor quantile fit (drug-discovery notebook
    shape: ~4k rows x 100 molecular descriptors, 40 iterations).

    Proxy baseline: 60 s — a Spark-cluster LightGBM fit of this scale in
    the reference's era spent tens of seconds on scheduling + JNI row
    marshalling + socket rendezvous before native training (the docs
    claim only "10-30% faster" than SparkML GBT, `docs/lightgbm.md:17`).
    """
    from mmlspark_tpu.gbdt.booster import Booster, BoosterParams
    rng = np.random.default_rng(0)
    n, f = 4096, 100
    X = rng.normal(size=(n, f))
    y = X[:, :5].sum(axis=1) + 0.3 * rng.normal(size=n) + 5.0
    p = BoosterParams(objective="quantile", alpha=0.9,
                      num_iterations=40, num_leaves=15)
    Booster.train(p, X, y)  # warm: bin + compile
    median, best = _timed_passes(lambda: Booster.train(p, X, y))
    baseline = 60.0
    return {"metric": "gbdt_quantile_fit_v2", "value": round(median, 2),
            "unit": "seconds", "best": round(best, 2),
            "baseline": baseline, "vs_baseline": round(baseline / median, 3),
            "chip": _chip()}


def bench_adult_census():
    """Config 2: LightGBMClassifier binary fit, census shape (32k rows x
    14 mixed columns, 100 iterations, 31 leaves — LightGBM defaults),
    data-parallel tree learner over all local devices.

    Proxy baseline: 60 s — same Spark-era reasoning as config 1, at
    Adult Census scale with the distributed learner's socket allreduce.
    """
    import jax
    from mmlspark_tpu.gbdt.booster import Booster, BoosterParams
    from mmlspark_tpu.parallel import build_mesh, batch_sharding

    rng = np.random.default_rng(0)
    n, f = 32768, 14
    X = rng.normal(size=(n, f))
    X[:, 10] = rng.integers(0, 16, n)   # categorical-ish columns
    X[:, 11] = rng.integers(0, 14, n)
    logit = X[:, 0] + 0.5 * X[:, 1] - 0.3 * X[:, 2] + 0.2 * (X[:, 10] > 8)
    y = (logit + rng.logistic(size=n) > 0).astype(np.float64)
    p = BoosterParams(objective="binary", num_iterations=100, num_leaves=31)
    sharding = (batch_sharding(build_mesh())
                if len(jax.devices()) > 1 else None)

    def fit():
        Booster.train(p, X, y, categorical_features=[10, 11],
                      sharding=sharding)
    fit()  # warm
    median, best = _timed_passes(fit, n_passes=2)
    baseline = 60.0
    return {"metric": "adult_census_fit_v2", "value": round(median, 2),
            "unit": "seconds", "best": round(best, 2),
            "baseline": baseline, "vs_baseline": round(baseline / median, 3),
            "chip": _chip()}


def bench_cifar10_scoring():
    """Config 3: CNTKModel.transform parity — ResNet-20 scoring over a
    CIFAR-sized set, through the full NNModel batching/padding pipeline.

    Proxy baseline: 1000 images/sec/chip — the era's GPU-VM ballpark for
    10k CIFAR images in ~10 s through CNTK-on-Spark including
    per-partition JNI marshalling (the notebook commits no number).
    Also reports pure device throughput (host transfers excluded) from a
    chained on-device loop.
    """
    import jax
    from mmlspark_tpu.models.function import NNFunction
    from mmlspark_tpu.models.nn import NNModel
    from mmlspark_tpu.core.dataframe import DataFrame

    batch, n_images = 1024, 10_240
    model = NNFunction.init(
        {"builder": "cifar_resnet", "depth": 20, "dtype": "bfloat16"},
        input_shape=(32, 32, 3), seed=0)
    rng = np.random.default_rng(0)
    images = rng.uniform(0, 1, size=(n_images, 32, 32, 3)).astype(np.float32)
    df = DataFrame({"image": images})
    # cache_inputs=False: this metric is FRESH-data scoring — every
    # timed pass pays the real host->device transfer (the repeated-
    # scoring cache's win is measured by transfer_learning_e2e_v2)
    scorer = NNModel(model=model, input_col="image", output_col="scores",
                     batch_size=batch, cache_inputs=False)
    scorer.transform(df.head(batch))  # warm: compile + first dispatch

    out = {}

    def run():
        out["scores"] = scorer.transform(df)["scores"]
    median, best = _timed_passes(run, n_passes=3)
    assert out["scores"].shape == (n_images, 10)
    n_chips = max(len(jax.devices()), 1)
    med_tput = n_images / median / n_chips
    best_tput = n_images / best / n_chips

    # pure device throughput (host<->device transfer and dispatch RTT
    # excluded) via the scan-slope method — see _device_seconds_per_batch
    import jax.numpy as jnp
    module = model.module()
    x_dev = jnp.asarray(images[:batch])
    p_dev = jax.device_put(model.params)
    # the scanned loop runs on a single device by construction, so this
    # is already a per-chip number — no division by n_chips
    dev_tput = batch / _device_seconds_per_batch(module, p_dev, x_dev)

    baseline = 1000.0
    return {"metric": "cifar10_scoring_v2", "value": round(med_tput, 1),
            "unit": "images/sec/chip", "best": round(best_tput, 1),
            "device_only": round(dev_tput, 1),
            "uplink_mb_per_s": _uplink_mb_per_s(),
            "baseline": baseline, "vs_baseline": round(med_tput / baseline, 3),
            "chip": _chip()}


def _uplink_mb_per_s(nbytes: int = 16 << 20) -> float:
    """Measured host->device link bandwidth (MB/s), reported alongside
    transfer-bound metrics: the link bounds what a full pipeline can
    score — e.g. 10k CIFAR images as bf16 are 60 MB, so the upload
    time is a floor under the pipeline whatever the chip does. Two
    transfer sizes, best-of-2 each, slope between them — cancels the
    fixed per-transfer cost exactly like :func:`_chain_slope_seconds`."""
    import jax.numpy as jnp
    x = np.random.default_rng(0).integers(
        0, 255, size=nbytes, dtype=np.uint8)
    d = jnp.asarray(x[:1024]); float(d[0])          # warm path
    times = {}
    for size in (nbytes // 4, nbytes):
        best = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            d = jnp.asarray(x[:size])
            float(d[0])                             # force completion
            best = min(best, time.perf_counter() - t0)
        times[size] = best
    slope = (times[nbytes] - times[nbytes // 4]) / (nbytes * 3 // 4)
    if slope <= 0:                                  # noise swamped it
        slope = times[nbytes] / nbytes
    return round(1e-6 / slope, 2)


def bench_cifar10_scoring_uint8():
    """Config 3b: the same ResNet-20 scoring pipeline fed what CIFAR
    actually is — uint8 RGB images — with normalization fused into the
    jitted forward (``NNModel(input_dtype="uint8")``). The reference
    pipeline also ingests byte images and normalizes inside the
    pipeline (`ImageTransformer` -> `CNTKModel`); shipping bytes and
    dequantizing on device is the TPU-first shape of that stage, and it
    cuts link traffic 2x vs bf16 / 4x vs f32. Same model, batching, and
    median-of-3 methodology as ``cifar10_scoring_v2``; baseline is the
    same 1000 img/s GPU-VM ballpark."""
    import jax
    from mmlspark_tpu.models.function import NNFunction
    from mmlspark_tpu.models.nn import NNModel
    from mmlspark_tpu.core.dataframe import DataFrame

    batch, n_images = 1024, 10_240
    model = NNFunction.init(
        {"builder": "cifar_resnet", "depth": 20, "dtype": "bfloat16"},
        input_shape=(32, 32, 3), seed=0)
    rng = np.random.default_rng(0)
    images = rng.integers(0, 256, size=(n_images, 32, 32, 3),
                          dtype=np.uint8)
    df = DataFrame({"image": images})
    scorer = NNModel(model=model, input_col="image", output_col="scores",
                     batch_size=batch, input_dtype="uint8",
                     cache_inputs=False)   # fresh-data semantics, as v2
    scorer.transform(df.head(batch))  # warm: compile + first dispatch

    out = {}

    def run():
        out["scores"] = scorer.transform(df)["scores"]
    median, best = _timed_passes(run, n_passes=3)
    assert out["scores"].shape == (n_images, 10)
    n_chips = max(len(jax.devices()), 1)
    baseline = 1000.0
    med_tput = n_images / median / n_chips
    return {"metric": "cifar10_scoring_u8_v1", "value": round(med_tput, 1),
            "unit": "images/sec/chip",
            "best": round(n_images / best / n_chips, 1),
            "baseline": baseline,
            "vs_baseline": round(med_tput / baseline, 3),
            "chip": _chip()}


def bench_transfer_learning():
    """Config 4: ImageFeaturizer (truncated ResNet backbone) +
    TrainClassifier end-to-end over 2048 images.

    Proxy baseline: 40 s — the reference's example-9 path featurized at
    GPU-VM CNTK speed (~100 img/s era with JNI row plumbing, so ~20 s
    for 2k images) plus a distributed LR fit of comparable cost.
    """
    from mmlspark_tpu.core.dataframe import DataFrame
    from mmlspark_tpu.models.function import NNFunction
    from mmlspark_tpu.models.featurizer import ImageFeaturizer
    from mmlspark_tpu.automl.train import TrainClassifier
    from mmlspark_tpu.gbdt import GBDTClassifier

    backbone = NNFunction.init(
        {"builder": "cifar_resnet", "depth": 14, "dtype": "bfloat16"},
        input_shape=(32, 32, 3), seed=0)
    rng = np.random.default_rng(0)
    n = 2048
    y = rng.integers(0, 2, n)
    images = (rng.uniform(0, 1, (n, 32, 32, 3)) * 0.5
              + y[:, None, None, None] * 0.45).astype(np.float32)
    df = DataFrame({"image": images, "label": y})

    # one featurizer across passes: its NNModel caches the compiled
    # truncated forward per instance, so the timed passes are truly warm
    featurizer = ImageFeaturizer(model=backbone, input_col="image",
                                 output_col="embedding",
                                 cut_output_layers=1)

    def run():
        feats = featurizer.transform(df)
        TrainClassifier(
            model=GBDTClassifier(num_iterations=20, num_leaves=7),
            label_col="label").fit(feats.select(["embedding", "label"]))
    run()  # warm: compile
    run()  # warm: second sighting stores the device-resident input cache
    median, best = _timed_passes(run, n_passes=2)
    baseline = 40.0
    return {"metric": "transfer_learning_e2e_v2", "value": round(median, 2),
            "unit": "seconds", "best": round(best, 2),
            "baseline": baseline, "vs_baseline": round(baseline / median, 3),
            "chip": _chip()}


def bench_distributed_sgd():
    """Config 5: the cntk-train replacement — one jitted data-parallel
    train step (ResNet-20, batch 256 CIFAR shape) over the device mesh,
    20 chained steps, blocked once (sustained device throughput).

    Proxy baseline: 10 steps/sec — the era's CNTK-on-K80 data-parallel
    SGD rate for ResNet-20/batch-256 once MPI/ssh overhead amortized.
    Mixed precision (bf16 convs, f32 params/optimizer — the same
    treatment cifar10_scoring_v2 gives this model); reports
    achieved_tflops/mfu from XLA's own cost analysis of the compiled
    step (r4 VERDICT #2: the training side was unmeasured).
    """
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.models.function import NNFunction
    from mmlspark_tpu.models.trainer import (
        NNLearner, make_loss, make_optimizer)
    from mmlspark_tpu.parallel import (
        MeshSpec, build_mesh, batch_sharding, replicated_sharding)

    n_dev = len(jax.devices())
    mesh = build_mesh(MeshSpec.from_dict({"data": n_dev}))
    model = NNFunction.init(
        {"builder": "cifar_resnet", "depth": 20, "dtype": "bfloat16"},
        input_shape=(32, 32, 3), seed=0)
    learner = NNLearner(arch=model.arch, learning_rate=0.1)
    tx = make_optimizer("momentum", 0.1)
    loss_fn = make_loss("softmax_cross_entropy")
    step_fn = learner.build_train_step(model.module(), tx, loss_fn)

    batch = 256
    repl, shard = replicated_sharding(mesh), batch_sharding(mesh)
    rng = np.random.default_rng(0)
    params = jax.device_put(model.params, repl)
    opt_state = jax.device_put(tx.init(params), repl)
    x = jax.device_put(
        rng.uniform(0, 1, (batch, 32, 32, 3)).astype(np.float32), shard)
    y = jax.device_put(rng.integers(0, 10, batch).astype(np.int32), shard)
    w = jax.device_put(np.ones(batch, np.float32), shard)

    # sustained DEVICE throughput: the whole step chain runs as ONE
    # scanned program (param/opt-state carries make every iteration
    # data-dependent; the loss stack forces real compute), because at
    # ~1 ms/step per-call host dispatch would be a large share of what
    # this metric claims to measure. The long/short scan slope cancels
    # the final fetch (same methodology as
    # _device_seconds_per_batch). FLOPs come from the SAME compiled
    # scan program (n=2, divided by 2) — no extra single-step compile.
    import functools as _ft
    import jax as _jax

    @_ft.partial(_jax.jit, static_argnames="n")
    def scan_steps(p, o, n):
        def body(c, _):
            pp, oo, l = step_fn(c[0], c[1], x, y, w)
            return (pp, oo), l
        _, losses = _jax.lax.scan(body, (p, o), None, length=n)
        return losses

    cost = scan_steps.lower(params, opt_state, n=2).compile() \
        .cost_analysis() or {}
    flops_per_step = float(cost.get("flops", 0.0)) / 2.0

    def run_chain(n):
        float(scan_steps(params, opt_state, n)[-1])

    sec_per_step = _chain_slope_seconds(run_chain, 2, 42)
    steps_per_sec = 1.0 / sec_per_step
    baseline = 10.0
    chip = _chip()
    out = {"metric": "distributed_sgd_step_v2",
           "value": round(steps_per_sec, 2), "unit": "steps/sec",
           "ms_per_step": round(1000 * sec_per_step, 1),
           "batch_size": batch, "baseline": baseline,
           "vs_baseline": round(steps_per_sec / baseline, 3),
           "chip": chip}
    peak = _peak_bf16_tflops(chip)
    if flops_per_step > 0:
        achieved = flops_per_step / sec_per_step / 1e12
        out["achieved_tflops"] = round(achieved, 2)
        if peak:
            out["mfu"] = round(achieved / peak, 4)
    return out


def _device_seconds_per_batch(module, params, x, n_long: int = 22,
                              n_short: int = 2, repeats: int = 3) -> float:
    """Device time per forward: ONE program scanning n forwards
    (data-dependent so no iteration can be elided), a scalar fetch to
    force completion, and the slope between a long and a short scan to
    cancel the fixed dispatch+fetch cost.
    """
    import jax
    import jax.numpy as jnp
    from functools import partial

    @partial(jax.jit, static_argnames="n")
    def scan_fwd(p, x, n):
        def body(carry, _):
            out = module.apply(p, carry)
            carry = carry + (jnp.mean(out) * 0).astype(carry.dtype)
            return carry, jnp.sum(out)
        _, sums = jax.lax.scan(body, x, None, length=n)
        return jnp.sum(sums)

    return _chain_slope_seconds(
        lambda n: float(scan_fwd(params, x, n)), n_short, n_long, repeats)


def bench_imagenet_scoring():
    """Large-model chip utilization: ResNet-50 (ImageNet shapes, bf16)
    device-resident scoring with an MFU figure.

    The CIFAR config measures the full pipeline; this one answers "how
    much of the chip do big scoring matmuls actually use": XLA's own
    cost analysis gives the program FLOPs, MFU = achieved FLOP/s over
    the chip's peak dense bf16 rate. No era baseline exists for this
    metric; the informational baseline is 0.30 MFU (a healthy inference
    utilization for a conv net without custom kernels).
    """
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.models.function import NNFunction

    model = NNFunction.init(
        {"builder": "imagenet_resnet", "depth": 50, "dtype": "bfloat16"},
        input_shape=(224, 224, 3), seed=0)
    module = model.module()
    rng = np.random.default_rng(0)
    p_dev = jax.device_put(model.params)
    chip = _chip()
    peak = _peak_bf16_tflops(chip)

    # probe the chip's utilization sweet spot instead of pinning one
    # batch: the historical fixed 128 measured anywhere from 0.37 to
    # 0.55 MFU across rounds on the SAME chip — b64 leaves MXU tiles
    # under-filled in the wide early layers, b256 spills, and where
    # the knee sits moves with runtime/XLA versions. An operator sizing
    # a scoring fleet tunes exactly this knob, so the metric reports
    # the best probed point (per-batch table alongside). On CPU, one
    # small probe keeps the bench fast.
    batches = (128, 160, 192, 256) if peak else (32,)
    probes = {}
    best = None
    for batch in batches:
        x = jnp.asarray(rng.uniform(0, 1, size=(batch, 224, 224, 3)),
                        dtype=jnp.bfloat16)
        fwd = jax.jit(lambda p, x: module.apply(p, x))
        cost = fwd.lower(p_dev, x).compile().cost_analysis() or {}
        if isinstance(cost, (list, tuple)):   # per-device list on some
            cost = cost[0] if cost else {}    # backends/versions
        flops_per_batch = float(cost.get("flops", 0.0))
        sec_per_batch = _device_seconds_per_batch(module, p_dev, x)
        tput = batch / sec_per_batch
        entry = {"batch_size": batch,
                 "ms_per_batch": round(sec_per_batch * 1000, 2),
                 "images_per_s": round(tput, 1)}
        if flops_per_batch > 0:
            achieved = flops_per_batch / sec_per_batch / 1e12
            entry["achieved_tflops"] = round(achieved, 2)
            if peak:
                entry["mfu"] = round(achieved / peak, 4)
        probes[str(batch)] = entry
        # rank MFU-bearing probes above flopless ones (raw img/s is
        # not commensurable with MFU — a probe whose cost analysis
        # came back empty must not win on magnitude alone)
        key = (1, entry["mfu"]) if "mfu" in entry else (0, tput)
        if best is None or key > best[0]:
            best = (key, entry)
    top = best[1]
    out = {"metric": "imagenet_scoring_v1",
           "value": top["images_per_s"],
           "unit": "images/sec/chip", "batch_size": top["batch_size"],
           "ms_per_batch": top["ms_per_batch"],
           "batch_probes": probes, "chip": chip}
    if "achieved_tflops" in top:
        out["achieved_tflops"] = top["achieved_tflops"]
    if "mfu" in top:
        out["mfu"] = top["mfu"]
        out["baseline"] = 0.30
        out["vs_baseline"] = round(top["mfu"] / 0.30, 3)
    if "vs_baseline" not in out:
        # CPU/unknown chip: report throughput against a nominal 100 img/s
        out["baseline"] = 100.0
        out["vs_baseline"] = round(out["value"] / 100.0, 3)
    return out


def _identity_model():
    """The trivial host-side serving model shared by the serving benches
    (so both measure the STACK, not a model)."""
    from mmlspark_tpu.core.stage import Transformer

    class Identity(Transformer):
        def transform(self, df):
            return df.with_column(
                "y", np.asarray(df["x"], dtype=np.float64))

    return Identity()


def bench_serving_latency():
    """Serving-stack request latency (reference headline: "sub-ms";
    "latencies as low as 1 ms", README.md:19, mmlspark-serving.md:10).

    Measures the serving machinery itself — HTTP loopback, batching
    queue, frame assembly, reply routing — with a trivial host-side
    model, so the number is the stack overhead a model's own device time
    adds onto. Baseline: the reference's 1 ms claim; vs_baseline =
    baseline / p50.
    """
    from mmlspark_tpu.serving import ServingServer

    # raw http.client on a kept-alive socket: the requests library adds
    # 1-2 ms of client-side machinery that is not serving-stack latency
    import http.client

    lat = []
    with ServingServer(_identity_model(), max_latency_ms=0) as srv:
        conn = http.client.HTTPConnection(srv.host, srv.port, timeout=10)

        def post(i):
            body = json.dumps({"x": i}).encode()
            conn.request("POST", srv.api_path, body,
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            data = resp.read()
            return resp.status, data

        for i in range(50):  # warm sockets + code paths
            post(i)
        for i in range(300):
            t0 = time.perf_counter()
            status, _ = post(i)
            lat.append(time.perf_counter() - t0)
            assert status == 200
        conn.close()
    p50 = float(np.percentile(lat, 50)) * 1000
    p99 = float(np.percentile(lat, 99)) * 1000
    baseline = 1.0
    return {"metric": "serving_latency_v1", "value": round(p50, 3),
            "unit": "ms p50", "p99_ms": round(p99, 3),
            "baseline": baseline,
            "vs_baseline": round(baseline / max(p50, 1e-9), 3),
            "chip": _chip()}


def _drive_serving(frontend: str, n_connections: int,
                   duration_s: Optional[float] = None,
                   requests_per_conn: Optional[int] = None) -> dict:
    """One timed window against a fresh worker on the given socket edge
    (same staged data plane either way), driven by the many-connection
    keep-alive loop in ``mmlspark_tpu.testing.load`` — the client that
    doesn't hit its own concurrency ceiling before the server's."""
    from mmlspark_tpu.serving import ServingServer
    from mmlspark_tpu.testing.load import drive_keepalive

    with ServingServer(_identity_model(), max_latency_ms=2,
                       max_batch_size=256, max_queue=4096,
                       frontend=frontend) as srv:
        # dispatch every shape bucket once before the timed window, so
        # the number is the pipelined plane's steady state (with a real
        # jitted model this is where the compiles land); the recompile
        # counter must then stay flat across the run
        srv.warmup({"x": 0.0})
        recompiles_warm = srv.n_recompiles
        out = drive_keepalive(
            srv.host, srv.port, srv.api_path, b'{"x": 0.0}',
            n_connections=n_connections, duration_s=duration_s,
            requests_per_conn=requests_per_conn)
        out["recompiles_after_warmup"] = \
            srv.n_recompiles - recompiles_warm
        out["frontend"] = frontend
    return out


def bench_serving_throughput():
    """Serving-stack sustained throughput under concurrent keep-alive
    load, measured for BOTH socket edges in one run: the event-loop
    frontend (headline) and the thread-per-connection http.server
    baseline (``ab_threaded``), each fed the same way at 8 connections
    (the pre-eventloop bench shape, for cross-run continuity) and 64
    (past the thread plane's comfort zone, where the edges separate).
    Same trivial host-side model as ``serving_latency_v1`` so the
    number is the STACK's ceiling, not a model's.

    Proxy baseline: 1000 req/s — a Spark-era continuous-serving
    executor handling ~1 request/ms end-to-end. NOTE on dev-box
    absolutes: client and server share this host, and on sandboxed
    kernels (gVisor-class, ~50-100 us per syscall) the ~6 syscalls a
    strictly serial request/response cycle costs bound the whole box
    well below the stack's ceiling on bare metal — the A/B ratio and
    the zero-error/zero-recompile evidence travel; the absolute req/s
    does not.
    """
    results = {}
    for fe in ("eventloop", "threaded"):
        for conns in (8, 64):
            results[(fe, conns)] = _drive_serving(
                fe, conns, duration_s=3.0)
    head = results[("eventloop", 64)]
    ab = results[("threaded", 64)]
    baseline = 1000.0
    import os
    cores = (len(os.sched_getaffinity(0))
             if hasattr(os, "sched_getaffinity") else os.cpu_count())
    rps = head["rps"]
    return {"metric": "serving_throughput_v1", "value": rps,
            "unit": "req/sec", "n_connections": 64,
            "frontend": "eventloop",
            "p50_ms": head["p50_ms"], "p99_ms": head["p99_ms"],
            "n_errors": head["conn_errors"] + head["http_errors"],
            "eventloop_8conn_rps": results[("eventloop", 8)]["rps"],
            "ab_threaded": {
                "rps": ab["rps"], "p50_ms": ab["p50_ms"],
                "p99_ms": ab["p99_ms"],
                "rps_8conn": results[("threaded", 8)]["rps"]},
            "frontend_speedup": round(rps / max(ab["rps"], 1e-9), 3),
            # clients and server share this host's cores: on a small
            # dev box the number is a floor, not the stack's ceiling
            "host_cores": cores,
            # 0 = the bucketed plane never retraced after warm-up
            # (tools/bench_serving_pipeline.py asserts this under
            # varying-batch-size load)
            "recompiles_after_warmup": head["recompiles_after_warmup"],
            "baseline": baseline,
            "vs_baseline": round(rps / baseline, 3), "chip": _chip()}


def bench_serving_quantized():
    """The quantized serving wire A/B (ISSUE 13 acceptance gate):
    identical jitted NNModel behind two live pipelined servers — one
    on the f32 wire, one on the u8 wire (``quantization=`` — see
    docs/serving.md "Quantization") — driven by the same
    keep-alive load. The u8 arm's payloads are small integers (2-4x
    fewer JSON bytes to parse, 4x fewer bytes assembled and uploaded)
    and the model dequantizes ``x * scale`` on device, fused into its
    first layer.

    Gates (``passed``): u8 rps >= 1.3x f32 rps, ZERO post-warmup
    recompiles on both arms, and row-wise output parity between the
    planes within tolerance (the u8 grid's f32 values are fed to the
    f32 arm exactly, so parity is fp-noise, not quantization error).
    """
    import requests as _requests
    from mmlspark_tpu.core.dataframe import DataFrame
    from mmlspark_tpu.models.function import NNFunction
    from mmlspark_tpu.models.nn import NNModel
    from mmlspark_tpu.serving import ServingServer
    from mmlspark_tpu.testing.load import drive_keepalive

    # a CIFAR image as the flat payload (the cifar10_scoring_u8_v1
    # ingest shape, now as live serving traffic): at image-scale
    # payloads the wire — JSON bytes, assembly, upload — is the
    # request's dominant cost, which is exactly the regime the
    # quantized plane exists for
    d_in, scale = 3072, 1.0 / 255.0
    fn = NNFunction.init({"builder": "mlp", "hidden": [64],
                          "num_outputs": 4}, input_shape=(d_in,), seed=0)

    def make_model(**kw):
        return NNModel(model=fn, input_col="x", output_col="y",
                       batch_size=256, cache_inputs=False,
                       data_parallel=False, **kw)

    rng = np.random.default_rng(0)
    q_rows = rng.integers(0, 256, size=(16, d_in))
    f_rows = q_rows.astype(np.float64) * scale

    arms = {}
    parity = {}
    configs = {
        "f32": (make_model(input_dtype="float32"), {},
                json.dumps({"x": list(f_rows[0])}).encode()),
        "u8": (make_model(),
               {"quantization": {"wire_dtype": "uint8", "scale": scale}},
               json.dumps({"x": [int(v) for v in q_rows[0]]}).encode()),
    }
    for arm, (model, kw, payload) in configs.items():
        with ServingServer(model, max_latency_ms=2, max_batch_size=256,
                           max_queue=4096, **kw) as srv:
            srv.warmup(json.loads(payload.decode()))
            warm = srv.n_recompiles
            # best-of-3 timed windows per arm: client and server share
            # this host, so any one window can eat a scheduler stall —
            # the best window is each arm's honest capability
            best = None
            errs = {"conn_errors": 0, "http_errors": 0}
            for _ in range(3):
                out = drive_keepalive(srv.host, srv.port, srv.api_path,
                                      payload, n_connections=32,
                                      duration_s=2.0)
                for k in errs:
                    errs[k] += out[k]
                if best is None or out["rps"] > best["rps"]:
                    best = out
            out = dict(best, **errs)   # errors across EVERY window
            out["recompiles_after_warmup"] = srv.n_recompiles - warm
            # row-wise parity probe through the live wire
            rows = (f_rows if arm == "f32" else q_rows)[:8]
            ys = []
            for r in rows:
                body = {"x": ([float(v) for v in r] if arm == "f32"
                              else [int(v) for v in r])}
                ys.append(_requests.post(srv.address, json=body,
                                         timeout=10).json()["y"])
            parity[arm] = np.asarray(ys, dtype=np.float64)
            # bytes each arm puts on the device wire per row
            out["payload_bytes"] = len(payload)
            arms[arm] = out
    parity_diff = float(np.abs(parity["f32"] - parity["u8"]).max())
    ratio = arms["u8"]["rps"] / max(arms["f32"]["rps"], 1e-9)
    errors = sum(arms[a]["conn_errors"] + arms[a]["http_errors"]
                 for a in arms)
    recompiles = sum(arms[a]["recompiles_after_warmup"] for a in arms)
    ok = (ratio >= 1.3 and recompiles == 0 and errors == 0
          and parity_diff < 1e-3)
    return {"metric": "serving_quantized_v1", "value": round(ratio, 3),
            "unit": "x u8/f32 rps", "baseline": 1.3,
            "vs_baseline": round(ratio / 1.3, 3),
            "rps_u8": arms["u8"]["rps"], "rps_f32": arms["f32"]["rps"],
            "p99_ms_u8": arms["u8"]["p99_ms"],
            "p99_ms_f32": arms["f32"]["p99_ms"],
            "payload_bytes_u8": arms["u8"]["payload_bytes"],
            "payload_bytes_f32": arms["f32"]["payload_bytes"],
            "n_errors": errors,
            "recompiles_after_warmup": recompiles,
            "parity_max_diff": parity_diff,
            "passed": ok, "chip": _chip()}


def bench_serving_concurrency():
    """1,000 concurrent keep-alive connections against one worker: the
    many-users shape the event-loop frontend exists for. Each
    connection runs 25 strictly serial (pipelining-free) request/
    response cycles; the acceptance gates are ZERO connection-level
    errors (no resets, refusals, or unexpected closes at 1k live
    sockets) and a connection-reuse rate above 95% (keep-alive held:
    reuse = 1 - 1/cycles = 0.96 when no connection is ever dropped).
    The threaded frontend runs the same 1k connections for 5 cycles as
    the A/B comparison — it holds them, but pays a thread per
    connection (~8 MB of stacks and a scheduler fight the loop never
    enters).
    """
    head = _drive_serving("eventloop", 1000, requests_per_conn=25)
    ab = _drive_serving("threaded", 1000, requests_per_conn=5)
    ok = (head["conn_errors"] == 0 and head["http_errors"] == 0
          and head["reuse_rate"] > 0.95)
    return {"metric": "serving_concurrency_v1",
            "value": head["rps"], "unit": "req/sec @1k conns",
            "frontend": "eventloop",
            "n_connections": head["n_connections"],
            "requests": head["requests"],
            "p50_ms": head["p50_ms"], "p99_ms": head["p99_ms"],
            "conn_errors": head["conn_errors"],
            "http_errors": head["http_errors"],
            "reuse_rate": head["reuse_rate"],
            "passed": ok,
            "ab_threaded": {
                "rps": ab["rps"], "p50_ms": ab["p50_ms"],
                "p99_ms": ab["p99_ms"],
                "conn_errors": ab["conn_errors"],
                "reuse_rate": ab["reuse_rate"]},
            "chip": _chip()}


def bench_tenant_isolation():
    """Noisy-neighbor isolation A/B (ISSUE 16 acceptance gate): one
    worker with tenancy enabled, a background flood tenant at a 10:1
    connection ratio against an interactive victim, run twice — once
    with deficit-weighted fair-share + priority-aware shedding on,
    once degraded to the plain full-queue check (``fair_share``
    off) — same registry, same load, same trivial host-side model, so
    the number is the overload-control machinery's doing.

    Each arm measures the victim alone first (its quiet baseline),
    then flood + victim concurrently. The queue is sized so the flood
    crosses the high-water mark (background sheds at ``0.5 * 64``)
    while the victim's interactive class holds full-queue headroom.

    Gates (``passed``, fair arm under flood): victim sees ZERO
    connection and HTTP errors (no 429 ever reaches the interactive
    class), the flood tenant sheds (429s on the wire AND
    ``n_shed_overload`` in its ledger row), victim p99 stays within
    2x its quiet baseline (floored at 25 ms against dev-box jitter),
    victim holds >= 20% of its quiet req/s, and ZERO post-warmup
    recompiles on BOTH arms — tenancy and fairness are host-side
    bookkeeping that reorder rows, never reshape dispatch.
    """
    import threading as _threading

    from mmlspark_tpu.core.stage import Transformer
    from mmlspark_tpu.serving import ServingServer
    from mmlspark_tpu.testing.load import drive_keepalive

    class _FixedCost(Transformer):
        """Identity with a fixed 2 ms per-batch cost: the server, not
        the shared-host client fleet, is the bottleneck, so victim
        latency is queue position — the thing fair-share controls —
        rather than scheduler noise."""

        def transform(self, df):
            time.sleep(0.002)
            return df.with_column(
                "y", np.asarray(df["x"], dtype=np.float64))

    tenancy_base = {
        "unknown_key_policy": "reject",
        "high_water": 0.5,
        "tenants": [
            {"id": "victim", "priority": "interactive",
             "api_keys": ["bench-victim"], "weight": 8.0},
            {"id": "flood", "priority": "background",
             "api_keys": ["bench-flood"], "weight": 1.0},
        ],
    }
    n_victim, n_flood = 3, 30   # the 10:1 noisy-neighbor mix

    arms = {}
    for fair in (True, False):
        cfg = dict(tenancy_base, fair_share=fair)
        # small batches + a tight queue so the flood lives above the
        # high-water mark (background sheds at depth 16) while the
        # interactive class keeps full-queue headroom (32)
        with ServingServer(_FixedCost(), max_latency_ms=2,
                           max_batch_size=8, max_queue=32,
                           tenancy=cfg) as srv:
            srv.warmup({"x": 0.0})
            warm = srv.n_recompiles

            def drive(key, conns, dur):
                return drive_keepalive(
                    srv.host, srv.port, srv.api_path, b'{"x": 0.0}',
                    n_connections=conns, duration_s=dur,
                    extra_headers=[("X-Api-Key", key)])

            quiet = drive("bench-victim", n_victim, 1.5)
            flooded = {}

            def run(name, key, conns):
                flooded[name] = drive(key, conns, 3.0)

            ts = [_threading.Thread(target=run,
                                    args=("victim", "bench-victim",
                                          n_victim)),
                  _threading.Thread(target=run,
                                    args=("flood", "bench-flood",
                                          n_flood))]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            rows = {r["id"]: r
                    for r in srv.tenancy.stats()["tenants"]}
            arms[fair] = {
                "quiet": quiet, "victim": flooded["victim"],
                "flood": flooded["flood"], "rows": rows,
                "recompiles_after_warmup":
                    srv.n_recompiles - warm}

    head = arms[True]
    ab = arms[False]
    quiet_p99 = max(head["quiet"]["p99_ms"], 1e-3)
    victim_p99 = head["victim"]["p99_ms"]
    p99_bound = max(2.0 * quiet_p99, 25.0)
    slowdown = victim_p99 / quiet_p99
    flood_shed = (head["flood"]["http_errors"] > 0
                  and head["rows"]["flood"]["n_shed_overload"] > 0)
    recompiles = (head["recompiles_after_warmup"]
                  + ab["recompiles_after_warmup"])
    ok = (head["victim"]["conn_errors"] == 0
          and head["victim"]["http_errors"] == 0
          and flood_shed
          and victim_p99 <= p99_bound
          and head["victim"]["rps"] >= 0.2 * head["quiet"]["rps"]
          and recompiles == 0)
    baseline = 2.0   # the chaos drill's bound: flooded p99 <= 2x quiet
    return {"metric": "tenant_isolation_v1",
            "value": round(slowdown, 3),
            "unit": "x victim p99 flooded/quiet (fair-share on)",
            "baseline": baseline,
            "vs_baseline": round(baseline / max(slowdown, 1e-9), 3),
            "victim_quiet_p99_ms": head["quiet"]["p99_ms"],
            "victim_flooded_p99_ms": victim_p99,
            "victim_p99_bound_ms": round(p99_bound, 3),
            "victim_rps_quiet": head["quiet"]["rps"],
            "victim_rps_flooded": head["victim"]["rps"],
            "victim_errors": head["victim"]["conn_errors"]
            + head["victim"]["http_errors"],
            "flood_rps": head["flood"]["rps"],
            "flood_429s": head["flood"]["http_errors"],
            "flood_shed_overload":
                head["rows"]["flood"]["n_shed_overload"],
            "ab_fair_share_off": {
                "victim_p99_ms": ab["victim"]["p99_ms"],
                "victim_rps": ab["victim"]["rps"],
                "victim_http_errors": ab["victim"]["http_errors"],
                "flood_rps": ab["flood"]["rps"],
                "flood_429s": ab["flood"]["http_errors"]},
            "recompiles_after_warmup": recompiles,
            "passed": ok, "chip": _chip()}


def bench_model_swap():
    """Zero-downtime hot-swap under sustained keep-alive load: a live
    model-version rollout (stage from a digest-verified checkpoint ->
    warm every shape bucket -> atomic flip) executed in the MIDDLE of a
    timed `drive_keepalive` window, gated against a no-swap baseline
    window on the same worker.

    Acceptance gates (`passed`): ZERO connection errors, ZERO http
    errors (every request answered 200 across the flip — nothing
    dropped, nothing errored), ZERO post-flip recompiles (the staged
    version was warmed on every bucket the live plane can emit), and a
    bounded p99 delta vs the no-swap baseline (the flip must not cost
    a visible latency cliff; shared-box absolutes are noisy, so the
    bound is generous: p99_swap <= max(3x baseline, baseline + 50 ms)).
    """
    import os
    import tempfile

    from mmlspark_tpu.serving import ServingServer
    from mmlspark_tpu.stages import ScaleColumn
    from mmlspark_tpu.testing.load import drive_keepalive

    tmp = tempfile.mkdtemp(prefix="model_swap_")
    v2_dir = os.path.join(tmp, "v2")
    ScaleColumn(input_col="x", output_col="y", scale=3.0).save(v2_dir)

    with ServingServer(ScaleColumn(input_col="x", output_col="y",
                                   scale=2.0),
                       max_latency_ms=2, max_batch_size=256,
                       max_queue=4096, model_version="v1") as srv:
        srv.warmup({"x": 0.0})
        # -- baseline window: same load, no swap
        base = drive_keepalive(srv.host, srv.port, srv.api_path,
                               b'{"x": 0.0}', n_connections=64,
                               duration_s=2.5)
        recompiles_before = srv.n_recompiles

        # -- swap window: stage (verify digest + warm all buckets) and
        # flip roughly mid-window, while the load loop runs
        import threading

        swap_state = {}

        def swap():
            time.sleep(1.0)
            srv.versions.stage(source=v2_dir, version="v2", sync=True)
            swap_state["staged"] = srv.versions.staged.to_dict() \
                if srv.versions.staged else None
            srv.versions.flip(version="v2")

        t = threading.Thread(target=swap)
        t.start()
        swapped = drive_keepalive(srv.host, srv.port, srv.api_path,
                                  b'{"x": 0.0}', n_connections=64,
                                  duration_s=3.0)
        t.join()
        active = srv.versions.active
        post_flip_recompiles = active.n_post_flip_recompiles
        flipped_version = active.version

    p99_base, p99_swap = base["p99_ms"], swapped["p99_ms"]
    n_errors = swapped["conn_errors"] + swapped["http_errors"]
    p99_ok = p99_swap <= max(3.0 * p99_base, p99_base + 50.0)
    ok = (n_errors == 0 and post_flip_recompiles == 0
          and flipped_version == "v2"
          and (swap_state.get("staged") or {}).get(
              "digest_verified") is True
          and p99_ok)
    return {"metric": "model_swap_v1", "value": swapped["rps"],
            "unit": "req/sec across a live hot-swap",
            "n_connections": 64,
            "flipped_to": flipped_version,
            "requests_through_swap": swapped["requests"],
            "conn_errors": swapped["conn_errors"],
            "http_errors": swapped["http_errors"],
            "post_flip_recompiles": post_flip_recompiles,
            "digest_verified": (swap_state.get("staged") or {}).get(
                "digest_verified"),
            "warmed_buckets": (swap_state.get("staged") or {}).get(
                "warmed_buckets"),
            "p50_ms": swapped["p50_ms"], "p99_ms": p99_swap,
            "no_swap_baseline": {"rps": base["rps"],
                                 "p50_ms": base["p50_ms"],
                                 "p99_ms": p99_base},
            "p99_delta_ms": round(p99_swap - p99_base, 3),
            "recompiles_before_swap": recompiles_before,
            "passed": ok, "chip": _chip()}


def _transformer_train_bench(metric: str, batch: int, seq: int):
    """Shared harness for the transformer train benches: GPT-small-ish
    dense config (~40M params) with the framework's mixed precision
    (bf16 projections/MLP/attention matmuls, f32 softmax/residuals —
    `transformer._compute_dtype`), one chip, dependent step chains + a
    scalar loss fetch with long/short slope (see
    _device_seconds_per_batch for why).

    Analytic train FLOPs (PaLM-appendix style): 6 x matmul-params x
    tokens + 12 x L x b x s^2 x d_attn for attention. XLA's
    cost_analysis matches this within ~1% on the all-XLA graph but
    cannot see inside pallas_call, so with the folded flash kernel in
    the path it would under-count; the analytic number is dtype- and
    kernel-independent. Informational baseline: 0.25 MFU (a healthy
    small-model training utilization).
    """
    import jax
    from mmlspark_tpu.models import transformer as T
    from mmlspark_tpu.parallel import MeshSpec, build_mesh

    cfg = T.TransformerConfig(vocab=32768, d_model=512, n_heads=8,
                              d_head=64, d_ff=2048, n_stages=1,
                              layers_per_stage=8, dtype="bfloat16")
    mesh = build_mesh(MeshSpec.from_dict({"data": 1}),
                      devices=[jax.devices()[0]])
    params = T.shard_params(T.init_params(cfg, seed=0), cfg, mesh)
    velocity = jax.tree.map(lambda p: p * 0.0, params)
    rng = np.random.default_rng(0)
    tokens, labels, mask = T.make_batch(rng, cfg, batch, seq)
    step = T.build_spmd_train_step(cfg, mesh, learning_rate=0.01)

    L = cfg.n_stages * cfg.layers_per_stage
    d_attn = cfg.n_heads * cfg.d_head
    n_matmul = (cfg.d_model * cfg.vocab                  # vocab head
                + L * (4 * cfg.d_model * d_attn          # qkv + o proj
                       + 2 * cfg.d_model * cfg.d_ff))    # mlp
    flops_per_step = (6.0 * n_matmul * batch * seq
                      + 12.0 * L * batch * seq * seq * d_attn)

    state = {"p": params, "v": velocity}

    def run_chain(n):
        for _ in range(n):
            state["p"], state["v"], loss = step(state["p"], state["v"],
                                                tokens, labels, mask)
        float(loss)

    sec_per_step = _chain_slope_seconds(run_chain, 2, 12)
    tput = batch * seq / sec_per_step
    chip = _chip()
    out = {"metric": metric, "value": round(tput, 1),
           "unit": "tokens/sec/chip", "batch": batch, "seq": seq,
           "ms_per_step": round(1000 * sec_per_step, 1), "chip": chip}
    peak = _peak_bf16_tflops(chip)
    achieved = flops_per_step / sec_per_step / 1e12
    out["achieved_tflops"] = round(achieved, 2)
    if peak:
        out["mfu"] = round(achieved / peak, 4)
        out["baseline"] = 0.25
        out["vs_baseline"] = round(out["mfu"] / 0.25, 3)
    return out


def bench_transformer_train():
    """SPMD transformer LM train step on one chip: tokens/sec + MFU.

    The framework's beyond-parity flagship (5-axis dp/tp/pp/sp/ep
    transformer, `models/transformer.py`) at b8 x s1024 — the folded
    flash-attention regime (`parallel/pallas_attention.py`).
    """
    return _transformer_train_bench("transformer_train_v1", 8, 1024)


def bench_transformer_train_long():
    """Long-context single-chip train step: the same model at seq 4096
    (batch 2 — constant tokens/step vs the s1024 config).

    Long context is where attention's S^2 terms take over; this is the
    regime the folded flash kernel exists for (nothing (S x S) ever
    reaches HBM in either direction) — measured 4.3x over XLA dense
    attention at this shape (tools/probe_transformer_perf.py:
    0.55 vs 0.13 MFU).
    """
    return _transformer_train_bench("transformer_train_long_v1", 2, 4096)


def bench_moe_train():
    """MoE transformer train step, experts ON: tokens/sec/chip + MFU.

    Production shape: 8 experts, Mixtral-style top-2 routing, capacity
    dispatch (factor 1.25 — per-token expert FLOPs scale with
    factor x k, not E), Switch balance aux + router z-loss. Same
    measurement methodology as ``transformer_train_v1``. The analytic
    FLOPs count the EXECUTED expert matmuls (E x C slots = factor x k
    x tokens), so padding waste inside under-filled expert queues
    counts against MFU — an honest utilization figure. Informational
    baseline: 0.2 MFU (capacity dispatch trades some utilization for
    bounded memory/compute; a dense-dispatch config would show higher
    MFU only by burning E x more FLOPs per token —
    `docs/artifacts/moe_dispatch.json` records that comparison).
    """
    import jax
    from mmlspark_tpu.models import transformer as T
    from mmlspark_tpu.parallel import MeshSpec, build_mesh

    cfg = T.TransformerConfig(vocab=32768, d_model=512, n_heads=8,
                              d_head=64, d_ff=2048, n_stages=1,
                              layers_per_stage=8, dtype="bfloat16",
                              n_experts=8, moe_top_k=2,
                              moe_capacity_factor=1.25,
                              moe_aux_weight=0.01, moe_zloss_weight=1e-3)
    mesh = build_mesh(MeshSpec.from_dict({"data": 1}),
                      devices=[jax.devices()[0]])
    batch, seq = 8, 1024
    params = T.shard_params(T.init_params(cfg, seed=0), cfg, mesh)
    velocity = jax.tree.map(lambda p: p * 0.0, params)
    rng = np.random.default_rng(0)
    tokens, labels, mask = T.make_batch(rng, cfg, batch, seq)
    step = T.build_spmd_train_step(cfg, mesh, learning_rate=0.01)

    L = cfg.n_stages * cfg.layers_per_stage
    d_attn = cfg.n_heads * cfg.d_head
    expert_macs = cfg.moe_capacity_factor * cfg.moe_top_k \
        * 2 * cfg.d_model * cfg.d_ff            # executed w1+w2 slots/token
    n_matmul = (cfg.d_model * cfg.vocab
                + L * (4 * cfg.d_model * d_attn
                       + cfg.d_model * cfg.n_experts   # router
                       + expert_macs))
    tokens_per_step = batch * seq
    flops_per_step = (6.0 * n_matmul * tokens_per_step
                      + 12.0 * L * batch * seq * seq * d_attn)

    state = {"p": params, "v": velocity}

    def run_chain(n):
        for _ in range(n):
            state["p"], state["v"], loss = step(state["p"], state["v"],
                                                tokens, labels, mask)
        float(loss)

    sec_per_step = _chain_slope_seconds(run_chain, 2, 12)
    tput = batch * seq / sec_per_step
    chip = _chip()
    out = {"metric": "moe_train_v1", "value": round(tput, 1),
           "unit": "tokens/sec/chip", "batch": batch, "seq": seq,
           "n_experts": cfg.n_experts, "top_k": cfg.moe_top_k,
           "capacity_factor": cfg.moe_capacity_factor,
           "ms_per_step": round(1000 * sec_per_step, 1), "chip": chip}
    peak = _peak_bf16_tflops(chip)
    achieved = flops_per_step / sec_per_step / 1e12
    out["achieved_tflops"] = round(achieved, 2)
    if peak:
        out["mfu"] = round(achieved / peak, 4)
        out["baseline"] = 0.20
        out["vs_baseline"] = round(out["mfu"] / 0.20, 3)
    return out


def bench_telemetry_overhead():
    """Telemetry hot-path overhead: ns per counter increment and per
    histogram observe (plus a StageTimings span, the serving plane's
    per-stage unit of work). The registry sits on every serving batch,
    train step, and HTTP send, so a regression here taxes every hot
    path at once — the acceptance budget is < 2 us (2000 ns) per
    update; vs_baseline = budget / measured (counter).
    """
    from mmlspark_tpu.core.profiling import StageTimings
    from mmlspark_tpu.core.telemetry import MetricsRegistry

    def per_op_ns(fn, n=200_000, rounds=3):
        best = float("inf")
        for _ in range(rounds):
            t0 = time.perf_counter_ns()
            for _ in range(n):
                fn()
            best = min(best, (time.perf_counter_ns() - t0) / n)
        return best

    reg = MetricsRegistry()
    counter = reg.counter("bench_total", labels=("k",)).labels("hot")
    hist = reg.histogram("bench_ms").labels()
    timings = StageTimings()

    def span():
        with timings.span("hot"):
            pass

    counter_ns = per_op_ns(counter.inc)
    observe_ns = per_op_ns(lambda: hist.observe(3.7))
    span_ns = per_op_ns(span, n=50_000)
    budget = 2000.0
    return {"metric": "telemetry_overhead_v1",
            "value": round(counter_ns, 1), "unit": "ns/counter_inc",
            "histogram_observe_ns": round(observe_ns, 1),
            "stage_span_ns": round(span_ns, 1),
            "baseline": budget,
            "vs_baseline": round(budget / max(counter_ns, 1e-9), 3),
            "chip": _chip()}


def bench_tracing_overhead():
    """Span-tracing hot-path overhead: ns per recorded span (start +
    finish, landing in the flight recorder's ring) for child spans, the
    contextmanager form, and completed-child ``add`` (the serving
    plane's per-request per-stage record), plus the ring's sustained
    record throughput. The tracer now sits on every serving request,
    pipeline stage, and train step — budget < 4 us (4000 ns) per span
    lifecycle: 2x the metrics-update budget, because a span is two
    timed clock reads + an object + a striped ring store where a
    counter inc is one locked add (same 2x precedent as the
    StageTimings span). vs_baseline = budget / measured (start+finish).
    """
    from mmlspark_tpu.core.tracing import Tracer

    def per_op_ns(fn, n=100_000, rounds=3):
        best = float("inf")
        for _ in range(rounds):
            t0 = time.perf_counter_ns()
            for _ in range(n):
                fn()
            best = min(best, (time.perf_counter_ns() - t0) / n)
        return best

    tracer = Tracer(default_slow_ms=None)   # never capture: hot path only
    root = tracer.start("bench_root", route="bench")

    def start_finish():
        tracer.finish(tracer.start("child", parent=root))

    def ctx():
        with tracer.span("child"):
            pass

    now = tracer.clock.now()

    def add():
        tracer.add("child", now, now, parent=root)

    span_ns = per_op_ns(start_finish)
    ctx_ns = per_op_ns(ctx, n=50_000)
    add_ns = per_op_ns(add)
    budget = 4000.0
    return {"metric": "tracing_overhead_v1",
            "value": round(span_ns, 1), "unit": "ns/span",
            "ctx_span_ns": round(ctx_ns, 1),
            "add_child_ns": round(add_ns, 1),
            "ring_records_per_s": round(1e9 / max(add_ns, 1e-9), 0),
            "baseline": budget,
            "vs_baseline": round(budget / max(span_ns, 1e-9), 3),
            "chip": _chip()}


def bench_trace_propagation():
    """Distributed-trace context propagation overhead: ns per
    inject+extract round trip — the full header tax one cross-process
    hop pays (egress stamps ``X-Trace-Id`` + ``X-Parent-Span-Id`` onto
    the request's headers; ingress sanitizes the trace id and strictly
    parses the parent span id). This runs once per egress ATTEMPT, so
    a failover schedule pays it per worker tried — budget < 2 us/hop
    (the telemetry-update budget: propagation must stay invisible next
    to any real network send). vs_baseline = budget / measured.
    """
    from mmlspark_tpu.core.tracing import (
        Tracer, extract_span_context, inject_span_context,
    )

    # best-of-rounds: the quantity is the code's cost, not the host's
    # scheduling noise — a loaded box swings per-op times ~2x between
    # rounds, and a budget check must not flake on that
    def per_op_ns(fn, n=100_000, rounds=7):
        best = float("inf")
        for _ in range(rounds):
            t0 = time.perf_counter_ns()
            for _ in range(n):
                fn()
            best = min(best, (time.perf_counter_ns() - t0) / n)
        return best

    tracer = Tracer(default_slow_ms=None)
    span = tracer.start("http_egress", trace_id="bench-hop-trace",
                        route="bench")
    base_headers = {"Content-Type": "application/json",
                    "X-Request-Id": "bench-rid"}

    def hop():
        extract_span_context(inject_span_context(base_headers, span))

    def inject_only():
        inject_span_context(base_headers, span)

    wired = inject_span_context(base_headers, span)

    def extract_only():
        extract_span_context(wired)

    hop_ns = per_op_ns(hop)
    budget = 2000.0
    return {"metric": "trace_propagation_overhead_v1",
            "value": round(hop_ns, 1), "unit": "ns/hop",
            "inject_ns": round(per_op_ns(inject_only), 1),
            "extract_ns": round(per_op_ns(extract_only), 1),
            "baseline": budget,
            "vs_baseline": round(budget / max(hop_ns, 1e-9), 3),
            "chip": _chip()}


def bench_slo_overhead():
    """SLO-plane overhead (ISSUE 18 acceptance gate): the decode
    timeline's per-token stamping cost and a full burn-rate
    ``evaluate()`` over a populated history.

    Two numbers, two budgets:

    * **stamping** — the hot-loop timeline cost per emitted token is
      two attribute stores, a list append, and a counter bump (the
      TTFT/TPOT histograms are fed once per request at ``_finish``,
      never per token); budget <= 1 us/token, the same gate the
      perf-marked test pins.
    * **evaluation** — one ``SLOEngine.evaluate()`` pass over the full
      default worker policy set with an hour of 5 s samples in
      history; it runs only when ``GET /alerts`` / ``GET /slo`` asks,
      so the budget is scrape-interval scale: <= 50 ms (it measures in
      the tens of MICROseconds).

    ``vs_baseline`` = stamping budget / measured; ``passed`` gates
    BOTH budgets.
    """
    import threading

    from mmlspark_tpu.core.resilience import ManualClock
    from mmlspark_tpu.core.telemetry import MetricsRegistry
    from mmlspark_tpu.models import transformer as T
    from mmlspark_tpu.serving import DecodeScheduler, TransformerDecoder
    from mmlspark_tpu.serving.decode import _DecodeRequest
    from mmlspark_tpu.serving.slo import SLOEngine, SLOPolicy

    # -- stamping: mirror tests/test_serving_slo.py TestStampingBudget
    cfg = T.TransformerConfig(vocab=64, d_model=16, n_heads=1,
                              d_head=16, d_ff=32, n_stages=1,
                              layers_per_stage=1)
    decoder = TransformerDecoder(T.init_params(cfg, seed=0), cfg,
                                 n_slots=2, max_len=16)
    sched = DecodeScheduler(decoder)

    class _Pending:
        def __init__(self):
            self.payload = {"prompt": [1]}
            self.rid = "bench"
            self.deadline = None
            self.event = threading.Event()
            self.callbacks = []
            self.reply = None
            self.status = 200
            self.span = None
            self.trace = "bench"

    req = _DecodeRequest(_Pending(),
                         *sched.parse({"prompt": [1, 2, 3],
                                       "max_new_tokens": 4}))
    n = 200_000
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter_ns()
        for _ in range(n):
            t = 1.0
            req.t_last = t
            req.produced.append(7)
            sched.n_tokens += 1
        best = min(best, (time.perf_counter_ns() - t0) / n)
        del req.produced[:]
    stamp_ns = best

    # -- evaluation: availability + TTFT-latency policies over an hour
    # of history, counters AND histogram buckets moving every sample
    clock = ManualClock()
    reg = MetricsRegistry(clock=clock)
    total = reg.counter("req_total", "t.", labels=("worker",))
    bad = reg.counter("err_total", "e.", labels=("worker",))
    ttft = reg.histogram("ttft_ms", "f.", labels=("route",))
    eng = SLOEngine(reg, [
        SLOPolicy("availability", "availability", 0.999,
                  total_metric="req_total", bad_metric="err_total"),
        SLOPolicy("ttft", "latency", 0.95, metric="ttft_ms",
                  threshold_ms=500.0),
    ], clock=clock)
    for i in range(720):                     # 1 h of 5 s samples
        total.labels(f"w{i % 3}").inc(50)
        if i % 40 == 0:
            bad.labels(f"w{i % 3}").inc(1)
        ttft.labels("decode").observe(120.0 + (i % 7) * 90.0)
        clock.advance(5.0)
        eng.evaluate()
    t0 = time.perf_counter_ns()
    rounds = 200
    for _ in range(rounds):
        clock.advance(5.0)
        eng.evaluate()
    eval_us = (time.perf_counter_ns() - t0) / rounds / 1e3

    stamp_budget_ns = 1000.0
    eval_budget_us = 50_000.0
    ok = stamp_ns < stamp_budget_ns and eval_us < eval_budget_us
    return {"metric": "slo_overhead_v1",
            "value": round(stamp_ns, 1), "unit": "ns/token_stamp",
            "evaluate_us": round(eval_us, 1),
            "eval_budget_us": eval_budget_us,
            "history_samples": 720, "n_policies": 2,
            "baseline": stamp_budget_ns,
            "vs_baseline": round(stamp_budget_ns / max(stamp_ns, 1e-9),
                                 3),
            "passed": ok, "chip": _chip()}


def bench_tsdb_overhead():
    """Retrospective-plane overhead (ISSUE 19 acceptance gate): the
    embedded TSDB must observe the server without becoming a workload
    of its own.

    Three gates:

    * **ingest** — one full scrape+ingest tick over a loaded registry
      (10 histogram families x 8 children + 200 counter children,
      ~760 ingest rows — more series than a real worker exposes) must
      average under the Recorder's 25 ms default budget;
    * **bounded memory** — a two-hour synthetic run at the 10 s scrape
      cadence holds the per-tier point count FLAT between the one-hour
      and two-hour marks (retention evicts exactly as fast as ingest
      adds: memory is retention/resolution per series, not runtime);
    * **query** — a full-retention ``query_range`` (rate over every
      series, 30 min window, 60 s steps) answers inside one 10 s
      scrape interval.

    ``vs_baseline`` = ingest budget / measured; ``passed`` gates all
    three.
    """
    from mmlspark_tpu.core.resilience import ManualClock
    from mmlspark_tpu.core.telemetry import MetricsRegistry
    from mmlspark_tpu.core.tsdb import TimeSeriesStore, take_scrape

    clock = ManualClock()
    reg = MetricsRegistry(clock=clock)
    hists = [reg.histogram(f"h{i}_ms", "x", labels=("k",),
                           buckets=(1.0, 5.0, 25.0, 100.0))
             for i in range(10)]
    ctrs = [reg.counter(f"c{i}_total", "x", labels=("k",))
            for i in range(20)]
    for h in hists:
        for j in range(8):
            h.labels(str(j)).observe(float(j))
    for c in ctrs:
        for j in range(10):
            c.labels(str(j)).inc()

    # -- ingest: mean scrape+ingest over live ticks at the loaded
    # registry, with the sources still moving between scrapes
    store = TimeSeriesStore()
    n_rows = store.ingest(take_scrape(reg, at=0.0))
    rounds = 50
    t0 = time.perf_counter_ns()
    for i in range(1, rounds + 1):
        ctrs[i % 20].labels(str(i % 10)).inc()
        hists[i % 10].labels(str(i % 8)).observe(float(i % 90))
        store.ingest(take_scrape(reg, at=float(i)))
    ingest_ms = (time.perf_counter_ns() - t0) / rounds / 1e6

    # -- bounded memory: 7 h of 10 s ticks; the coarsest default tier
    # retains 6 h, so the point count must be FLAT between the 6 h
    # and 7 h marks (every tier past its retention by then)
    def _retained(st):
        return sum(len(ring) for s in st._series.values()
                   for ring in s.rings)

    marks = []
    for i in range(1, 2521):
        ctrs[0].labels("0").inc()
        store.ingest(take_scrape(reg, at=50.0 + i * 10.0))
        if i in (2160, 2520):
            marks.append(_retained(store))
    flat = marks[0] == marks[1]

    # -- query: full-retention range query over every counter series
    t0 = time.perf_counter_ns()
    n_series = 0
    for i in range(20):
        out = store.query_range(f"rate(c{i}_total[300s])",
                                start=-1800.0, step=60.0)
        n_series += len(out["series"])
    query_ms = (time.perf_counter_ns() - t0) / 1e6

    ingest_budget_ms = 25.0
    query_budget_ms = 10_000.0
    ok = (ingest_ms < ingest_budget_ms and flat
          and query_ms < query_budget_ms)
    return {"metric": "tsdb_overhead_v1",
            "value": round(ingest_ms, 3), "unit": "ms/scrape_ingest",
            "n_rows": n_rows, "points_6h": marks[0],
            "points_7h": marks[1], "rss_flat": flat,
            "query_range_ms": round(query_ms, 2),
            "query_series": n_series,
            "query_budget_ms": query_budget_ms,
            "baseline": ingest_budget_ms,
            "vs_baseline": round(ingest_budget_ms /
                                 max(ingest_ms, 1e-9), 3),
            "passed": ok, "chip": _chip()}


def bench_profiler_overhead():
    """Always-on sampling profiler overhead (ISSUE 20 acceptance
    gate): the postmortem plane's CPU sampler must be cheap enough to
    leave on in production.

    Two gates:

    * **throughput** — serving rps A/B with the profiler off vs on at
      the default 50 hz, interleaved rounds (off/on/off/on...) with
      the MEDIAN of each arm compared, so host drift lands on both
      arms: the on-arm must hold within 3% of the off-arm;
    * **flat memory** — a long synthetic run (3x the ring's capacity
      in samples) holds the sample ring EXACTLY at its cap and the
      interned-stack table flat between the 2x and 3x marks (the ring
      is a deque(maxlen), stacks are interned once — memory is
      retention x hz, not runtime).

    ``vs_baseline`` = measured delta / the 3% budget (<1 passes).
    """
    import threading

    from mmlspark_tpu.core.profiler import SamplingProfiler
    from mmlspark_tpu.serving import ServingServer
    from mmlspark_tpu.testing.load import drive_keepalive

    def run_arm(profiler_cfg):
        with ServingServer(_identity_model(), max_latency_ms=2,
                           max_batch_size=256, max_queue=4096,
                           cpu_profiler=profiler_cfg) as srv:
            srv.warmup({"x": 0.0})
            out = drive_keepalive(
                srv.host, srv.port, srv.api_path, b'{"x": 0.0}',
                n_connections=16, duration_s=2.0)
            return out["rps"]

    run_arm(False)                 # warm the stack off the record
    offs, ons = [], []
    for _ in range(5):
        offs.append(run_arm(False))
        ons.append(run_arm(None))  # None = the stock always-on 50 hz

    def med(xs):
        return sorted(xs)[len(xs) // 2]

    rps_off, rps_on = med(offs), med(ons)
    delta = (rps_off - rps_on) / max(rps_off, 1e-9)

    # -- flat memory: sample far past the ring's capacity and check
    # both bounds (ring pinned at maxlen, intern table flat once the
    # process's thread stacks have all been seen). A pair of busy
    # worker threads gives the sampler real stacks to intern —
    # sampling only an idle main thread would prove nothing.
    prof = SamplingProfiler(hz=50.0, retention_s=2.0)
    stop = threading.Event()

    def _churn():
        while not stop.is_set():
            sum(i * i for i in range(200))
            stop.wait(0.0005)

    workers = [threading.Thread(target=_churn, daemon=True)
               for _ in range(2)]
    for w in workers:
        w.start()
    cap = prof._ring.maxlen
    marks = []
    try:
        for i in range(1, cap * 3 + 1):
            prof.sample_once()
            if i in (cap * 2, cap * 3):
                marks.append((len(prof._ring), len(prof._stacks)))
    finally:
        stop.set()
        for w in workers:
            w.join(timeout=2)
    ring_flat = (marks[0][0] == cap and marks[1][0] == cap
                 and marks[0][1] > 0)
    # tolerance: late-arriving thread states may intern a few new
    # stacks between the marks, but growth must have saturated
    stacks_flat = (marks[1][1] - marks[0][1]) <= max(8, marks[0][1]
                                                     // 10)

    budget = 0.03
    ok = delta < budget and ring_flat and stacks_flat
    return {"metric": "profiler_overhead_v1",
            "value": round(delta * 100, 2), "unit": "% rps_delta",
            "rps_off": round(rps_off, 1), "rps_on": round(rps_on, 1),
            "rounds": 5, "hz": 50.0,
            "ring_cap": cap, "ring_flat": ring_flat,
            "stacks_2x": marks[0][1], "stacks_3x": marks[1][1],
            "stacks_flat": stacks_flat,
            "ewma_sample_ms": round(prof.ewma_sample_ms, 4),
            "baseline": budget * 100,
            "vs_baseline": round((delta * 100) / (budget * 100), 3),
            "passed": ok, "chip": _chip()}


def bench_decode_continuous():
    """Continuous batching for autoregressive decode vs the static
    whole-batch baseline (ISSUE 9 acceptance gate).

    One :class:`TransformerDecoder` (slot-indexed KV pool, donated
    cache, fixed-shape step) serves a seeded mixed-arrival workload —
    requests join and leave mid-flight — under both disciplines
    (``mmlspark_tpu.testing.decode_load``). The gates, in order of
    importance:

    * **zero post-warmup recompiles** — the continuous run's compile
      count stays flat however occupancy churns;
    * **zero steady-state device allocations** — the KV pool's buffer
      pointer never moves across steps (donation lands IN PLACE) and
      the device live-array count does not grow over the run;
    * **throughput** — continuous beats static on tokens/s at mixed
      arrival times (``vs_baseline`` = the ratio): static pays twice,
      waiting for stragglers before admitting arrivals AND padding
      the batch with early finishers.
    """
    from mmlspark_tpu.models import transformer as T
    from mmlspark_tpu.serving.decode import TransformerDecoder
    from mmlspark_tpu.testing.decode_load import (
        make_workload, run_continuous, run_static,
    )

    cfg = T.TransformerConfig(vocab=512, d_model=64, n_heads=4,
                              d_head=16, d_ff=256, n_stages=1,
                              layers_per_stage=4)
    params = T.init_params(cfg, seed=0)
    decoder = TransformerDecoder(params, cfg, n_slots=8, max_len=128)
    decoder.warmup()
    # heterogeneous token budgets + arrivals faster than a batch
    # drains: exactly the regime where whole-batch decode pays for
    # stragglers twice (arrivals wait for the drain, early finishers
    # pad the batch)
    jobs = make_workload(cfg.vocab, n_requests=48, seed=0,
                         mean_gap_ms=3.0, max_new=(4, 12, 40))
    static = run_static(decoder, jobs)
    cont = run_continuous(decoder, jobs)
    ratio = cont["tokens_per_s"] / max(static["tokens_per_s"], 1e-9)
    ok = (cont["post_warmup_recompiles"] == 0
          and cont["cache_buffer_stable"]
          and cont["live_array_growth"] == 0
          and ratio > 1.0)
    return {"metric": "decode_continuous_v1",
            "value": cont["tokens_per_s"], "unit": "tokens/sec",
            "n_requests": len(jobs), "n_slots": decoder.n_slots,
            "max_len": decoder.max_len,
            "continuous": cont, "static": static,
            "post_warmup_recompiles": cont["post_warmup_recompiles"],
            "cache_buffer_stable": cont["cache_buffer_stable"],
            "live_array_growth": cont["live_array_growth"],
            "baseline": static["tokens_per_s"],
            "vs_baseline": round(ratio, 3),
            "passed": ok, "chip": _chip()}


def bench_decode_speculative():
    """Speculative decoding vs plain single-token decode (ISSUE 11
    acceptance gate).

    The same paged target model serves the same greedy workload twice:
    once stepping one token per host round-trip, once with a
    1-layer truncated draft proposing ``spec_k`` tokens in ONE fused
    device program and the target verifying them in ONE width-k pass
    (``testing/decode_load.make_spec_model_pair`` constructs the
    trained-pair agreement regime the machinery is measured at — the
    acceptance rate is measured and gated, never assumed). Gates:

    * **tokens/s >= 1.3x** the non-speculative run;
    * **acceptance >= 0.6** (below that, speculation shouldn't win —
      and the SpeculationPolicy would turn it off);
    * **exact greedy parity** — token-for-token equal sequences;
    * **zero post-warmup recompiles** across draft + verify shapes.
    """
    from mmlspark_tpu.models import transformer as T
    from mmlspark_tpu.serving.decode import (
        DecodeScheduler, TransformerDecoder,
    )
    from mmlspark_tpu.testing.decode_load import (
        make_spec_model_pair, make_workload, run_scheduler_sessions,
    )

    cfg = T.TransformerConfig(vocab=128, d_model=32, n_heads=2,
                              d_head=16, d_ff=64, n_stages=1,
                              layers_per_stage=4)
    params, draft_params, draft_cfg = make_spec_model_pair(
        cfg, draft_layers=1)
    jobs = make_workload(cfg.vocab, n_requests=24, seed=0,
                         mean_gap_ms=0.0, prompt_lens=(4, 6, 8),
                         max_new=(16, 24, 32))

    def run(decoder):
        sched = DecodeScheduler(decoder,
                                max_waiting=len(jobs) + 1).start()
        try:
            decoder.warmup()
            return run_scheduler_sessions(sched, jobs)
        finally:
            sched.stop()

    plain = run(TransformerDecoder(params, cfg, n_slots=4,
                                   max_len=64))
    spec = run(TransformerDecoder(params, cfg, n_slots=4, max_len=64,
                                  draft_params=draft_params,
                                  draft_cfg=draft_cfg, spec_k=6))
    ratio = spec["tokens_per_s"] / max(plain["tokens_per_s"], 1e-9)
    parity = plain["sequences"] == spec["sequences"]
    acc = spec.get("acceptance_rate") or 0.0
    ok = (ratio >= 1.3 and acc >= 0.6 and parity
          and spec["post_warmup_recompiles"] == 0
          and spec["slots_all_freed"] and spec["pages_all_freed"]
          and plain["errors"] == spec["errors"] == 0)
    strip = lambda d: {k: v for k, v in d.items()  # noqa: E731
                       if k != "sequences"}
    return {"metric": "decode_speculative_v1",
            "value": spec["tokens_per_s"], "unit": "tokens/sec",
            "baseline": plain["tokens_per_s"],
            "vs_baseline": round(ratio, 3),
            "acceptance_rate": acc,
            "spec_rounds": spec.get("spec_rounds"),
            "spec_k": 6, "draft_layers": 1,
            "token_parity": parity,
            "post_warmup_recompiles": spec["post_warmup_recompiles"],
            "plain": strip(plain), "speculative": strip(spec),
            "passed": ok, "chip": _chip()}


def bench_decode_prefix_cache():
    """Cross-request prefix cache vs prefix-cache-off (ISSUE 15
    acceptance gate).

    Multi-tenant prompts overlap heavily — shared system preambles,
    few-shot templates — yet a cache-off decode plane prefills every
    prompt from token 0. The radix-indexed page cache attaches the
    longest cached prefix by REFERENCE (refcounted shared pages) and
    computes only the uncached suffix. Both arms serve the SAME seeded
    70 %-shared-prefix workload (``make_workload(prefix_share=...)``)
    through live schedulers. Gates, in order:

    * **>= 1.5x prefill tokens/s** (prompt tokens per prefill
      wall-second; equivalently lower TTFT) for the cached arm;
    * **token-for-token parity** across greedy, seeded-sampled, and
      speculative decode — offset prefill over shared pages is exact,
      not approximate;
    * **zero steady-state recompiles** in the cached arm (hit depth
      is data, not shape: one compile per suffix bucket, all warmed);
    * **refcount ledger clean after churn** — three back-to-back
      workloads with publication + LRU eviction pressure end with
      every claimable page free or index-held exactly once.
    """
    from mmlspark_tpu.models import transformer as T
    from mmlspark_tpu.serving.decode import (
        DecodeScheduler, TransformerDecoder,
    )
    from mmlspark_tpu.testing.decode_load import (
        make_spec_model_pair, make_workload, run_scheduler_sessions,
    )

    cfg = T.TransformerConfig(vocab=512, d_model=96, n_heads=4,
                              d_head=24, d_ff=384, n_stages=1,
                              layers_per_stage=6)
    params = T.init_params(cfg, seed=0)
    max_len, page = 128, 8
    # 70 % of prompts share one of two 104-token preambles; the rest
    # carry a unique same-length head (identical length distribution,
    # different overlap) — cycled 3-6 token suffixes on top. The
    # preamble-heavy shape (few-shot template + short user tail) is
    # exactly the traffic the cache targets.
    jobs = make_workload(cfg.vocab, n_requests=28, seed=0,
                         mean_gap_ms=0.0, prompt_lens=(3, 5, 6),
                         max_new=(4, 6, 8), prefix_share=0.7,
                         prefix_len=104, prefix_pool=2)
    sampled = {"temperature": 0.8, "top_k": 12, "seed": 1234}

    def build(prefix_on, spec=False):
        kw = {}
        pcfg = cfg
        p = params
        if spec:
            pcfg = T.TransformerConfig(vocab=128, d_model=32,
                                       n_heads=2, d_head=16, d_ff=64,
                                       n_stages=1, layers_per_stage=4)
            p, dp, dcfg = make_spec_model_pair(pcfg, draft_layers=1)
            kw = dict(draft_params=dp, draft_cfg=dcfg, spec_k=4)
        # pool = live working set (4 slots x 16 pages) + cache
        # headroom: the LRU bound keeps the two hot preambles
        # (2 x 13 pages) resident while unique-head residue churns
        # through eviction — both arms get the SAME pool so HBM is
        # held fixed across the A/B
        dec = TransformerDecoder(p, pcfg, n_slots=4, max_len=max_len,
                                 page_size=page,
                                 n_pages=1 + 4 * (max_len // page)
                                 + 120,
                                 prefix_cache=prefix_on, **kw)
        sched = DecodeScheduler(dec, max_waiting=256,
                                prefix_cache_pages=120).start()
        dec.warmup()
        return sched

    out = {"arms": {}}
    live = []
    try:
        # greedy A/B (the perf metric) then the seeded-sampled parity
        # probe on the SAME schedulers — the cached arm's second pass
        # hits the pages the first pass published (real churn)
        for name, prefix_on in (("off", False), ("on", True)):
            sched = build(prefix_on)
            live.append(sched)
            greedy = run_scheduler_sessions(sched, jobs,
                                            rid_prefix=f"g-{name}")
            samp = run_scheduler_sessions(sched, jobs,
                                          payload_extra=sampled,
                                          rid_prefix=f"s-{name}")
            out["arms"][name] = {"greedy": greedy, "sampled": samp}
        # speculative parity: the offset prefill must compose with the
        # draft/verify machinery (draft full-prefills its dense lane)
        sjobs = make_workload(128, n_requests=12, seed=1,
                              mean_gap_ms=0.0, prompt_lens=(3, 5),
                              max_new=(8, 12), prefix_share=0.7,
                              prefix_len=40, prefix_pool=2)
        for name, prefix_on in (("spec_off", False),
                                ("spec_on", True)):
            sched = build(prefix_on, spec=True)
            live.append(sched)
            out["arms"][name] = run_scheduler_sessions(
                sched, sjobs, rid_prefix=name)
    finally:
        for sched in live:
            sched.stop()
    a, b = out["arms"]["off"], out["arms"]["on"]
    ratio = (b["greedy"]["prefill_tokens_per_s"]
             / max(a["greedy"]["prefill_tokens_per_s"], 1e-9))
    parity = {
        "greedy": a["greedy"]["sequences"] == b["greedy"]["sequences"],
        "sampled": (a["sampled"]["sequences"]
                    == b["sampled"]["sequences"]),
        "speculative": (out["arms"]["spec_off"]["sequences"]
                        == out["arms"]["spec_on"]["sequences"]),
    }
    pc = b["sampled"]["prefix_cache"]       # after BOTH cached passes
    recompiles = (b["greedy"]["post_warmup_recompiles"]
                  + b["sampled"]["post_warmup_recompiles"]
                  + out["arms"]["spec_on"]["post_warmup_recompiles"])
    ledgers = (b["sampled"]["pages_all_freed"]
               and out["arms"]["spec_on"]["pages_all_freed"])
    errors = sum(arm.get("errors", 0) if "errors" in arm
                 else arm["greedy"]["errors"] + arm["sampled"]["errors"]
                 for arm in out["arms"].values())
    ok = (ratio >= 1.5
          and all(parity.values())
          and recompiles == 0
          and ledgers
          and pc["hits"] > 0 and pc["hit_tokens"] > 0
          and errors == 0)
    strip = lambda d: {k: v for k, v in d.items()  # noqa: E731
                       if k != "sequences"}
    return {"metric": "decode_prefix_cache_v1",
            "value": b["greedy"]["prefill_tokens_per_s"],
            "unit": "prefill tokens/sec @ 70% shared-prefix",
            "baseline": a["greedy"]["prefill_tokens_per_s"],
            "vs_baseline": round(ratio, 3),
            "mean_prefill_ms": {
                "off": a["greedy"]["mean_prefill_ms"],
                "on": b["greedy"]["mean_prefill_ms"]},
            "token_parity": parity,
            "hit_rate": pc["hit_rate"],
            "hit_tokens": pc["hit_tokens"],
            "cached_pages": pc["cached_pages"],
            "evicted_pages": pc["evicted_pages"],
            "post_warmup_recompiles": recompiles,
            "ledger_clean": ledgers,
            "off": {"greedy": strip(a["greedy"]),
                    "sampled": strip(a["sampled"])},
            "on": {"greedy": strip(b["greedy"]),
                   "sampled": strip(b["sampled"])},
            "speculative": {
                "off": strip(out["arms"]["spec_off"]),
                "on": strip(out["arms"]["spec_on"])},
            "passed": ok, "chip": _chip()}


def bench_prefill_flash():
    """Pallas flash prefill vs dense prefill (ISSUE 17 acceptance gate
    — ``prefill_flash_v1``).

    Dense prefill materializes the full ``[S, S]`` causal score matrix
    (and, on the prefix path, the gathered ``[S, V]`` virtual lane) in
    HBM for every layer of every prompt. The streaming-softmax Pallas
    kernel (``flash_prefill_attention`` /
    ``paged_prefix_prefill_attention``) carries (m, l, acc) in VMEM
    scratch across k-tiles instead, so prefill attention memory is
    O(S x tile), not O(S^2). Both arms serve the SAME seeded
    shared-prefix workload through live schedulers
    (``attn_impl="dense"`` vs the flash engine — ``"pallas"`` on TPU,
    ``"pallas_interpret"`` for CPU parity). Gates, in order:

    * **token-for-token parity** greedy, seeded-sampled, AND
      prefix-offset (the sampled pass re-runs the same prompts over
      pages the greedy pass published, so the flash arm's second pass
      is offset/partial prefill over shared pages — hits > 0 pinned);
    * **no [S, S] score tensor in the flash jaxpr** — the cold
      builders' ``[B, H, S, S]`` scores and the prefix builder's
      ``[S, H, V]`` lane scores appear in the dense trace and must NOT
      appear in the flash trace, across all three prefill builders;
    * **zero steady-state recompiles** in the flash arm across every
      pass (the kernel's grid is shape-static per bucket: hit depth
      and true length are data);
    * clean refcount ledger + zero request errors on both arms.

    Prefill tokens/s is reported for both arms; the >= 1.0x ratio is
    gated only when the kernel runs compiled (TPU) — interpret mode
    executes the kernel body as a Python loop on CPU, so the CPU
    sandbox carries a ``speedup_justification`` instead.
    """
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.models import transformer as T
    from mmlspark_tpu.parallel.pallas_attention import (
        paged_attention_available,
    )
    from mmlspark_tpu.serving.decode import (
        DecodeScheduler, TransformerDecoder,
    )
    from mmlspark_tpu.testing.decode_load import (
        make_workload, run_scheduler_sessions,
    )

    flash_impl = ("pallas" if paged_attention_available()
                  else "pallas_interpret")

    # -- jaxpr memory-shape evidence on a probe config sized so the
    # score shapes are textually unambiguous: S=256 self-attn scores
    # trace as "...,256,256]" (no other tensor has two adjacent
    # 256-axes — d_model/d_ff/vocab all differ), and the prefix
    # builder's lane scores as the exact [S, H, V] = [128,2,256]
    pcfg = T.TransformerConfig(vocab=512, d_model=48, n_heads=2,
                               d_head=16, d_ff=96, n_stages=1,
                               layers_per_stage=1)
    pp = T.init_params(pcfg, seed=0)
    S, page, pps = 256, 8, 32
    jaxpr_clean = {}

    def probe(builder_name, needles, argmaker, **bkw):
        build = getattr(T, builder_name)
        found = {}
        for impl in ("dense", flash_impl):
            fn = build(pcfg, donate=False, attn_impl=impl, **bkw)
            txt = str(jax.make_jaxpr(fn)(*argmaker()))
            found[impl] = any(n in txt for n in needles)
        # evidence only counts if the needle is REAL (dense shows it)
        # and the flash trace dropped it
        jaxpr_clean[builder_name] = (found["dense"]
                                     and not found[flash_impl])

    def cold_args():
        cache = {
            "k": jnp.zeros((1, 2, S, 2, 16), jnp.float32),
            "v": jnp.zeros((1, 2, S, 2, 16), jnp.float32)}
        return (pp, cache, jnp.zeros((S,), jnp.int32),
                jnp.int32(0), jnp.int32(S))

    def paged_args():
        cache = {
            "k": jnp.zeros((1, pps + 2, page, 2, 16), jnp.float32),
            "v": jnp.zeros((1, pps + 2, page, 2, 16), jnp.float32)}
        return (pp, cache, jnp.zeros((S,), jnp.int32),
                jnp.arange(1, pps + 1, dtype=jnp.int32), jnp.int32(S))

    def prefix_args():
        cache = {
            "k": jnp.zeros((1, pps + 2, page, 2, 16), jnp.float32),
            "v": jnp.zeros((1, pps + 2, page, 2, 16), jnp.float32)}
        return (pp, cache, jnp.zeros((128,), jnp.int32),
                jnp.arange(1, pps + 1, dtype=jnp.int32),
                jnp.int32(144), jnp.int32(16))

    probe("build_prefill", (",256,256]",), cold_args)
    probe("build_paged_prefill", (",256,256]",), paged_args,
          page_size=page, pages_per_slot=pps)
    # the gathered-lane scores [S, H, V]: einsum lowering may batch
    # the head axis first, so accept either layout
    probe("build_paged_prefix_prefill",
          ("[128,2,256]", "[2,128,256]"), prefix_args,
          page_size=page, pages_per_slot=pps)

    # -- the serving A/B: live schedulers, shared-prefix traffic
    cfg = T.TransformerConfig(vocab=256, d_model=64, n_heads=4,
                              d_head=16, d_ff=128, n_stages=1,
                              layers_per_stage=2)
    params = T.init_params(cfg, seed=0)
    max_len, page = 128, 8
    jobs = make_workload(cfg.vocab, n_requests=24, seed=0,
                         mean_gap_ms=0.0, prompt_lens=(3, 5, 6),
                         max_new=(4, 6, 8), prefix_share=0.6,
                         prefix_len=40, prefix_pool=2)
    sampled = {"temperature": 0.8, "top_k": 12, "seed": 1234}

    def build(impl):
        dec = TransformerDecoder(
            params, cfg, n_slots=4, max_len=max_len, page_size=page,
            n_pages=1 + 4 * (max_len // page) + 60,
            prefix_cache=True, attn_impl=impl)
        sched = DecodeScheduler(dec, max_waiting=256,
                                prefix_cache_pages=60).start()
        dec.warmup()
        return sched

    arms = {}
    live = []
    try:
        for name, impl in (("dense", "dense"), ("flash", flash_impl)):
            sched = build(impl)
            live.append(sched)
            greedy = run_scheduler_sessions(sched, jobs,
                                            rid_prefix=f"g-{name}")
            samp = run_scheduler_sessions(sched, jobs,
                                          payload_extra=sampled,
                                          rid_prefix=f"s-{name}")
            arms[name] = {"greedy": greedy, "sampled": samp,
                          "stats": sched.stats()}
    finally:
        for sched in live:
            sched.stop()
    a, b = arms["dense"], arms["flash"]
    parity = {
        "greedy": a["greedy"]["sequences"] == b["greedy"]["sequences"],
        "sampled": (a["sampled"]["sequences"]
                    == b["sampled"]["sequences"]),
    }
    pc = b["sampled"]["prefix_cache"]     # offset prefill exercised
    recompiles = (b["greedy"]["post_warmup_recompiles"]
                  + b["sampled"]["post_warmup_recompiles"])
    ledgers = (a["sampled"]["pages_all_freed"]
               and b["sampled"]["pages_all_freed"])
    errors = sum(arms[n][p]["errors"] for n in arms
                 for p in ("greedy", "sampled"))
    ratio = (b["greedy"]["prefill_tokens_per_s"]
             / max(a["greedy"]["prefill_tokens_per_s"], 1e-9))
    compiled = flash_impl == "pallas"
    justification = None if compiled else (
        "attn_impl=pallas_interpret executes the kernel body as a "
        "Python loop on CPU (no Mosaic compile target), so kernel "
        "throughput is not expressible in this sandbox; the gate "
        "carries token parity, the no-[S,S]-in-jaxpr evidence, and "
        "zero steady-state recompiles instead")
    ok = (all(parity.values())
          and all(jaxpr_clean.values())
          and recompiles == 0
          and ledgers
          and pc["hits"] > 0
          and errors == 0
          and (ratio >= 1.0 or not compiled)
          and b["stats"].get("attn_impl_prefill") == flash_impl)
    strip = lambda d: {k: v for k, v in d.items()  # noqa: E731
                       if k != "sequences"}
    return {"metric": "prefill_flash_v1",
            "value": b["greedy"]["prefill_tokens_per_s"],
            "unit": "prefill tokens/sec (flash arm)",
            "attn_impl": flash_impl,
            "baseline": a["greedy"]["prefill_tokens_per_s"],
            "vs_baseline": round(ratio, 3),
            "speedup_justification": justification,
            "token_parity": parity,
            "no_ss_in_jaxpr": jaxpr_clean,
            "offset_prefill_hits": pc["hits"],
            "post_warmup_recompiles": recompiles,
            "ledger_clean": ledgers,
            "stats_attn_impl_prefill":
                b["stats"].get("attn_impl_prefill"),
            "dense": {"greedy": strip(a["greedy"]),
                      "sampled": strip(a["sampled"])},
            "flash": {"greedy": strip(b["greedy"]),
                      "sampled": strip(b["sampled"])},
            "passed": ok, "chip": _chip()}


def bench_quantized_compute():
    """int8 on-device compute vs the f32 plane (ISSUE 17 acceptance
    gate — ``quantized_compute_v1``), staged through the live rollout
    machinery so a bad scale config rolls back automatically.

    Two live servers score the same traffic: the f32 arm serves the
    reference model; the quantized arm starts on the SAME f32 model as
    v1, then stages v2 with ``quantization={"wire_dtype": "none",
    "compute": {...}}`` — per-output-channel int8 weight scales
    computed once at stage time, f32 accumulate, activations bf16 —
    through stage -> quant-verify -> warm -> flip. Gates (``passed``):

    * the staged version's **row-wise parity report passed** (the
      ``rollout_quant_verify`` step: quantized forward vs f32
      reference within the config tolerance on a real frame);
    * **live-wire parity** between the arms within the same tolerance
      (``|q - f32| <= tol * max(|f32|, 1)`` row-wise);
    * **zero post-flip recompiles** — the staged quantized executable
      was warmed on every bucket before the flip;
    * **the rollback drill**: staging a deliberately corrupted scale
      config (``scale_multiplier=7``) must land in state ``error``
      WITHOUT flipping — the quantized v2 keeps serving and still
      answers 200 afterwards;
    * zero connection/http errors, and **>= 1.3x rps** over the f32
      arm — or the explicit ``speedup_justification`` on CPU, where
      XLA dequantizes int8 into an f32 GEMM (no int8 VNNI/MXU path)
      and the weight-dtype compute win is not expressible.
    """
    import requests as _requests
    from mmlspark_tpu.models.function import NNFunction
    from mmlspark_tpu.models.nn import NNModel
    from mmlspark_tpu.serving import ServingServer
    from mmlspark_tpu.testing.load import drive_keepalive

    d_in, tol = 512, 5e-2

    def make_model():
        fn = NNFunction.init({"builder": "mlp", "hidden": [128, 128],
                              "num_outputs": 8},
                             input_shape=(d_in,), seed=0)
        return NNModel(model=fn, input_col="x", output_col="y",
                       batch_size=256, cache_inputs=False,
                       data_parallel=False, input_dtype="float32")

    qdict = lambda **kw: {  # noqa: E731
        "wire_dtype": "none",
        "compute": dict({"weight_dtype": "int8",
                         "activation_dtype": "bfloat16",
                         "tolerance": tol}, **kw)}
    rng = np.random.default_rng(0)
    rows = rng.standard_normal((8, d_in)) * 0.5
    payload = json.dumps({"x": [float(v) for v in rows[0]]}).encode()

    def drive(srv):
        best, errs = None, {"conn_errors": 0, "http_errors": 0}
        for _ in range(3):
            out = drive_keepalive(srv.host, srv.port, srv.api_path,
                                  payload, n_connections=32,
                                  duration_s=2.0)
            for k in errs:
                errs[k] += out[k]
            if best is None or out["rps"] > best["rps"]:
                best = out
        return dict(best, **errs)

    def score_rows(srv):
        ys = []
        for r in rows:
            ys.append(_requests.post(
                srv.address, json={"x": [float(v) for v in r]},
                timeout=10).json()["y"])
        return np.asarray(ys, dtype=np.float64)

    # -- f32 reference arm
    with ServingServer(make_model(), max_latency_ms=2,
                       max_batch_size=256, max_queue=4096,
                       model_version="f32") as srv:
        srv.warmup(json.loads(payload.decode()))
        warm = srv.n_recompiles
        f32 = drive(srv)
        f32_rows = score_rows(srv)
        f32["recompiles_after_warmup"] = srv.n_recompiles - warm

    # -- quantized arm: f32 v1 -> stage v2q (verify + warm) -> flip
    with ServingServer(make_model(), max_latency_ms=2,
                       max_batch_size=256, max_queue=4096,
                       model_version="v1") as srv:
        srv.warmup(json.loads(payload.decode()))
        staged = srv.versions.stage(model=make_model(), version="v2q",
                                    quantization=qdict(), sync=True)
        quant_parity = staged.get("quant_parity")
        srv.versions.flip(version="v2q")
        quant = drive(srv)
        q_rows = score_rows(srv)
        active = srv.versions.active
        post_flip_recompiles = active.n_post_flip_recompiles
        flipped_version = active.version

        # -- rollback drill: a corrupted scale config must be refused
        # by the verify step, leaving v2q serving untouched
        broken = srv.versions.stage(
            model=make_model(), version="v3-broken",
            quantization=qdict(scale_multiplier=7.0), sync=True)
        rollback = {
            "staged_state": broken.get("state"),
            "error": (broken.get("error") or "")[:160],
            "active_after": srv.versions.active.version,
            "n_rollout_failures": srv.versions.n_rollout_failures,
            "still_serving": bool(_requests.post(
                srv.address, json=json.loads(payload.decode()),
                timeout=10).status_code == 200),
        }

    # int8 weight error is additive at output scale, so live parity
    # uses the verify step's semantics: tol bounds relative error on
    # O(1) outputs and absolute error near zero
    parity_ok = bool(np.isclose(q_rows, f32_rows,
                                rtol=tol, atol=tol).all())
    parity_max = float(np.abs(q_rows - f32_rows).max())
    ratio = quant["rps"] / max(f32["rps"], 1e-9)
    errors = sum(arm["conn_errors"] + arm["http_errors"]
                 for arm in (f32, quant))
    on_cpu = _chip().get("platform") == "cpu"
    justification = None if not on_cpu else (
        "CPU XLA lowers the int8 weights to dequantize-into-f32-GEMM "
        "(no int8 VNNI/MXU contraction path), so the weight-dtype "
        "compute win is not expressible in this sandbox; the gate "
        "carries verify-step parity, live-wire parity, zero post-flip "
        "recompiles, and the scale-corruption rollback drill instead")
    rollback_ok = (rollback["staged_state"] == "error"
                   and rollback["active_after"] == "v2q"
                   and rollback["n_rollout_failures"] >= 1
                   and rollback["still_serving"])
    ok = (bool((quant_parity or {}).get("passed"))
          and parity_ok
          and post_flip_recompiles == 0
          and flipped_version == "v2q"
          and rollback_ok
          and errors == 0
          and f32["recompiles_after_warmup"] == 0
          and (ratio >= 1.3 or on_cpu))
    return {"metric": "quantized_compute_v1",
            "value": round(ratio, 3), "unit": "x int8/f32 rps",
            "baseline": 1.3, "vs_baseline": round(ratio / 1.3, 3),
            "speedup_justification": justification,
            "rps_int8": quant["rps"], "rps_f32": f32["rps"],
            "p99_ms_int8": quant["p99_ms"],
            "p99_ms_f32": f32["p99_ms"],
            "verify_parity": quant_parity,
            "live_parity_ok": parity_ok,
            "live_parity_max_diff": parity_max,
            "tolerance": tol,
            "flipped_to": flipped_version,
            "post_flip_recompiles": post_flip_recompiles,
            "rollback_drill": rollback,
            "n_errors": errors,
            "passed": ok, "chip": _chip()}


def _spawn_evidence(argv, timeout: float):
    """Run a tools/* evidence harness in its OWN process (device-count
    XLA_FLAGS must precede backend init; this process's jax is live)
    and parse its last stdout line as the evidence JSON. Returns
    ``(rc, evidence_dict)`` — a timeout or unparseable output becomes
    a failed evidence dict (``passed: False``, which ``main`` turns
    into a non-zero exit) so the remaining entries still run.

    The harnesses are virtual-CPU-device drills by design, and this
    parent holds the chip once it has run a jitted bench (one process
    per chip): the child's platform is ASSIGNED, never inherited."""
    import subprocess
    import sys as _sys
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    try:
        proc = subprocess.run([_sys.executable] + argv,
                              capture_output=True, text=True, env=env,
                              timeout=timeout)
        line = (proc.stdout.strip().splitlines() or ["{}"])[-1]
        try:
            return proc.returncode, json.loads(line)
        except ValueError:
            return proc.returncode, {
                "passed": False,
                "error": proc.stdout[-2000:] or proc.stderr[-2000:]}
    except subprocess.TimeoutExpired as e:
        return 1, {"passed": False,
                   "error": f"{os.path.basename(argv[0])} timed out "
                            f"after {e.timeout}s"}


def bench_multihost_scaling():
    """Multi-device scaling + parity gate (ISSUE 10 acceptance).

    Spawns ``tools/bench_multihost.py --json`` in a subprocess (the
    virtual-device count and per-device threading are XLA_FLAGS that
    must be set before the backend initializes — this process's jax is
    already live) and gates on its evidence:

    * sharded train step is **loss/score-parity** with the
      single-device baseline on fixed seeds (pjit data x model
      NNLearner fit + tensor-parallel greedy decode token equality);
    * **zero post-warmup recompiles** in tensor-parallel serving
      dispatch (live server, ``tensor_parallel=2``, placement visible
      in /stats) and TP decode;
    * the **devices-vs-throughput curve** is emitted (1/2/4/8
      simulated devices), with >= 1.5x step throughput at 4 devices
      over 1 for the model-parallel-friendly config — or an explicit
      ``speedup_justification`` when the CPU sandbox can't express it;
    * sharded checkpoints **round-trip across a topology change**
      (2x2 save -> 4x1 and 1x1 restore, digests strict-verified).
    """
    rc, ev = _spawn_evidence(
        [os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "tools", "bench_multihost.py"),
         "--json", "--devices", "8", "--dcn"], timeout=1800)
    by_n = {c["devices"]: c["steps_per_s"]
            for c in ev.get("curve", ())}
    dcn = ev.get("dcn") or {}
    return {"metric": "multihost_scaling_v1",
            "value": by_n.get(4) or by_n.get(max(by_n) if by_n else 0, 0),
            "unit": "steps/sec@4dev",
            "curve": ev.get("curve"),
            "speedup_4x_vs_1": ev.get("speedup_4x_vs_1"),
            "speedup_justification": ev.get("speedup_justification"),
            "parity": ev.get("parity"),
            "tp_serving": ev.get("serving"),
            "checkpoint_topology": ev.get("checkpoint"),
            # the REAL multi-process story (ISSUE 14): the 2-process
            # gloo drill's smoke sub-result — cross-process psum, fit
            # parity, stage split across processes, cooperative save
            "dcn": {"passed": dcn.get("passed"),
                    "phases": {k: (v.get("ok") if isinstance(v, dict)
                                   else v)
                               for k, v in (dcn.get("phases")
                                            or {}).items()},
                    "checkpoint_restore": dcn.get("checkpoint_restore")},
            "baseline": by_n.get(1),
            "vs_baseline": ev.get("speedup_4x_vs_1"),
            "error": ev.get("error"),
            "passed": bool(ev.get("passed")) and rc == 0,
            "chip": _chip()}


def bench_multihost_pipeline():
    """Pipeline-parallel serving over mesh slices (ISSUE 14 acceptance
    — ``multihost_pipeline_v1``).

    Spawns ``tools/bench_multihost.py --phase pipeline`` (own process:
    the 2-virtual-device + one-eigen-thread XLA_FLAGS must precede
    backend init). Gates: a deep MLP REALLY partitioned into >= 2
    pipeline stages on distinct device slices
    (``NNModel(pipeline_parallel=2)``), row-parity with the fused
    forward, **zero post-warmup recompiles** through a live
    ServingServer (whose ``/stats`` carries the pipeline block),
    measured **bubble fraction** reported, and >= 1.25x rows/s vs
    serving the same model on a single stage's devices — or the
    explicit ``speedup_justification`` when the CPU sandbox cannot
    express inter-stage overlap (virtual slices share the host's
    cores; the satellite contract of ISSUE 14).
    """
    rc, ev = _spawn_evidence(
        [os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "tools", "bench_multihost.py"),
         "--json", "--phase", "pipeline"], timeout=900)
    return {"metric": "multihost_pipeline_v1",
            "value": ev.get("pipeline_rows_per_s"),
            "unit": "rows/sec",
            "n_stages": ev.get("n_stages"),
            "stages": ev.get("stages"),
            "bubble_ratio": ev.get("bubble_ratio"),
            "parity_max_diff": ev.get("parity_max_diff"),
            "post_warmup_recompiles": ev.get("post_warmup_recompiles"),
            "live_stats_pipeline_block":
                ev.get("live_stats_pipeline_block"),
            "speedup_vs_single_stage":
                ev.get("speedup_vs_single_stage"),
            "speedup_justification": ev.get("speedup_justification"),
            "baseline": ev.get("single_stage_rows_per_s"),
            "vs_baseline": ev.get("speedup_vs_single_stage"),
            "error": ev.get("error"),
            "passed": bool(ev.get("passed")) and rc == 0,
            "chip": _chip()}


def bench_multiprocess_dcn():
    """The 2-process DCN drill (ISSUE 14 acceptance —
    ``multiprocess_dcn_v1``): REAL cross-process collectives, not
    simulation.

    Spawns ``tools/launch_multiprocess.py``: two OS processes x 4
    virtual CPU devices join one jax.distributed runtime (gloo TCP
    collectives — XLA:CPU's default refuses multi-process outright)
    and must (a) execute a genuine cross-process psum through the
    ``put_batch`` / ``make_array_from_process_local_data`` path,
    (b) reproduce the single-process fit's scores to <= 1e-6 from
    per-host input sharding, (c) run the pjit train step with its two
    pipeline stages SPLIT ACROSS THE PROCESSES (stage-0 weights wholly
    on process 0), and (d) cooperatively save ONE sharded checkpoint
    from both processes that restores bit-exact in a single process
    (topology-change restore across process counts).
    """
    rc, ev = _spawn_evidence(
        [os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "tools", "launch_multiprocess.py"),
         "--json", "--timeout", "180"], timeout=900)
    phases = ev.get("phases") or {}
    return {"metric": "multiprocess_dcn_v1",
            "value": (phases.get("fit") or {}).get("max_score_diff"),
            "unit": "max_score_diff(2proc vs 1proc)",
            "psum": phases.get("psum"),
            "fit": phases.get("fit"),
            "pipe": phases.get("pipe"),
            "checkpoint_restore": ev.get("checkpoint_restore"),
            "baseline": 0.0,
            "vs_baseline": None,
            "error": ev.get("error"),
            "passed": bool(ev.get("passed")) and rc == 0,
            "chip": _chip()}


def bench_retrain_loop():
    """The retrain->redeploy loop end to end (ISSUE 12 acceptance).

    Two live workers + a coordinator serve a v1 MLP while background
    keep-alive-ish traffic runs; committed request/reply rows journal
    into the traffic capture; a ``fit_stream`` query trains the model
    from its own traffic — with an INJECTED CRASH of the streaming
    query between the trainer-sink write and the commit-log append,
    then a restart from the same checkpoints — and exports a
    digest-manifested checkpoint a ``RetrainLoop`` pushes through
    ``POST /rollout`` (canary on).

    Gates (``passed``): the loop COMPLETES (rollout ``completed``),
    the fleet ends version-coherent on the retrained checkpoint, ZERO
    dropped/wrong replies across the whole run (every request a
    well-formed 200 — zero downtime), and EXACTLY-ONCE sink counts
    across the injected crash (the replayed batch id is detected and
    skipped: no micro-batch trains twice). ``value`` is the
    traffic-to-redeployed wall-clock of the loop's rollout leg.
    """
    import tempfile
    import threading

    import requests

    from mmlspark_tpu.core.resilience import RetryPolicy
    from mmlspark_tpu.core.stage import PipelineStage
    from mmlspark_tpu.models.function import NNFunction
    from mmlspark_tpu.models.nn import NNModel
    from mmlspark_tpu.models.trainer import NNLearner
    from mmlspark_tpu.serving import (
        ServingCoordinator, ServingServer, TrafficCapture)
    from mmlspark_tpu.streaming import RetrainLoop, TrafficLogSource

    tmp = tempfile.mkdtemp(prefix="retrain_loop_")
    v1_dir = os.path.join(tmp, "v1")
    fn = NNFunction.init({"builder": "mlp", "hidden": [4],
                          "num_outputs": 1}, (2,), seed=0)
    NNModel(model=fn, input_col="x", output_col="scores").save(v1_dir)
    capdir = os.path.join(tmp, "cap")
    warm = {"x": [0.0, 0.0], "label": 0.0}

    def make_fit():
        learner = NNLearner(
            arch={"builder": "mlp", "hidden": [4], "num_outputs": 1},
            features_col="x", label_col="label", loss="squared_error",
            optimizer="adam", learning_rate=0.02, batch_size=16,
            checkpoint_dir=os.path.join(tmp, "train"))
        return learner.fit_stream(
            TrafficLogSource(capdir),
            export_dir=os.path.join(tmp, "exp"),
            # exports on a sane cadence (the trainer keeps running
            # through the rollout — per-batch exports would flood
            # hundreds of staging candidates); the exactly-once pin
            # rides the per-batch TRAIN-STATE checkpoint, which is
            # independent of the export cadence by design
            export_every_batches=8,
            checkpoint_dir=os.path.join(tmp, "wal"),
            max_batch_rows=16,
            retry_policy=RetryPolicy(max_attempts=1))

    cap = TrafficCapture(capdir)
    coord = ServingCoordinator().start()
    workers = []
    stop = threading.Event()
    results = {"ok": 0, "bad": 0}
    loop = None
    try:
        for i in range(2):
            srv = ServingServer(PipelineStage.load(v1_dir),
                                max_batch_size=4, max_latency_ms=1,
                                model_version="v1",
                                capture=cap if i == 0 else None,
                                slow_trace_ms=None)
            srv.warmup(warm)
            srv.start()
            ServingCoordinator.register_worker(
                f"http://{coord.host}:{coord.port}", srv.host, srv.port)
            workers.append(srv)

        rng = np.random.default_rng(3)

        def traffic():
            i = 0
            while not stop.is_set():
                x = rng.normal(size=2)
                try:
                    r = requests.post(
                        workers[i % 2].address,
                        json={"x": x.tolist(), "label": float(x.sum())},
                        timeout=10)
                    if r.status_code == 200 and "scores" in r.json():
                        results["ok"] += 1
                    else:
                        results["bad"] += 1
                except Exception:  # noqa: BLE001
                    results["bad"] += 1
                i += 1
                time.sleep(0.004)

        t = threading.Thread(target=traffic, daemon=True)
        t.start()
        t0 = time.perf_counter()

        # -- fit run 1, crashed between sink write and commit append
        fit = make_fit()
        inner = fit.query.sink
        crash_at = {"bid": None}

        class Crasher:
            def process(self, bid, df):
                inner.process(bid, df)
                if inner.n_batches_trained == 2 \
                        and crash_at["bid"] is None:
                    crash_at["bid"] = bid
                    raise RuntimeError("injected crash")

        fit.query.sink = Crasher()
        deadline = time.monotonic() + 60
        crashed = False
        while time.monotonic() < deadline and not crashed:
            try:
                fit.query.process_available()
            except RuntimeError:
                crashed = True
            time.sleep(0.02)
        run1 = inner.status()

        # -- fit run 2: restart from the same WAL + train checkpoints;
        # the crashed batch replays and is SKIPPED (exactly-once)
        fit2 = make_fit()
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline and not fit2.exports:
            fit2.query.process_available()
            time.sleep(0.02)
        run2 = fit2.status()["trainer"]
        replays = fit2.status()["query"]["n_replayed_batches"]

        # -- the retrain loop drives the rollout. A canary rollback
        # (box-noise p95 on a shared host) is the safety gate WORKING,
        # not a loop failure: keep training so newer exports appear
        # and the loop retries — the gate below waits for a COMPLETED
        # rollout. p95 ratio is relaxed vs the production default
        # because 20-request windows on a noisy sandbox are sparse.
        t_roll = time.perf_counter()
        loop = RetrainLoop(
            os.path.join(tmp, "exp"),
            f"http://{coord.host}:{coord.port}",
            warmup_payload=warm, poll_interval_s=0.1,
            rollout={"canary": True, "canary_min_requests": 20,
                     "canary_window_s": 5.0, "max_p95_ratio": 10.0,
                     "stage_timeout_s": 60.0}).start()
        # wait for a COMPLETED rollout: rollbacks/failures along the
        # way retry with the next export (that resilience IS the loop)
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline and loop.n_completed == 0:
            fit2.query.process_available()   # fresh exports keep coming
            time.sleep(0.1)
        loop.stop()
        redeploy_s = time.perf_counter() - t_roll
        total_s = time.perf_counter() - t0
        stop.set()
        t.join(timeout=10)

        # the loop may have pushed a SECOND (newer) export before
        # stop() landed: wait for the coordinator's in-flight rollout
        # to reach a terminal state before judging fleet coherence —
        # reading /version mid-flip is a harness race, not a finding
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            st = requests.get(
                f"http://{coord.host}:{coord.port}/rollout",
                timeout=5).json()
            if st.get("state") in ("idle", "completed", "rolled_back",
                                   "failed"):
                break
            time.sleep(0.1)
        versions = []
        for srv in workers:
            v = requests.get(f"http://{srv.host}:{srv.port}/version",
                             timeout=5).json()
            versions.append(v["active"]["version"])
        completed = [h["version"] for h in loop.status()["history"]
                     if h.get("state") == "completed"]
        if st.get("state") == "completed":
            completed.append(st["version"])
        # a trailing rolled-back push leaves the fleet on the last
        # COMPLETED version — that is the coherence target
        new_version = completed[-1] if completed else None
        exactly_once = (crashed and replays >= 1
                        and run2["n_replays_skipped"] >= 1
                        and run1["last_trained_batch"]
                        == crash_at["bid"])
        coherent = (len(set(versions)) == 1
                    and versions[0] == new_version)
        ok = (bool(completed) and coherent
              and results["bad"] == 0 and results["ok"] > 0
              and exactly_once)
    finally:
        stop.set()
        if loop is not None:
            # an exception mid-bench must not leave the loop's poll
            # thread warning at a dead coordinator for later benches
            loop.stop()
        for srv in workers:
            srv.stop()
        coord.stop()

    return {"metric": "retrain_loop_v1", "value": round(redeploy_s, 3),
            "unit": "seconds export->fleet-redeployed (canary incl.)",
            "loop_total_s": round(total_s, 3),
            "rollout_state": "completed" if completed else (
                (loop.status()["history"] or [{}])[-1].get("state")),
            "canary_rollbacks_along_the_way": loop.n_rolled_back,
            "new_version": new_version,
            "fleet_versions": versions,
            "version_coherent": coherent,
            "requests_ok": results["ok"],
            "requests_bad": results["bad"],
            "crash_injected_at_batch": crash_at["bid"],
            "replayed_batches": replays,
            "replays_skipped_by_trainer": run2["n_replays_skipped"],
            "rows_trained": run1["n_rows_trained"]
            + run2["n_rows_trained"],
            "batches_trained": run1["n_batches_trained"]
            + run2["n_batches_trained"],
            "exports": run2["n_exports"],
            "exactly_once": exactly_once,
            "capture": cap.status(),
            "passed": ok, "chip": _chip()}


BENCHES = [bench_gbdt_quantile, bench_adult_census, bench_cifar10_scoring,
           bench_cifar10_scoring_uint8, bench_imagenet_scoring,
           bench_transfer_learning, bench_distributed_sgd,
           bench_serving_latency, bench_serving_throughput,
           bench_serving_quantized,
           bench_serving_concurrency, bench_tenant_isolation,
           bench_model_swap,
           bench_transformer_train,
           bench_transformer_train_long, bench_moe_train,
           bench_telemetry_overhead, bench_tracing_overhead,
           bench_trace_propagation, bench_slo_overhead,
           bench_tsdb_overhead,
           bench_profiler_overhead,
           bench_decode_continuous,
           bench_decode_speculative,
           bench_decode_prefix_cache,
           bench_prefill_flash, bench_quantized_compute,
           bench_multihost_scaling, bench_retrain_loop,
           bench_multihost_pipeline, bench_multiprocess_dcn]


def main() -> None:
    """Run the selected entries, one JSON line each. Exits non-zero
    when any entry raised or reported ``passed: false`` — every entry
    still runs first, and a raised entry prints its own failed line."""
    import sys
    import traceback
    only = sys.argv[1] if len(sys.argv) > 1 else None
    selected = [fn for fn in BENCHES
                if only is None or only in fn.__name__]
    if not selected:
        names = ", ".join(fn.__name__ for fn in BENCHES)
        raise SystemExit(f"no benchmark matches {only!r}; choose from: {names}")
    failed = []
    for fn in selected:
        try:
            out = fn()
        except Exception as e:  # noqa: BLE001 — reported, then exit != 0
            traceback.print_exc()
            out = {"metric": fn.__name__, "passed": False,
                   "error": f"{type(e).__name__}: {e}"}
        print(json.dumps(out), flush=True)
        if out.get("passed") is False:
            failed.append(fn.__name__)
    if failed:
        raise SystemExit(f"bench.py: {len(failed)} of {len(selected)} "
                         f"entries failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
