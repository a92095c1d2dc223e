"""NNLearner: in-process data-parallel deep-net training on the mesh.

Capability parity with `src/cntk-train` (`CNTKLearner.scala:85-190`): an
Estimator that takes a labeled frame, trains a network with configurable
loss/optimizer/schedule (the role BrainScript configs play), and returns
an ``NNModel`` for scoring. The reference's entire data-export ->
ssh/scp -> `mpirun cntk` -> copy-model-back chain
(`CommandBuilders.scala:149-266`) collapses to a jitted train step with
sharding-induced ICI allreduce — zero processes, zero sockets, zero MPI.

Distribution: batches are sharded over the mesh's ``data`` axis
(per-host input sharding on a multi-process runtime — each host feeds
only its rows); params and optimizer state are replicated, or sharded
over ``model`` for tensor parallelism when ``mesh_shape`` names a
``model`` axis (:mod:`mmlspark_tpu.parallel.dist` owns the sharding
rule; XLA/GSPMD inserts the gradient allreduce and the TP collectives
from the ``NamedSharding`` annotations, and the train state is donated
through every step so the optimizer update lands in place). Step
checkpointing uses the native sharded store
(:mod:`mmlspark_tpu.io.checkpoint`): each device writes its own
shards, and a resume may use a different topology than the save.
"""

from __future__ import annotations

import math
import os
import time
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np

from mmlspark_tpu.core.dataframe import DataFrame
from mmlspark_tpu.core.params import (
    Param, HasLabelCol, HasFeaturesCol, in_set, in_range,
)
from mmlspark_tpu.core.stage import Estimator
from mmlspark_tpu.models.function import NNFunction
from mmlspark_tpu.models.nn import NNModel
from mmlspark_tpu.parallel import MeshSpec, build_mesh, pad_to_multiple

LOSSES = ("softmax_cross_entropy", "sigmoid_cross_entropy", "squared_error")
OPTIMIZERS = ("sgd", "momentum", "adam", "adamw")

_TRAINER_METRICS = None


def _metrics():
    """Process-registry training telemetry, bound lazily so importing
    the trainer costs nothing."""
    global _TRAINER_METRICS
    if _TRAINER_METRICS is None:
        from mmlspark_tpu.core.telemetry import REGISTRY, log_buckets
        _TRAINER_METRICS = {
            "step_ms": REGISTRY.histogram(
                "trainer_step_ms",
                "Host-loop wall-clock per train step (dispatch is "
                "async: mostly host+transfer time, with periodic "
                "device blocks when the in-flight window fills)."),
            "examples_per_sec": REGISTRY.histogram(
                "trainer_examples_per_sec",
                "Real (unpadded) examples per second per host-loop "
                "step.", buckets=log_buckets(1.0, 1e7)),
            # wider ladder than the request-latency default: a
            # multi-GB save/restore routinely takes 30-120 s, and a
            # 10 s top edge would collapse every sample into +Inf
            "ckpt_save_ms": REGISTRY.histogram(
                "trainer_checkpoint_save_ms",
                "Checkpoint save call wall-clock (per-shard writes + "
                "digest manifest).",
                buckets=log_buckets(10.0, 1e6)),
            "ckpt_restore_ms": REGISTRY.histogram(
                "trainer_checkpoint_restore_ms",
                "Checkpoint restore wall-clock.",
                buckets=log_buckets(10.0, 1e6)),
            "restarts": REGISTRY.counter(
                "trainer_restarts_total",
                "Bounded in-process fit restarts (restore + "
                "fast-forward) taken after step failures."),
        }
    return _TRAINER_METRICS


def make_loss(name: str) -> Callable:
    import jax.numpy as jnp
    import optax

    if name == "softmax_cross_entropy":
        def loss(logits, labels, weights):
            l = optax.softmax_cross_entropy_with_integer_labels(
                logits, labels.astype(jnp.int32))
            return jnp.sum(l * weights) / jnp.maximum(jnp.sum(weights), 1e-8)
    elif name == "sigmoid_cross_entropy":
        def loss(logits, labels, weights):
            l = optax.sigmoid_binary_cross_entropy(logits[..., 0], labels)
            return jnp.sum(l * weights) / jnp.maximum(jnp.sum(weights), 1e-8)
    elif name == "squared_error":
        def loss(logits, labels, weights):
            l = jnp.square(logits[..., 0] - labels)
            return jnp.sum(l * weights) / jnp.maximum(jnp.sum(weights), 1e-8)
    else:
        raise ValueError(f"unknown loss {name!r}; have {LOSSES}")
    return loss


def make_optimizer(name: str, learning_rate: float, momentum: float = 0.9,
                   weight_decay: float = 1e-4, clip_norm: float = 0.0):
    import optax
    if name == "sgd":
        tx = optax.sgd(learning_rate)
    elif name == "momentum":
        tx = optax.sgd(learning_rate, momentum=momentum)
    elif name == "adam":
        tx = optax.adam(learning_rate)
    elif name == "adamw":
        tx = optax.adamw(learning_rate, weight_decay=weight_decay)
    else:
        raise ValueError(f"unknown optimizer {name!r}; have {OPTIMIZERS}")
    if clip_norm and clip_norm > 0:
        tx = optax.chain(optax.clip_by_global_norm(clip_norm), tx)
    return tx


class NNLearner(Estimator, HasLabelCol, HasFeaturesCol):
    """Train an NNFunction on a labeled frame; returns an NNModel."""

    features_col = Param("features", "input column (vectors or images)", ptype=str)
    label_col = Param("label", "label column", ptype=str)
    weight_col = Param(None, "optional per-row weight column", ptype=str)
    arch = Param(None, "architecture config dict (builder + kwargs)", ptype=dict)
    model = Param(None, "optional warm-start NNFunction", complex=True)
    loss = Param("softmax_cross_entropy", "training loss",
                 validator=in_set(*LOSSES))
    optimizer = Param("momentum", "optimizer", validator=in_set(*OPTIMIZERS))
    learning_rate = Param(0.1, "peak learning rate", ptype=float)
    momentum = Param(0.9, "sgd momentum", ptype=float)
    weight_decay = Param(1e-4, "adamw weight decay", ptype=float)
    clip_norm = Param(0.0, "global-norm gradient clipping (0 = off); "
                      "guards deep-net fits against divergence at "
                      "aggressive peak learning rates", ptype=float)
    epochs = Param(10, "passes over the data", ptype=int)
    batch_size = Param(256, "global batch size", ptype=int)
    warmup_steps = Param(0, "linear LR warmup steps", ptype=int)
    cosine_decay = Param(True, "cosine-decay LR to 0 over training", ptype=bool)
    seed = Param(0, "init/shuffle seed", ptype=int)
    mesh_shape = Param(None, "mesh axes dict, e.g. {'data': -1}; a "
                       "'model' axis > 1 turns on tensor parallelism "
                       "(params + optimizer state sharded per "
                       "parallel/dist rules, XLA inserts the "
                       "collectives)", ptype=dict)
    checkpoint_dir = Param(None, "sharded step-checkpoint directory "
                           "(io/checkpoint native store)", ptype=str)
    checkpoint_every = Param(0, "steps between checkpoints (0 = off)", ptype=int)
    push_gateway_url = Param(None, "optional metrics remote-write URL "
                             "(Prometheus Pushgateway job path or any "
                             "endpoint accepting the text exposition): "
                             "a MetricsPusher POSTs the process "
                             "registry there on an interval during "
                             "fit, with a final flush when the fit "
                             "ends — a batch fit's telemetry reaches a "
                             "LIVE Prometheus even though the job "
                             "exits between scrapes (checkpoint-side "
                             ".prom snapshots remain the on-disk "
                             "fallback)", ptype=str)
    push_interval_s = Param(30.0, "seconds between remote-write pushes",
                            ptype=float, validator=in_range(lo=1.0))
    max_restarts = Param(2, "bounded in-process auto-restarts: when a "
                         "train step fails and checkpointing is "
                         "configured, restore the latest step "
                         "checkpoint and resume the SAME shuffle "
                         "stream (deterministic fast-forward); after "
                         "this many restores the error propagates — a "
                         "persistent fault must fail the fit, not loop "
                         "it", ptype=int)
    fault_injector = Param(None, "chaos-test hook: callable(global_step)"
                           " invoked before each host-loop step; "
                           "exceptions it raises exercise the bounded-"
                           "restart path (see testing.faults.FaultPlan."
                           "step_fault)", complex=True)
    log_every = Param(50, "steps between loss logs (0 = off)", ptype=int)
    device_resident = Param(False, "upload the dataset to the device ONCE "
                            "and run each epoch as one scanned device "
                            "program (batches gathered on device from an "
                            "uploaded permutation): one dispatch + one "
                            "loss fetch per epoch instead of a transfer "
                            "per step — the fit shape for high-latency "
                            "host<->device links (integer image data "
                            "stays integer on the wire and is "
                            "normalized on device). Single-data-shard "
                            "fits only; falls back otherwise", ptype=bool)
    augment = Param("none", "on-device per-batch augmentation: flip_crop "
                    "= random horizontal flip + random 4px translate "
                    "(the standard CIFAR recipe), applied inside the "
                    "jitted step", validator=in_set("none", "flip_crop"))

    # -- jitted step construction ------------------------------------------

    def build_train_step(self, module, tx, loss_fn):
        """(params, opt_state, batch) -> (params, opt_state, loss), jittable."""
        import jax

        def step(params, opt_state, x, y, w):
            def objective(p):
                logits = module.apply(p, x, train=True)
                return loss_fn(logits, y, w)

            loss, grads = jax.value_and_grad(objective)(params)
            updates, opt_state = tx.update(grads, opt_state, params)
            import optax
            params = optax.apply_updates(params, updates)
            return params, opt_state, loss

        return step

    @staticmethod
    def _augment_flip_crop(key, xb):
        """Random horizontal flip + random 4px translate, on device."""
        import jax
        import jax.numpy as jnp
        b, hgt, wid = xb.shape[0], xb.shape[1], xb.shape[2]
        k1, k2 = jax.random.split(key)
        flip = jax.random.bernoulli(k1, 0.5, (b,))
        xb = jnp.where(flip[:, None, None, None], xb[:, :, ::-1, :], xb)
        pad = 4
        padded = jnp.pad(xb, ((0, 0), (pad, pad), (pad, pad), (0, 0)),
                         mode="reflect")
        offs = jax.random.randint(k2, (b, 2), 0, 2 * pad + 1)

        def crop(img, o):
            return jax.lax.dynamic_slice(
                img, (o[0], o[1], 0), (hgt, wid, img.shape[-1]))

        return jax.vmap(crop)(padded, offs)

    def _fit_device_resident(self, x, y, w, fn, module, bs, tx, loss_fn):
        """Whole-epoch scanned training with a device-resident dataset.

        The per-step host loop below pays one host->device batch
        transfer and one dispatch per step — hundreds of host->device
        round-trips per epoch. Here the dataset
        (kept uint8 if it arrived uint8: 4x fewer link bytes than f32)
        is uploaded once, each epoch's shuffled batch indices are one
        small int32 upload, and ``lax.scan`` gathers + steps entirely
        on device: one dispatch and one loss fetch per epoch. The same
        shape as the fused GBDT fit (`gbdt/tree.py::boost_loop_device`).
        """
        import jax
        import jax.numpy as jnp

        # ONLY uint8 is treated as image bytes (x/255 + a uint8-tagged
        # scorer); other integer dtypes are plain numerics cast to f32 —
        # scaling counts by 1/255 and round-tripping them through uint8
        # at scoring time would silently corrupt values > 255
        is_int = x.dtype == np.uint8
        scale = np.float32(1.0 / 255.0) if is_int else np.float32(1.0)
        # datasets smaller than the batch keep working (the host loop
        # pads ragged batches; here the batch shrinks to the data)
        bs = min(bs, len(x))
        steps_per_epoch = max(len(x) // bs, 1)
        x_dev = jnp.asarray(x)
        y_dev = jnp.asarray(y)
        w_dev = jnp.asarray(w)
        step_fn = self.build_train_step(module, tx, loss_fn)
        aug = self.augment

        def epoch_fn(params, opt_state, key, perm):
            def body(carry, idx):
                p, o, k = carry
                k, k_aug = jax.random.split(k)
                xb = x_dev[idx].astype(jnp.float32) * scale
                if aug == "flip_crop":
                    xb = self._augment_flip_crop(k_aug, xb)
                p, o, loss = step_fn(p, o, xb, y_dev[idx], w_dev[idx])
                return (p, o, k), loss

            (params, opt_state, _), losses = jax.lax.scan(
                body, (params, opt_state, key), perm)
            return params, opt_state, losses

        epoch_jit = jax.jit(epoch_fn, donate_argnums=(0, 1))

        params = jax.device_put(fn.params)
        opt_state = tx.init(params)
        rng = np.random.default_rng(self.seed)
        n_use = steps_per_epoch * bs
        from mmlspark_tpu.core.tracing import ambient_tracer
        TRACER = ambient_tracer()
        for epoch in range(self.epochs):
            perm = rng.permutation(len(x))[:n_use].astype(np.int32) \
                .reshape(steps_per_epoch, bs)
            key = jax.random.PRNGKey(self.seed * 100003 + epoch)
            # the scanned fit's unit of work is the EPOCH (one dispatch
            # + one loss fetch), so that is its span granularity
            with TRACER.span("train_epoch", route="trainer",
                             epoch=epoch + 1,
                             steps=int(steps_per_epoch)):
                params, opt_state, losses = epoch_jit(
                    params, opt_state, key, jnp.asarray(perm))
            if self.log_every:
                print(f"[NNLearner] epoch {epoch + 1}/{self.epochs} "
                      f"mean loss {float(jnp.mean(losses)):.5f}")

        trained = NNFunction(arch=dict(fn.arch),
                             params=jax.device_get(params))
        # an integer-trained model's scorer must keep the same input
        # convention (uint8 in, /255 on device) or every consumer would
        # silently feed 0-255 floats into a net trained on [0, 1]
        extra = {"input_dtype": "uint8"} if is_int else {}
        return NNModel(model=trained, input_col=self.features_col,
                       output_col="scores", **extra)

    def _schedule(self, steps_per_epoch: int):
        import optax
        warmup = max(self.warmup_steps, 1)
        total = max(self.epochs * steps_per_epoch, warmup + 1)
        if self.cosine_decay:
            return optax.warmup_cosine_decay_schedule(
                0.0, self.learning_rate, warmup, total)
        if self.warmup_steps:
            return optax.linear_schedule(0.0, self.learning_rate,
                                         self.warmup_steps)
        return self.learning_rate

    # -- fit ----------------------------------------------------------------

    def fit(self, df: DataFrame) -> NNModel:
        if not self.push_gateway_url:
            return self._fit(df)
        # remote-write rides the whole fit: periodic pushes while the
        # host loop runs, one final flush in the finally (success OR
        # failure — a crashed fit's last counters are exactly the
        # telemetry worth having). Step/egress spans carry trace
        # context on any HTTP the fit fans out (io/http injects the
        # ambient train_step span), so pushed exemplars and captured
        # step traces stay correlated.
        from mmlspark_tpu.core.telemetry import MetricsPusher
        with MetricsPusher(self.push_gateway_url,
                           interval_s=self.push_interval_s):
            return self._fit(df)

    def _fit(self, df: DataFrame) -> NNModel:
        import jax
        import optax

        from mmlspark_tpu.models.nn import _stack_column
        # _stack_column preserves source dtype; training computes in
        # f32, but a device-resident fit keeps integer image data
        # integer ON THE LINK and normalizes on device
        x = _stack_column(df[self.features_col])
        # uint8 survives for BOTH paths (each normalizes /255 and tags
        # the scorer identically — a perf flag must never change the
        # learned function); every other dtype trains as f32
        if x.dtype != np.uint8:
            x = x.astype(np.float32, copy=False)
        y = np.asarray(df[self.label_col])
        w = (np.asarray(df[self.weight_col], dtype=np.float32)
             if self.weight_col else np.ones(len(y), dtype=np.float32))

        fn = self.model or NNFunction.init(self.arch, x.shape[1:],
                                           seed=self.seed)
        module = fn.module()

        from mmlspark_tpu.parallel.topology import in_single_device_scope
        if in_single_device_scope():
            # pinned-trial context (TuneHyperparameters trial_devices):
            # train on the thread's default device only
            dev = jax.config.jax_default_device or jax.local_devices()[0]
            mesh = build_mesh(MeshSpec.from_dict({"data": 1}),
                              devices=[dev])
        else:
            mesh = build_mesh(MeshSpec.from_dict(self.mesh_shape)
                              if self.mesh_shape else None)
        n_data = mesh.shape.get("data", 1)
        bs = max(self.batch_size - self.batch_size % n_data, n_data)
        steps_per_epoch = max(len(x) // bs, 1)

        tx = make_optimizer(self.optimizer, self._schedule(steps_per_epoch),
                            self.momentum, self.weight_decay,
                            self.clip_norm)
        loss_fn = make_loss(self.loss)
        if self.device_resident and n_data == 1 \
                and self._checkpoint_manager() is None:
            return self._fit_device_resident(x, y, w, fn, module, bs,
                                             tx, loss_fn)
        if self.augment != "none":
            import warnings
            warnings.warn(
                "augment is applied by the device-resident scanned fit "
                "only; this fit takes the per-step host loop "
                f"(device_resident={self.device_resident}, data shards="
                f"{n_data}, checkpointing="
                f"{self.checkpoint_dir is not None}) and trains WITHOUT "
                "augmentation", stacklevel=2)
        was_int = x.dtype == np.uint8        # image bytes only, as above
        if was_int:
            x = x.astype(np.float32) / 255.0   # host fallback normalizes
        step = jax.jit(self.build_train_step(module, tx, loss_fn),
                       donate_argnums=(0, 1))

        # state placement: replicated on a pure-data mesh (byte-for-byte
        # the pre-TP behavior — every spec degenerates to P() when no
        # model axis exists), model-sharded per the dist rule otherwise;
        # optimizer moments land with their param's layout because the
        # rule is shape-driven. The jitted step donates both trees, so
        # the sharded update happens in place in device memory.
        from mmlspark_tpu.parallel import dist as _dist
        repl = _dist.state_shardings(fn.params, mesh)
        params = jax.device_put(fn.params, repl)
        opt_state = tx.init(params)
        opt_repl = _dist.state_shardings(opt_state, mesh)
        opt_state = jax.device_put(opt_state, opt_repl)

        start_step = 0
        mngr = self._checkpoint_manager()
        template = None
        if mngr is not None:
            # host-side structure template, captured BEFORE any step
            # runs: the jitted step donates its params/opt_state
            # buffers, so after a mid-step fault the live buffers may
            # already be invalidated — restores must not depend on them
            template = {"params": jax.device_get(params),
                        "opt_state": jax.device_get(opt_state)}
        if mngr is not None and mngr.latest_step() is not None:
            raw_params, raw_opt, start_step = self._restore(mngr, template)
            params = jax.device_put(raw_params, repl)
            opt_state = jax.device_put(raw_opt, opt_repl)

        # -- fault-tolerant fit: a step failure (preempted chip, injected
        # chaos fault, failed checkpoint write) restores the latest
        # checkpoint and re-enters the SAME deterministic shuffle stream
        # (the fast-forward below), bounded by max_restarts so a
        # persistent fault still fails the fit
        restarts = 0
        while True:
            try:
                params, opt_state = self._host_loop(
                    x, y, w, step, mesh, params, opt_state, start_step,
                    steps_per_epoch, bs, n_data, mngr)
                break
            except Exception as e:  # noqa: BLE001 — classified below
                if isinstance(e, NotImplementedError):
                    raise   # a permanent capability gap, not a fault
                if mngr is None or restarts >= self.max_restarts:
                    raise
                restarts += 1
                _metrics()["restarts"].inc()
                latest = mngr.latest_step()
                print(f"[NNLearner] step failed ({type(e).__name__}: {e});"
                      f" restoring "
                      f"{'step ' + str(latest) if latest is not None else 'init'}"
                      f" (restart {restarts}/{self.max_restarts})")
                if latest is None:
                    params = jax.device_put(fn.params, repl)
                    opt_state = jax.device_put(tx.init(params), opt_repl)
                    start_step = 0
                else:
                    raw_params, raw_opt, start_step = \
                        self._restore(mngr, template)
                    params = jax.device_put(raw_params, repl)
                    opt_state = jax.device_put(raw_opt, opt_repl)

        trained = NNFunction(arch=dict(fn.arch), params=jax.device_get(params))
        # keep the training-time input convention (see _fit_device_resident)
        extra = {"input_dtype": "uint8"} if was_int else {}
        return NNModel(model=trained, input_col=self.features_col,
                       output_col="scores", **extra)

    def _host_loop(self, x, y, w, step, mesh, params, opt_state,
                   start_step, steps_per_epoch, bs, n_data, mngr):
        """One attempt at the per-step host loop, resumable at
        ``start_step``: the shuffle stream is regenerated from the seed
        and already-done steps are skipped, so every attempt sees the
        identical batch sequence (restart N reaches the same params an
        uninterrupted run does)."""
        import jax
        from mmlspark_tpu.parallel import dist as _dist

        from mmlspark_tpu.core.tracing import ambient_tracer
        TRACER = ambient_tracer()

        rng = np.random.default_rng(self.seed)
        metrics = _metrics()
        m_step, m_eps = metrics["step_ms"], metrics["examples_per_sec"]
        global_step = 0
        # ragged-tail staging reuse: the last batch of every epoch pads
        # to the data multiple through ONE buffer instead of a fresh
        # allocation per step (dist.put_batch pad_cache contract)
        pad_cache: dict = {}
        # per-attempt dispatch-shape memory: a batch shape this attempt
        # has not dispatched yet forces a jit retrace, and the step's
        # span marks it (recompile=True) so a captured slow step says
        # WHY it was slow (the ragged tail batch is the usual culprit)
        shapes_seen: set = set()
        # bound the number of dispatched-but-unfinished steps: an
        # unthrottled loop queues every step at once, and XLA:CPU's
        # cross-device collective rendezvous can deadlock when executions
        # from many run_ids oversubscribe the shared thread pool (the
        # virtual 8-device test mesh hits this). A window of 2 keeps
        # host/device pipelining on real chips while serializing enough.
        from collections import deque
        inflight: deque = deque()
        for epoch in range(self.epochs):
            order = rng.permutation(len(x))
            for s in range(steps_per_epoch):
                global_step += 1
                if global_step <= start_step:
                    continue  # fast-forward after resume (same shuffle stream)
                # one root span per step (route "trainer"): a chaos
                # fault raised inside finishes it with status=error, so
                # failed steps are tail-captured with their timeline;
                # the step_ms observe below runs inside the span, so
                # the histogram's exemplar links a slow bucket straight
                # to the captured step trace
                with TRACER.span("train_step", route="trainer",
                                 step=global_step,
                                 epoch=epoch + 1) as sp:
                    if self.fault_injector is not None:
                        self.fault_injector(global_step)
                    t_step = time.perf_counter()
                    idx = order[s * bs:(s + 1) * bs]
                    # ragged tail: pad to the data-axis multiple, zero
                    # the pad rows' weights so they contribute nothing
                    # to the loss
                    xp, n_real = pad_to_multiple(x[idx], n_data)
                    yp, _ = pad_to_multiple(y[idx], n_data)
                    wp, _ = pad_to_multiple(w[idx], n_data)
                    if n_real < len(wp):
                        wp = wp.copy()
                        wp[n_real:] = 0.0
                    recompile = xp.shape not in shapes_seen
                    if recompile:
                        shapes_seen.add(xp.shape)
                    t_disp = TRACER.clock.now()
                    # data-sharded global placement. Multi-process: the
                    # shuffle stream is seed-identical on every host, so
                    # each host contributes ONLY its row slice of the
                    # padded global batch and parallel/dist assembles —
                    # feeding the full batch would duplicate every row
                    # n_proc times and silently change the gradient
                    if jax.process_count() > 1:
                        plo, phi = _dist.process_local_rows(len(xp), mesh)
                        xp, yp, wp = xp[plo:phi], yp[plo:phi], wp[plo:phi]
                    placed, _ = _dist.put_batch(
                        {"x": xp, "y": yp, "w": wp}, mesh,
                        pad_cache=pad_cache)
                    xb, yb, wb = placed["x"], placed["y"], placed["w"]
                    params, opt_state, loss = step(params, opt_state,
                                                   xb, yb, wb)
                    inflight.append(loss)
                    if len(inflight) > 2:
                        inflight.popleft().block_until_ready()
                    # dispatch is async: this child is transfer +
                    # enqueue time, plus the periodic device block when
                    # the in-flight window fills (and the whole trace/
                    # compile, on a recompile=True step)
                    TRACER.add("step_dispatch", t_disp,
                               TRACER.clock.now(), parent=sp,
                               recompile=recompile, batch=int(len(xp)))
                    dt = time.perf_counter() - t_step
                    m_step.observe(dt * 1000.0)
                    if dt > 0:
                        m_eps.observe(n_real / dt)
                    if self.log_every and global_step % self.log_every == 0:
                        print(f"[NNLearner] step {global_step} "
                              f"epoch {epoch + 1}/{self.epochs} "
                              f"loss {float(loss):.5f}")
                    if (mngr is not None and self.checkpoint_every
                            and global_step % self.checkpoint_every == 0):
                        self._checkpoint(mngr, global_step, params,
                                         opt_state)
        if mngr is not None:
            self._checkpoint(mngr, global_step, params, opt_state)
            mngr.wait_until_finished()
        return params, opt_state

    # -- sharded step checkpointing ----------------------------------------

    def _checkpoint_manager(self):
        if not self.checkpoint_dir:
            return None
        # multi-process runtimes save cooperatively into ONE directory
        # (io/checkpoint.save_sharded: per-slice shard ownership +
        # barriers, manifest by process 0) — checkpoint_dir must sit
        # on a filesystem every host shares, the standard pod setup
        from mmlspark_tpu.io import checkpoint as _ckpt
        return _ckpt.manager(self.checkpoint_dir)

    def _checkpoint(self, mngr, step_num: int, params, opt_state) -> None:
        from mmlspark_tpu.core.tracing import ambient_tracer
        TRACER = ambient_tracer()
        with TRACER.span("checkpoint_save", step=step_num), \
                _metrics()["ckpt_save_ms"].time():
            # the live trees are written shard-by-shard (replicated
            # leaves once, model-sharded leaves per slice) — no host
            # gather; the digest manifest lands last
            mngr.save(step_num,
                      {"params": params, "opt_state": opt_state})
        # a scrape rides every checkpoint: batch fits usually exit (or
        # are preempted) before any Prometheus scrape, so the registry
        # state lands next to the step it describes — under telemetry/
        # (NOT the checkpoint root: the manager owns that namespace's
        # step listing). Best-effort: telemetry must never fail a save.
        try:
            from mmlspark_tpu.core.telemetry import snapshot_registries
            from mmlspark_tpu.io import fs as _fs
            snapshot_registries(_fs.join(self.checkpoint_dir, "telemetry"),
                                tag=f"step{step_num:08d}", keep=8)
        except Exception:  # noqa: BLE001
            from mmlspark_tpu.core.logs import get_logger
            get_logger("trainer").warning(
                "checkpoint metrics snapshot failed", exc_info=True)

    def _restore(self, mngr, template):
        """Restore the latest step against a host-side (params,
        opt_state) structure template, so optax NamedTuple states
        round-trip intact. The template must predate the first step:
        the donated live buffers are not safe to read after a fault.
        Host arrays come back; the caller re-places them with the
        current mesh's shardings — which may differ from the saving
        run's (topology-change resume)."""
        from mmlspark_tpu.core.tracing import ambient_tracer
        TRACER = ambient_tracer()
        latest = mngr.latest_step()
        with TRACER.span("checkpoint_restore", step=latest), \
                _metrics()["ckpt_restore_ms"].time():
            restored = mngr.restore(latest, template)
        print(f"[NNLearner] resumed from step {latest}")
        return restored["params"], restored["opt_state"], latest

    # -- incremental training from a stream ---------------------------------

    def fit_stream(self, source, export_dir: Optional[str] = None,
                   export_every_batches: int = 4,
                   export_prefix: str = "r",
                   steps_per_batch: int = 1,
                   checkpoint_every_batches: int = 1,
                   transform=None,
                   **query_kwargs) -> "StreamingFit":
        """Train incrementally from a micro-batch stream.

        ``source`` is either an engine source (``plan``/``read``/
        ``ack`` — e.g. a :class:`~mmlspark_tpu.streaming.traffic.
        TrafficLogSource` over served-traffic capture segments) from
        which a :class:`~mmlspark_tpu.streaming.engine.StreamingQuery`
        is built (``query_kwargs`` forwarded — ``checkpoint_dir`` for
        the WAL, watermarks, backpressure knobs), or an already-built
        ``StreamingQuery`` whose sink slot is free — ``fit_stream``
        installs itself as the sink either way.

        Semantics: every micro-batch becomes ``steps_per_batch``
        gradient steps on the SAME mesh-sharded, donated jitted step
        ``fit`` uses (rows padded to the data-axis multiple on a
        power-of-two ladder, pad rows zero-weighted — the compiled
        shape set stays bounded). With ``checkpoint_dir`` (the Param)
        set, the fit WARM-STARTS from the latest digest-manifested
        train-state checkpoint and saves one every
        ``checkpoint_every_batches`` batches (default 1: EVERY
        trained batch), recording the high-water stream batch id
        inside it — a post-crash replayed batch id at or below that
        mark is SKIPPED, which is what makes this sink idempotent and
        the end-to-end loop exactly-once. Raising
        ``checkpoint_every_batches`` above 1 trades durability for
        save cost: batches the engine committed AFTER the last
        train-state checkpoint warm-start as if untrained after a
        crash (at-most-once inside that window) — acceptable for
        training (a lost gradient step is not a lost reply), but the
        default keeps the strict contract. With ``export_dir`` set, a
        servable ``NNModel`` stage checkpoint is exported every
        ``export_every_batches`` batches on its own cadence (manifest
        written last, so every export is flip-eligible the moment it
        appears — what a
        :class:`~mmlspark_tpu.streaming.loop.RetrainLoop` watches).

        Streaming fits use a constant learning rate (no fixed horizon
        to decay over); ``cosine_decay``/``warmup_steps`` are ignored.
        Returns a :class:`StreamingFit` handle (drive the query
        synchronously via ``handle.query.process_available()`` or
        threaded via ``handle.query.start()``).
        """
        from mmlspark_tpu.streaming.engine import StreamingQuery
        sink = _StreamTrainerSink(self, export_dir=export_dir,
                                  export_every=export_every_batches,
                                  export_prefix=export_prefix,
                                  steps_per_batch=steps_per_batch,
                                  checkpoint_every=checkpoint_every_batches)
        if isinstance(source, StreamingQuery):
            if source.sink is not None:
                raise ValueError(
                    "fit_stream needs the query's sink slot (build the "
                    "StreamingQuery with sink=None)")
            if query_kwargs or transform is not None:
                raise ValueError(
                    "pass transform/query knobs when fit_stream builds "
                    "the query, not alongside a pre-built one")
            query = source
            query.sink = sink
        else:
            query_kwargs.setdefault("name", "fit_stream")
            query = StreamingQuery(source, sink=sink,
                                   transform=transform, **query_kwargs)
        return StreamingFit(query, sink)


def _as_label(v) -> float:
    """A usable numeric label or NaN (filtered): captured traffic rows
    carry JSON values, so None holes / strings / lists are expected."""
    try:
        if isinstance(v, (list, tuple, np.ndarray)):
            return float("nan")
        return float(v)
    except (TypeError, ValueError):
        return float("nan")


class _StreamTrainerSink:
    """The ``fit_stream`` sink: micro-batches -> sharded train steps.

    Idempotent by batch id: the train-state checkpoint records the
    high-water stream batch id it covers, so a replayed batch (the
    engine re-runs planned-but-uncommitted batches after a crash) at or
    below the restored mark is skipped — replay beats re-dispatch, and
    a crash anywhere in the write/commit window never trains a batch
    twice past a checkpoint. Lazily initialized on the first frame
    (shapes come from the stream).
    """

    def __init__(self, learner: NNLearner, export_dir: Optional[str],
                 export_every: int, export_prefix: str,
                 steps_per_batch: int, checkpoint_every: int = 1):
        self.learner = learner
        self.export_dir = (os.path.abspath(export_dir)
                           if export_dir else None)
        self.export_every = max(int(export_every), 1)
        self.export_prefix = str(export_prefix)
        self.steps_per_batch = max(int(steps_per_batch), 1)
        self.checkpoint_every = max(int(checkpoint_every), 1)
        self._ready = False
        self._was_int = False
        self.last_trained_batch = 0
        self.global_step = 0
        self.n_batches_trained = 0
        self.n_rows_trained = 0
        self.n_replays_skipped = 0
        self.n_rows_unlabeled = 0
        self.n_batches_unusable = 0
        self.n_exports = 0
        self.exports: "list[str]" = []
        self.last_loss: Optional[float] = None

    # -- lazy setup ----------------------------------------------------------

    def _setup(self, x: np.ndarray) -> None:
        import jax
        from mmlspark_tpu.parallel import dist as _dist

        learner = self.learner
        self._was_int = x.dtype == np.uint8
        shape = ((x.shape[1:]) if x.ndim > 1 else ())
        fn = learner.model or NNFunction.init(
            learner.arch, shape, seed=learner.seed)
        self._arch = dict(fn.arch)
        module = fn.module()
        self._mesh = build_mesh(MeshSpec.from_dict(learner.mesh_shape)
                                if learner.mesh_shape else None)
        self._n_data = self._mesh.shape.get("data", 1)
        # a stream has no fixed horizon: constant learning rate (the
        # schedule params cosine_decay/warmup_steps are batch-fit only)
        tx = make_optimizer(learner.optimizer, learner.learning_rate,
                            learner.momentum, learner.weight_decay,
                            learner.clip_norm)
        self._step = jax.jit(
            learner.build_train_step(module, tx, make_loss(learner.loss)),
            donate_argnums=(0, 1))
        repl = _dist.state_shardings(fn.params, self._mesh)
        params = jax.device_put(fn.params, repl)
        opt_state = tx.init(params)
        opt_repl = _dist.state_shardings(opt_state, self._mesh)
        opt_state = jax.device_put(opt_state, opt_repl)
        self._repl, self._opt_repl = repl, opt_repl
        self._dist = _dist
        self._pad_cache: dict = {}
        self._mngr = learner._checkpoint_manager()
        if self._mngr is not None:
            # host-side template BEFORE any step: the donated buffers
            # are not restore-safe afterwards (same rule as fit)
            template = {"params": jax.device_get(params),
                        "opt_state": jax.device_get(opt_state)}
            self._template = template
            latest = self._mngr.latest_step()
            if latest is not None:
                restored = self._mngr.restore(latest, template)
                params = jax.device_put(restored["params"], repl)
                opt_state = jax.device_put(restored["opt_state"],
                                           opt_repl)
                self.global_step = int(latest)
                from mmlspark_tpu.io.checkpoint import read_index
                extra = read_index(
                    self._mngr._step_dir(latest)).get("extra", {})
                self.last_trained_batch = int(
                    extra.get("stream_batch_id", 0))
                self.n_exports = int(extra.get("n_exports", 0))
                print(f"[NNLearner] fit_stream warm-started from step "
                      f"{latest} (stream batch "
                      f"{self.last_trained_batch})")
        if self.export_dir:
            os.makedirs(self.export_dir, exist_ok=True)
            # continue the export sequence past anything already there
            # (a restarted loop must never reuse a pushed version name)
            for name in os.listdir(self.export_dir):
                if name.startswith(self.export_prefix):
                    try:
                        self.n_exports = max(
                            self.n_exports,
                            int(name[len(self.export_prefix):]))
                    except ValueError:
                        continue
        self._params, self._opt = params, opt_state

    # -- the sink ------------------------------------------------------------

    def process(self, batch_id: int, df: DataFrame) -> None:
        from mmlspark_tpu.models.nn import _stack_column
        from mmlspark_tpu.parallel.sharding import (
            pad_to_bucket, pad_to_multiple)

        learner = self.learner
        if df.num_rows == 0 or learner.features_col not in df:
            return
        # bad DATA must never kill the retrain loop: captured traffic
        # routinely mixes labeled (feedback) and unlabeled (plain
        # inference) rows, and a malformed payload is a data problem,
        # not a query-terminal fault. Rows without a usable numeric
        # label are dropped (counted); a batch with nothing trainable
        # is ignored — deterministically, so a replay skips it too.
        try:
            if learner.label_col in df:
                y_raw = df[learner.label_col]
                if y_raw.dtype == object:
                    y = np.array([_as_label(v) for v in y_raw],
                                 dtype=np.float32)
                else:
                    y = np.asarray(y_raw, dtype=np.float32)
                mask = np.isfinite(y)
            else:
                y = np.zeros(df.num_rows, dtype=np.float32)
                mask = np.zeros(df.num_rows, dtype=bool)
            n_bad = int(df.num_rows - mask.sum())
            if n_bad:
                self.n_rows_unlabeled += n_bad
            if not mask.any():
                return
            if n_bad:
                df = df.filter(mask)
                y = y[mask]
            x = _stack_column(df[learner.features_col])
            if not self._ready:
                self._setup(x)
                self._ready = True
            if self._was_int and x.dtype == np.uint8:
                x = x.astype(np.float32) / 255.0
            elif x.dtype != np.float32:
                x = np.asarray(x, dtype=np.float32)
            w = (np.asarray(df[learner.weight_col], dtype=np.float32)
                 if learner.weight_col and learner.weight_col in df
                 else np.ones(len(y), dtype=np.float32))
        except (KeyError, TypeError, ValueError) as e:
            # a data-shape problem (ragged features, non-numeric
            # payloads): skip the batch loudly, keep the stream alive
            self.n_batches_unusable += 1
            from mmlspark_tpu.core.logs import get_logger
            get_logger("trainer").warning(
                "fit_stream batch %d unusable (%s: %s); skipped",
                batch_id, type(e).__name__, e)
            return
        if batch_id <= self.last_trained_batch:
            # the idempotent-sink contract: this batch is already
            # inside the restored checkpoint's high-water mark
            self.n_replays_skipped += 1
            return
        # two-stage pad: power-of-two bucket (bounded compile set under
        # ragged stream batches), then the data-axis multiple; pad rows
        # carry zero weight so they contribute nothing to the loss
        cap = max(int(learner.batch_size), self._n_data)
        xp, n_real = pad_to_bucket(x, cap=cap)
        xp, _ = pad_to_multiple(xp, self._n_data)
        target = len(xp)
        yp = np.zeros(target, dtype=np.float32)
        yp[:n_real] = y[:n_real]
        wp = np.zeros(target, dtype=np.float32)
        wp[:n_real] = w[:n_real]
        metrics = _metrics()
        for _ in range(self.steps_per_batch):
            t0 = time.perf_counter()
            placed, _ = self._dist.put_batch(
                {"x": xp, "y": yp, "w": wp}, self._mesh,
                pad_cache=self._pad_cache)
            self._params, self._opt, loss = self._step(
                self._params, self._opt,
                placed["x"], placed["y"], placed["w"])
            self.global_step += 1
            dt = time.perf_counter() - t0
            metrics["step_ms"].observe(dt * 1000.0)
            if dt > 0:
                metrics["examples_per_sec"].observe(n_real / dt)
        self.last_loss = float(loss)
        self.last_trained_batch = int(batch_id)
        self.n_batches_trained += 1
        self.n_rows_trained += int(n_real)
        # two independent cadences: the train-state checkpoint is the
        # exactly-once high-water mark (default every batch — raising
        # the cadence opens an at-most-once window after a crash, see
        # fit_stream); the servable export is the rollout feed
        if self.n_batches_trained % self.checkpoint_every == 0:
            self._save_train_state()
        if self.export_dir \
                and self.n_batches_trained % self.export_every == 0:
            self._export()

    # -- checkpoint + servable export ----------------------------------------

    def _save_train_state(self) -> None:
        """Save the train state; the idempotence high-water mark
        (``stream_batch_id``) rides in ``extra``."""
        if self._mngr is None:
            return
        with _metrics()["ckpt_save_ms"].time():
            self._mngr.save(
                self.global_step,
                {"params": self._params, "opt_state": self._opt},
                extra={"stream_batch_id": self.last_trained_batch,
                       "n_exports": self.n_exports})

    def _export(self) -> Optional[str]:
        """Export a servable NNModel stage checkpoint whose digest
        manifest lands LAST — flip-eligible for the rollout plane the
        moment the directory is complete."""
        if not self.export_dir:
            return None
        self.n_exports += 1
        name = f"{self.export_prefix}{self.n_exports:06d}"
        path = os.path.join(self.export_dir, name)
        self.model().save(path)
        self.exports.append(path)
        return path

    def checkpoint_and_export(self) -> Optional[str]:
        """Off-cadence save + export (drain/shutdown; ``export_now``)."""
        if not self._ready:
            return None
        path = self._export()
        self._save_train_state()
        return path

    def model(self) -> NNModel:
        """A servable snapshot of the current streamed-trained model."""
        if not self._ready:
            raise RuntimeError("fit_stream has not seen a batch yet")
        import jax
        fn = NNFunction(arch=dict(self._arch),
                        params=jax.device_get(self._params))
        extra = {"input_dtype": "uint8"} if self._was_int else {}
        return NNModel(model=fn, input_col=self.learner.features_col,
                       output_col="scores", **extra)

    def status(self) -> Dict[str, Any]:
        return {"ready": self._ready,
                "global_step": self.global_step,
                "last_trained_batch": self.last_trained_batch,
                "n_batches_trained": self.n_batches_trained,
                "n_rows_trained": self.n_rows_trained,
                "n_replays_skipped": self.n_replays_skipped,
                "n_rows_unlabeled": self.n_rows_unlabeled,
                "n_batches_unusable": self.n_batches_unusable,
                "n_exports": self.n_exports,
                "exports": list(self.exports),
                "last_loss": self.last_loss}


class StreamingFit:
    """Handle over a streaming fit: the query (drive/stop it here) plus
    the trainer sink's counters, snapshots, and exports."""

    def __init__(self, query, sink: _StreamTrainerSink):
        self.query = query
        self._sink = sink

    @property
    def exports(self) -> "list[str]":
        return list(self._sink.exports)

    def model(self) -> NNModel:
        return self._sink.model()

    def export_now(self) -> Optional[str]:
        """Checkpoint + export outside the cadence (drain/shutdown)."""
        return self._sink.checkpoint_and_export()

    def status(self) -> Dict[str, Any]:
        return {"trainer": self._sink.status(),
                "query": self.query.status()}

    def stop(self) -> None:
        self.query.stop()

    def __enter__(self) -> "StreamingFit":
        return self

    def __exit__(self, *exc) -> None:
        self.stop()
