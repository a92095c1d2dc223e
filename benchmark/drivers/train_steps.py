"""Driver ``train_steps``: ``build_spmd_train_step`` on the mesh the
configuration names (``train.mesh``, e.g. ``{"data": 1}``), fed a new
batch every step.

Set-up builds ONE object — the compiled step with its state and its
feed — drives it through its first ``check_steps`` steps (the readings
`correct` compares: each step's loss, the first gradient as the
optimizer got it, the parameters' change), and hands that same object
to the window. After the window: the peak memory is read, the program's
state freed, and the plain reference follows the same first steps.

Configuration keys used: the model's sizes, ``train`` (``dtype``,
``attention_impl``, ``ce_impl``, ``mesh``) and ``optimizer``. Traffic keys:
``batch``, ``seq``, ``check_steps``, ``trace_seconds``.
"""

from __future__ import annotations

import gc
import re
import shutil
import time
from collections import Counter
from typing import Any, Dict, List

import numpy as np

import reference as R
import trace_reduce
import traffic as traffic_mod

FAULTS = ("state_unchanged", "half_batch")


class TrainRun:
    """The compiled step, its state and its feed. ``step()`` is the one
    call set-up and the window both make."""

    def __init__(self, compiled, params, velocity, feed, fault=None):
        self.compiled, self.feed, self.fault = compiled, feed, fault
        self.params, self.velocity = params, velocity
        self.n_steps = 0
        self.next = self._put(next(feed))

    def _put(self, batch):
        import jax.numpy as jnp
        tokens, labels, mask = batch
        if self.fault == "half_batch":
            mask = mask.copy()
            mask[mask.shape[0] // 2:] = 0.0
        return jnp.asarray(tokens), jnp.asarray(labels), jnp.asarray(mask)

    def step(self):
        """Dispatch one step on the batch handed over a step ago, hand
        over the next one. Returns the step's loss (not waited for)."""
        import jax.profiler
        batch = self.next
        params, velocity, loss = self.compiled(self.params, self.velocity,
                                               *batch)
        if self.fault != "state_unchanged":
            self.params, self.velocity = params, velocity
        with jax.profiler.TraceAnnotation("bench.feed"):
            self.next = self._put(next(self.feed))
        self.n_steps += 1
        return loss


def pallas_calls(compiled_text: str) -> Dict[str, int]:
    """``{op_name: count}`` of the Mosaic calls a compiled program holds."""
    return dict(Counter(re.findall(
        r'custom_call_target="tpu_custom_call".*?op_name="([^"]+)"',
        compiled_text)))


def build(ctx) -> TrainRun:
    import jax
    from mmlspark_tpu.models import transformer as T
    from mmlspark_tpu.parallel import MeshSpec, build_mesh

    ctx.mark("import_model")
    m, tr, opt = ctx.model, ctx.config["train"], ctx.config["optimizer"]
    cfg = T.TransformerConfig(
        vocab=m.vocab, d_model=m.d_model, n_heads=m.n_heads,
        d_head=m.d_head, d_ff=m.d_ff, n_stages=1,
        layers_per_stage=m.n_layers, dtype=tr["dtype"],
        attention_impl=tr["attention_impl"], ce_impl=tr["ce_impl"])
    axes = dict(tr.get("mesh") or {"data": 1})
    mesh = build_mesh(MeshSpec.from_dict(axes),
                      devices=jax.devices()[:int(np.prod(list(
                          axes.values())))])
    step = T.build_spmd_train_step(
        cfg, mesh, learning_rate=opt["learning_rate"],
        momentum=opt["momentum"],
        donate=ctx.fault != "state_unchanged")
    ctx.mark("step_built")
    params = T.shard_params(R.make_params(m, ctx.seed), cfg, mesh)
    velocity = jax.tree.map(lambda p: p * 0.0, params)
    feed = traffic_mod.token_batches(ctx.traffic, m.vocab, ctx.seed)
    run = TrainRun(None, params, velocity, feed, ctx.fault)
    jax.block_until_ready(velocity)
    ctx.mark("weights")
    lowered = step.lower(params, velocity, *run.next)
    ctx.mark("lowered")
    run.compiled = lowered.compile()
    ctx.mark("compiled")
    return run


def measure(run: TrainRun, seconds: float, tokens_per_step: int,
            trace_after: float = -1.0, trace_seconds: float = 0.0,
            trace_dir: str = "") -> Dict[str, Any]:
    """The window: steps back to back, each dispatched before the one
    before it is waited for, closed by waiting for the last loss. With
    ``trace_after >= 0`` the profiler runs over a part of it."""
    import jax
    out: Dict[str, Any] = {}
    tracing, t_on, n_on = 0, 0.0, 0       # 0 before, 1 during, 2 after
    prev = None
    n0 = run.n_steps
    t0 = time.perf_counter()
    while True:
        loss = run.step()
        if prev is not None:
            with jax.profiler.TraceAnnotation("bench.wait_loss"):
                prev.block_until_ready()
        prev = loss
        now = time.perf_counter() - t0
        if tracing == 0 and 0 <= trace_after <= now:
            prev.block_until_ready()
            jax.profiler.start_trace(
                trace_dir, profiler_options=trace_reduce.profile_options())
            tracing, t_on, n_on = 1, time.perf_counter(), run.n_steps
        elif tracing == 1 and time.perf_counter() - t_on >= trace_seconds:
            prev.block_until_ready()
            out["traced_s"] = time.perf_counter() - t_on
            out["traced_steps"] = run.n_steps - n_on
            jax.profiler.stop_trace()
            tracing = 2
        if now >= seconds:
            break
    prev.block_until_ready()
    t1 = time.perf_counter()
    if tracing == 1:
        out["traced_s"] = t1 - t_on
        out["traced_steps"] = run.n_steps - n_on
        jax.profiler.stop_trace()
    out["steps"] = run.n_steps - n0
    out["window_s"] = t1 - t0
    out["last_loss"] = float(prev)
    out["train_tokens_per_s"] = out["steps"] * tokens_per_step / (t1 - t0)
    return out


def first_steps(run: TrainRun, ctx) -> Dict[str, Any]:
    """The readings of the program: through ``run.step()`` itself."""
    import jax
    losses: List[float] = []
    grad_norms = change_norms = None
    n = int(ctx.traffic["check_steps"])
    for i in range(n):
        losses.append(float(run.step()))
        if i == 0:
            # momentum SGD from a zero velocity: after one step the
            # velocity IS the gradient the optimizer got
            grad_norms = R.program_leaf_norms(run.velocity)
        if i == n - 1:
            start = R.make_params(ctx.model, ctx.seed)
            change_norms = R.program_leaf_norms(
                jax.tree.map(lambda a, b: a - b, run.params, start))
            del start
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change_norms}


def compare(got: Dict[str, Any], want: Dict[str, Any],
            limits: Dict[str, float], names: List[str]):
    """Each number compared, beside its limit (``[value, limit]``), and
    which leaves were the worst."""
    out: Dict[str, Any] = {}
    for i, (a, b) in enumerate(zip(got["losses"], want["losses"])):
        out[f"loss_gap_step{i + 1}"] = [abs(a - b) / abs(b),
                                        limits["loss_gap"]]
    g, gi = R.worst_leaf_gap(got["grad_norms"], want["grad_norms"])
    out["grad_norm_gap_worst_leaf"] = [g, limits["grad_norm_gap"]]
    keep = R.moving_leaves(want["grad_norms"])
    c, ci = R.worst_leaf_gap(got["change_norms"], want["change_norms"],
                             keep)
    out["change_norm_gap_worst_leaf"] = [c, limits["change_norm_gap"]]
    info = {"grad_worst_leaf": names[gi], "change_worst_leaf": names[ci],
            "leaves_compared_for_change": int(keep.sum()),
            "leaves": int(len(keep))}
    return out, info


def is_correct(compared: Dict[str, Any]) -> bool:
    return all(np.isfinite(v[0]) and v[0] <= v[1]
               for v in compared.values())


def run(ctx) -> Dict[str, Any]:
    import jax
    m = ctx.model
    tokens_per_step = int(ctx.traffic["batch"]) * int(ctx.traffic["seq"])
    tr = build(ctx)
    facts: Dict[str, Any] = {"driver": "train_steps"}
    if ctx.on_chip:
        calls = pallas_calls(tr.compiled.as_text())
        facts["pallas_calls"] = {
            "flash_fwd": sum(n for k, n in calls.items()
                             if "_flash_call" in k),
            "flash_bwd": sum(n for k, n in calls.items()
                             if "_flash_bwd_call" in k),
            "other": sum(n for k, n in calls.items()
                         if "_flash_call" not in k
                         and "_flash_bwd_call" not in k)}
        if ctx.config["train"].get("expect_flash_attention") and not \
                facts["pallas_calls"]["flash_fwd"]:
            raise RuntimeError(
                f"no flash-attention Pallas call in the compiled step: "
                f"{calls}")
    ctx.mark("kernel_census")
    got = first_steps(tr, ctx)
    setup_s = time.perf_counter() - ctx.t_start
    facts["setup_phases_s"] = dict(ctx.phases, first_steps=setup_s)

    shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    win = measure(
        tr, ctx.seconds, tokens_per_step,
        trace_after=0.4 * ctx.seconds if ctx.trace else -1.0,
        trace_seconds=min(float(ctx.traffic["trace_seconds"]),
                          0.5 * ctx.seconds),
        trace_dir=ctx.trace_dir)
    # the peak on the fullest chip
    memory_peak = max(int((d.memory_stats() or {})
                          .get("peak_bytes_in_use", 0))
                      for d in jax.devices())

    # free the program's state before the reference runs
    tr.params = tr.velocity = tr.next = tr.compiled = None
    del tr
    gc.collect()

    reduced = None
    if ctx.trace:
        reduced = trace_reduce.read_and_remove(
            ctx.trace_dir, ctx.on_chip, ctx.traffic.get("trace_hole_s"))

    opt = ctx.config["optimizer"]
    names = R.leaf_names(m)
    feed = traffic_mod.token_batches(ctx.traffic, m.vocab, ctx.seed)
    batches = [next(feed) for _ in range(int(ctx.traffic["check_steps"]))]
    t_ref = time.perf_counter()
    want = R.train_readings(m, ctx.seed, batches, opt["learning_rate"],
                            opt["momentum"], "highest")
    compared, facts["leaves"] = compare(got, want, ctx.limits, names)
    facts["reference_s"] = time.perf_counter() - t_ref
    out: Dict[str, Any] = {}
    if ctx.control:
        # --control: the reference in the program's place, one
        # precision below what the configuration states, and the
        # planted fault that a reference can carry, each judged by the
        # same comparison
        ctl = R.train_readings(m, ctx.seed, batches, opt["learning_rate"],
                               opt["momentum"], ctx.config["control"])
        half = R.train_readings(
            m, ctx.seed, batches, opt["learning_rate"], opt["momentum"],
            "highest", rows_kept=int(ctx.traffic["batch"]) // 2)
        for key, readings in (("control", ctl),
                              ("half_batch_in_reference", half)):
            cmp_ = compare(readings, want, ctx.limits, names)[0]
            out[key] = {"correct": is_correct(cmp_), "compared": cmp_}
    finite = bool(np.isfinite(got["losses"]).all()
                  and np.isfinite(win["last_loss"]))
    facts.update(steps=win["steps"], window_s=win["window_s"],
                 losses=got["losses"], last_loss=win["last_loss"],
                 reference_losses=want["losses"])
    out.update({
        "correct": is_correct(compared) and finite,
        "attempted": win["steps"], "failed": 0,
        "end_to_end": {"train_tokens_per_s": win["train_tokens_per_s"],
                       "setup_s": setup_s},
        "counters": {"tokens_per_step": tokens_per_step, **win},
        "reduced": reduced, "memory_peak_bytes": memory_peak,
        "compared": compared, "facts": facts})
    return out
