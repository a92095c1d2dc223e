#!/usr/bin/env python3
"""The quickest proof that the main path still starts on the chip.

    python chip_smoke.py                 # on a TPU host; exits 0 or says why not

One process, no child processes, no network. It drives the system once
through the entry points a user calls, at the full width of the widest
transformer any record of this repo has run (bench.py
``transformer_train_v1``: vocab 32,768, d_model 512, 8 heads x 64, d_ff
2,048, 8 layers; random weights from a seed), and checks what comes out
by the repo's own means:

``train``  ``build_spmd_train_step`` (bf16) on a one-device mesh, b8 x
           s1024, 4 steps on one fixed batch: loss finite and falling,
           the folded-attention and fused-CE Pallas calls present in the
           COMPILED program, donation real (the donated input is gone).
``serve``  ``TransformerDecoder`` -> ``DecodeScheduler`` ->
           ``ServingServer`` (frame model: the hash-verified zoo
           ``cifar10s_resnet20``); prefill/prefix-prefill/step logits
           against a float32 reference forward on the same device, then
           HTTP from concurrent clients: ``/generate`` (cold, prefix
           hit, ``?stream=1``) and ``/predict``, then ``/decode/stats``.
``fit``    ``GBDTRegressor`` quantile fit at the drug-discovery shape
           with ``histogram_impl="pallas"`` NAMED.
``train4`` / ``serve4``  the same on ``{"data": 2, "model": 2}`` /
           ``mesh={"model": 4}`` — only with four devices; on fewer they
           are reported ``not_run``, never passed.

Every engine on this path is either chosen by ``"auto"`` and then READ
BACK (compiled text, ``/decode/stats``) or named; nothing here sets the
platform. The default invocation refuses to run without an accelerator
whose ``device_kind`` is in the peaks table
(``mmlspark_tpu.core.environment.DEVICE_PEAKS``): it exits non-zero and
prints no result. ``--rehearse-cpu`` is the builder's control-flow
rehearsal at a tiny size (interpreted/XLA engines, named); its output is
labelled ``cpu`` and proves nothing about the chip.

The last line of stdout is one JSON object with exactly these keys:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
The line above it is the run's summary (jax version, wall and compile
seconds, cache hits, each phase's status).
"""

from __future__ import annotations

import argparse
import dataclasses
import http.client
import json
import os
import sys
import time
import traceback
import warnings
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))

# ---------------------------------------------------------------------------
# Tolerances. Each was measured on the chip (TPU v5 lite, jax 0.9.0,
# PR 21; PERF.md "Findings") and is written down with its margin. The
# inputs are seeded and the programs fixed, so the measured values
# repeat from run to run.
#
# Served logits vs the float32 reference forward (its matmuls at
# ``highest`` precision) on the same device. Measured: at most 0.0137
# on one chip and 0.0134 under model=4, at |logit| up to 2.13. The
# decode path holds f32 arrays, but XLA's DEFAULT f32 matmul on the MXU
# is one bf16 pass: the reference itself moves by 0.0098 between
# default and ``highest`` precision, so the served error is that
# rounding and no more. It is also why tokens are compared only where
# the reference's top-2 margin exceeds the tolerance (ROADMAP C7): with
# random weights 62 of 384 served tokens sit inside it.
LOGITS_TOL = 2e-2
# One-chip vs data=2 x model=2 loss after each of two bf16 steps
# (measured 2.5e-5: the shards reduce in another order).
TRAIN4_LOSS_TOL = 5e-4
# mesh={"model": 4} decoder logits vs the one-chip decoder's (measured
# at most 0.0074: heads and the MLP hidden are summed across four
# devices, each partial carrying its own bf16-pass rounding).
SERVE4_LOGITS_TOL = 1e-2
# P(y <= q90(x)) bound tests/test_gbdt.py::test_quantile_coverage_
# calibrated holds for alpha=0.9 at 40 iterations (measured 0.9136)
FIT_COVERAGE = (0.86, 0.94)


@dataclasses.dataclass(frozen=True)
class Sizes:
    """One model + the traffic sent to it. Widths of ``FULL`` are
    bench.py ``transformer_train_v1``'s; ``TINY`` is the CPU rehearsal."""

    vocab: int
    d_model: int
    n_heads: int
    d_head: int
    d_ff: int
    n_layers: int
    batch: int
    seq: int
    train_steps: int
    n_slots: int
    max_len: int
    prompt_lens: Tuple[int, ...]     # two /generate requests each
    max_new: Tuple[int, ...]         # cycled over the requests
    prefix_len: int                  # shared by the prefix-hit requests
    n_predict: int
    fit_rows: int
    fit_features: int
    fit_iterations: int
    # engines: "auto" on the chip (and read back); named on the CPU
    attention_impl: str = "auto"
    ce_impl: str = "auto"
    attn_impl: str = "auto"
    histogram_impl: str = "pallas"


FULL = Sizes(vocab=32768, d_model=512, n_heads=8, d_head=64, d_ff=2048,
             n_layers=8, batch=8, seq=1024, train_steps=4,
             n_slots=8, max_len=1024, prompt_lens=(5, 40, 200, 700),
             max_new=(16, 32, 48, 64), prefix_len=128, n_predict=16,
             fit_rows=4096, fit_features=100, fit_iterations=40)

TINY = Sizes(vocab=512, d_model=64, n_heads=4, d_head=16, d_ff=128,
             n_layers=2, batch=2, seq=128, train_steps=4,
             n_slots=4, max_len=128, prompt_lens=(5, 12, 40, 90),
             max_new=(4, 6, 8, 10), prefix_len=32, n_predict=4,
             fit_rows=512, fit_features=10, fit_iterations=5,
             attention_impl="dense", ce_impl="xla",
             attn_impl="pallas_interpret",
             histogram_impl="pallas_interpret")


def transformer_config(sz: Sizes, dtype: str):
    from mmlspark_tpu.models import transformer as T
    return T.TransformerConfig(
        vocab=sz.vocab, d_model=sz.d_model, n_heads=sz.n_heads,
        d_head=sz.d_head, d_ff=sz.d_ff, n_stages=1,
        layers_per_stage=sz.n_layers, dtype=dtype,
        attention_impl=sz.attention_impl, ce_impl=sz.ce_impl)


class Report(dict):
    """One phase's findings. ``check`` records a failed check and goes
    on, so that one run on the chip reports EVERY number the phase
    measures; a phase with any failed check has failed."""

    def check(self, cond: bool, what: str) -> bool:
        if not cond:
            self.setdefault("failed_checks", []).append(what)
            print(f"CHECK FAILED: {what}", file=sys.stderr, flush=True)
        return bool(cond)


def _sig(v: float) -> float:
    return float(f"{v:.3g}")


# ---------------------------------------------------------------------------
# compile accounting: backend-compile seconds and persistent-cache hits,
# from jax's own monitoring events (what "compile seconds" means below)


class CompileMeter:
    def __init__(self):
        import jax
        self.seconds = 0.0
        self.requests = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self) -> Tuple[float, int, int]:
        return self.seconds, self.requests, self.hits


# ---------------------------------------------------------------------------
# train


def _pallas_calls(compiled_text: str) -> Dict[str, int]:
    """``{op_name: count}`` of the Mosaic custom calls in a compiled
    program's text — what the executable really holds, not what the
    config asked for."""
    import re
    from collections import Counter
    return dict(Counter(re.findall(
        r'custom_call_target="tpu_custom_call".*?op_name="([^"]+)"',
        compiled_text)))


def _train_steps(sz: Sizes, mesh, n_steps: int, seed: int = 0):
    """Build, compile (ahead of time, so the text can be read) and run
    ``n_steps`` on one fixed batch. Returns (losses, compiled text,
    final params, whether the first step's donated input is gone)."""
    import jax
    from mmlspark_tpu.models import transformer as T

    cfg = transformer_config(sz, "bfloat16")
    step = T.build_spmd_train_step(cfg, mesh, learning_rate=0.01)
    params = T.shard_params(T.init_params(cfg, seed=seed), cfg, mesh)
    velocity = jax.tree.map(lambda p: p * 0.0, params)
    tokens, labels, mask = T.make_batch(np.random.default_rng(seed), cfg,
                                        sz.batch, sz.seq)
    compiled = step.lower(params, velocity, tokens, labels,
                          mask).compile()
    losses = []
    donated = None
    for _ in range(n_steps):
        old = params["embed"]
        # rebind: params and velocity are DONATED (build_spmd_train_step's
        # warning) — a reused donated array is an error on the chip
        params, velocity, loss = compiled(params, velocity, tokens,
                                          labels, mask)
        losses.append(float(loss))
        if donated is None:
            donated = bool(old.is_deleted())
    return losses, compiled.as_text(), params, donated


def phase_train(sz: Sizes, on_chip: bool, rep: Report) -> None:
    import jax
    from mmlspark_tpu.ops import fused_ce
    from mmlspark_tpu.parallel import MeshSpec, build_mesh
    from mmlspark_tpu.parallel import pallas_attention as PA

    mesh = build_mesh(MeshSpec.from_dict({"data": 1}),
                      devices=[jax.devices()[0]])
    losses, text, _, donated = _train_steps(sz, mesh, sz.train_steps)
    rep["losses"] = [round(v, 4) for v in losses]
    rep["donated_input_deleted"] = donated
    rep.check(bool(np.isfinite(losses).all()), f"non-finite loss: {losses}")
    rep.check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    if not on_chip:
        return
    calls = rep["pallas_calls"] = _pallas_calls(text)

    def n_calls(jitted) -> int:
        tag = f"jit({jitted.__name__})"
        return sum(n for name, n in calls.items() if tag in name)

    L = sz.n_layers
    for what, fn, n in (("folded attention fwd", PA._ffwd_call, L),
                        ("folded attention bwd", PA._fbwd_call, 2 * L),
                        ("fused CE fwd", fused_ce._fwd_call, 1),
                        ("fused CE bwd", fused_ce._bwd_call, 2)):
        rep.check(n_calls(fn) == n,
                  f"compiled train step holds {n_calls(fn)} {what} "
                  f"Pallas calls, expected {n}")
    rep.check(donated is True,
              "params were not donated: the input outlived the step")


def phase_train4(sz: Sizes, on_chip: bool, rep: Report) -> None:
    """The same step on data=2 x model=2 against the one-chip step."""
    import jax
    from jax.sharding import PartitionSpec as P
    from mmlspark_tpu.models import transformer as T
    from mmlspark_tpu.parallel import MeshSpec, build_mesh

    devs = jax.devices()
    one = build_mesh(MeshSpec.from_dict({"data": 1}), devices=devs[:1])
    four = build_mesh(MeshSpec.from_dict({"data": 2, "model": 2}),
                      devices=devs[:4])
    ref, _, _, _ = _train_steps(sz, one, 2)
    got, _, params, _ = _train_steps(sz, four, 2)
    rep["losses_one_chip"] = [round(v, 5) for v in ref]
    rep["losses_2x2"] = [round(v, 5) for v in got]
    rep["max_loss_diff"] = _sig(max(abs(a - b) for a, b in zip(ref, got)))
    rep["loss_tol"] = TRAIN4_LOSS_TOL
    rep.check(rep["max_loss_diff"] <= TRAIN4_LOSS_TOL,
              f"loss parity broke: one chip {ref}, 2x2 {got}")
    # every leaf really spans the four devices, with the per-device
    # bytes its spec implies
    specs = T.param_specs(transformer_config(sz, "bfloat16"), four)
    leaves = jax.tree.leaves(params)
    spec_leaves = jax.tree.leaves(
        specs, is_leaf=lambda s: isinstance(s, P))
    n_sharded = 0
    for leaf, spec in zip(leaves, spec_leaves):
        shards = leaf.addressable_shards
        n_dev = len({s.device for s in shards})
        rep.check(n_dev == 4,
                  f"leaf {leaf.shape} {spec} sits on {n_dev} devices")
        factor = 1
        for axis in spec:
            for a in ((axis,) if isinstance(axis, str) else axis or ()):
                factor *= four.shape[a]
        n_sharded += factor > 1
        rep.check(all(s.data.nbytes * factor == leaf.nbytes
                      for s in shards),
                  f"leaf {leaf.shape} {spec}: shards hold "
                  f"{[s.data.nbytes for s in shards]} B, spec implies "
                  f"{leaf.nbytes // factor}")
    rep["sharded_leaves"], rep["leaves"] = int(n_sharded), len(leaves)
    rep.check(n_sharded > 0, "no leaf is sharded on the 2x2 mesh")


# ---------------------------------------------------------------------------
# serve


def _make_reference(cfg, max_len: int, precision: Optional[str]):
    """``ref(params, tokens) -> [len(tokens), vocab]`` logits of the
    plain float32 forward (``T.reference_logits``), jitted once at
    ``max_len`` (the forward is causal: zero padding behind a prompt
    does not reach it). ``precision="highest"`` makes the reference's
    own matmuls true float32 on the MXU."""
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.models import transformer as T

    @jax.jit
    def fwd(params, tokens):
        return T.reference_logits(params, tokens[None], cfg)[0]

    def ref(params, tokens) -> np.ndarray:
        n = len(tokens)
        padded = np.zeros(max_len, np.int32)
        padded[:n] = tokens
        if precision is None:
            out = fwd(params, jnp.asarray(padded))
        else:
            with jax.default_matmul_precision(precision):
                out = fwd(params, jnp.asarray(padded))
        return np.asarray(out[:n])

    return ref


def _post(host: str, port: int, path: str, payload: dict,
          timeout: float = 120.0) -> Tuple[int, bytes]:
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        conn.request("POST", path, body=json.dumps(payload),
                     headers={"Content-Type": "application/json"})
        r = conn.getresponse()
        return r.status, r.read()      # read() de-chunks a stream
    finally:
        conn.close()


def _get_json(host: str, port: int, path: str) -> dict:
    conn = http.client.HTTPConnection(host, port, timeout=60.0)
    try:
        conn.request("GET", path)
        r = conn.getresponse()
        body = r.read()
        if r.status != 200:
            raise RuntimeError(f"GET {path} -> {r.status}: {body[:200]!r}")
        return json.loads(body)
    finally:
        conn.close()


def _direct_logits(sz: Sizes, decoder, prompts: Dict[int, np.ndarray],
                   prefix_prompt: np.ndarray) -> Dict[str, np.ndarray]:
    """Logits straight from the decoder's own entry points (before a
    scheduler owns it): cold prefill per prompt length, one decode step
    behind the prefills, and the prefix prefill over pages a cold
    prefill just wrote."""
    out: Dict[str, np.ndarray] = {}
    tokens = np.zeros(sz.n_slots, np.int32)
    pos = np.zeros(sz.n_slots, np.int32)
    for slot, (n, prompt) in enumerate(prompts.items()):
        nxt, logits = decoder.prefill_logits(slot, prompt)
        out[f"prefill_{n}"] = np.asarray(logits)
        tokens[slot], pos[slot] = nxt, n
    _, step_logits = decoder.step_logits(tokens, pos)
    step_logits = np.asarray(step_logits)
    for slot, n in enumerate(prompts):
        out[f"step_{n}"] = step_logits[slot]
    # the slot whose prompt `prefix_prompt` repeats the head of already
    # holds those rows in its (identity-table) pages
    src = next(s for s, p in enumerate(prompts.values())
               if len(p) >= sz.prefix_len
               and np.array_equal(p[:sz.prefix_len],
                                  prefix_prompt[:sz.prefix_len]))
    _, logits = decoder.prefill_prefix_logits(
        src, prefix_prompt, sz.prefix_len, None)
    out["prefix_prefill"] = np.asarray(logits)
    return out


def _direct_references(ref, params, direct, prompts,
                       prefix_prompt) -> Dict[str, np.ndarray]:
    """The reference logits for every entry of ``_direct_logits``."""
    want: Dict[str, np.ndarray] = {}
    for n, prompt in prompts.items():
        nxt = int(np.argmax(direct[f"prefill_{n}"]))
        both = ref(params, np.append(prompt, nxt).astype(np.int32))
        want[f"prefill_{n}"] = both[n - 1]
        want[f"step_{n}"] = both[n]
    want["prefix_prefill"] = ref(params, prefix_prompt)[-1]
    return want


def _check_generated(rep: Report, ref, params, prompt: np.ndarray,
                     tokens: List[int], tol: float) -> int:
    """Teacher-forced: ONE reference forward over prompt + served
    tokens scores every served token. Where the reference's top-2
    margin exceeds ``tol`` the served token must BE the argmax;
    elsewhere (a near-tie that rounding may flip) its reference logit
    must be within ``tol`` of the top. Returns the near-tie count."""
    seq = np.concatenate([prompt, np.asarray(tokens[:-1], np.int32)])
    logits = ref(params, seq.astype(np.int32))[len(prompt) - 1:]
    near = 0
    for i, tok in enumerate(tokens):
        row = logits[i]
        top2 = np.partition(row, -2)[-2:]
        margin = float(top2[1] - top2[0])
        if margin > tol:
            rep.check(int(np.argmax(row)) == tok,
                      f"prompt_len {len(prompt)}: served token {tok} at "
                      f"step {i} != reference argmax "
                      f"{int(np.argmax(row))} (margin {margin:.4f} > "
                      f"{tol})")
        else:
            near += 1
            rep.check(float(top2[1] - row[tok]) <= tol,
                      f"prompt_len {len(prompt)}: served token {tok} at "
                      f"step {i} is {float(top2[1] - row[tok]):.4f} "
                      f"below the reference top (near-tie margin "
                      f"{margin:.4f})")
    return near


def _serve(sz: Sizes, on_chip: bool, rep: Report, mesh,
           baseline: Optional[Dict[str, np.ndarray]],
           baseline_tol: float) -> Dict[str, np.ndarray]:
    """The serve phase over ``mesh`` (None = one device). Returns the
    decoder's direct logits (``serve4`` compares its own against the
    one-chip decoder's)."""
    from mmlspark_tpu.core.dataframe import DataFrame
    from mmlspark_tpu.models import transformer as T
    from mmlspark_tpu.models.nn import NNModel
    from mmlspark_tpu.models.zoo import ModelDownloader
    from mmlspark_tpu.serving import (
        DecodeScheduler, ServingServer, TransformerDecoder)
    from mmlspark_tpu.testing.datagen import synth_cifar

    cfg = transformer_config(sz, "float32")     # the decode path is f32
    params = T.init_params(cfg, seed=0)
    kw = {} if sz.attn_impl == "auto" else {"attn_impl": sz.attn_impl}
    if mesh is not None:
        kw["mesh"] = mesh
    decoder = TransformerDecoder(params, cfg, n_slots=sz.n_slots,
                                 max_len=sz.max_len, **kw)
    rep["attn_impl"] = decoder.attn_impl
    t0 = time.perf_counter()
    warm = decoder.warmup()
    rep["warmup_s"] = round(time.perf_counter() - t0, 2)
    rep["warmup_programs"] = warm
    n_buckets = len(decoder.prompt_buckets())
    rep.check(warm == 1 + 2 * n_buckets,
              f"warmup compiled {warm} programs, expected "
              f"{1 + 2 * n_buckets} (the step + {n_buckets} prefill + "
              f"{n_buckets} prefix-prefill buckets)")

    # ---- prompts: two requests of each length, plus two that repeat
    # the first `prefix_len` tokens of one long prompt with own tails
    rng = np.random.default_rng(1234)

    def draw(n: int) -> np.ndarray:
        return rng.integers(0, sz.vocab, size=n).astype(np.int32)

    prompts = {n: draw(n) for n in sz.prompt_lens}
    shared = next(p for p in prompts.values() if len(p) > sz.prefix_len)
    prefix_prompts = [np.concatenate([shared[:sz.prefix_len], draw(m)])
                      for m in (9, 23)]

    # ---- logits against the reference, through the decoder's own API
    ref = _make_reference(cfg, sz.max_len, "highest")
    direct = _direct_logits(sz, decoder, prompts, prefix_prompts[0])
    want = _direct_references(ref, params, direct, prompts,
                              prefix_prompts[0])
    diffs = {k: float(np.abs(direct[k] - want[k]).max()) for k in direct}
    rep["logits_max_abs_diff_vs_f32_reference"] = {
        k: _sig(v) for k, v in diffs.items()}
    rep["logits_abs_max"] = _sig(max(
        float(np.abs(v).max()) for v in want.values()))
    rep["logits_tol"] = LOGITS_TOL
    for k, v in direct.items():
        rep.check(v.shape == (sz.vocab,) and bool(np.isfinite(v).all()),
                  f"{k}: logits shape {v.shape} / non-finite")
    rep.check(max(diffs.values()) <= LOGITS_TOL,
              f"logits off the f32 reference by up to "
              f"{max(diffs.values()):.4g} > {LOGITS_TOL}")
    # for the record: the same reference at the backend's DEFAULT
    # matmul precision (what token-for-token parity leans on)
    ref_default = _make_reference(cfg, sz.max_len, None)
    longest = prompts[max(prompts)]
    rep["default_precision_reference_vs_f32_reference"] = _sig(float(
        np.abs(ref_default(params, longest)[-1]
               - ref(params, longest)[-1]).max()))
    if baseline is not None:
        bdiffs = {k: float(np.abs(direct[k] - baseline[k]).max())
                  for k in direct}
        rep["logits_max_abs_diff_vs_one_chip"] = {
            k: _sig(v) for k, v in bdiffs.items()}
        rep["one_chip_tol"] = baseline_tol
        rep.check(max(bdiffs.values()) <= baseline_tol,
                  f"logits off the one-chip decoder by up to "
                  f"{max(bdiffs.values()):.4g} > {baseline_tol}")

    # ---- the frame model: the hash-verified zoo checkpoint
    dl = ModelDownloader(os.path.join(REPO, ".zoo_cache"),
                         repo=os.path.join(REPO, "zoo"))
    meta = dl.list_models()["cifar10s_resnet20"]
    frame_model = NNModel(model=dl.load("cifar10s_resnet20"),
                          input_col="image", output_col="scores",
                          input_dtype=meta.input_dtype)
    images, _ = synth_cifar(sz.n_predict, seed=7)
    direct_scores = np.asarray(frame_model.transform(
        DataFrame({"image": images}))["scores"])

    # ---- the server
    sched = DecodeScheduler(decoder)
    srv = ServingServer(frame_model, port=0, decoder=sched)
    srv.start()
    try:
        host, port = srv.host, srv.port

        def generate(prompt, max_new, stream=False):
            return _post(
                host, port, "/generate" + ("?stream=1" if stream else ""),
                {"prompt": [int(t) for t in prompt],
                 "max_new_tokens": int(max_new)})

        def predict(i):
            return _post(host, port, "/predict",
                         {"image": images[i].tolist()})

        # wave 1: every cold /generate and every /predict, from 8
        # concurrent clients
        gen_jobs = [(prompts[n], sz.max_new[(2 * j + r) % len(sz.max_new)])
                    for j, n in enumerate(sz.prompt_lens)
                    for r in range(2)]
        with ThreadPoolExecutor(max_workers=8) as pool:
            gen_f = [pool.submit(generate, p, m) for p, m in gen_jobs]
            pred_f = [pool.submit(predict, i)
                      for i in range(sz.n_predict)]
            gen_r = [f.result() for f in gen_f]
            pred_r = [f.result() for f in pred_f]
        # wave 2 (the first wave's prompts are published now): the
        # prefix-hit pair, and one prompt twice — plain and streamed —
        # so both replies take the SAME path through the prefix cache
        again = prompts[sorted(prompts)[-2]]
        with ThreadPoolExecutor(max_workers=4) as pool:
            pre_f = [pool.submit(generate, p, sz.max_new[0])
                     for p in prefix_prompts]
            plain_f = pool.submit(generate, again, sz.max_new[1])
            stream_f = pool.submit(generate, again, sz.max_new[1], True)
            pre_r = [f.result() for f in pre_f]
            plain_r, stream_r = plain_f.result(), stream_f.result()

        replies = gen_r + pred_r + pre_r + [plain_r, stream_r]
        rep["n_replies"] = len(replies)
        bad = [(s, b[:200]) for s, b in replies if s != 200]
        if not rep.check(not bad, f"{len(bad)} non-200 replies, first: "
                                  f"{bad[:3]}"):
            return direct

        # /predict: argmax equals a direct transform
        for i, (_, body) in enumerate(pred_r):
            got = int(np.argmax(json.loads(body)["scores"]))
            rep.check(got == int(direct_scores[i].argmax()),
                      f"/predict image {i}: argmax {got} != direct "
                      f"{int(direct_scores[i].argmax())}")

        # the streamed final event equals the non-streamed reply
        events = [json.loads(e.split(b"data: ", 1)[1])
                  for e in stream_r[1].split(b"\n\n") if e.strip()]
        final = [dict(e) for e in events if e.get("done")]
        plain = json.loads(plain_r[1])
        if rep.check(len(final) == 1,
                     f"stream ended with {len(final)} final events"):
            final[0].pop("done")
            rep.check(final[0] == plain,
                      f"streamed final {final[0]} != plain reply {plain}")
        rep.check([e["token"] for e in events if "done" not in e]
                  == plain["tokens"], "streamed tokens != final tokens")

        # tokens against the reference, where the margin allows
        served = ([(p, m, json.loads(b)) for (p, m), (_, b)
                   in zip(gen_jobs, gen_r)]
                  + [(p, sz.max_new[0], json.loads(b)) for p, (_, b)
                     in zip(prefix_prompts, pre_r)]
                  + [(again, sz.max_new[1], plain)])
        compared = near = 0
        for prompt, want_new, reply in served:
            rep.check(reply["finish_reason"] == "length"
                      and reply["n_tokens"] == want_new,
                      f"reply {reply['finish_reason']}/"
                      f"{reply['n_tokens']} != length/{want_new}")
            near += _check_generated(rep, ref, params, prompt,
                                     reply["tokens"], LOGITS_TOL)
            compared += len(reply["tokens"])
        rep["tokens_compared"], rep["tokens_near_tie"] = compared, near

        # /decode/stats
        st = _get_json(host, port, "/decode/stats")
        pg = st["pages"]
        rep["decode_stats"] = {
            **{k: st[k] for k in (
                "attn_impl", "attn_impl_prefill", "n_step_faults",
                "n_compiles", "n_steps", "n_tokens", "n_prefills",
                "slots_free", "releases", "placement")},
            "pages": {k: pg[k] for k in ("n_pages", "free", "in_use",
                                         "cached", "pool_bytes")},
            "prefix_cache": {k: st["prefix_cache"][k] for k in (
                "lookups", "hits", "hit_tokens", "ledger_clean")}}
        if on_chip:
            rep.check(st["attn_impl"] == st["attn_impl_prefill"]
                      == "pallas",
                      f"decode engines: {st['attn_impl']}/"
                      f"{st['attn_impl_prefill']}")
        rep.check(st["n_step_faults"] == 0,
                  f"n_step_faults={st['n_step_faults']}")
        rep.check(set(st["releases"]) == {"length"},
                  f"release reasons: {st['releases']}")
        rep.check(st["n_compiles"] == warm,
                  f"compiles after warmup: {warm} -> {st['n_compiles']}")
        rep.check(st["slots_free"] == st["n_slots"] == sz.n_slots,
                  f"slots_free={st['slots_free']} of {st['n_slots']}")
        rep.check(pg["in_use"] == 0
                  and pg["free"] + pg["cached"] == pg["n_pages"],
                  f"pages not returned: {pg}")
        rep.check(st["prefix_cache"]["hits"] >= 1
                  and st["prefix_cache"]["ledger_clean"],
                  f"prefix cache: {st['prefix_cache']}")
        if mesh is not None:
            pl = st["placement"]
            n_dev = int(mesh.devices.size)
            rep.check(pl["mode"] == "tensor_parallel"
                      and pl["n_devices"] == n_dev
                      and pl["sharded_leaves"] > 0,
                      f"placement: {pl}")
            # the KV pool is all sharded: each device holds 1/n_dev
            rep.check(pl["state_bytes"] - pl["state_bytes_per_device"]
                      >= pg["pool_bytes"] * (n_dev - 1) // n_dev,
                      f"the KV pool is not split {n_dev} ways: {pl}")
    finally:
        srv.stop()                      # drains
    idle = sched.stats()
    rep.check(idle["slots_free"] == sz.n_slots and idle["waiting"] == 0,
              f"scheduler did not drain: {idle['slots_free']} free, "
              f"{idle['waiting']} waiting")
    return direct


def phase_serve(sz: Sizes, on_chip: bool, rep: Report, state: dict) -> None:
    state["one_chip_logits"] = _serve(sz, on_chip, rep, None, None, 0.0)
    if on_chip:
        rep.check(rep["attn_impl"] == "pallas",
                  f"attn_impl 'auto' resolved to {rep['attn_impl']!r}")


def phase_serve4(sz: Sizes, on_chip: bool, rep: Report, state: dict) -> None:
    import jax
    from mmlspark_tpu.parallel import MeshSpec, build_mesh
    if "one_chip_logits" not in state:
        raise RuntimeError("serve4 compares against the serve phase, "
                           "which did not produce logits")
    mesh = build_mesh(MeshSpec.from_dict({"model": 4}),
                      devices=jax.devices()[:4])
    _serve(sz, on_chip, rep, mesh, state["one_chip_logits"],
           SERVE4_LOGITS_TOL)


# ---------------------------------------------------------------------------
# fit


def phase_fit(sz: Sizes, on_chip: bool, rep: Report) -> None:
    """The paper's first workload (BASELINE config 1,
    examples/drug_discovery_quantile.py): quantile GBDT over a
    molecular-descriptor-shaped table, the Pallas histogram NAMED so an
    engine that cannot run raises instead of falling back."""
    from mmlspark_tpu.core.dataframe import DataFrame
    from mmlspark_tpu.gbdt import GBDTRegressor

    rng = np.random.default_rng(0)
    n, f = sz.fit_rows, sz.fit_features
    X = rng.normal(size=(n, f))
    y = X[:, :5].sum(axis=1) + 0.3 * rng.normal(size=n) + 5.0
    df = DataFrame({"features": X, "label": y})
    # the Pallas histogram is a one-device kernel, and a SHARDED fit
    # overrides a named engine with a warning (gbdt/booster.py — off
    # this PR's path, listed in CHANGES.md): on a four-chip host the
    # default data-parallel fit would quietly run XLA, so the fit is
    # pinned serial and any fallback warning fails the phase
    reg = GBDTRegressor(objective="quantile", alpha=0.9,
                        num_iterations=sz.fit_iterations, num_leaves=15,
                        histogram_impl=sz.histogram_impl,
                        parallelism="serial")
    t0 = time.perf_counter()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        model = reg.fit(df)
    rep["histogram_impl"] = sz.histogram_impl
    rep["first_fit_s"] = round(time.perf_counter() - t0, 2)
    fell_back = [str(w.message) for w in caught
                 if "falling back" in str(w.message)]
    rep.check(not fell_back, f"the fit fell back: {fell_back}")
    pred = np.asarray(model.transform(df)["prediction"])
    rep.check(pred.shape == (n,) and bool(np.isfinite(pred).all()),
              f"predictions shape {pred.shape} / non-finite")
    coverage = rep["p90_coverage"] = round(float((y <= pred).mean()), 4)
    rep["coverage_bound"] = list(FIT_COVERAGE)
    if on_chip:
        rep.check(FIT_COVERAGE[0] <= coverage <= FIT_COVERAGE[1],
                  f"P90 coverage {coverage} outside {FIT_COVERAGE}")


# ---------------------------------------------------------------------------
# driver


def _devices_or_exit(rehearse_cpu: bool):
    """The devices this run may use, or exit non-zero naming what was
    found. Nothing goes to stdout on refusal."""
    try:
        import jax
        devices = jax.devices()
    except Exception as e:  # noqa: BLE001 — no backend at all
        print(f"chip_smoke: JAX found no device: {type(e).__name__}: {e}",
              file=sys.stderr)
        raise SystemExit(2)
    d0 = devices[0]
    want = "cpu" if rehearse_cpu else "tpu"
    if d0.platform != want:
        print(f"chip_smoke: found platform={d0.platform} "
              f"(device_kind={d0.device_kind!r}, {len(devices)} "
              f"device(s)) but this invocation runs on {want} only and "
              f"does not fall back"
              + ("" if rehearse_cpu else
                 "; --rehearse-cpu is the tiny control-flow rehearsal"),
              file=sys.stderr)
        raise SystemExit(2)
    return devices


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny control-flow rehearsal on the CPU "
                         "(named XLA/interpreted engines; labelled cpu)")
    args = ap.parse_args(argv)

    devices = _devices_or_exit(args.rehearse_cpu)
    import jax
    d0 = devices[0]
    on_chip = not args.rehearse_cpu
    sz = FULL if on_chip else TINY

    # past this line the repo is needed: in a directory that holds this
    # file and nothing else the import raises (non-zero, no result)
    from mmlspark_tpu.core.environment import device_peaks
    if on_chip:
        device_peaks(d0.device_kind, d0.platform)   # unknown kind raises
    print(f"chip_smoke[{'tpu' if on_chip else 'cpu'}]: "
          f"platform={d0.platform} device_kind={d0.device_kind!r} "
          f"count={len(devices)} jax={jax.__version__} "
          f"compile_cache={jax.config.jax_compilation_cache_dir}",
          flush=True)

    state: dict = {}
    phases: List[Tuple[str, Callable[[Report], None], int]] = [
        ("train", lambda r: phase_train(sz, on_chip, r), 1),
        ("serve", lambda r: phase_serve(sz, on_chip, r, state), 1),
        ("fit", lambda r: phase_fit(sz, on_chip, r), 1),
        ("train4", lambda r: phase_train4(sz, on_chip, r), 4),
        ("serve4", lambda r: phase_serve4(sz, on_chip, r, state), 4),
    ]

    meter = CompileMeter()
    status: Dict[str, str] = {}
    t_all = time.perf_counter()
    for name, fn, need in phases:
        if len(devices) < need:
            status[name] = "not_run"
            print(f"phase {name}: not_run (needs {need} devices, have "
                  f"{len(devices)})", flush=True)
            continue
        rep = Report()
        c0, r0, h0 = meter.snapshot()
        t0 = time.perf_counter()
        try:
            fn(rep)
        except Exception as e:  # noqa: BLE001 — reported; exit != 0
            traceback.print_exc()
            rep["error"] = f"{type(e).__name__}: {e}"
        c1, r1, h1 = meter.snapshot()
        status[name] = ("failed" if "error" in rep or "failed_checks" in rep
                        else "passed")
        line = {"phase": name, "status": status[name],
                "wall_s": round(time.perf_counter() - t0, 2),
                "compile_s": round(c1 - c0, 2),
                "compile_requests": r1 - r0, "cache_hits": h1 - h0, **rep}
        print(f"phase {name}: {status[name]} wall={line['wall_s']}s "
              f"compile={line['compile_s']}s "
              f"cache_hits={h1 - h0}/{r1 - r0}", flush=True)
        print(json.dumps(line), flush=True)

    # "not_run" (too few devices) is never a pass: train, serve and fit
    # need one device, so they ran, and must have passed
    ok = "failed" not in status.values() and all(
        status[p] == "passed" for p in ("train", "serve", "fit"))
    total_c, total_r, total_h = meter.snapshot()
    print(json.dumps({
        "rehearsal": None if on_chip else "cpu",
        "jax": jax.__version__,
        "wall_s": round(time.perf_counter() - t_all, 2),
        "compile_s": round(total_c, 2),
        "compile_requests": total_r, "cache_hits": total_h,
        "phases": status}), flush=True)
    # the LAST line is the contract's object and holds exactly these
    # keys (the driver rejects any other); the run's own facts are the
    # summary line above it
    print(json.dumps({
        "ok": ok,
        "device": {"platform": d0.platform, "kind": d0.device_kind,
                   "count": len(devices)}}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
