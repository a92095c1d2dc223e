"""The decode loop's phases and the model's parts under their own
names (ISSUE 26): one span primitive (``core/profiling.span``) in
``DecodeScheduler`` and ``TransformerDecoder``, one ``decode.pass`` span
a pass in the tracer's ring, ``jax.named_scope`` on every part of the
transformer's programs."""

import glob
import json
import os
import re
import threading
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu.core.profiling import collect, span
from mmlspark_tpu.core.tracing import TRACER, Tracer
from mmlspark_tpu.models import transformer as T
from mmlspark_tpu.parallel import MeshSpec, build_mesh
from mmlspark_tpu.serving import DecodeScheduler, TransformerDecoder
from mmlspark_tpu.serving.decode import (
    LOOP_PHASES, LOOP_ROUTE, SLOW_PASS_MULTIPLE, pass_view,
)

CFG = T.TransformerConfig(vocab=64, d_model=16, n_heads=2, d_head=8,
                          d_ff=32, n_stages=1, layers_per_stage=2)
PARAMS = T.init_params(CFG, seed=0)
STEP_PHASES = ("admit", "prepare", "dispatch", "fetch", "emit")


class _Pending:
    """The slice of _PendingRequest the standalone scheduler touches."""

    def __init__(self, payload, rid):
        self.payload, self.rid, self.trace = payload, rid, f"trace-{rid}"
        self.deadline = self.reply = self.span = None
        self.event = threading.Event()
        self.callbacks = []
        self.status = 200


def _decoder(**kw) -> TransformerDecoder:
    return TransformerDecoder(PARAMS, CFG, n_slots=4, max_len=32, **kw)


def _drive(sched, n=6, max_new=6):
    rng = np.random.default_rng(3)
    reqs = [_Pending({"prompt": [int(t) for t in rng.integers(
        0, CFG.vocab, size=2 + i)], "max_new_tokens": max_new}, f"r{i}")
        for i in range(n)]
    for p in reqs:
        sched.submit(p)
    for p in reqs:
        assert p.event.wait(60) and p.status == 200
    return reqs


# ---------------------------------------------------------------------------
# (a) a scheduler run leaves whole passes in TRACER


@pytest.fixture(scope="module")
def run():
    dec = _decoder()
    dec.warmup()
    t0 = time.monotonic()
    sched = DecodeScheduler(dec, tracer=TRACER).start()
    try:
        reqs = _drive(sched)
        deadline = time.monotonic() + 10
        while sched.stats()["slots_in_use"] and time.monotonic() < deadline:
            time.sleep(0.005)
    finally:
        sched.stop()
    stats = sched.stats()           # the loop is dead: the final counts
    # each pass with its phases spelled out
    passes = [types.SimpleNamespace(
        trace_id=sp.trace_id, parent_id=sp.parent_id, attrs=sp.attrs,
        duration_ms=sp.duration_ms, view=pass_view(sp.attrs["phases"]))
        for sp in TRACER.recorder.scan("decode.pass", t0,
                                       time.monotonic())]
    return {"passes": passes, "stats": stats, "reqs": reqs}


@pytest.mark.parametrize("what", ["phases_present", "chain", "prefills",
                                  "stats_loop", "pool", "ahead",
                                  "in_turn"])
def test_scheduler_run_leaves_whole_passes(run, what):
    passes = run["passes"]
    stepped = [sp for sp in passes if sp.attrs["step"] is not None]
    assert stepped and all(sp.attrs["route"] == LOOP_ROUTE
                           and sp.parent_id is None for sp in passes)
    if what == "phases_present":
        seen = set().union(*(sp.view["phases_ms"] for sp in passes))
        # ``compact`` is the phase of decoders with a second kind of
        # cache row (tests/test_serving_decode.py TestTwoRowKinds): the
        # softmax block never opens it
        assert seen == set(LOOP_PHASES) - {"compact"}
        # a pass that dispatched a step fetched and emitted one too, or
        # left its step in flight for the next pass to queue behind
        for sp, nxt in zip(stepped, stepped[1:] + [None]):
            assert set(STEP_PHASES[:3]) <= set(sp.view["phases_ms"])
            if not set(STEP_PHASES) <= set(sp.view["phases_ms"]):
                assert not sp.view["ahead"] and nxt.view["ahead"]
                assert nxt.view["fetched"] == sp.view["seq"]
        # every pass under a trace id of its own, steps in sequence
        assert len({sp.trace_id for sp in passes}) == len(passes)
        steps = [sp.attrs["step"] for sp in stepped]
        assert steps == list(range(steps[0], steps[0] + len(steps)))
    elif what == "chain":
        # the top-level phases (prefill is admit's child) do not
        # overlap and sum to the pass: within 5% over the run, and no
        # pass loses more than 0.2 ms to the Python between them
        total = covered = 0.0
        for sp in stepped:
            ms = sp.duration_ms
            top = sum(v for k, v in sp.view["phases_ms"].items()
                      if k != "prefill")
            assert top <= ms + 1e-6 and ms - top < 0.2
            assert sp.view["phases_ms"].get("prefill", 0.0) \
                <= sp.view["phases_ms"]["admit"]
            total, covered = total + ms, covered + top
        assert covered >= 0.95 * total
    elif what == "prefills":
        got = [p for sp in passes for p in sp.view["prefills"]]
        assert sorted(p["trace"] for p in got) == sorted(
            r.trace for r in run["reqs"])
        for p in got:
            r = next(r for r in run["reqs"] if r.trace == p["trace"])
            n = len(r.payload["prompt"])
            assert p["prompt_len"] == n and p["prefix_hit"] == 0
            assert p["bucket"] >= n and p["bucket"] & (p["bucket"] - 1) == 0
            assert p["ms"] > 0 and p["queue_wait_ms"] >= 0
            assert 0 <= p["slot"] < 4 and 0 <= p["others_active"] < 4
        # a request's trace id names the passes it rode
        rider = run["reqs"][0].trace
        assert [sp for sp in stepped if rider in sp.attrs["traces"]]
    elif what == "stats_loop":
        loop = run["stats"]["loop"]
        assert set(loop) == set(LOOP_PHASES)
        n = {k: sum(1 for sp in passes if k in sp.view["phases_ms"])
             for k in LOOP_PHASES if k != "prefill"}
        assert {k: loop[k]["n"] for k in n} == n
        # every step dispatched was fetched, in a later pass or its own
        assert loop["dispatch"]["n"] == loop["fetch"]["n"] \
            == run["stats"]["n_steps"]
        assert run["stats"]["n_steps_ahead"] == sum(
            sp.view["ahead"] for sp in stepped)
        assert run["stats"]["n_tokens_discarded"] == 0
        assert loop["prefill"]["n"] == run["stats"]["n_prefills"] == 6
        # prefill_s and the prefill phase are the same clock reads
        assert loop["prefill"]["s"] == pytest.approx(
            run["stats"]["prefill_s"], abs=1e-4)
        for k in LOOP_PHASES:
            ms = sum(sp.view["phases_ms"].get(k, 0.0) for sp in passes)
            assert loop[k]["s"] == pytest.approx(ms * 1e-3, abs=1e-5)
    elif what == "ahead":
        # four slots taken by greedy requests: the loop queues a step
        # behind the one in flight, and fetches that one after
        ahead = [sp for sp in stepped if sp.view["ahead"]]
        assert ahead and len(ahead) == run["stats"]["n_steps_ahead"]
        for sp in ahead:
            by_name = {name[7:]: (a, b)
                       for name, a, b, _ in sp.attrs["phases"]}
            assert by_name["dispatch"][1] <= by_name["fetch"][0]
            assert sp.view["fetched"] == sp.view["seq"] - 1
            assert sp.view["active"] == sp.view["emitted"] == 4
    elif what == "in_turn":
        # with a free slot, or a request whose token in flight is its
        # last, a pass fetches the step it dispatched, or only fetches
        turn = [sp for sp in stepped if not sp.view["ahead"]
                and "fetch" in sp.view["phases_ms"]]
        assert turn and all(sp.view["fetched"] == sp.view["seq"]
                            for sp in turn)
        assert any(sp.view["active"] < 4 for sp in turn)
        only = [sp for sp in passes if sp.attrs["step"] is None
                and "fetch" in sp.view["phases_ms"]]
        assert only and all(
            set(sp.view["phases_ms"]) == {"admit", "prepare", "fetch",
                                          "emit"}
            for sp in only)
    else:
        for sp in stepped:
            at = sp.view
            assert 1 <= at["active"] <= 4
            assert at.get("emitted", at["active"]) == at["active"]
            assert at["n_pages"] == run["stats"]["pages"]["n_pages"]
            assert at["active"] <= at["pages_in_use"] <= at["n_pages"]


# ---------------------------------------------------------------------------
# (b) under a profiler session the phases are host events of the xplane


@pytest.fixture(scope="module")
def xplane_events(tmp_path_factory):
    from jax.profiler import ProfileData
    log_dir = str(tmp_path_factory.mktemp("loop_trace"))
    dec = _decoder()
    dec.warmup()
    sched = DecodeScheduler(dec, tracer=Tracer()).start()
    try:
        with jax.profiler.trace(log_dir):
            _drive(sched, n=3, max_new=4)
    finally:
        sched.stop()
    path = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    names = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("decode."):
                    names.setdefault(ev.name, []).append(
                        (plane.name, int(ev.duration_ns)))
    return names


@pytest.mark.parametrize("name", ["decode.dispatch", "decode.fetch",
                                  "decode.emit", "decode.prefill"])
def test_profiler_trace_holds_the_phases(xplane_events, name):
    assert name in xplane_events, sorted(xplane_events)
    assert all(plane.startswith("/host:") and ns > 0
               for plane, ns in xplane_events[name])


# ---------------------------------------------------------------------------
# (c) the compiled programs carry every scope, and keep their names


def _program(kind: str) -> str:
    if kind == "train":
        cfg = T.TransformerConfig(
            vocab=64, d_model=16, n_heads=2, d_head=8, d_ff=32,
            n_stages=1, layers_per_stage=1, microbatches=1)
        mesh = build_mesh(MeshSpec.from_dict({"data": 1}),
                          devices=jax.devices()[:1])
        step = T.build_spmd_train_step(cfg, mesh, donate=False)
        params = T.shard_params(T.init_params(cfg, 0), cfg, mesh)
        tok = jnp.zeros((2, 8), jnp.int32)
        return step.lower(params, params, tok, tok,
                          jnp.ones((2, 8), jnp.float32)).compile().as_text()
    cache = T.init_paged_kv_cache(CFG, 9, 8)
    table = jnp.zeros(4, jnp.int32)
    slots = (jnp.zeros(2, jnp.int32), jnp.zeros(2, jnp.int32))
    if kind == "step":
        fn = T.build_paged_decode_step(CFG, 2, 8, 4, donate=False)
        args = slots + (jnp.zeros((2, 4), jnp.int32),)
    elif kind == "verify":
        fn = T.build_paged_verify_step(CFG, 2, 3, 8, 4, donate=False,
                                       with_scores=True, ce_impl="xla")
        args = (jnp.zeros((2, 3), jnp.int32), slots[1],
                jnp.zeros((2, 4), jnp.int32))
    elif kind in ("propose", "lane_prefill"):
        # the draft's programs, over its unpaged lane pool
        cache = T.init_kv_cache(CFG, 2, 32)
        if kind == "propose":
            fn, args = T.build_draft_propose(CFG, 2, 32, 3,
                                             donate=False), slots
        else:
            fn = T.build_prefill(CFG, donate=False)
            args = (jnp.zeros(16, jnp.int32), np.int32(1), np.int32(3))
    elif kind == "prefill":
        fn = T.build_paged_prefill(CFG, 8, 4, donate=False)
        args = (jnp.zeros(16, jnp.int32), table, np.int32(3))
    else:
        fn = T.build_paged_prefix_prefill(CFG, 8, 4, donate=False)
        args = (jnp.zeros(8, jnp.int32), table, np.int32(11), np.int32(8))
    return fn.lower(PARAMS, cache, *args).compile().as_text()


_FORWARD = ("embed", "norm", "attn.qkv", "attn.core", "attn.out", "ffn",
            "head")
_PROGRAMS = {
    "train": ("jit_local_step", _FORWARD + ("ce", "optimizer"),
              ("norm", "attn.qkv", "attn.core", "attn.out", "ffn",
               "head", "ce")),
    "step": ("jit_step", _FORWARD + ("kv.write", "kv.gather"), ()),
    "prefill": ("jit_prefill", _FORWARD + ("kv.write",), ()),
    "prefix_prefill": ("jit_prefill",
                       _FORWARD + ("kv.write", "kv.gather"), ()),
    "verify": ("jit_verify",
               _FORWARD + ("kv.write", "kv.gather", "ce"), ()),
    "propose": ("jit_propose", _FORWARD + ("kv.write", "kv.gather"), ()),
    "lane_prefill": ("jit_prefill", _FORWARD + ("kv.write",), ()),
}


@pytest.mark.parametrize("kind", sorted(_PROGRAMS))
def test_compiled_programs_carry_every_scope(kind):
    module, forward, backward = _PROGRAMS[kind]
    text = _program(kind)
    assert re.search(rf"^HloModule {module}\b", text, re.M)
    ops = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in forward:
        part = re.compile(rf"[/(]{re.escape(scope)}[/)]")
        assert any(part.search(op) and "transpose(" not in op
                   for op in ops), (scope, sorted(ops)[:40])
    for scope in backward:
        part = re.compile(rf"transpose\(jvp\({re.escape(scope)}[/)]")
        assert any(part.search(op) for op in ops), scope


# ---------------------------------------------------------------------------
# (d) the primitive's cost with no profiler session running


@pytest.mark.perf
@pytest.mark.parametrize("how", ["unowned", "owned", "with_attrs"])
def test_span_under_budget_with_no_session(how):
    """4 us a span, the flight recorder's own budget
    (``tests/test_tracing.py`` ``SPAN_BUDGET_NS``): the loop opens six
    or seven a step against a step of milliseconds."""
    budget_ns = 4000

    def one():
        if how == "with_attrs":
            with span("decode.prefill", bucket=128, slot=3, trace="t"):
                pass
        else:
            with span("decode.emit"):
                pass

    def per_op(n=20000, rounds=3):
        best = float("inf")
        for _ in range(rounds):
            t0 = time.perf_counter_ns()
            for _ in range(n):
                one()
            best = min(best, (time.perf_counter_ns() - t0) / n)
        return best

    one()                                  # import the annotation once
    if how == "unowned":
        assert per_op() < budget_ns
        return
    with collect() as spans:
        assert per_op() < budget_ns
    assert len(spans) == 60000 and spans[0][0].startswith("decode.")


# ---------------------------------------------------------------------------
# (e) a slow pass is retained under route decode.loop, with its phases


def _phases(ms: float, t0_ns: int, idle: bool = False):
    a = t0_ns
    if idle:
        return [("decode.admit", a, a + 1000, {"admitted": 0}),
                ("decode.idle", a + 1000, a + int(ms * 1e6), None)]
    b = a + int(ms * 1e6)
    return [("decode.admit", a, a + 1000, {"admitted": 0}),
            ("decode.prepare", a + 1000, a + 2000,
             {"active": 1, "traces": ["t-1"]}),
            ("decode.dispatch", a + 2000, a + 3000, None),
            ("decode.fetch", a + 3000, b - 1000, None),
            ("decode.emit", b - 1000, b, {"emitted": 1})]


@pytest.mark.parametrize("ms, idle, kept", [
    (1.0 * SLOW_PASS_MULTIPLE + 1.0, False, True),
    (1.0 * SLOW_PASS_MULTIPLE - 1.0, False, False),
    (10_000.0, True, False)])
def test_slow_pass_is_retained_under_its_route(ms, idle, kept):
    tracer = Tracer()
    sched = DecodeScheduler(_decoder(), tracer=tracer)
    t = 1_000_000_000
    for _ in range(64):                     # the running median: 1 ms
        sched._record_pass(_phases(1.0, t))
        t += 2_000_000
    assert tracer.threshold(LOOP_ROUTE) == pytest.approx(
        SLOW_PASS_MULTIPLE * 1.0)
    assert tracer.traces() == []
    sched._record_pass(_phases(ms, t, idle))
    got = tracer.traces()
    assert bool(got) == kept
    if kept:
        assert got[0]["route"] == LOOP_ROUTE and got[0]["reason"] == "slow"
        tr = tracer.get_trace(got[0]["trace_id"])
        (root,) = tr["spans"]
        assert root["name"] == "decode.pass"
        assert root["attrs"]["traces"] == ["t-1"]
        # a retained pass has its view spelled out beside the phases
        assert root["attrs"]["phases_ms"]["fetch"] == pytest.approx(
            ms - 0.004)
        assert root["attrs"]["active"] == 1
        json.dumps(tr)                      # /trace/<id> can serve it


def test_slow_requests_do_not_churn_out_a_retained_stall():
    """On a decode worker every request lasts seconds and is retained
    as slow: the store's quota is per reason AND route, so a hundred of
    them leave the loop's one stall where ``GET /traces`` finds it."""
    tracer = Tracer(default_slow_ms=250.0)
    sched = DecodeScheduler(_decoder(), tracer=tracer)
    sched._record_pass(_phases(400.0, 1_000_000_000))      # the stall
    (stall,) = tracer.traces()
    for i in range(100):
        root = tracer.start("request", route="/generate")
        root.t0 -= 6.0                  # six seconds of tokens
        tracer.finish(root)
    kept = tracer.traces()
    assert stall["trace_id"] in {t["trace_id"] for t in kept}
    assert sum(t["route"] == "/generate" for t in kept) <= 33
