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
    HELD_BY, LOOP_PHASES, LOOP_ROUTE, PASS_ORDERS, SLOW_PASS_MULTIPLE,
    STARVED_PHASES, pass_view, stall_word,
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


_WARM = []


def _warm_decoder() -> TransformerDecoder:
    """One decoder with its programs compiled, for the cases that run
    a scheduler over it one after another."""
    if not _WARM:
        _WARM.append(_decoder())
        _WARM[0].warmup()
    return _WARM[0]


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
    dec = _warm_decoder()
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
                                  "in_turn", "order", "starved"])
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
        loop = dict(run["stats"]["loop"])
        assert set(loop.pop("starved")) == set(STARVED_PHASES)
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
    elif what == "order":
        # every pass that reached its prepare says in which order it
        # ran, a held one which rule held it, and the dispatch's own
        # ``ahead`` agrees; the share of steps ahead is the counters'
        said = [sp for sp in passes if "prepare" in sp.view["phases_ms"]]
        assert all(sp.view["order"] in PASS_ORDERS for sp in said)
        assert not any("order" in sp.view for sp in passes
                       if sp not in said)
        for sp in said:
            held = sp.view["order"] not in ("ahead", "start")
            assert ("held_by" in sp.view) == held
            assert not held or sp.view["held_by"] in HELD_BY
        assert all(sp.view["ahead"] == (sp.view["order"] == "ahead")
                   for sp in stepped)
        n_ahead = sum(sp.view["order"] == "ahead" for sp in stepped)
        assert n_ahead / len(stepped) == pytest.approx(
            run["stats"]["n_steps_ahead"] / run["stats"]["n_steps"],
            abs=0.01)
        held = run["stats"]["held_by"]
        assert sum(held.values()) == sum("held_by" in sp.view
                                         for sp in said)
        assert held["free_slot"] > 0 and held["last_token"] > 0
    elif what == "starved":
        # /decode/stats' account is the sum of the passes', and a pass
        # says how much of it its thread and its process computed
        total = dict.fromkeys(STARVED_PHASES, 0.0)
        for sp in passes:
            for k, ms in sp.attrs["starved_ms"].items():
                assert 0 < ms <= sp.view["phases_ms"][k] + 1e-9
                total[k] += ms
            if sp.view.get("order") == "ahead":
                assert sp.attrs["starved_ms"] == {}
            assert 0 <= sp.attrs["cpu_ms"] <= sp.attrs["proc_cpu_ms"] + 0.5
        assert run["stats"]["loop"]["starved"] == pytest.approx(
            {k: ms * 1e-3 for k, ms in total.items()}, abs=1e-5)
        assert all(ms > 0 for ms in total.values())
        # an idle wait is no starvation: the account is far under the
        # seconds the loop spent in ``idle`` and in everything else
        assert sum(total.values()) < sum(
            sp.duration_ms - sp.view["phases_ms"].get("idle", 0.0)
            for sp in passes)
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


def _phases(ms: float, t0_ns: int, idle: bool = False, order=None,
            seq=None, fetched=None, prefill_ns: int = 0):
    """One pass of ``ms`` milliseconds as the loop hands it to
    ``_record_pass``: admit, prepare, dispatch, fetch and emit of 1 us
    each but the fetch, which takes the rest. ``order`` goes onto the
    prepare and leaves out the dispatch (``fetch_only``) or the fetch
    and emit (``start``); ``seq`` / ``fetched`` onto dispatch and
    fetch; ``prefill_ns`` is a prefill inside an admit that much
    longer."""
    a = t0_ns
    if idle:
        return [("decode.admit", a, a + 1000, {"admitted": 0}),
                ("decode.idle", a + 1000, a + int(ms * 1e6), None)]
    b = a + int(ms * 1e6)
    out = []
    if prefill_ns:
        out.append(("decode.prefill", a, a + prefill_ns, {"slot": 0}))
    at = a + 1000 + prefill_ns
    out.append(("decode.admit", a, at, {"admitted": bool(prefill_ns)}))
    attrs = {"active": 1, "traces": ["t-1"]}
    if order is not None:
        attrs["order"] = order
    out.append(("decode.prepare", at, at + 1000, attrs))
    at += 1000
    if order != "fetch_only":
        out.append(("decode.dispatch", at, at + 1000,
                    None if seq is None else {"seq": seq}))
        at += 1000
    if order != "start":
        out += [("decode.fetch", at, b - 1000,
                 None if fetched is None else {"fetched": fetched}),
                ("decode.emit", b - 1000, b, {"emitted": 1})]
    return out


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


# ---------------------------------------------------------------------------
# (f) the loop's own account of the time it left the device without work


def _starved_us(sched, passes):
    """The passes through ``_record_pass`` -> each one's ``starved_ms``
    in whole microseconds (the helper's phases are 1 us long)."""
    for phases in passes:
        sched._record_pass(phases)
    return [{k: round(ms * 1e3) for k, ms in sp.attrs["starved_ms"].items()}
            for sp in sched.tracer.recorder.scan("decode.pass")]


_T = 1_000_000_000
_WHOLE_TURN = {"admit": 1, "prepare": 1, "dispatch": 1, "emit": 1}


@pytest.mark.parametrize("case, passes, want", [
    # today's order: the whole host's turn, and not the fetch
    ("in_turn", [_phases(9.0, _T, order="in_turn", seq=1, fetched=1)],
     [_WHOLE_TURN]),
    # the helper as the older cases use it, with no attribute at all
    ("no_attrs", [_phases(9.0, _T)], [_WHOLE_TURN]),
    # a step left in flight, then one queued behind it: the pass that
    # starts pays its turn, the pass that runs ahead nothing
    ("ahead", [_phases(1.0, _T, order="start", seq=1),
               _phases(9.0, 2 * _T, order="ahead", seq=2, fetched=1),
               _phases(9.0, 3 * _T, order="ahead", seq=3, fetched=2)],
     [{"admit": 1, "prepare": 1, "dispatch": 1}, {}, {}]),
    # the pass that only fetches is starved from its fetch on; the one
    # behind it keeps today's order and pays the whole turn
    ("fetch_only_then_in_turn",
     [_phases(1.0, _T, order="start", seq=1),
      _phases(9.0, 2 * _T, order="fetch_only", fetched=1),
      _phases(9.0, 3 * _T, order="in_turn", seq=2, fetched=2)],
     [{"admit": 1, "prepare": 1, "dispatch": 1}, {"emit": 1},
      _WHOLE_TURN]),
    # a prefill is device work of another program: the admit around it
    # counts less its child
    ("prefill_child",
     [_phases(9.0, _T, order="in_turn", seq=1, fetched=1,
              prefill_ns=5_000_000)], [_WHOLE_TURN]),
    # a prefill behind a step in flight waits for both: what follows it
    # finds the device with nothing queued (admit: the 1 us behind it)
    ("prefill_behind_a_step",
     [_phases(1.0, _T, order="start", seq=1),
      _phases(9.0, 2 * _T, order="fetch_only", fetched=1,
              prefill_ns=5_000_000)],
     [{"admit": 1, "prepare": 1, "dispatch": 1},
      {"admit": 1, "prepare": 1, "emit": 1}]),
    # waiting for work is no starvation, and leaves nothing in flight
    ("idle", [_phases(50.0, _T, idle=True),
              _phases(9.0, 2 * _T, order="in_turn", seq=1, fetched=1)],
     [{"admit": 1}, _WHOLE_TURN]),
    # a speculative round is another program's device work: the turn
    # before it counts, its dispatches and its emit do not
    ("spec_round", [_phases(9.0, _T, order="spec_round")],
     [{"admit": 1, "prepare": 1}]),
])
def test_record_pass_keeps_the_starved_account(case, passes, want):
    sched = DecodeScheduler(_decoder(), tracer=Tracer())
    got = _starved_us(sched, passes)
    assert got == want
    total = {k: sum(p.get(k, 0) for p in want) for k in STARVED_PHASES}
    assert {k: round(ns / 1000) for k, ns in sched.starved_ns.items()} \
        == total
    assert sched.stats()["loop"]["starved"] == pytest.approx(
        {k: us * 1e-6 for k, us in total.items()}, abs=1e-9)


def test_the_account_is_exported_by_phase():
    """``serving_decode_device_starved_seconds_total{phase}`` reads the
    same sums when it is scraped, with nothing on the loop's path."""
    from mmlspark_tpu.core.telemetry import MetricsRegistry
    reg = MetricsRegistry()
    sched = DecodeScheduler(_decoder(), tracer=Tracer(), registry=reg)
    sched._record_pass(_phases(9.0, _T, order="in_turn", seq=1, fetched=1))
    text = reg.render()
    got = dict(re.findall(
        r'^serving_decode_device_starved_seconds_total\{phase="(\w+)"\} '
        r'(\S+)$', text, re.M))
    assert {k: float(v) for k, v in got.items()} == pytest.approx(
        dict.fromkeys(STARVED_PHASES, 1e-6))


# ---------------------------------------------------------------------------
# (g) a stall says whether anybody ran


def _spin_cpu(seconds: float) -> None:
    end = time.thread_time() + seconds
    while time.thread_time() < end:
        pass


@pytest.mark.parametrize("how, want", [
    ("sleeps", "blocked"), ("spins", "on_cpu"), ("waits_for_a_thread",
                                                 "contended")])
def test_a_retained_stall_says_who_ran(how, want):
    """A fetch that holds a pass for 0.2 s (in the decoder, before
    its span opens: the pass's length and its clocks see it): asleep,
    nothing of the process ran (``blocked``); spinning, the loop's own thread did
    (``on_cpu``); waiting for another thread that spins, the process
    did and the loop's thread did not (``contended``). The words are
    decided by halves of the pass, so each case has a factor of two of
    room under other tests' load."""
    dec = _warm_decoder()
    tracer = Tracer(default_slow_ms=150.0)
    sched = DecodeScheduler(dec, tracer=tracer)
    inner, n = dec.fetch_step, [0]

    def fetch_step(step):
        n[0] += 1
        if n[0] == 3:
            if how == "sleeps":
                time.sleep(0.2)
            elif how == "spins":
                _spin_cpu(0.2)
            else:
                other = threading.Thread(target=_spin_cpu, args=(0.2,))
                other.start()
                other.join()
        return inner(step)

    dec.fetch_step = fetch_step
    sched.start()
    try:
        _drive(sched, n=1, max_new=6)
    finally:
        sched.stop()
        del dec.fetch_step
    (kept,) = [t for t in tracer.traces() if t["route"] == LOOP_ROUTE
               and t["duration_ms"] >= 190.0]
    ms = kept["duration_ms"]
    stall = tracer.get_trace(kept["trace_id"])["spans"][0]["attrs"]
    assert stall["stall"] == want, stall
    if want == "blocked":
        assert stall["cpu_ms"] < ms / 2 and stall["proc_cpu_ms"] < ms / 2
    elif want == "on_cpu":
        assert stall["cpu_ms"] > ms / 2
    else:
        assert stall["cpu_ms"] < ms / 2 < stall["proc_cpu_ms"]
    # only a retained pass is spelled out
    assert all("stall" not in sp.attrs
               for sp in tracer.recorder.scan("decode.pass")
               if "phases_ms" not in sp.attrs)


@pytest.mark.parametrize("ns, cpu, proc, want", [
    (100, 51, 51, "on_cpu"), (100, 50, 51, "contended"),
    (100, 10, 400, "contended"), (100, 50, 50, "blocked"),
    (100, 0, 0, "blocked")])
def test_stall_word(ns, cpu, proc, want):
    assert stall_word(ns, cpu, proc) == want


# ---------------------------------------------------------------------------
# (h) what the account costs a pass


@pytest.mark.perf
def test_record_pass_costs_a_few_spans():
    """``_record_pass`` runs between two steps. On a pass of seven
    phases (a prefill inside its admit, prepare, dispatch, fetch,
    emit, compact) it costs no more than 12 spans of the primitive
    opened and closed under an owner, which is what the loop pays to
    HAVE the seven: a ratio on one machine, not a wall-clock bound
    (measured 4-5 spans' worth; PERF.md section 6, PR 37)."""
    sched = DecodeScheduler(_decoder(), tracer=Tracer())
    seven = _phases(9.0, _T, order="in_turn", seq=1, fetched=1,
                    prefill_ns=5_000_000)
    seven.append(("decode.compact", seven[-1][2], seven[-1][2] + 1000,
                  {"slot": 0}))
    assert len(seven) == 7

    def best_of(fn, n=5000, rounds=5):
        best = float("inf")
        for _ in range(rounds):
            t0 = time.perf_counter_ns()
            for _ in range(n):
                fn()
            best = min(best, (time.perf_counter_ns() - t0) / n)
        return best

    def one_span():
        with span("decode.emit") as sp:
            sp.attrs = {"emitted": 1}

    with collect():
        span_ns = best_of(one_span)
    pass_ns = best_of(lambda: sched._record_pass(seven))
    assert pass_ns < 12 * span_ns, (pass_ns, span_ns)
