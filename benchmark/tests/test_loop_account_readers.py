"""The five readers of the decode loop's own account (PR 37) against
hand-made ``decode.pass`` spans: known answers, ``None`` on a recorder
whose passes say no ``order`` (the parent), 0.0 for a window without a
stall, and their entries in ``BENCHMARK.json``."""

import types

import pytest

import run as harness
from mmlspark_tpu.core.tracing import TRACER

READERS = ("steps_ahead_share.serve", "device_starved_share.serve",
           "starved_ms_per_request.serve", "loop_stall_share.serve",
           "loop_stall_blocked_share.serve")
SERVE_CELLS = ["pythia-1.4b.chat-closed", "evabyte-6.5b.doc-closed",
               "granite-4.0-h-small.rag-closed", "ouro-2.6b.reason-closed"]
_EPOCH = [7.0e6]           # seconds no real span of this process has


def ctx_at(t_open: float, seconds: float = 10.0):
    return types.SimpleNamespace(
        t_start=t_open - 4.0, phases={"warm_request": 3.0},
        traffic={"ramp_s": 1.0}, seconds=seconds)


def a_pass(t0, ms, order, starved=None, cpu_ms=None, proc_cpu_ms=None,
           prefill_ms=0.0, dispatch=True, says_order=True):
    """One pass as the scheduler records it since PR 37: the phases as
    they closed, ``order`` on the prepare, the account on the pass."""
    def ns(x_ms):
        return int(round(x_ms * 1e6))
    a = ns(t0 * 1e3)
    phases, at = [], a
    if prefill_ms:
        phases.append(("decode.prefill", at, at + ns(prefill_ms),
                       {"slot": 0, "queue_wait_ms": 1.0}))
        at += ns(prefill_ms)
    phases.append(("decode.admit", a, at + ns(0.25), {"admitted": 0}))
    at += ns(0.25)
    prep = {"active": 2, "pages_in_use": 8, "n_pages": 64, "traces": []}
    if says_order:
        prep["order"] = order
        if order in ("in_turn", "fetch_only"):
            prep["held_by"] = "free_slot"
    phases.append(("decode.prepare", at, at + ns(0.25), prep))
    at += ns(0.25)
    if dispatch:
        phases.append(("decode.dispatch", at, at + ns(0.5),
                       {"seq": 2, "ahead": order == "ahead"}))
        at += ns(0.5)
    end = a + ns(ms)
    if order != "start":
        phases.append(("decode.fetch", at, end - ns(0.25),
                       {"fetched": 1}))
        phases.append(("decode.emit", end - ns(0.25), end,
                       {"emitted": 2}))
    attrs = {}
    if says_order:
        attrs = {"starved_ms": starved or {},
                 "cpu_ms": ms / 4 if cpu_ms is None else cpu_ms,
                 "proc_cpu_ms": ms / 3 if proc_cpu_ms is None
                 else proc_cpu_ms}
    TRACER.add("decode.pass", a * 1e-9, end * 1e-9, None, capture=False,
               route="decode.loop", step=1, traces=[], phases=phases,
               **attrs)


_TURN = {"admit": 0.25, "prepare": 0.25, "dispatch": 0.5, "emit": 0.25}


def fill(t, stalls: bool, says_order: bool = True):
    """A window of 10 s: eight passes that ran ahead (10 ms each), one
    that started the pipe behind a 30 ms prefill, one that only
    fetched, one in today's order; with ``stalls`` two more in today's
    order, 160 ms and 110 ms long, the first asleep and the second
    computing; a pass before the window and one after it."""
    kw = {"says_order": says_order}
    a_pass(t - 0.5, 500.0, "in_turn", _TURN, **kw)              # before
    for k in range(8):
        a_pass(t + 1.0 + 0.02 * k, 10.0, "ahead", **kw)
    a_pass(t + 2.0, 31.0, "start", {"admit": 0.25, "prepare": 0.25,
                                    "dispatch": 0.5},
           prefill_ms=30.0, **kw)
    a_pass(t + 3.0, 10.0, "fetch_only", {"emit": 0.25}, dispatch=False,
           **kw)
    a_pass(t + 4.0, 10.0, "in_turn", _TURN, **kw)
    if stalls:
        a_pass(t + 5.0, 160.0, "in_turn", _TURN, cpu_ms=2.0,
               proc_cpu_ms=5.0, **kw)
        a_pass(t + 6.0, 110.0, "in_turn", _TURN, cpu_ms=100.0,
               proc_cpu_ms=104.0, **kw)
    a_pass(t + 10.0, 500.0, "in_turn", _TURN, **kw)             # after
    return ctx_at(t)


def window(stalls: bool, **kw):
    _EPOCH[0] += 1000.0
    return fill(_EPOCH[0], stalls, **kw)


# with the stalls: 12 passes dispatched a step, 8 of them ahead; the
# account is 1.0 + 0.25 + 1.25 x 3 = 5.0 ms over 10 s and one prefill;
# the median pass (less its prefill) is 10 ms, so the line is 50 ms and
# the stalls are 110 and 60 ms over it, the first of them blocked
EXPECTED = {
    True: {"steps_ahead_share.serve": 100.0 * 8 / 12,
           "device_starved_share.serve": 100.0 * 0.005 / 10.0,
           "starved_ms_per_request.serve": 5.0,
           "loop_stall_share.serve": 100.0 * 0.170 / 10.0,
           "loop_stall_blocked_share.serve": 100.0 * 0.110 / 10.0},
    False: {"steps_ahead_share.serve": 100.0 * 8 / 10,
            "device_starved_share.serve": 100.0 * 0.0025 / 10.0,
            "starved_ms_per_request.serve": 2.5,
            "loop_stall_share.serve": 0.0,
            "loop_stall_blocked_share.serve": 0.0},
}


@pytest.mark.parametrize("stalls", [True, False],
                         ids=["stalls", "no_stall"])
@pytest.mark.parametrize("metric", READERS)
def test_known_answer(metric, stalls):
    got = harness.reader_of(metric).read(None, {}, window(stalls))
    assert got == pytest.approx(EXPECTED[stalls][metric])
    assert isinstance(got, float)


@pytest.mark.parametrize("metric", READERS)
def test_a_program_that_says_no_order_reads_nothing(metric, monkeypatch):
    """The parent's passes carry neither ``order`` nor the account;
    its recorder before PR 26 has no ``scan``; a run may not have
    reached its window: ``None`` each time, and no error."""
    read = harness.reader_of(metric).read
    assert read(None, {}, window(True, says_order=False)) is None
    _EPOCH[0] += 1000.0
    assert read(None, {}, ctx_at(_EPOCH[0])) is None       # empty window
    ctx = window(True)
    ctx.phases = {}
    assert read(None, {}, ctx) is None
    monkeypatch.setattr(TRACER, "recorder", object())
    assert read(None, {}, window(True)) is None


def test_a_stall_names_its_phase_and_who_ran():
    from layer_metrics import loop_account
    got = loop_account.stalls(loop_account.passes(window(True)))
    assert [(round(s["over_ms"]), s["stall"], s["held"]) for s in got] \
        == [(110, "blocked", "fetch"), (60, "on_cpu", "fetch")]


def test_the_account_between_two_times():
    """What the builder's traced runs put beside the device's idle
    share: the account restricted to a slice of the window."""
    from layer_metrics import loop_account
    ctx = window(True)
    t = _EPOCH[0]
    ps = loop_account.passes(ctx, t + 2.5, t + 4.5)
    assert [p["order"] for p in ps] == ["fetch_only", "in_turn"]
    assert loop_account.starved_ms(ps) == pytest.approx(1.5)


def test_entries_are_the_last_five_of_per_layer():
    import json
    import os

    from conftest import ROOT
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    assert [m["name"] for m in per_layer[-5:]] == list(READERS)
    for m in per_layer[-5:]:
        assert m["source"] == "program_span"
        assert m["layer"] == "decode scheduler"
        assert m["moves"] == "gen_tokens_per_s"
        assert m["workloads"] == SERVE_CELLS
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
