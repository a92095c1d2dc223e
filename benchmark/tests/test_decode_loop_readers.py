"""The seven readers of the decode loop against hand-made
``decode.pass`` spans: known answers, ``None`` on an empty window, a
span outside the window left out."""

import types

import pytest

import run as harness
from mmlspark_tpu.core.tracing import TRACER

READERS = ("host_loop_ms.serve", "loop_dispatch_ms.serve",
           "loop_emit_ms.serve", "prefill_call_ms.serve",
           "prefill_stall_share.serve", "queue_wait_ms.serve",
           "kv_page_occupancy.serve")
_EPOCH = [4.0e6]           # seconds no real span of this process has


def ctx_at(t_open: float, seconds: float = 10.0):
    """A run whose window opens at ``t_open``: 3 s of set-up to the
    warm request, a 1 s ramp."""
    return types.SimpleNamespace(
        t_start=t_open - 4.0, phases={"warm_request": 3.0},
        traffic={"ramp_s": 1.0}, seconds=seconds)


def a_pass(t0, ms, fetch, prefills=(), dispatch=0.5, emit=0.25,
           pages=(32, 64)):
    """One pass as the scheduler records it: the spans as they closed
    (each prefill before the admit it lies in), in nanoseconds."""
    def ns(x_ms):
        return int(round(x_ms * 1e6))
    a = ns(t0 * 1e3)
    phases, at = [], a + ns(0.0625)
    for p in prefills:
        attrs = {k: v for k, v in p.items() if k != "ms"}
        phases.append(("decode.prefill", at, at + ns(p["ms"]), attrs))
        at += ns(p["ms"])
    phases.append(("decode.admit", a, at + ns(0.0625), {"admitted": 0}))
    at += ns(0.0625)
    for name, length, attrs in (
            ("prepare", 0.125, {"active": 2, "pages_in_use": pages[0],
                                "n_pages": pages[1], "traces": []}),
            ("dispatch", dispatch, None), ("fetch", fetch, None)):
        phases.append((f"decode.{name}", at, at + ns(length), attrs))
        at += ns(length)
    end = a + ns(ms)
    phases.append(("decode.emit", end - ns(emit), end, {"emitted": 2}))
    TRACER.add("decode.pass", a * 1e-9, end * 1e-9, None, capture=False,
               route="decode.loop", step=1, traces=[], phases=phases)


@pytest.fixture()
def window():
    """Two passes inside a window of 10 s, one with a prefill that
    stalled another slot and one with a prefill into an empty batch;
    an idle pass; a pass before the window and one after it."""
    _EPOCH[0] += 1000.0
    t = _EPOCH[0]
    stalls = {"ms": 30.0, "bucket": 128, "others_active": 3,
              "queue_wait_ms": 2.0}
    alone = {"ms": 10.0, "bucket": 16, "others_active": 0,
             "queue_wait_ms": 4.0}
    a_pass(t - 0.5, 500.0, 400.0, dispatch=99.0)           # before
    a_pass(t + 1.0, 51.0, 16.0, [stalls], pages=(32, 64))
    # host: 51 - 16 - 30 = 5
    a_pass(t + 2.0, 34.0, 21.0, [alone], dispatch=1.5, emit=0.75,
           pages=(16, 64))
    # host: 34 - 21 - 10 = 3
    idle = int((t + 3.0) * 1e9)
    TRACER.add("decode.pass", t + 3.0, t + 3.5, None, capture=False,
               route="decode.loop", step=None, traces=(), phases=[
                   ("decode.admit", idle, idle + 100_000, None),
                   ("decode.idle", idle + 100_000, idle + 500_000_000,
                    None)])
    a_pass(t + 10.0, 500.0, 400.0, dispatch=99.0)          # after
    return ctx_at(t)


EXPECTED = {
    "host_loop_ms.serve": 4.0,
    "loop_dispatch_ms.serve": 1.0,
    "loop_emit_ms.serve": 0.5,
    "prefill_call_ms.serve": 20.0,
    "prefill_stall_share.serve": 100.0 * 0.030 / 10.0,
    "queue_wait_ms.serve": 3.0,
    "kv_page_occupancy.serve": 37.5,
}


@pytest.mark.parametrize("metric", READERS)
def test_known_answer(window, metric):
    got = harness.reader_of(metric).read(None, {}, window)
    assert got == pytest.approx(EXPECTED[metric])


@pytest.mark.parametrize("metric", READERS)
def test_empty_window_reads_nothing(metric):
    _EPOCH[0] += 1000.0
    read = harness.reader_of(metric).read
    assert read(None, {}, ctx_at(_EPOCH[0])) is None
    # a run that never reached its window (no warm request marked)
    ctx = ctx_at(_EPOCH[0])
    ctx.phases = {}
    assert read(None, {}, ctx) is None


@pytest.mark.parametrize("metric", READERS)
def test_program_without_the_spans_reads_nothing(window, metric,
                                                 monkeypatch):
    """The parent commit's recorder has no ``scan``: nothing to read,
    and no error."""
    monkeypatch.setattr(TRACER, "recorder", object())
    assert harness.reader_of(metric).read(None, {}, window) is None


def test_entries_are_program_spans_of_the_serve_cell():
    import json
    import os

    from conftest import ROOT
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    assert list(per_layer)[-7:] == list(READERS)
    for name in READERS:
        assert per_layer[name]["source"] == "program_span"
        assert per_layer[name]["workloads"] == ["pythia-1.4b.chat-closed"]
