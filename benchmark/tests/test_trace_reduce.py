"""``trace_reduce.py``: the interval arithmetic on hand-made events, and
the whole reduction on a short cut of a trace recorded on the chip
(``data/trace_train.json.gz``: 1.4 s of ``pythia-410m.pretrain-2k``,
PR 25)."""

import gzip
import json
import os

import pytest

import trace_reduce as TR
from conftest import HERE


def test_union_and_gaps_by_hand():
    ev = [("a", 0, 10), ("b", 5, 10), ("c", 30, 5), ("d", 31, 2),
          ("e", 50, 10)]
    assert TR.union_ns(ev) == 15 + 5 + 10
    assert TR.extent_ns(ev) == (0, 60)
    g = TR.gaps(ev)
    assert sorted((x[0], x[2], x[3]) for x in g) == [(15, "b", "c"),
                                                     (15, "c", "e")]
    assert TR.time_by_name(ev)["a"] == (10, 1)


def test_reduce_busy_and_idle_share_by_hand():
    trace = {"devices": {0: {
        "ops": [("f.1", 0, 400), ("f.2", 400, 200),
                ("jit__flash_call__.3", 800, 200)],
        "modules": [("jit_step(12)", 0, 600), ("jit_step(12)", 800, 200)],
        "labels": {}}},
        "host": [("bench.feed", 610, 150)], "lines": {}}
    red = TR.reduce(trace)
    assert red["busy_s"] == pytest.approx(800e-9)
    assert red["window_s"] == pytest.approx(1000e-9)
    assert red["idle_gaps"] == [["bench.feed", pytest.approx(200e-9)]]
    assert TR.op_seconds(red, "_flash_call") == (pytest.approx(200e-9), 1)
    assert TR.module_seconds(red, "^jit_step$") == (pytest.approx(800e-9), 2)
    assert red["device_ops"][0] == ["f", pytest.approx(600e-9)]
    assert TR.short_name("%fusion.12 = f32[4]{0} fusion(...)") == "fusion.12"
    assert TR.kind("copy.3.clone.1") == "copy.3.clone"
    with pytest.raises(ValueError):
        TR.reduce({"devices": {0: {"ops": [], "modules": [], "labels": {}}},
                   "host": []})


def test_holes_are_not_idleness_and_slices_combine():
    ops = [("a.1", 0, 100), ("a.2", 150, 100), ("a.1", 5000, 100)]
    trace = {"devices": {0: {"ops": ops, "modules": [], "labels": {}}},
             "host": []}
    whole = TR.reduce(trace)
    assert whole["window_s"] == pytest.approx(5100e-9)
    cut = TR.reduce(trace, hole_s=1000e-9)
    assert cut["holes"] == 1
    assert cut["busy_s"] == pytest.approx(300e-9)
    assert cut["window_s"] == pytest.approx(350e-9)
    assert cut["idle_gaps"] == [["a->a", pytest.approx(50e-9)]]
    both = TR.combine([cut, cut])
    assert both["busy_s"] == pytest.approx(600e-9)
    assert both["window_s"] == pytest.approx(700e-9)
    assert both["ops"]["a.1"] == (400, 4) and both["holes"] == 2


def test_recorded_chip_trace():
    path = os.path.join(HERE, "data", "trace_train.json.gz")
    with gzip.open(path, "rt") as f:
        trace = TR.from_record(json.load(f))
    red = TR.reduce(trace)
    ops = trace["devices"][0]["ops"]
    # the busy union can pass neither the window nor the plain sum
    assert 0 < red["busy_s"] <= red["window_s"]
    assert red["busy_s"] <= sum(e[2] for e in ops) / 1e9 + 1e-12
    idle = 1.0 - red["busy_s"] / red["window_s"]
    assert 0.0 <= idle < 0.2
    # one whole step program lies in the cut, and inside it the flash
    # forward kernel runs once a layer
    (name, (ns, calls)), = red["modules"].items()
    assert (name, calls) == ("jit_local_step", 1) and 0.5e9 < ns < 0.8e9
    _, start, dur = trace["devices"][0]["modules"][0]
    inside = [e for e in ops if "_flash_call" in e[0]
              and start <= e[1] and e[1] + e[2] <= start + dur]
    assert len(inside) == 24
    seconds, calls = TR.op_seconds(red, "_flash_call")
    assert calls >= 24 and seconds > 0
    assert len(red["device_ops"]) <= 10 and len(red["idle_gaps"]) <= 10
