"""The sandbox's copy of the cell ``evabyte-6.5b.doc-closed``: the new
driver's ``run(ctx)`` end to end on the CPU, over a ``Context`` built
here (``configs/tiny-evabyte.json``: window 32, chunk 4, three layers;
``traffic/tiny-doc.json``: prompts past their first window, the longest
past its third). ``rehearsal.json`` and the cells of ``BENCHMARK.json``
are not touched: the copy is found by its files' names.

`correct` has to come out true for the program and false for each
fault: every eighth step's bytes altered (the timed path), and the
summaries left out of the reference's softmax (the mechanism)."""

import json
import os
import time

import pytest

import reference
import run as harness
import traffic as traffic_mod
from conftest import BENCH, ROOT

CELL = "tiny-evabyte.tiny-doc"


def context(seed, seconds, **extra):
    config = harness.load_json(BENCH, "configs", "tiny-evabyte.json")
    return harness.Context(
        cell={"name": CELL, "config": "tiny-evabyte", "traffic": "tiny-doc",
              "chips": 1},
        config=config, traffic=traffic_mod.load("tiny-doc"),
        limits=harness.load_json(BENCH, "limits", f"{CELL}.json"),
        model=reference.Model.from_config(config), seed=seed,
        seconds=seconds, trace=False, t_start=time.perf_counter(),
        peak=None, on_chip=False,
        trace_dir=os.path.join(ROOT, ".bench_trace", CELL), root=ROOT,
        **extra)


def drive(seed, seconds=2.0, **extra):
    ctx = context(seed, seconds, **extra)
    driver = harness.load_module("drivers", ctx.traffic["driver"])
    assert ctx.fault is None or ctx.fault in driver.FAULTS
    return ctx, driver.run(ctx)


def failed(compared):
    return [k for k, (value, limit) in compared.items()
            if not value <= limit]


@pytest.fixture(scope="module")
def sound():
    return drive(21, control=True)


def test_sound_run_is_correct_and_control_is_not(sound):
    _, out = sound
    assert out["correct"], (out["compared"], out["facts"])
    assert out["failed"] == 0 and out["attempted"] > 0
    facts = out["facts"]
    assert facts["n_compiles"] == facts["warm_programs"]
    # the check saw the mechanism: its longest request lies past its
    # third window, and the window's prefills compacted
    assert facts["readings"]["boundaries_crossed"] >= 3
    assert facts["window_compactions"] > 0
    assert not out["control"]["correct"]
    assert failed(out["control"]["compared"]), out["control"]
    for key in ("gen_tokens_per_s", "ttft_p50_ms", "gap_p95_ms", "setup_s"):
        assert out["end_to_end"][key] > 0


@pytest.mark.parametrize("fault", ["token_altered", "summaries_dropped"])
def test_fault_is_not_correct(fault):
    _, out = drive(22, fault=fault)
    assert not out["correct"]
    assert failed(out["compared"]), out["compared"]
    assert out["facts"]["sound"]      # the path ran: the numbers failed


def test_readers_return_a_number_or_nothing(sound):
    """Every reader this cell lists, over the sound run's counters and a
    stored reduction: a number, or ``None`` where it finds nothing (no
    device trace on the CPU), never an exception."""
    ctx, out = sound
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    mine = [mt["name"] for mt in bench["per_layer"]
            if "evabyte-6.5b.doc-closed" in mt.get("workloads", ())]
    assert len(mine) >= 14
    with open(os.path.join(BENCH, "tests", "data",
                           "eva_reduced.json")) as f:
        stored = json.load(f)
    ctx.peak = {"bf16_flops_per_s": 197.0e12, "hbm_bytes_per_s": 819.0e9}
    counters = dict(out["counters"], traced_steps=40, traced_s=0.8,
                    traced_summary_rows=40 * 64, traced_window_rows=40 * 80,
                    traced_slices=[[ctx.t_start, time.perf_counter()]])
    got = {}
    for name in mine:
        reader = harness.reader_of(name)
        assert reader.read(None, out["counters"], ctx) is None \
            or name.split(".")[0] not in ("eva_decode_attn_roofline",
                                          "eva_prefill_attn_roofline",
                                          "eva_step_roofline",
                                          "eva_compact_roofline",
                                          "device_idle_share")
        got[name] = reader.read(stored, counters, ctx)
    for name in ("eva_decode_attn_roofline.serve", "eva_step_roofline.serve",
                 "eva_prefill_attn_roofline.serve",
                 "eva_compact_roofline.serve",
                 "eva_summary_row_share.serve",
                 "eva_prefill_window_ms.serve", "mfu.serve",
                 "slot_occupancy.serve", "kv_page_occupancy.serve"):
        assert got[name] is not None and got[name] > 0, (name, got)
    assert 0 < got["eva_summary_row_share.serve"] < 100


def test_readers_read_nothing_from_another_block_kind(sound):
    """On a cell of the softmax block (what the parent commit runs) the
    new readers find nothing and do not raise."""
    ctx, out = sound
    other = harness.Context(**{**ctx.__dict__, "config": harness.load_json(
        BENCH, "configs", "tiny.json")})
    for name in ("eva_decode_attn_roofline.serve", "eva_step_roofline.serve",
                 "eva_prefill_attn_roofline.serve",
                 "eva_compact_roofline.serve"):
        assert harness.reader_of(name).read(
            {"ops": {}, "modules": {}}, {}, other) is None
