"""The sandbox's copy of the cell ``granite-4.0-h-small.rag-closed``:
the new driver's ``run(ctx)`` end to end on the CPU, over a ``Context``
built here (``configs/tiny-granite.json``: two Mamba layers, an
attention layer and a third Mamba layer, 4 of 8 experts held, top 3;
``traffic/tiny-rag.json``: prompts of one to eight tiles of 16).
``rehearsal.json`` and the cells of ``BENCHMARK.json`` are not touched:
the copy is found by its files' names.

`correct` has to come out true for the program and false for each
fault: every eighth step's tokens altered (the timed path), the
recurrent state zeroed at every 16th position and the least of each
token's held routings left out (the mechanisms, in the reference)."""

import os
import time

import pytest

import reference
import run as harness
import traffic as traffic_mod
from conftest import BENCH, ROOT

CELL = "tiny-granite.tiny-rag"
REAL = "granite-4.0-h-small.rag-closed"
NEW = ("hybrid_step_roofline.serve", "hybrid_prefill_mfu.serve",
       "hybrid_prefill_tile_ms.serve", "expert_load_max_over_mean.serve")


def context(seed, seconds, **extra):
    config = harness.load_json(BENCH, "configs", "tiny-granite.json")
    return harness.Context(
        cell={"name": CELL, "config": "tiny-granite", "traffic": "tiny-rag",
              "chips": 1},
        config=config, traffic=traffic_mod.load("tiny-rag"),
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
    assert facts["n_compiles"] == facts["warm_programs"] == 2
    # the check saw the mechanisms: its longest request lies past
    # several resets of the planted fault, every request reset its
    # slot's state, and the held experts received routings
    assert facts["readings"]["resets_crossed"] >= 3
    assert facts["n_state_resets"] >= facts["requests_sent"]
    assert min(facts["expert_routings"]) > 0
    assert 0 < facts["held_per_token_layer"] < 3
    assert not out["control"]["correct"]
    assert failed(out["control"]["compared"]), out["control"]
    for key in ("gen_tokens_per_s", "ttft_p50_ms", "gap_p95_ms", "setup_s"):
        assert out["end_to_end"][key] > 0


@pytest.mark.parametrize("fault", ["token_altered", "state_reset",
                                   "expert_dropped"])
def test_fault_is_not_correct(fault):
    _, out = drive(22, fault=fault)
    assert not out["correct"]
    assert failed(out["compared"]), out["compared"]
    assert out["facts"]["sound"]      # the path ran: the numbers failed


def test_readers_return_a_number_or_nothing(sound):
    """Every reader the cell lists, over the sound run's counters and a
    reduction that holds the two programs: a number, or ``None`` where
    it finds nothing (no device trace on the CPU), never an
    exception."""
    ctx, out = sound
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    mine = [mt["name"] for mt in bench["per_layer"]
            if REAL in mt.get("workloads", ())]
    assert set(NEW) <= set(mine) and len(mine) >= 13
    for name in mine:
        value = harness.reader_of(name).read(None, out["counters"], ctx)
        assert value is None or name.split(".")[0] not in (
            "hybrid_step_roofline", "hybrid_prefill_mfu",
            "device_idle_share")
    ctx.peak = {"bf16_flops_per_s": 197.0e12, "hbm_bytes_per_s": 819.0e9}
    stored = {"busy_s": 0.5, "window_s": 0.8, "device_ops": [],
              "idle_gaps": [], "ops": {},
              "modules": {"jit_hybrid_step": [0.4e9, 40],
                          "jit_hybrid_prefill": [0.1e9, 10]}}
    counters = dict(out["counters"], traced_steps=40, traced_s=0.8,
                    traced_slices=[[ctx.t_start, time.perf_counter()]])
    got = {name: harness.reader_of(name).read(stored, counters, ctx)
           for name in mine}
    for name in NEW + ("mfu.serve", "slot_occupancy.serve",
                       "kv_page_occupancy.serve"):
        assert got[name] is not None and got[name] > 0, (name, got)
    assert got["expert_load_max_over_mean.serve"] >= 1.0


def test_readers_read_nothing_from_another_block_kind(sound):
    """On a cell of the softmax block (what the parent commit runs) the
    new readers find nothing and do not raise."""
    ctx, out = sound
    other = harness.Context(**{**ctx.__dict__, "config": harness.load_json(
        BENCH, "configs", "tiny.json")})
    counters = {"traced_slices": [[ctx.t_start, time.perf_counter()]]}
    for name in ("hybrid_step_roofline.serve", "hybrid_prefill_mfu.serve"):
        assert harness.reader_of(name).read(
            {"ops": {}, "modules": {}}, counters, other) is None
