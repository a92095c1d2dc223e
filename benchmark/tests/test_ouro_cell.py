"""The sandbox's copy of the cell ``ouro-2.6b.reason-closed``: the new
driver's ``run(ctx)`` end to end on the CPU, over a ``Context`` built
here (``configs/tiny-ouro.json``: 2 layers x 3 passes, d_model 64, 4
heads x 16, bfloat16 with an int8 control; ``traffic/tiny-reason.json``:
prompts of 3-40 tokens over pages of 4). ``rehearsal.json`` and the
cells of ``BENCHMARK.json`` are not touched: the copy is found by its
files' names.

`correct` has to come out true for the program and false for the
control and for each fault: every eighth step's tokens altered (the
timed path), one pass fewer and every pass attending the first pass's
rows (the mechanism, in the reference)."""

import os
import time

import pytest

import flops_ouro as F
import reference
import reference_ouro as RO
import run as harness
import traffic as traffic_mod
from conftest import BENCH, ROOT

CELL = "tiny-ouro.tiny-reason"
REAL = "ouro-2.6b.reason-closed"
NEW = ("looped_step_roofline.serve", "looped_decode_attn_roofline.serve",
       "looped_prefill_mfu.serve")


def context(seed, seconds, **extra):
    config = harness.load_json(BENCH, "configs", "tiny-ouro.json")
    return harness.Context(
        cell={"name": CELL, "config": "tiny-ouro", "traffic": "tiny-reason",
              "chips": 1},
        config=config, traffic=traffic_mod.load("tiny-reason"),
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
    # the step and the four buckets of the ladder, and no other
    assert facts["n_compiles"] == facts["warm_programs"] == 5
    assert facts["n_loops"] == 3
    assert facts["kv_bytes_per_position"] == 3 * 2 * 2 * 4 * 16 * 2
    assert facts["n_params"] == RO.n_params(
        RO.Model.from_config(harness.load_json(
            BENCH, "configs", "tiny-ouro.json")))
    assert not out["control"]["correct"]
    assert failed(out["control"]["compared"]), out["control"]
    for key in ("gen_tokens_per_s", "ttft_p50_ms", "gap_p95_ms", "setup_s"):
        assert out["end_to_end"][key] > 0


@pytest.mark.parametrize("fault", ["token_altered", "loop_dropped",
                                   "loop_cache_shared"])
def test_fault_is_not_correct(fault):
    _, out = drive(22, fault=fault)
    assert not out["correct"]
    assert failed(out["compared"]), out["compared"]
    assert out["facts"]["sound"]      # the path ran: the numbers failed


def test_readers_return_a_number_or_nothing(sound):
    """Every reader the cell lists, over the sound run's counters and a
    reduction that holds the two programs and the kernel: a number, or
    ``None`` where it finds nothing (no device trace on the CPU), never
    an exception."""
    ctx, out = sound
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    mine = [mt["name"] for mt in bench["per_layer"]
            if REAL in mt.get("workloads", ())]
    assert set(NEW) <= set(mine) and len(mine) == 15
    for name in mine:
        value = harness.reader_of(name).read(None, out["counters"], ctx)
        assert value is None or name.split(".")[0] not in (
            "looped_step_roofline", "looped_decode_attn_roofline",
            "looped_prefill_mfu", "device_idle_share")
    ctx.peak = {"bf16_flops_per_s": 197.0e12, "hbm_bytes_per_s": 819.0e9}
    stored = {"busy_s": 0.5, "window_s": 0.8, "device_ops": [],
              "idle_gaps": [],
              "ops": {"paged_decode_attention.3": [0.02e9, 240]},
              "modules": {"jit_looped_step": [0.4e9, 40],
                          "jit_looped_prefill": [0.1e9, 10]}}
    counters = dict(out["counters"], traced_steps=40, traced_s=0.8,
                    traced_slices=[[ctx.t_start, time.perf_counter()]])
    got = {name: harness.reader_of(name).read(stored, counters, ctx)
           for name in mine}
    for name in NEW + ("mfu.serve", "slot_occupancy.serve",
                       "kv_page_occupancy.serve"):
        assert got[name] is not None and got[name] > 0, (name, got)


def test_readers_read_nothing_from_another_block_kind(sound):
    """On a cell of the unlooped block (what the parent commit runs)
    the new readers find nothing and do not raise."""
    ctx, out = sound
    other = harness.Context(**{**ctx.__dict__, "config": harness.load_json(
        BENCH, "configs", "tiny.json")})
    counters = {"traced_slices": [[ctx.t_start, time.perf_counter()]]}
    for name in NEW:
        assert harness.reader_of(name).read(
            {"ops": {}, "modules": {}}, counters, other) is None


def test_counts_against_a_count_by_hand():
    """``flops_ouro`` and ``n_params`` at the published sizes, against
    the arithmetic of ISSUE 35."""
    m = RO.Model.from_config(harness.load_json(BENCH, "configs",
                                               "ouro-2.6b.json"))
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert F.layer_matmul_params(m) == layer == 51_380_224
    assert RO.n_params(m) == 48 * (layer + 4 * 2048) \
        + 2 * 49152 * 2048 + 2048 + 2049 == 2_667_974_657
    assert F.kv_row_bytes(m) == 8192
    assert F.kv_bytes_per_position(m) == 1_572_864
    # a token: every matrix four times, 2 FLOPs a weight
    assert F.token_flops(m) == 2.0 * 4 * 48 * layer
    assert F.decode_flops(m, 1, 0) == F.token_flops(m) + 2.0 * 49152 * 2048
    # a prompt of 3: six causal pairs in each of 192 (pass, layer)s
    assert F.prefill_flops(m, 3) - 3 * F.token_flops(m) \
        - 2.0 * 49152 * 2048 == 4.0 * 192 * 16 * 128 * 6
    # a step of 8 slots over 2,160 live positions: 23.3 GB
    weights = 4 * 48 * (layer * 2 + 4 * 2048 * 4)
    assert F.step_bytes(m, 8, 2160) == weights + 49152 * 2048 * 2 \
        + (2160 + 8) * 1_572_864
    assert 23.2e9 < F.step_bytes(m, 8, 2160) < 23.5e9
