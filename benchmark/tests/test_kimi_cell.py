"""The CPU copy of the cell ``kimi-k2.6.think-closed``: the new
driver's ``run(ctx)`` end to end on the CPU, over a ``Context`` built
here (``configs/tiny-kimi.json``: three latent-attention layers, the
first with a dense FFN, 32 routed experts of which 8 are held, top 4 by
sigmoid score plus a bias, one shared expert; ``traffic/tiny-think.json``:
prompts of one to seven tiles of 16). ``rehearsal.json`` and the cells of
``BENCHMARK.json`` are not touched: the copy is found by its files'
names.

`correct` has to come out true for the program and false for each
fault: every eighth step's tokens altered, the latent kernel's rotary
key ignored and YaRN's scale dropped (the timed path), the experts
chosen without the bias and the shared expert left out (the reference).
The FLOP and byte counts are checked against a hand count at the cell's
sizes, and the configuration file's cut."""

import os
import time

import numpy as np
import pytest

import flops_kimi as F
import reference
import reference_kimi as RK
import run as harness
import traffic as traffic_mod
from conftest import BENCH, ROOT

CELL = "tiny-kimi.tiny-think"
REAL = "kimi-k2.6.think-closed"
NEW = ("kimi_latent_attn_roofline.serve", "kimi_step_roofline.serve",
       "kimi_tile_roofline.serve")
PEAK = {"bf16_flops_per_s": 197.0e12, "hbm_bytes_per_s": 819.0e9}


def context(seed, seconds, **extra):
    config = harness.load_json(BENCH, "configs", "tiny-kimi.json")
    return harness.Context(
        cell={"name": CELL, "config": "tiny-kimi", "traffic": "tiny-think",
              "chips": 1},
        config=config, traffic=traffic_mod.load("tiny-think"),
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
    return drive(2**31 + 41, control=True)


def test_sound_run_is_correct_and_control_is_not(sound):
    _, out = sound
    assert out["correct"], (out["compared"], out["facts"])
    assert out["failed"] == 0 and out["attempted"] > 0
    facts = out["facts"]
    assert facts["n_compiles"] == facts["warm_programs"] == 2
    assert facts["latent_lanes"] == 80
    assert sum(facts["expert_routings"]) > 0
    assert not out["control"]["correct"]
    assert failed(out["control"]["compared"]), out["control"]
    # the faults the reference carries, read over the same served tokens
    ref_faults = out["control"]["reference_faults"]
    assert set(ref_faults) == set(RK.FAULTS)
    for fault, verdict in ref_faults.items():
        assert not verdict["correct"] and failed(verdict["compared"]), fault
    for key in ("gen_tokens_per_s", "ttft_p50_ms", "gap_p95_ms", "setup_s"):
        assert out["end_to_end"][key] > 0


@pytest.mark.parametrize("fault", ["token_altered", "latent_rope_dropped",
                                   "yarn_mscale_dropped",
                                   "expert_bias_ignored",
                                   "shared_expert_dropped"])
def test_fault_is_not_correct(fault):
    _, out = drive(42, fault=fault)
    assert not out["correct"]
    assert failed(out["compared"]), out["compared"]
    assert out["facts"]["sound"]      # the path ran: the numbers failed


def test_the_planted_kernel_fault_is_taken_out_again():
    """``latent_rope_dropped`` wraps the module's kernel while the
    program runs and puts it back when the server closes."""
    from mmlspark_tpu.parallel import pallas_mla
    before = pallas_mla.paged_latent_decode_attention
    drive(43, seconds=1.0, fault="latent_rope_dropped")
    assert pallas_mla.paged_latent_decode_attention is before


def test_readers_return_a_number_or_nothing(sound):
    """Every reader the cell lists, over the sound run's counters and a
    reduction that holds the two programs and the kernel: a number, or
    ``None`` where it finds nothing, never an exception."""
    ctx, out = sound
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    mine = [mt["name"] for mt in bench["per_layer"]
            if REAL in mt.get("workloads", ())]
    assert set(NEW) <= set(mine) and len(mine) == 22
    for name in mine:
        harness.reader_of(name).read(None, out["counters"], ctx)
    ctx.peak = PEAK
    stored = {"busy_s": 0.5, "window_s": 0.8, "device_ops": [],
              "idle_gaps": [],
              "ops": {"paged_latent_decode_attention": [0.05e9, 120]},
              "modules": {"jit_hybrid_step": [0.4e9, 40],
                          "jit_hybrid_prefill": [0.1e9, 10]}}
    counters = dict(out["counters"], traced_steps=40, traced_s=0.8,
                    traced_slices=[[ctx.t_start, time.perf_counter()]])
    got = {name: harness.reader_of(name).read(stored, counters, ctx)
           for name in mine}
    for name in NEW + ("mfu.serve", "slot_occupancy.serve",
                       "kv_page_occupancy.serve"):
        assert got[name] is not None and got[name] > 0, (name, got)


def test_readers_read_nothing_from_another_block_kind(sound):
    """On cells of other configurations (the softmax block, LFM2's
    layer) the new readers find nothing and do not raise."""
    ctx, _ = sound
    counters = {"traced_slices": [[ctx.t_start, time.perf_counter()]]}
    stored = {"ops": {"paged_latent_decode_attention": [1e9, 10]},
              "modules": {"jit_hybrid_step": [1e9, 10],
                          "jit_hybrid_prefill": [1e9, 10]}}
    for config in ("tiny.json", "tiny-lfm2.json"):
        other = harness.Context(**{**ctx.__dict__, "peak": PEAK,
                                   "config": harness.load_json(
                                       BENCH, "configs", config)})
        for name in NEW:
            assert harness.reader_of(name).read(stored, counters,
                                                other) is None


@pytest.mark.parametrize("traffic", ["think-closed", "tiny-think"])
def test_every_seed_sends_the_same_sizes_in_one_order(traffic):
    """The plan's sizes and their order are the traffic's, whatever the
    seed; the seed draws the token ids, and the same seed the same
    ones."""
    tf = traffic_mod.load(traffic)
    driver = harness.load_module("drivers", tf["driver"])
    a, b, again = (driver.plan_of(tf, 20480, seed, 3)
                   for seed in (2**31 + 7, 5, 2**31 + 7))
    sizes = [(len(r["prompt"]), r["max_new_tokens"]) for r in a]
    assert sizes == [(len(r["prompt"]), r["max_new_tokens"]) for r in b]
    assert sorted(sizes[:int(tf["sizes_per_block"])]) == sorted(
        traffic_mod.block_sizes(tf))
    assert any((x["prompt"] != y["prompt"]).any() for x, y in zip(a, b))
    assert all((x["prompt"] == y["prompt"]).all() for x, y in zip(a, again))
    assert all(r["prompt"].dtype.name == "int32" and r["prompt"].max() < 20480
               for r in a)


@pytest.mark.parametrize("config", ["kimi-k2.6.json", "tiny-kimi.json"])
def test_the_bias_is_every_seeds_and_stratified_over_the_held(config):
    """``expert_bias`` takes no seed; its values are the normal quantiles
    of the stated std, and the held experts take one from each equal
    stratum of them."""
    m = RK.Model.from_config(harness.load_json(BENCH, "configs", config))
    b = RK.expert_bias(m, 1)
    z = np.sort(b) / m.bias_std
    assert b.shape == (m.n_experts,) and abs(np.mean(z)) < 1e-6
    assert abs(np.std(z) - 1.0) < 0.05
    ranks = np.searchsorted(np.sort(b), b[list(m.experts_held)])
    stride = m.n_experts // len(m.experts_held)
    assert sorted(ranks // stride) == list(range(len(m.experts_held)))
    assert (RK.expert_bias(m, 2)[list(m.experts_held)]
            == b[list(m.experts_held)]).all()
    assert not (RK.expert_bias(m, 2) == b).all()


def test_config_file_states_the_cut():
    """The published widths as the source gives them; what is cut is in
    ``reduced``, each with the published count beside it."""
    hf = harness.load_json(BENCH, "configs", "kimi-k2.6.json")
    assert hf["source"] == ("https://huggingface.co/moonshotai/Kimi-K2.6/"
                            "blob/main/config.json")
    assert sorted(hf["reduced"]) == sorted(hf["reduced_from"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert (hf["n_routed_experts"], hf["num_hidden_layers"],
            hf["vocab_size"]) == (12, 6, 20480)
    assert (hf["n_routed_experts_published"],
            hf["num_hidden_layers_published"],
            hf["vocab_size_published"]) == (384, 61, 163840)
    assert (hf["hidden_size"], hf["q_lora_rank"], hf["kv_lora_rank"],
            hf["qk_nope_head_dim"], hf["qk_rope_head_dim"], hf["v_head_dim"],
            hf["num_attention_heads"], hf["moe_intermediate_size"],
            hf["intermediate_size"], hf["num_experts_per_tok"]) == (
        7168, 1536, 512, 128, 64, 128, 64, 2048, 18432, 8)
    assert hf["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    assert len(range(*hf["experts_held"])) == 12
    assert hf["serve"]["n_pages"] == 1 + 64 * 12288 // 16


def test_counts_by_hand_at_the_cells_sizes():
    """At 64 live slots of 4,200 rows that touch 8.9 of 12 held experts
    in each of 5 layers, a step must read some 8.5 GB: 2.77 GB of weights
    outside the routed experts, 3.9 GB of experts, 1.86 GB of latent
    rows at 1,152 B a row and layer. One call of the latent kernel over
    those rows is half bandwidth, half compute."""
    m = RK.Model.from_config(harness.load_json(BENCH, "configs",
                                               "kimi-k2.6.json"))
    d = 7168
    assert m.n_moe == 5 and F.n_dense(m) == 1
    assert F.latent_row_bytes(m) == 1152
    assert F.kernel_flops_per_row(m) == 2 * 64 * (576 + 512)
    mla = F.mla_params(m)
    assert 101.0e6 < mla < 101.2e6
    outside = 2 * (6 * mla + 3 * d * 18432 + 5 * (3 * d * 2048 + d * 384)
                   + 20480 * d) + 5 * (d * 384 * 2 + 384 * 4)
    assert F.weight_bytes(m) == outside
    assert 2.77e9 < outside < 2.80e9
    touched = 5 * 12 * (1 - (1 - 8 / 384) ** 64)
    assert F.expected_touched(m, 64) == pytest.approx(touched)
    assert 8.8 < touched / 5 < 9.0
    rows = 64 * 4200
    step = F.step_bytes(m, touched, rows)
    assert step == pytest.approx(outside + touched * 3 * d * 2048 * 2
                                 + 6 * 1152 * rows)
    assert 8.4e9 < step < 8.7e9
    per_call = rows * 1152 / PEAK["hbm_bytes_per_s"]
    flops_call = rows * 2 * 64 * 1088 / PEAK["bf16_flops_per_s"]
    assert F.kernel_least_s(m, rows, PEAK) == max(per_call, flops_call)
    assert 0.4 < flops_call / per_call < 0.6
    # a 512-token tile at the lane's start, and one at 4,608
    held = m.top_k_held
    t0 = F.tile_least_s(m, 0, 512, False, held, PEAK)
    t9 = F.tile_least_s(m, 4608, 512, True, held, PEAK)
    assert t9 > t0 > 0
