"""``flops.py`` against hand counts for both configurations, the peaks
table, and the harness's refusals."""

import json
import os
import subprocess
import sys

import pytest

import flops
import reference as R
from conftest import BENCH, ROOT


def model(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return R.Model.from_config(json.load(f))


def test_pythia_410m_hand_counts():
    m = model("pythia-410m")
    # per layer 4 x 1024^2 + 2 x 1024 x 4096 = 12,582,912; x 24, + head
    assert flops.matmul_params(m) == 24 * 12_582_912 + 1024 * 50_304
    assert R.n_params(m) == 405_185_536
    # 6 x 353,501,184 + 6 x 2048 x 1024 x 24 = 2.423 GFLOP a token
    per_token = flops.train_flops_per_token(m, 2048)
    assert per_token == 6 * 353_501_184 + 6 * 2048 * 1024 * 24
    assert round(per_token / 1e9, 2) == 2.42


def test_pythia_1_4b_hand_counts():
    m = model("pythia-1.4b")
    assert flops.matmul_params(m) == 24 * (4 * 2048**2 + 2 * 2048 * 8192) \
        + 2048 * 50_304
    assert 1.40e9 < R.n_params(m) < 1.42e9
    # one decode step reads every matmul weight once: 5.25 GB in f32
    assert flops.decode_step_bytes(m, 0) == 4 * flops.matmul_params(m)
    # K and V of 24 layers x 2048 features x 4 bytes a cached row
    assert flops.kv_bytes(m, 1) == 2 * 24 * 2048 * 4
    # a one-token prompt: the layers and the head once, one q.k pair
    assert flops.prefill_flops(m, 1) == flops.decode_flops(m, 1, 1)


def test_flash_forward_is_compute_bound_at_2k():
    m = model("pythia-410m")
    need = flops.flash_attention_fwd(m, 4, 2048)
    assert need["flops"] == 2 * 4 * 16 * 2048 * 2048 * 64
    assert need["bytes"] == 4 * (4 * 2048 * 16 * 64) * 2
    least = flops.roofline(need["flops"], need["bytes"],
                           flops.peaks("TPU v5 lite"))
    assert least["bound"] == "compute"


def test_unknown_device_kind_is_an_error():
    assert flops.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(LookupError):
        flops.peaks("TPU v9 imaginary")


def test_harness_refuses_a_cpu_without_rehearse():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "pythia-410m.pretrain-2k", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert r.returncode != 0
    assert r.stdout == ""
    assert "does not fall back" in r.stderr
