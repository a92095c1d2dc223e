"""What keeps a run from passing on the wrong device (tier-1, seconds).

``chip_smoke.py`` is the proof that the main path runs on the chip; the
rules it rests on are checked here, on the CPU: the default invocation
refuses anything but a TPU, the compile cache is placed from outside or
at one fixed path, a NAMED engine that cannot run raises (only
``"auto"`` chooses), an unknown accelerator has no peak, and
``bench.py`` fails when an entry fails.
"""

import os
import subprocess
import sys
import types

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_default_invocation_refuses_the_cpu():
    """``JAX_PLATFORMS=cpu python chip_smoke.py``: non-zero, names the
    platform it found, prints no result."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode not in (0, None)
    assert "platform=cpu" in proc.stderr
    assert proc.stdout == ""


@pytest.mark.slow
def test_cpu_rehearsal_runs_every_phase_and_says_cpu():
    """``--rehearse-cpu`` on four virtual devices (~35 s; part of the
    pre-chip check with tests/test_aot_tpu.py): the ``TINY`` sizes walk
    the control flow of all five phases, so the path the chip run takes
    does not drift unrun — and the result is labelled ``cpu``."""
    import json
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"),
         "--rehearse-cpu"], cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 XLA_FLAGS="--xla_force_host_platform_device_count=4"),
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    summary, last = map(json.loads, proc.stdout.splitlines()[-2:])
    # the last line holds exactly the contract's keys, no more
    assert last == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 4}}
    assert summary["rehearsal"] == "cpu"
    assert summary["phases"] == dict.fromkeys(
        ("train", "serve", "fit", "train4", "serve4"), "passed")
    assert "chip_smoke[cpu]" in proc.stdout


class TestCompileCachePlacement:
    """``core/environment.place_compile_cache`` — the rule, not JAX's
    cache: ``jax`` in ``sys.modules`` is a stand-in, so no test run ever
    gets a cache directory from here."""

    @pytest.fixture
    def fake_jax(self, monkeypatch):
        calls = []
        fake = types.SimpleNamespace(config=types.SimpleNamespace(
            update=lambda k, v: calls.append((k, v))))
        monkeypatch.setitem(sys.modules, "jax", fake)
        return calls

    FLOOR = "jax_persistent_cache_min_compile_time_secs"

    def test_variable_set_means_code_sets_no_path(self, monkeypatch,
                                                  fake_jax):
        from mmlspark_tpu.core.environment import place_compile_cache
        monkeypatch.setenv("JAX_PLATFORMS", "tpu")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/where")
        assert place_compile_cache() is None
        assert fake_jax == [(self.FLOOR, 0.0)]       # no directory
        assert os.environ["JAX_COMPILATION_CACHE_DIR"] == "/some/where"

    def test_unset_means_the_fixed_checkout_path(self, monkeypatch,
                                                 fake_jax):
        from mmlspark_tpu.core.environment import place_compile_cache
        monkeypatch.setenv("JAX_PLATFORMS", "tpu")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "")
        monkeypatch.delenv(self.FLOOR.upper(), raising=False)
        fixed = os.path.join(REPO, ".jax_cache")
        assert place_compile_cache() == fixed
        assert fake_jax == [(self.FLOOR, 0.0),
                            ("jax_compilation_cache_dir", fixed)]
        # both went to jax's config, neither to the environment a
        # child would inherit
        assert not os.environ["JAX_COMPILATION_CACHE_DIR"]
        assert self.FLOOR.upper() not in os.environ

    def test_the_operators_floor_stands(self, monkeypatch, fake_jax):
        from mmlspark_tpu.core.environment import place_compile_cache
        monkeypatch.setenv("JAX_PLATFORMS", "tpu")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/where")
        monkeypatch.setenv(self.FLOOR.upper(), "2.5")
        assert place_compile_cache() is None and fake_jax == []

    def test_cpu_runs_stay_out(self, monkeypatch, fake_jax):
        """The suite's own setting: a cache filled in the sandbox would
        ride the checkout to the chip host as dead weight."""
        from mmlspark_tpu.core.environment import place_compile_cache
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "")
        assert place_compile_cache() is None
        assert fake_jax == [] and not os.environ["JAX_COMPILATION_CACHE_DIR"]

    def test_this_run_has_no_cache_and_the_path_is_ignored(self):
        assert jax.config.jax_compilation_cache_dir is None
        for name in (".gitignore", ".dockerignore"):
            with open(os.path.join(REPO, name)) as f:
                assert any(line.strip().rstrip("/") == ".jax_cache"
                           for line in f), name

    def test_a_cpu_child_of_an_accelerator_parent_inherits_no_cache(self):
        """The real import under ``JAX_PLATFORMS=tpu`` (no device is
        touched): the fixed path is in jax's config and NOT in the
        environment, so the env ``bench._spawn_evidence`` hands its CPU
        drills carries no code-set cache directory or floor; and a
        process that turns to the CPU itself drops the directory."""
        script = (
            "import os, subprocess, types\n"
            "import mmlspark_tpu, jax, bench\n"
            "from mmlspark_tpu.parallel.topology import use_cpu_devices\n"
            "fixed = os.path.join(%r, '.jax_cache')\n"
            "assert jax.config.jax_compilation_cache_dir == fixed\n"
            "assert jax.config.jax_persistent_cache_min_compile_time_secs"
            " == 0.0\n"
            "seen = {}\n"
            "def fake_run(argv, env=None, **_kw):\n"
            "    seen.update(env)\n"
            "    return types.SimpleNamespace(returncode=0, stdout='{}',"
            " stderr='')\n"
            "subprocess.run = fake_run\n"
            "bench._spawn_evidence(['x.py'], 5)\n"
            "leaked = [k for k in list(seen) + list(os.environ)\n"
            "          if k.startswith(('JAX_COMPILATION_CACHE',"
            " 'JAX_PERSISTENT_CACHE'))]\n"
            "assert not leaked, leaked\n"
            "use_cpu_devices(1)\n"
            "assert jax.config.jax_compilation_cache_dir is None\n"
            % REPO)
        env = {k: v for k, v in os.environ.items()
               if not k.startswith(("JAX_COMPILATION_CACHE",
                                    "JAX_PERSISTENT_CACHE"))}
        proc = subprocess.run(
            [sys.executable, "-c", script], cwd=REPO,
            env=dict(env, JAX_PLATFORMS="tpu", PYTHONPATH=REPO),
            capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr


class TestNamedEngineRaises:
    """``"auto"`` may choose by platform and shape; a named engine that
    cannot run raises — it never warns and runs something else."""

    @staticmethod
    def _lower(seq, mesh_shape=None, **cfg_kw):
        from mmlspark_tpu.models import transformer as T
        from mmlspark_tpu.parallel import MeshSpec, build_mesh
        shape = mesh_shape or {"data": 1}
        n = int(np.prod(list(shape.values())))
        mesh = build_mesh(MeshSpec.from_dict(shape),
                          devices=jax.devices()[:n])
        cfg = T.TransformerConfig(vocab=64, d_model=16, n_heads=2,
                                  d_head=8, d_ff=32, **cfg_kw)
        step = T.build_spmd_train_step(cfg, mesh, donate=False)
        params = T.shard_params(T.init_params(cfg, 0), cfg, mesh)
        batch = T.make_batch(np.random.default_rng(0), cfg, 2, seq)
        return step.lower(params, params, *batch)

    def test_folded_attention_at_an_ineligible_shape(self):
        with pytest.raises(ValueError, match="'folded' cannot take shape"):
            self._lower(12, attention_impl="folded")

    @pytest.mark.parametrize("impl", ["folded", "flash"])
    def test_pallas_attention_off_the_tpu(self, impl):
        with pytest.raises(ValueError, match="interpret mode"):
            self._lower(128, attention_impl=impl)

    def test_fused_ce_off_the_tpu(self):
        with pytest.raises(ValueError, match="interpret mode"):
            self._lower(128, ce_impl="fused")

    def test_flash_cannot_train_under_a_seq_axis(self):
        with pytest.raises(ValueError, match="forward-only"):
            self._lower(16, {"seq": 2}, attention_impl="flash")

    def test_unknown_attention_impl(self):
        with pytest.raises(ValueError, match="unknown attention_impl"):
            self._lower(16, attention_impl="sparse")

    def test_auto_still_chooses_on_the_cpu(self):
        """The other side of the rule: ``auto`` resolves to engines
        that run here (XLA dense, XLA CE) instead of raising."""
        assert "tpu_custom_call" not in self._lower(128).as_text()

    def test_decoder_pallas_off_the_tpu(self):
        from mmlspark_tpu.models import transformer as T
        from mmlspark_tpu.serving import TransformerDecoder
        cfg = T.TransformerConfig(vocab=64, d_model=16, n_heads=2,
                                  d_head=8, d_ff=32)
        dec = TransformerDecoder(T.init_params(cfg, 0), cfg, n_slots=2,
                                 max_len=32, attn_impl="pallas")
        assert dec.attn_impl == "pallas"     # named: not re-resolved
        with pytest.raises(ValueError, match="interpret mode"):
            dec.step(np.zeros(2, np.int32), np.zeros(2, np.int32))

    def test_gbdt_pallas_histogram_off_the_tpu(self):
        from mmlspark_tpu.gbdt.booster import Booster, BoosterParams
        rng = np.random.default_rng(0)
        X = rng.normal(size=(64, 4))
        with pytest.raises(ValueError, match="needs a TPU backend"):
            Booster.train(BoosterParams(num_iterations=1,
                                        histogram_impl="pallas"),
                          X, X[:, 0])


class TestPeaks:
    def test_one_table_holds_the_v5e(self):
        import bench
        from mmlspark_tpu.core import environment, profiling
        assert environment.DEVICE_PEAKS["TPU v5 lite"] == {
            "bf16_tflops": 197.0, "int8_tops": 393.0,
            "hbm_gbytes_per_s": 819.0}
        # the copies are gone: bench.py and the MfuMeter read this one
        assert not hasattr(bench, "_PEAK_BF16_TFLOPS")
        assert not hasattr(profiling, "_PEAK_BF16_TFLOPS")
        chip = {"device_kind": "TPU v5 lite", "platform": "tpu"}
        assert bench._peak_bf16_tflops(chip) == 197.0

    def test_unknown_accelerator_is_an_error_cpu_has_no_peak(self):
        from mmlspark_tpu.core.environment import device_peaks
        with pytest.raises(LookupError, match="TPU v9"):
            device_peaks("TPU v9", "tpu")
        assert device_peaks("cpu", "cpu") is None

    def test_mfu_meter_does_not_guess(self, monkeypatch):
        from mmlspark_tpu.core.profiling import MfuMeter
        meter = MfuMeter()
        assert (meter.device_kind, meter.peak_flops) == ("cpu", None)
        meter.note(8, 1.0, flops=2e12)
        assert "mfu" not in meter.snapshot()["buckets"]["8"]
        # a lookup failure is not swallowed into "no peak"
        unknown = types.SimpleNamespace(device_kind="TPU v9",
                                        platform="tpu")
        monkeypatch.setattr(jax, "devices", lambda: [unknown])
        with pytest.raises(LookupError):
            MfuMeter()


class TestBenchExitCode:
    """``bench.py``'s ``main``: every selected entry runs and prints its
    line; the process fails if any raised or said ``passed: false``."""

    @staticmethod
    def _run(monkeypatch, capsys, entries):
        import bench
        monkeypatch.setattr(bench, "BENCHES", entries)
        monkeypatch.setattr(sys, "argv", ["bench.py"])
        try:
            bench.main()
            code = 0
        except SystemExit as e:
            code = e.code
        import json
        return code, [json.loads(line) for line
                      in capsys.readouterr().out.splitlines()]

    def test_all_entries_pass(self, monkeypatch, capsys):
        def bench_ok():
            return {"metric": "ok_v1", "value": 1.0}

        def bench_gate():
            return {"metric": "gate_v1", "passed": True}

        code, lines = self._run(monkeypatch, capsys, [bench_ok, bench_gate])
        assert code == 0 and [l["metric"] for l in lines] == [
            "ok_v1", "gate_v1"]

    def test_failed_and_raised_entries_fail_the_run(self, monkeypatch,
                                                    capsys):
        def bench_gate():
            return {"metric": "gate_v1", "passed": False}

        def bench_boom():
            raise LookupError("no published peaks")

        def bench_after():
            return {"metric": "after_v1", "value": 2.0}

        code, lines = self._run(monkeypatch, capsys,
                                [bench_gate, bench_boom, bench_after])
        assert code not in (0, None)
        assert "bench_gate" in str(code) and "bench_boom" in str(code)
        # the raised entry printed its own failed line; later ones ran
        assert lines[1]["passed"] is False
        assert "no published peaks" in lines[1]["error"]
        assert lines[2]["metric"] == "after_v1"

    def test_evidence_children_are_assigned_the_cpu(self, monkeypatch):
        """One process per chip: a harness spawned by a parent that
        holds the chip must not inherit ``JAX_PLATFORMS=tpu``."""
        import bench
        seen = {}

        def fake_run(argv, env=None, **_kw):
            seen.update(env)
            return types.SimpleNamespace(returncode=0,
                                         stdout='{"passed": true}',
                                         stderr="")

        monkeypatch.setenv("JAX_PLATFORMS", "tpu")
        monkeypatch.setattr(subprocess, "run", fake_run)
        assert bench._spawn_evidence(["x.py"], 5) == (0, {"passed": True})
        assert seen["JAX_PLATFORMS"] == "cpu"
