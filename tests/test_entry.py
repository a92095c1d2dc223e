"""Driver entry-point contract tests (``__graft_entry__.py``).

``dryrun_multichip(n)`` runs on the devices its process has — it never
flips the platform and never starts a child (one process per chip). A
CPU rehearsal is the caller's environment (``JAX_PLATFORMS=cpu`` plus
the virtual device count, parity in spirit with the reference
exercising its distributed path inside one JVM,
`LightGBMUtils.scala:147-155`); too few devices is an error.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _clean_env(**overrides):
    env = dict(os.environ)
    # start from a 1-device CPU platform with no force-count flag
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ""
    env.update(overrides)
    return env


@pytest.mark.slow
def test_dryrun_runs_on_the_devices_it_has():
    """An 8-device platform set up by the ENVIRONMENT: the dry run uses
    it in-process and leaves it as it found it."""
    script = (
        "import sys; sys.path.insert(0, %r)\n"
        "import __graft_entry__ as e\n"
        "e.dryrun_multichip(8)\n"
        "import jax; assert len(jax.devices()) == 8\n" % REPO)
    proc = subprocess.run(
        [sys.executable, "-c", script], cwd=REPO,
        env=_clean_env(
            XLA_FLAGS="--xla_force_host_platform_device_count=8"),
        capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr
    assert "dryrun_multichip(8): ok" in proc.stdout


def test_dryrun_raises_on_too_few_devices():
    """No self-healing: asked for more devices than the process has,
    the dry run raises and names both counts and the platform — it
    does not re-exec onto a virtual CPU mesh."""
    import jax
    import __graft_entry__ as e
    n = len(jax.devices()) + 1
    platform = jax.devices()[0].platform
    with pytest.raises(
            RuntimeError,
            match=rf"needs {n} devices.*has {n - 1}.*{platform!r}"):
        e.dryrun_multichip(n)


@pytest.mark.slow
def test_bench_emits_json_line_per_config():
    """bench.py's driver contract: each config prints one JSON line with
    metric/value/unit/vs_baseline (+ chip metadata). Smoke-run the
    cheapest config on a CPU mesh."""
    import json
    env = _clean_env()
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "gbdt_quantile"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr
    lines = [l for l in proc.stdout.splitlines() if l.startswith("{")]
    assert len(lines) == 1
    rec = json.loads(lines[0])
    for key in ("metric", "value", "unit", "vs_baseline", "baseline", "chip"):
        assert key in rec, f"missing {key}"
    assert rec["chip"]["n_devices"] >= 1


def test_bump_host_device_count():
    """The virtual-device flag behind ``use_cpu_devices`` (and so this
    suite's mesh): a missing count is appended, a smaller one raised, a
    larger one preserved, unrelated flags kept."""
    from mmlspark_tpu.parallel.topology import bump_host_device_count
    flags = bump_host_device_count(
        "--xla_force_host_platform_device_count=2 --foo", 8)
    assert "xla_force_host_platform_device_count=8" in flags
    assert "--foo" in flags
    assert bump_host_device_count("", 4) == (
        "--xla_force_host_platform_device_count=4")
    # an existing LARGER count is preserved, not shrunk
    assert bump_host_device_count(
        "--xla_force_host_platform_device_count=16", 8) == (
        "--xla_force_host_platform_device_count=16")
