"""Platform introspection: chip type, topology, memory, host info.

Capability parity with `core/env/src/main/scala/EnvironmentUtils.scala:41-51`
(GPU discovery by shelling out to ``nvidia-smi -L``; OS detection) — the
TPU equivalent reads everything from the jax backend: device kind,
counts, process topology, per-device HBM stats when the runtime exposes
them. Used to stamp benchmark output and logs so recorded numbers are
interpretable (which chip, how many, which platform).
"""

from __future__ import annotations

import os
import platform as _platform
from typing import Any, Dict, Optional

#: the checkout this package was imported from (the directory holding
#: ``pyproject.toml`` and ``mmlspark_tpu/``)
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def place_compile_cache() -> Optional[str]:
    """Give JAX's persistent compilation cache a directory that can be
    placed from outside; called once, at ``import mmlspark_tpu``.

    * ``JAX_COMPILATION_CACHE_DIR`` set: nothing is set in code — JAX
      reads the variable itself, so an operator (or a benchmark driver)
      decides where compiled programs live and finds them again.
    * Otherwise the FIXED ``<checkout>/.jax_cache`` (git- and
      docker-ignored). A cache is found again only at the path it was
      written to, so the path never holds a pid, a time or a temp dir.
    * ``JAX_PLATFORMS=cpu`` runs (the test suite, the CPU drills) stay
      out: the chip tool ships the checkout as it stands, and a cache
      filled here would travel to the chip host as dead weight
      (executables for another backend never hit there).
    * A package imported from outside a checkout (site-packages) gets
      no directory from code: set the variable.

    What code sets goes into jax's live config and NEVER into
    ``os.environ``: the CPU drills a chip-holding parent spawns copy
    its environment with ``JAX_PLATFORMS=cpu`` assigned, and a
    directory left there would have every one of them cache its CPU
    executables in the checkout. The price is that an accelerator
    process imports jax with the package.

    Wherever the directory comes from, programs are cached whatever
    their compile time (unless ``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_
    SECS`` says otherwise): most of what a start compiles here is
    sub-second — the decode ladder's small buckets, one init op per
    leaf shape — and JAX's default 1 s floor skips all of it. Measured
    on the v5e (chip_smoke.py, PR 21): of a cold start's ~98
    compile-seconds a warm start still spent 30 with the floor and
    6-13 without it, for 3 MiB more cache (73 MiB).

    Returns the path set in code, or ``None``."""
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return None
    import jax
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    if not os.path.isfile(os.path.join(_CHECKOUT, "pyproject.toml")):
        return None
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def environment_info() -> Dict[str, Any]:
    """One JSON-able dict describing the accelerator + host environment.

    Safe to call before or after backend init; initializes the backend.
    """
    import jax

    devices = jax.devices()
    info: Dict[str, Any] = {
        "platform": devices[0].platform if devices else "none",
        "device_kind": devices[0].device_kind if devices else None,
        "n_devices": len(devices),
        "n_local_devices": jax.local_device_count(),
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "jax_version": jax.__version__,
        "host": {
            "os": _platform.system(),
            "machine": _platform.machine(),
            "python": _platform.python_version(),
            "cpu_count": os.cpu_count(),
        },
    }
    hbm = device_memory_stats(devices[0]) if devices else None
    if hbm:
        info["memory"] = hbm
    return info


def device_memory_stats(device=None) -> Optional[Dict[str, int]]:
    """Per-device memory stats (bytes) when the runtime exposes them
    (TPU/GPU runtimes do; CPU returns None)."""
    import jax

    dev = device if device is not None else jax.devices()[0]
    stats = getattr(dev, "memory_stats", None)
    if stats is None:
        return None
    try:
        raw = stats()
    except Exception:  # noqa: BLE001 - backend without stats support
        return None
    if not raw:
        return None
    keep = ("bytes_in_use", "bytes_limit", "peak_bytes_in_use",
            "bytes_reserved", "largest_free_block_bytes")
    return {k: int(raw[k]) for k in keep if k in raw}


#: Published per-chip peaks, keyed by the ``device_kind`` JAX reports —
#: the ONE table every utilization figure divides by (bench.py's MFU,
#: the serving ``MfuMeter``, chip_smoke.py's device check). Source:
#: Google Cloud documentation, "TPU v5e" (system architecture): 197
#: TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip. Only
#: chips this repo has been run on are listed: a utilization against a
#: guessed peak is worse than none, so an accelerator that is not here
#: is an error (:func:`device_peaks`), not a default.
DEVICE_PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_tflops": 197.0, "int8_tops": 393.0,
                    "hbm_gbytes_per_s": 819.0},
}


def device_peaks(device_kind: str, platform: str
                 ) -> Optional[Dict[str, float]]:
    """The :data:`DEVICE_PEAKS` row for a device, by the
    ``device_kind`` and ``platform`` JAX reports for it. ``None`` on the
    CPU platform — a host has no peak anyone measures against, so its
    numbers carry no utilization — and ``LookupError`` for an
    accelerator kind that is not in the table."""
    if platform == "cpu":
        return None
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise LookupError(
            f"no published peaks for accelerator kind {device_kind!r} "
            f"(platform {platform!r}); add its row, with the source, to "
            f"mmlspark_tpu.core.environment.DEVICE_PEAKS") from None


def accelerator_count() -> int:
    """Parity: `EnvironmentUtils.GPUCount` — the number of accelerator
    devices visible to this process (0 on CPU-only hosts)."""
    import jax

    return sum(1 for d in jax.devices() if d.platform != "cpu")


def describe() -> str:
    """Human-readable one-liner for logs: platform/kind/counts/memory."""
    info = environment_info()
    parts = [f"{info['platform']}:{info['device_kind']}",
             f"{info['n_devices']} device(s)"]
    if info["process_count"] > 1:
        parts.append(f"process {info['process_index']}/"
                     f"{info['process_count']}")
    mem = info.get("memory")
    if mem and "bytes_limit" in mem:
        parts.append(f"{mem['bytes_limit'] / 2**30:.1f} GiB/device")
    return ", ".join(parts)
