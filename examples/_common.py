"""Shared example plumbing: device selection + timing."""

import os
import sys
import time

# runnable straight from a checkout: python examples/<script>.py
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def setup_devices():
    """``JAX_PLATFORMS=cpu`` -> the virtual 8-device CPU mesh the tests
    use; otherwise whatever platform JAX finds."""
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        from mmlspark_tpu.parallel.topology import use_cpu_devices
        use_cpu_devices(8)
    import jax
    return jax.devices()


class timed:
    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.seconds = time.perf_counter() - self.t0
