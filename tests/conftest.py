"""Test harness configuration.

Multi-device story (parity with the reference's trick of exercising
distributed paths in `local[*]` by treating each partition as a worker,
`LightGBMUtils.scala:147-155`): we run the REAL collective code paths on a
virtual 8-device CPU mesh via ``xla_force_host_platform_device_count``, so
the distributed code tested here is identical to what runs on a TPU pod.

JAX's own ``JAX_PLATFORMS`` is the one platform switch: ``cpu`` or
unset builds the virtual mesh (before any jax backend is initialized);
any other value leaves the platform alone — ``JAX_PLATFORMS=tpu pytest
-m tpu`` is how the ``tpu``-marked tests reach real chips.
"""

import os

if (os.environ.get("JAX_PLATFORMS") or "cpu") == "cpu":
    # assigned BEFORE the package import: CPU runs keep out of the
    # compile cache (core/environment.place_compile_cache)
    os.environ["JAX_PLATFORMS"] = "cpu"
    from mmlspark_tpu.parallel.topology import use_cpu_devices
    use_cpu_devices(8)

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture
def basic_df():
    """Parity: TestBase.makeBasicDF (`TestBase.scala:156`)."""
    from mmlspark_tpu import DataFrame
    return DataFrame({
        "numbers": np.array([0, 1, 2, 3], dtype=np.int64),
        "doubles": np.array([0.0, 1.5, 2.5, 3.5]),
        "words": ["guitars", "drums", "bass", "keys"],
    })


def assert_df_eq(a, b, rtol=1e-5, atol=1e-6):
    """Tolerant frame equality (parity: DataFrameEquality, TestBase.scala:209)."""
    assert a.columns == b.columns, f"{a.columns} != {b.columns}"
    assert a.num_rows == b.num_rows
    for name in a.columns:
        ca, cb = a[name], b[name]
        if ca.dtype == np.dtype("O") or cb.dtype == np.dtype("O"):
            assert list(ca) == list(cb), f"column {name} differs"
        else:
            np.testing.assert_allclose(ca, cb, rtol=rtol, atol=atol,
                                       err_msg=f"column {name} differs")
