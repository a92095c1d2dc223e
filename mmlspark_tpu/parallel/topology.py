"""Device mesh topology and multi-host initialization.

This is the framework's single communication story, replacing every
coordination mechanism in the reference: the Spark-driver ServerSocket
rendezvous + LightGBM TCP allreduce mesh (`LightGBMUtils.scala:97-142`,
`TrainUtils.scala:217-267`), the `mpirun --hostfile` ring for CNTK
(`CommandBuilders.scala:102-128`), and Spark broadcast. Within a slice,
XLA collectives ride ICI; across hosts, the JAX distributed runtime
coordinates over DCN.

Axis conventions (reserved from day one so TP/PP/SP/EP are addable without
API change — SURVEY.md §7 "hard parts"):

- ``data``   — batch/data parallelism (the reference's only strategy)
- ``model``  — tensor parallelism
- ``seq``    — sequence/context parallelism (ring attention)
- ``expert`` — expert parallelism
- ``pipe``   — pipeline parallelism
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import os
import threading
from typing import Dict, List, Optional, Sequence, Tuple

AXIS_DATA = "data"
AXIS_MODEL = "model"
AXIS_SEQ = "seq"
AXIS_EXPERT = "expert"
AXIS_PIPE = "pipe"

ALL_AXES = (AXIS_DATA, AXIS_MODEL, AXIS_SEQ, AXIS_EXPERT, AXIS_PIPE)


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Logical mesh shape over named axes; -1 on one axis means 'the rest'."""

    axes: Tuple[Tuple[str, int], ...] = ((AXIS_DATA, -1),)

    @staticmethod
    def data_parallel() -> "MeshSpec":
        return MeshSpec(((AXIS_DATA, -1),))

    @staticmethod
    def from_dict(shape: Dict[str, int]) -> "MeshSpec":
        return MeshSpec(tuple(shape.items()))

    @staticmethod
    def full_spmd(n_devices: int) -> "MeshSpec":
        """All five axes over ``n_devices``: factors of 2 are handed to
        ``model``, ``pipe``, ``seq``, ``expert`` in that order; the
        remainder becomes ``data``. Every axis is always present so the
        complete tp/pp/sp/ep/dp code path compiles and runs at any
        device count (size-1 axes degenerate gracefully)."""
        sizes = {AXIS_DATA: 1, AXIS_SEQ: 1, AXIS_MODEL: 1,
                 AXIS_EXPERT: 1, AXIS_PIPE: 1}
        rest = n_devices
        for axis in (AXIS_MODEL, AXIS_PIPE, AXIS_SEQ, AXIS_EXPERT):
            if rest % 2 == 0 and rest > 1:
                sizes[axis] = 2
                rest //= 2
        sizes[AXIS_DATA] = rest
        return MeshSpec.from_dict(sizes)

    def resolve(self, n_devices: int) -> Dict[str, int]:
        """Concrete per-axis sizes for a device count."""
        sizes = dict(self.axes)
        wildcards = [a for a, s in sizes.items() if s == -1]
        if len(wildcards) > 1:
            raise ValueError("at most one axis may be -1")
        fixed = math.prod(s for s in sizes.values() if s != -1)
        if wildcards:
            if n_devices % fixed:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes {sizes}")
            sizes[wildcards[0]] = n_devices // fixed
        elif fixed != n_devices:
            raise ValueError(f"mesh {sizes} needs {fixed} devices, have {n_devices}")
        return sizes

    @property
    def axis_names(self) -> Tuple[str, ...]:
        return tuple(a for a, _ in self.axes)


def local_device_count() -> int:
    import jax
    return len(jax.devices())


def use_cpu_devices(n: int = 8) -> None:
    """Switch this process to ``n`` virtual CPU devices (test/dev mode).

    Must run before any jax backend is initialized (first device touch);
    jax may already be *imported*, because backends init lazily.
    This is how the distributed code paths run unchanged from laptop to pod.
    """
    import jax
    os.environ["XLA_FLAGS"] = bump_host_device_count(
        os.environ.get("XLA_FLAGS", ""), n)
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        # a CPU run writes no compile cache: drop the directory
        # core/environment.place_compile_cache gave the accelerator
        # this process has just turned away from
        jax.config.update("jax_compilation_cache_dir", None)


def bump_host_device_count(flags: str, n: int) -> str:
    """Return ``flags`` with ``xla_force_host_platform_device_count >= n``.

    A missing count is appended; a smaller one is raised; a larger one is
    preserved (a caller prepping a bigger mesh keeps it).
    """
    import re
    m = re.search(r"xla_force_host_platform_device_count=(\d+)", flags)
    if m is None:
        return (flags + f" --xla_force_host_platform_device_count={n}").strip()
    if int(m.group(1)) < n:
        return re.sub(r"xla_force_host_platform_device_count=\d+",
                      f"xla_force_host_platform_device_count={n}", flags)
    return flags


_scope_state = threading.local()


@contextlib.contextmanager
def single_device_scope():
    """Context manager confining framework stages to one device.

    Inside the scope, :func:`in_single_device_scope` is True and
    framework stages (GBDT stages, NNLearner, NNModel scoring) skip
    building multi-device mesh shardings — their device work stays on
    the thread's default device. Used by
    ``TuneHyperparameters(trial_devices=True)`` so concurrently
    dispatched trials can't interleave full-mesh collectives across
    threads (which deadlocks on real chips). The flag is thread-local:
    other threads keep their sharded behavior.
    """
    prev = getattr(_scope_state, "single", False)
    _scope_state.single = True
    try:
        yield
    finally:
        _scope_state.single = prev


def in_single_device_scope() -> bool:
    return getattr(_scope_state, "single", False)


def build_mesh(spec: Optional[MeshSpec] = None, devices=None):
    """Build a ``jax.sharding.Mesh`` over the given (default: all) devices.

    A fully fixed spec smaller than the host's device count takes the
    leading subset (``{"data": 1}`` on an 8-device host is a 1-device
    mesh, not an error) — what lets one process build the 1/2/4/8-
    device meshes of a scaling curve, or pin a small fit while the
    rest of the chips serve."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    spec = spec or MeshSpec.data_parallel()
    devices = list(devices) if devices is not None else list(jax.devices())
    fixed = [s for _, s in spec.axes if s != -1]
    if len(fixed) == len(spec.axes):
        need = math.prod(fixed)
        if 0 < need < len(devices):
            if jax.process_count() > 1:
                # a leading subset of the GLOBAL device list can leave
                # a process with a mesh containing none of its local
                # devices — collectives then fail obscurely or hang;
                # multi-process meshes must name every device
                raise ValueError(
                    f"mesh {dict(spec.axes)} needs {need} devices but "
                    f"the multi-process runtime has {len(devices)}: "
                    f"subsetting is single-process only — size the "
                    f"mesh to the pod (or use -1 for one axis)")
            from mmlspark_tpu.core.logs import get_logger
            get_logger("parallel.topology").info(
                "mesh %s uses the leading %d of %d devices",
                dict(spec.axes), need, len(devices))
            devices = devices[:need]
    sizes = spec.resolve(len(devices))
    shape = tuple(sizes[a] for a in spec.axis_names)
    dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, spec.axis_names)


def distributed_init(coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None) -> None:
    """Join the multi-host JAX distributed runtime (DCN rendezvous).

    The one-call replacement for the reference's entire driver-socket
    rendezvous + ssh/scp/MPI machinery. No-ops when single-process (env
    unset), so the same program runs unchanged from laptop to pod.

    On a CPU backend this also selects the **gloo** TCP collectives
    implementation (when this jax ships it): XLA:CPU's default refuses
    multi-process computations outright ("Multiprocess computations
    aren't implemented on the CPU backend"), so without gloo a CPU
    "multi-host" run could rendezvous but never execute a
    cross-process psum — the gap that kept the 2-process DCN drill
    simulated. Gloo rides the same coordinator the rendezvous uses; on
    TPU the flag is irrelevant (collectives ride ICI/DCN natively).
    Must run before the backend initializes, like ``use_cpu_devices``.
    """
    import jax
    addr = coordinator_address or os.environ.get("MMLSPARK_TPU_COORDINATOR")
    if addr is None and num_processes is None:
        return  # single-process
    if os.environ.get("JAX_PLATFORMS", "").strip() == "cpu" \
            or jax.config.jax_platforms == "cpu":
        try:
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        except (AttributeError, ValueError):
            from mmlspark_tpu.core.logs import get_logger
            get_logger("parallel.topology").warning(
                "this jax has no gloo CPU collectives: cross-process "
                "computations will fail on the CPU backend")
    jax.distributed.initialize(coordinator_address=addr,
                               num_processes=num_processes,
                               process_id=process_id)
