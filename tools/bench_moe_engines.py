"""Scatter vs counting-sort capacity-dispatch engines: real-chip wall
time of dispatch+combine (fwd+bwd) as the expert count grows.

The FLOPs-side scaling story lives in ``bench_moe_dispatch.py`` (cost
analysis on the CPU dryrun mesh); this tool times the dispatch
MACHINERY itself on the actual chip at the production token shape —
the r4 verdict's "one-hot/scatter dispatch cost grows with E" item.
Total queue slots E*C are held constant (C = ceil(factor*Tk/E)), so any
growth is pure engine overhead, not capacity.

    python tools/bench_moe_engines.py      # needs the TPU chip

Appends an ``engine_wall_time`` section to
``docs/artifacts/moe_dispatch.json``.
"""

import functools
import json
import math
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def _engine(mode, h_rep, top, wf, E, C, dt):
    # both engines come FROM the transformer: the A/B times exactly the
    # dispatch code `_moe_capacity` runs, and cannot drift from it
    import mmlspark_tpu.models.transformer as TT
    if mode == "sort":
        return TT._sorted_capacity_queues(h_rep.astype(dt), top, wf,
                                          E, C, dt)
    return TT._scatter_capacity_queues(h_rep, top, wf, E, C, dt)


def time_engine(mode: str, E: int, Tk: int = 16384, d: int = 512,
                factor: float = 1.25) -> float:
    """ms per dispatch+combine fwd+bwd at constant total slots."""
    C = max(int(math.ceil(factor * Tk / E)), 1)
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.normal(size=(Tk, d)), dtype=jnp.float32)
    top = jnp.asarray(rng.integers(0, E, Tk), dtype=jnp.int32)
    wf = jnp.ones((Tk,), jnp.float32)

    def roundtrip(hh):
        disp, combine = _engine(mode, hh, top, wf, E, C, jnp.bfloat16)
        return jnp.sum(combine(disp.astype(jnp.float32)) ** 2)

    @functools.partial(jax.jit, static_argnames="n")
    def scan(hh, n):
        def body(c, _):
            l, g = jax.value_and_grad(roundtrip)(c)
            return c + 1e-9 * g, l
        _, ls = jax.lax.scan(body, hh, None, length=n)
        return ls

    def run(n):
        float(scan(h, n)[-1])

    run(2)
    # sub-ms per iteration: the chain must be long enough that the
    # long/short delta (~60 iterations) dwarfs the fetch jitter
    ts = {}
    for n in (4, 64):
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            run(n)
            best = min(best, time.perf_counter() - t0)
        ts[n] = best
    slope = (ts[64] - ts[4]) / 60 * 1000
    return slope if slope > 0 else ts[64] / 64 * 1000


def main() -> None:
    from mmlspark_tpu.core.environment import environment_info
    info = environment_info()
    # two interleaved rounds, min per cell: host-side timing drifts
    # between process phases, and the min of interleaved rounds
    # cancels that drift for both engines equally
    cells = {(E, m): float("inf") for E in (8, 16, 32)
             for m in ("scatter", "sort")}
    for _ in range(2):
        for E in (8, 16, 32):
            for mode in ("scatter", "sort"):
                cells[(E, mode)] = min(cells[(E, mode)],
                                       time_engine(mode, E))
    rows = []
    for E in (8, 16, 32):
        row = {"n_experts": E,
               "scatter_ms": round(cells[(E, "scatter")], 3),
               "sort_ms": round(cells[(E, "sort")], 3)}
        rows.append(row)
        print(row, flush=True)
    speedups = [r["scatter_ms"] / r["sort_ms"] for r in rows]
    section = {
        "what": "dispatch+combine fwd+bwd wall time per layer, Tk=16384 "
                "x d=512, total slots E*C constant (factor 1.25)",
        "chip": info.get("device_kind"),
        "rows": rows,
        "summary": "counting-sort beats the scatter engine {:.1f}-{:.1f}x "
                   "across E=8..32 (no row scatter in either autodiff "
                   "direction)".format(min(speedups), max(speedups)),
    }
    path = os.path.join(REPO, "docs", "artifacts", "moe_dispatch.json")
    with open(path) as fh:
        art = json.load(fh)
    art["engine_wall_time"] = section
    with open(path, "w") as fh:
        json.dump(art, fh, indent=2)
    print(json.dumps(section))


if __name__ == "__main__":
    main()
