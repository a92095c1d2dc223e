"""Replay a closed-loop traffic mix of the benchmark against a cost
model of the decode loop, in the sandbox: how far `gen_tokens_per_s`
and `ttft_p50_ms` spread over seeds from NOTHING but which requests a
seed puts into the window. No device, no program: the plans are the
generator's (`benchmark/traffic.py`), the costs are arguments (defaults:
`evabyte-6.5b.doc-closed` as PERF.md section 5 has it, PR 29). What it
prints is arithmetic over the traffic, never a measurement.

    python3 tools/replay_closed_loop.py --traffic doc-closed --ladder pow2 16

The loop it replays is `DecodeScheduler._loop`'s: a pass admits every
waiting client (each prefill blocks the pass: a prompt is walked a
window tile at a time, the tail tile padded up the ladder), then runs
one step for all live slots; client `c` takes `plan[c::clients]` and
sends its next request when the last byte of the previous one arrives.
"""

import argparse
import os
import statistics as st
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "benchmark"))
import traffic as T  # noqa: E402


def prefill_s(p_len, ladder, a):
    full, rest = divmod(p_len, a.window)
    t = a.call_s + a.tile_s * full
    if rest:
        if ladder == "pow2":
            b = a.window // 16
            while b < rest:
                b *= 2
        else:
            b = -(-rest // int(ladder)) * int(ladder)
        t += a.tail_s + a.tile_s * b / a.window
    return t


def run(seed, spec, ladder, a):
    n = int(spec["clients"])
    plan = T.requests(spec, 320, seed, 8)
    queues = [plan[c::n] for c in range(n)]
    t, t0 = 0.0, float(spec["ramp_s"])
    t1 = t0 + a.seconds
    waiting, sent_at, left = list(range(n)), [0.0] * n, [0] * n
    tokens, ttfts = 0, []
    while t < t1:
        for c in list(waiting):
            r = queues[c].pop(0)
            t += prefill_s(len(r["prompt"]), ladder, a)
            if t0 <= sent_at[c] < t1:
                ttfts.append(t - sent_at[c] + a.edge_s)
            tokens += t0 <= t < t1
            left[c] = r["max_new_tokens"] - 1
            if left[c]:
                waiting.remove(c)
            else:
                sent_at[c] = t
        t += a.step_s
        for c in range(n):
            if left[c]:
                left[c] -= 1
                tokens += t0 <= t < t1
                if not left[c]:
                    waiting.append(c)
                    sent_at[c] = t
    return tokens / a.seconds, 1e3 * st.median(ttfts)


def spread(v):
    q = st.quantiles(v, n=4)
    return (q[2] - q[0]) / st.median(v)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--traffic", default="doc-closed")
    ap.add_argument("--ladder", nargs="+", default=["pow2", "16"],
                    help="'pow2' (window/16 doubling) or a tile step in rows")
    ap.add_argument("--seeds", type=int, default=240)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--window", type=int, default=2048)
    ap.add_argument("--step-s", type=float, default=0.01765)
    ap.add_argument("--tile-s", type=float, default=0.075,
                    help="a full window tile, its compaction included")
    ap.add_argument("--tail-s", type=float, default=0.005)
    ap.add_argument("--call-s", type=float, default=0.010)
    ap.add_argument("--edge-s", type=float, default=0.004)
    a = ap.parse_args()
    spec = T.load(a.traffic)
    for ladder in a.ladder:
        res = [run(5000 + s, spec, ladder, a) for s in range(a.seeds)]
        for k, name, half in ((0, "gen_tokens_per_s", 0.04),
                              (1, "ttft_p50_ms", 0.035)):
            v = [r[k] for r in res]
            sets = [spread(v[i:i + 6]) for i in range(0, len(v) - 5, 6)]
            print(f"ladder {ladder:>5} {name:<17} median {st.median(v):7.1f}"
                  f"  range {min(v):6.1f}-{max(v):6.1f}"
                  f"  spread {100 * spread(v):5.2f}%"
                  f"  sets of six: median {100 * st.median(sets):5.2f}%,"
                  f" {sum(s > half for s in sets)} of {len(sets)} over"
                  f" {100 * half:.1f}%")


if __name__ == "__main__":
    main()
