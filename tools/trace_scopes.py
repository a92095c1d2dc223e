"""A kept profiler trace, split by hand: PERF.md section 5's reading.

``BENCH_KEEP_TRACE=1 python3 benchmark/run.py ... --trace 1`` leaves the
trace under ``.bench_trace/<cell>/``; this prints, from its
``.xplane.pb`` files, each program's device time a call (line ``XLA
Modules``), the three largest programs' op time by named scope (an
op's scope is the stat ``tf_op`` of its event METADATA, which
``jax.profiler.ProfileData`` does not show, hence ``xplane_pb2``) and
by op kind, the heaviest ``copy`` / ``copy-done`` instructions, and
the mean of every ``decode.*`` host span, and the gaps the device
leaves between two calls of its largest program with the ``decode.*``
host span that was open in the middle of each::

    python tools/trace_scopes.py .bench_trace/<cell>/**/*.xplane.pb

A stop-gap until ``benchmark/trace_reduce.py`` keeps the scopes
(ROADMAP A4, a ``benchmark`` PR); needs tensorflow's protobufs.
"""

import bisect
import collections
import re
import sys

SCOPES = ("embed", "norm", "attn.qkv", "attn.core", "attn.out", "ffn",
          "moe", "head", "ce", "optimizer", "kv.write", "kv.gather",
          # the Granite-hybrid programs' parts (models/granite_hybrid.py)
          "ssm.in_proj", "ssm.conv", "ssm.scan", "ssm.gate_norm", "ssm.out",
          "moe.route", "moe.experts", "moe.shared",
          # LFM2's layer on the same block: the gated short convolution,
          # the leading dense FFN, and the two parts of attn.qkv
          "conv.in", "conv.core", "conv.out", "ffn.dense",
          "attn.qknorm", "attn.rope",
          # multi-head latent attention (Kimi-K2's "mla" layers): the
          # down/up projections and the absorption, the latent row's
          # write, the attention over latent rows (the step's kernel or
          # its twin, the tile's chunks), the tile's expansion of the
          # lane through W_UK / W_UV, and W_UV / W_O after
          "mla.q", "mla.latent_write", "mla.latent_attn", "mla.expand",
          "mla.out",
          # a looped stack's programs (jit_looped_step / _prefill): the
          # norm between passes and the exit gate; the layers' parts
          # keep the block's names under while/body
          "loop.norm", "loop.gate")


def scope_of(tf_op: str) -> str:
    """``jit(step)/kv.write/scatter`` -> ``kv.write``;
    ``jit(looped_step)/while/body/closed_call/loop.norm/norm/mul`` ->
    ``loop.norm`` (the outermost known scope); a known scope of the same
    family inside it is named instead:
    ``jit(hybrid_step)/attn.qkv/attn.rope/mul`` -> ``attn.rope``."""
    parts = [p for p in tf_op.split("/") if p and not p.startswith("jit(")]
    known = [p for p in parts if p in SCOPES]
    if not known:
        return parts[0] if parts else "(none)"
    family = known[0].split(".")[0] + "."
    return next((p for p in reversed(known) if p.startswith(family)),
                known[0])


def main(paths):
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    Counter = collections.Counter
    mod_t, mod_n = Counter(), Counter()
    by_scope = collections.defaultdict(Counter)
    by_kind = collections.defaultdict(Counter)
    kind_scope = collections.defaultdict(Counter)
    heaviest = collections.defaultdict(Counter)
    host_t, host_n = Counter(), Counter()
    gaps = collections.defaultdict(list)    # program -> (ps, midpoint)
    spans = []                              # host (start, end, name)
    for path in paths:
        space = xplane_pb2.XSpace()
        with open(path, "rb") as f:
            space.ParseFromString(f.read())
        for plane in space.planes:
            events = {line.name: [
                (line.timestamp_ns * 1000 + ev.offset_ps, ev.duration_ps,
                 plane.event_metadata[ev.metadata_id])
                for ev in line.events] for line in plane.lines}
            if not re.match(r"^/device:TPU:\d+$", plane.name):
                for evs in events.values():
                    for start, dur, md in evs:
                        if md.name.startswith("decode."):
                            host_t[md.name] += dur
                            host_n[md.name] += 1
                            spans.append((start, start + dur, md.name))
                continue
            tf_op_id = next((k for k, v in plane.stat_metadata.items()
                             if v.name == "tf_op"), None)
            mods = sorted((s, d, re.sub(r"\(\d+\)$", "", md.name))
                          for s, d, md in events.get("XLA Modules", []))
            starts = [m[0] for m in mods]
            for _, dur, name in mods:
                mod_t[name] += dur
                mod_n[name] += 1
            for (s0, d0, n0), (s1, _, n1) in zip(mods, mods[1:]):
                if n0 == n1:     # nothing else ran between the two calls
                    gaps[n0].append((s1 - s0 - d0, (s0 + d0 + s1) // 2))
            for start, dur, md in events.get("XLA Ops", []):
                i = bisect.bisect_right(starts, start) - 1
                inside = i >= 0 and start < mods[i][0] + mods[i][1]
                mod = mods[i][2] if inside else "(outside)"
                tf_op = ""
                for st in md.stats:
                    if st.metadata_id == tf_op_id:
                        tf_op = (plane.stat_metadata[st.ref_value].name
                                 if st.WhichOneof("value") == "ref_value"
                                 else st.str_value)
                short = md.name.split(" = ", 1)[0].lstrip("%")
                kind = re.sub(r"(\.\d+)+$", "", short)
                scope = scope_of(tf_op)
                by_scope[mod][scope] += dur
                by_kind[mod][kind] += dur
                kind_scope[mod, kind][scope] += dur
                heaviest[mod, kind][md.name[:200]] += dur
    print("programs: seconds, calls, ms a call")
    for mod, t in mod_t.most_common(8):
        print(f"  {mod:36s} {t / 1e12:8.4f} {mod_n[mod]:6d} "
              f"{t / 1e9 / mod_n[mod]:8.3f}")
    for mod, _ in mod_t.most_common(3):
        total = sum(by_scope[mod].values())
        print(f"{mod}: op time {total / 1e12:.4f} s; by scope "
              "(seconds, share, ms a call)")
        for scope, t in by_scope[mod].most_common(14):
            print(f"  {scope:20s} {t / 1e12:8.4f} {100 * t / total:6.2f}% "
                  f"{t / 1e9 / mod_n[mod]:7.3f}")
        print("  by op kind (seconds, share, the scopes it runs under)")
        for kind, t in by_kind[mod].most_common(10):
            under = ", ".join(
                f"{s} {100 * v / t:.0f}%"
                for s, v in kind_scope[mod, kind].most_common(3))
            print(f"  {kind:32s} {t / 1e12:8.4f} {100 * t / total:6.2f}% "
                  f"[{under}]")
        for kind in ("copy", "copy-done"):
            for name, t in heaviest[mod, kind].most_common(2):
                print(f"  heaviest {kind} {t / 1e12:.4f} s: {name}")
    for mod, _ in mod_t.most_common(1):
        g = sorted(gaps[mod])
        if g:
            # the host span open at a gap's midpoint, the shortest if
            # several are (one clock: both planes are the profiler's)
            cover = Counter()
            for _, mid in g:
                over = [(e - b, n) for b, e, n in spans if b <= mid < e]
                cover[min(over)[1] if over else "(no decode.* span)"] += 1
            ms = [x[0] / 1e9 for x in g]
            print(f"{mod}: {len(ms)} gaps between two calls in a row, ms: "
                  f"median {ms[len(ms) // 2]:.3f} mean "
                  f"{sum(ms) / len(ms):.3f} p90 {ms[len(ms) * 9 // 10]:.3f} "
                  f"max {ms[-1]:.3f}; host span open in the middle: "
                  + ", ".join(f"{n} {c}" for n, c in cover.most_common(5)))
    print("host spans: calls, mean ms")
    for name, t in host_t.most_common():
        print(f"  {name:20s} {host_n[name]:6d} {t / host_n[name] / 1e9:8.3f}")


if __name__ == "__main__":
    main(sorted(sys.argv[1:]))
