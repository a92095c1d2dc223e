"""A side process for a chip call: no JAX, no device, one sleeper.

    python3 tools/freeze_watch.py chiprun_out/freeze.log &   # then the runs
    kill $!

It sleeps 20 ms at a time and writes ``GAP wall=<unix s> slept=<s>``
whenever it wakes over 0.25 s late: a sleeper that oversleeps by seconds
was frozen with everything else on the machine, so a stall of the same
length at the same moment in a run's ``facts.loop_stalls`` was the
machine's and not the program's (PERF.md section 7, PR 29: a 1.265 s gap
here beside a 1.267 s ``decode.fetch`` there). Once a second it also
reads the cgroup's CPU throttling, memory events, steal and I/O wait and
writes a line when one moved (and every tenth second regardless).
"""

import os
import sys
import time

CG = "/sys/fs/cgroup"


def read(path):
    try:
        with open(path) as f:
            return f.read().strip()
    except OSError:
        return None


def counters(path):
    out = {}
    for line in (read(path) or "").splitlines():
        key, _, value = line.partition(" ")
        if value.isdigit():
            out[key] = int(value)
    return out


def first(*paths):
    return next((p for p in paths if os.path.exists(p)), paths[0])


def cpu_times():
    line = (read("/proc/stat") or "cpu").splitlines()[0].split()
    return {"iowait": int(line[5]), "steal": int(line[8])} \
        if len(line) > 8 else {"iowait": 0, "steal": 0}


def main():
    out = open(sys.argv[1], "w", buffering=1)
    cpu_stat = first(CG + "/cpu.stat", CG + "/cpu/cpu.stat")
    mem_events = first(CG + "/memory.events", CG + "/memory/memory.failcnt")
    mem_now = first(CG + "/memory.current",
                    CG + "/memory/memory.usage_in_bytes")
    out.write(f"start wall={time.time():.3f} cpus={os.cpu_count()} "
              f"cpu.max={read(CG + '/cpu.max')} "
              f"memory.max={read(CG + '/memory.max')}\n")
    last = tick = time.monotonic()
    prev, n = None, 0
    while True:
        time.sleep(0.02)
        t = time.monotonic()
        if t - last > 0.25:
            out.write(f"GAP wall={time.time():.3f} slept={t - last:.3f}\n")
        last = t
        if t - tick < 1.0:
            continue
        tick, n = t, n + 1
        cur = {**{"cpu." + k: v for k, v in counters(cpu_stat).items()},
               **{"mem." + k: v for k, v in counters(mem_events).items()},
               **cpu_times()}
        # throttling and memory events whenever they move; steal and I/O
        # wait (jiffies, all CPUs) from half a second a second
        floor = {"nr_throttled": 1, "high": 1, "max": 1, "oom": 1,
                 "steal": 50, "iowait": 50}
        moved = {k: v - prev.get(k, 0) for k, v in cur.items()
                 if prev is not None and v - prev.get(k, 0)
                 >= floor.get(k.split(".")[-1], 1 << 62)}
        if moved or n % 10 == 0:
            out.write(f"t wall={time.time():.3f} moved={moved} "
                      f"memory={read(mem_now)} load={read('/proc/loadavg')}\n")
        prev = cur


if __name__ == "__main__":
    main()
