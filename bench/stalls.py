#!/usr/bin/env python3
"""Look for stalls of a cell's served loop on the chip: windows in which no
answer comes back for a while, and what every thread of the process was
doing then.

    python3 bench/stalls.py --workload vgg16-int8.offline --seconds 51 \
        --windows 4 --seed 7

One process: the cell's set-up, then ``--windows`` windows of its traffic.
A watcher thread ticks every 10 ms. Once no answer has come for 60 ms it
samples every thread's stack at each tick until answers resume; a stall of
100 ms or more is printed with its length, how late the watcher's own ticks
came (a process that is not scheduled, or a thread that holds the GIL,
delays them), the process's CPU seconds during it, and each thread's most
frequent innermost frames. Each window also reports the machine's CPU
quota and how long the kernel throttled the process's cgroup
(``/sys/fs/cgroup/cpu.stat``), the steal time (``/proc/stat``), and the
threads that used the most CPU (``/proc/self/task``).
"""
from __future__ import annotations

import argparse
import collections
import gc
import os
import sys
import threading
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
TICK_S, QUIET_S, REPORT_S = 0.01, 0.06, 0.1


class Watcher(threading.Thread):
    def __init__(self, progress):
        super().__init__(daemon=True, name="bench-stall-watcher")
        self.progress, self.stop, self.stalls = progress, False, []

    def run(self):
        last_n, last_t, prev = -1, time.monotonic(), time.monotonic()
        last_thr = throttled_us()
        stall = None
        names = {}
        while not self.stop:
            time.sleep(TICK_S)
            now = time.monotonic()
            late = now - prev - TICK_S
            prev = now
            n = self.progress()
            thr = throttled_us()
            if n != last_n:
                if stall is not None and now - stall["t0"] >= REPORT_S:
                    stall["seconds"] = now - stall["t0"]
                    stall["cpu_s"] = time.process_time() - stall["cpu0"]
                    stall["throttled_ms"] = (thr - stall["thr0"]) / 1e3
                    self.stalls.append(stall)
                stall, last_n, last_t, last_thr = None, n, now, thr
                continue
            if now - last_t < QUIET_S:
                last_thr = thr
                continue
            if stall is None:
                stall = {"t0": last_t, "cpu0": time.process_time(),
                         "thr0": last_thr,
                         "late_max_s": 0.0, "stacks": collections.defaultdict(
                             collections.Counter)}
                names = {t.ident: t.name for t in threading.enumerate()}
            stall["late_max_s"] = max(stall["late_max_s"], late)
            for ident, frame in sys._current_frames().items():
                if ident == self.ident:
                    continue
                inner = traceback.extract_stack(frame)[-4:]
                key = " <- ".join(f"{Path(f.filename).name}:{f.lineno} "
                                  f"{f.name}" for f in reversed(inner))
                stall["stacks"][names.get(ident, str(ident))][key] += 1


def _read(path: str) -> str:
    try:
        return Path(path).read_text()
    except OSError:
        return ""


def throttled_us() -> int:
    for line in _read("/sys/fs/cgroup/cpu.stat").splitlines():
        if line.startswith("throttled_usec "):
            return int(line.split()[1])
    return 0


def machine() -> dict:
    """Throttling of this cgroup, steal time, and CPU seconds per thread."""
    cg = dict(line.split() for line in _read("/sys/fs/cgroup/cpu.stat")
              .splitlines() if len(line.split()) == 2)
    cpu = _read("/proc/stat").splitlines()[:1]
    steal = int(cpu[0].split()[8]) if cpu else 0
    tick = os.sysconf("SC_CLK_TCK")
    threads = {}
    for task in Path("/proc/self/task").glob("*"):
        stat = _read(str(task / "stat"))
        if stat:
            name = stat[stat.index("(") + 1:stat.rindex(")")]
            f = stat[stat.rindex(")") + 2:].split()
            threads[(task.name, name)] = (int(f[11]) + int(f[12])) / tick
    return {"throttled_us": int(cg.get("throttled_usec", 0)),
            "nr_throttled": int(cg.get("nr_throttled", 0)),
            "steal_s": steal / tick, "threads": threads}


def report(a: dict, b: dict, seconds: float) -> None:
    use = collections.Counter()
    for key, t in b["threads"].items():
        use[key[1]] += t - a["threads"].get(key, 0.0)
    top = ", ".join(f"{n} {t:.2f}" for n, t in use.most_common(8))
    print(f"  machine: cgroup throttled {b['nr_throttled'] - a['nr_throttled']}"
          f" times, {(b['throttled_us'] - a['throttled_us']) / 1e3:.1f} ms; "
          f"steal {b['steal_s'] - a['steal_s']:.2f} s; threads "
          f"{len(b['threads'])}, CPU s {sum(use.values()):.2f} in "
          f"{seconds:.0f} s: {top}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--windows", type=int, default=3)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax

    from bench import harness, registry

    bench = registry.load_benchmark()
    cell = registry.workload(bench, args.workload)
    cfg = registry.load_config(cell["config"])
    mix = registry.load_traffic(cell["traffic"])
    chips = int(cell["chips"])
    print(f"cpu.max {_read('/sys/fs/cgroup/cpu.max').strip()!r}; "
          f"cpu_count {os.cpu_count()}, affinity "
          f"{len(os.sched_getaffinity(0))}", flush=True)
    harness.enable_cache(jax, ROOT)
    devices, _ = harness.check_chip(jax, chips)
    served = harness.build(jax, cfg, mix, chips, args.seed, devices)
    harness.drive(served, mix, 1.0, args.seed + 1).settle()
    gc.collect()
    gc.freeze()
    for w in range(args.windows):
        stats = served.session.stats
        watcher = Watcher(lambda: stats.requests)   # requests completed
        watcher.start()
        before = machine()
        log, delta, _ = harness.window(jax, served, mix, args.seconds,
                                       args.seed + 10 + w)
        after = machine()
        watcher.stop = True
        watcher.join()
        print(f"window {w}: {log.counts()}, {len(watcher.stalls)} stalls of "
              f">= {REPORT_S * 1e3:.0f} ms; counters {delta}", flush=True)
        report(before, after, args.seconds)
        for s in watcher.stalls:
            print(f"  stall at +{s['t0'] - log.t_start:.3f} s: "
                  f"{s['seconds'] * 1e3:.1f} ms, watcher late by up to "
                  f"{s['late_max_s'] * 1e3:.1f} ms, process CPU "
                  f"{s['cpu_s']:.3f} s, cgroup throttled "
                  f"{s['throttled_ms']:.1f} ms", flush=True)
            for name, counter in s["stacks"].items():
                stack, k = counter.most_common(1)[0]
                print(f"    {name} ({k}/{sum(counter.values())}): {stack}",
                      flush=True)
    served.session.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
