#!/usr/bin/env python3
"""Run one cell several times, each run its own process, and print the
spread of every metric.

    python3 bench/repeat.py --workload vgg16-fp32.offline --seconds 10 \
        --seeds 11,12,13,14,15,16 --sets 2 --out chiprun_out/runs.jsonl

Each run is ``bench/run.py`` with one seed; ``--sets 2`` runs the seed list
twice. The parent never imports JAX, so each child has the chips to itself.
Every run's result line (or its failure) is appended to ``--out``. For each
set and metric the spread is the distance between the first and third
quartile (``statistics.quantiles(n=4)``) as a share of the median.
"""
from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent


def spread(values):
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2 if q2 else float("nan")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", required=True)
    ap.add_argument("--timeout", type=float, default=1200)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    sets = []
    for k in range(args.sets):
        runs = []
        for seed in seeds:
            cmd = [sys.executable, str(HERE / "run.py"), "--workload",
                   args.workload, "--seed", str(seed), "--seconds",
                   str(args.seconds), "--trace", str(args.trace)]
            t = time.monotonic()
            p = subprocess.run(cmd, capture_output=True, text=True,
                               timeout=args.timeout)
            wall = time.monotonic() - t
            line = p.stdout.strip().splitlines()[-1] if p.stdout.strip() else ""
            rec = {"workload": args.workload, "set": k, "seed": seed,
                   "rc": p.returncode, "wall_s": wall}
            try:
                rec["result"] = json.loads(line)
            except json.JSONDecodeError:
                pass
            rec["stderr_tail"] = p.stderr[-3000:]
            with out.open("a") as f:
                f.write(json.dumps(rec) + "\n")
            res = rec.get("result", {})
            print(f"set {k} seed {seed} rc {p.returncode} wall {wall:.1f}s "
                  f"correct {res.get('correct')} checks {res.get('checks')} "
                  f"metrics { {n: m['value'] for n, m in res.get('metrics', {}).items()} }",
                  flush=True)
            if "result" not in rec:
                print(p.stderr[-3000:], flush=True)
            runs.append(res)
        sets.append(runs)
    names = sorted({n for runs in sets for r in runs
                    for n in r.get("metrics", {})})
    for name in names:
        for k, runs in enumerate(sets):
            vals = [r["metrics"][name]["value"] for r in runs
                    if name in r.get("metrics", {})]
            if len(vals) >= 2:
                print(f"{name} set {k}: median {statistics.median(vals)!r} "
                      f"spread {spread(vals)!r} n {len(vals)} values {vals}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
