#!/usr/bin/env python3
"""Find the knee of an open-loop cell on the chip: serve its traffic at each
of the mix's ``sweep_rates`` in turn, in one process, and print the table.

    python3 bench/sweep.py --workload vgg16-fp32.server --seconds 10

The knee is picked by the mix's ``knee_rule``: the highest swept rate at
which the requests answered inside the window are at least 98% of those sent
and the p95 latency (due time to result, a failed request counting as
missing) is under ``latency_limit_ms``. The cell's ``rate_per_s`` is then
set to about four fifths of it, by hand, in the mix file.
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
KEEP_PACE = 0.98


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", default=None,
                    help="comma-separated; default: the mix's sweep_rates")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax
    import numpy as np

    from bench import harness, registry

    bench = registry.load_benchmark()
    cell = registry.workload(bench, args.workload)
    cfg = registry.load_config(cell["config"])
    mix = registry.load_traffic(cell["traffic"])
    if mix["loop"] != "open":
        raise SystemExit(f"{args.workload} is not an open-loop cell")
    rates = ([float(r) for r in args.rates.split(",")] if args.rates
             else mix["sweep_rates"])
    harness.enable_cache(jax, ROOT)
    devices, _ = harness.check_chip(jax, int(cell["chips"]))
    served = harness.build(jax, cfg, mix, int(cell["chips"]), args.seed,
                           devices)
    limit = mix["latency_limit_ms"]
    print(f"| rate req/s | sent | answered in window | p50 ms | p95 ms | "
          f"occupancy % | keeps pace, p95 < {limit} ms |")
    print("| --- | --- | --- | --- | --- | --- | --- |")
    knee = None
    for rate in rates:
        log, delta, _ = harness.window(jax, served, mix, args.seconds,
                                       args.seed, False, rate=rate)
        lat = log.latencies_ms()
        in_window = log.answered_by(log.t_end)
        p50, p95 = np.percentile(lat, 50), np.percentile(lat, 95)
        rows = delta["dispatched_rows"] + delta["padded_rows"]
        occ = 100.0 * delta["dispatched_rows"] / rows if rows else 0.0
        good = in_window >= KEEP_PACE * len(lat) and p95 < limit
        if good:
            knee = rate
        print(f"| {rate:g} | {len(lat)} | {in_window} | {p50:.2f} | "
              f"{p95:.2f} | {occ:.1f} | {'yes' if good else 'no'} |",
              flush=True)
    served.session.close()
    print(f"knee: {knee} req/s; four fifths: "
          f"{None if knee is None else 0.8 * knee}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
