#!/usr/bin/env python3
"""Read the comparison's two ends for one cell on the chip, in one process:
what sound runs of the program read over a dozen seeds or more (the lower
reading) and what the configuration's lower-precision control reads (the
upper reading). The limit in ``configs/<name>.json`` is set between them.

    python3 bench/control.py --workload vgg16-fp32.offline --seconds 3 \
        --seeds 1,2,...,12 --control-seeds 3

For each seed: the cell's set-up, a window of ``--seconds`` at the cell's
own load, then the reference over the input pool and ``worst_rms_rel`` of
every answer. For the first ``--control-seeds`` seeds also the control (the
reference with every matmul operand rounded to the configuration's
``control`` precision) against the reference, over the same pool.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--out", default=None, help="JSON lines, appended")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax
    import numpy as np

    from bench import correct, harness, registry

    bench = registry.load_benchmark()
    cell = registry.workload(bench, args.workload)
    cfg = registry.load_config(cell["config"])
    mix = registry.load_traffic(cell["traffic"])
    chips = int(cell["chips"])
    harness.enable_cache(jax, ROOT)
    devices, _ = harness.check_chip(jax, chips)
    rows = []
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t = time.monotonic()
        served = harness.build(jax, cfg, mix, chips, seed, devices)
        log, delta, _ = harness.window(jax, served, mix, args.seconds, seed,
                                       False)
        served.session.close()
        params, pool, model, sizes = (served.params, served.pool,
                                      served.model, served.sizes)
        del served
        gc.collect()
        ref = correct.logits(model, params, pool, sizes)
        limit = cfg["correct"]["worst_rms_rel"]["limit"]
        counts = log.counts()
        worst = correct.worst_rms_rel(log.answers, ref)
        row = {"workload": args.workload, "seed": seed, **counts,
               "program": worst,
               "correct": correct.judge(worst, counts["answered"],
                                        counts["failed"], limit)}
        if i < args.control_seeds:
            # the control in the program's place: its answers for the inputs
            # the window served, judged as the harness judges the program's
            ctrl = correct.logits(model, params, pool, sizes,
                                  control=cfg["control"])
            served_idx = sorted({k for k, _ in log.answers})
            answers = correct.answers_of(ctrl[served_idx], served_idx)
            row["control"] = correct.worst_rms_rel(answers, ref)
            row["control_correct"] = correct.judge(
                row["control"], len(served_idx), 0, limit)
            row["control_name"] = cfg["control"]
        row["seconds"] = time.monotonic() - t
        rows.append(row)
        print(json.dumps(row), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
        del params, pool, ref
        gc.collect()
    prog = [r["program"] for r in rows]
    ctrl = [r["control"] for r in rows if "control" in r]
    print(f"{args.workload}: program worst_rms_rel over {len(prog)} seeds: "
          f"max {max(prog)!r} median {float(np.median(prog))!r}; control "
          f"{cfg['control']} over {len(ctrl)} seeds: min "
          f"{min(ctrl) if ctrl else None!r}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
