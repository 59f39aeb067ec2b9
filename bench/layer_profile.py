#!/usr/bin/env python3
"""Device time per ISA layer, the session's host spans, and what recording
the spans costs, on one cell.

    python3 bench/layer_profile.py --workload vgg16-fp32.offline --seed 7 \
        --pairs 3 --out layers-fp32.offline.json

Builds and warms the cell as ``bench/run.py`` does, then traces windows of
``trace.TRACE_SECONDS`` in pairs, one with the session's spans off and one
with them on, the order turning each pair (off first in the first). Per
window it prints the images answered per second, the latency percentiles,
the idle share, the step's device ms and the session's host phases per
image; for a window with spans on also the table per ISA layer (device ms
per executor call, roofline share and its bound; ``scopes.py``), the ops
labelled with their layer and the longest idle gaps, each named by the
innermost open span, the session's included. At the end, per arm (spans
off, spans on): the windows, how many stalled (no answer for over
``STALL_MS``), and the medians of answered images/s and p95 latency over
every window and over those that did not stall. ``--out`` gets all of it
and the plain events of the last window with spans on.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
STALL_MS = 100.0    # no answer for this long: the window stalled


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--pairs", type=int, default=3)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import gc

    import jax
    import numpy as np

    from bench import harness, registry, scopes
    from bench import trace as tracing

    bench = registry.load_benchmark()
    cell = registry.workload(bench, args.workload)
    cfg = registry.load_config(cell["config"])
    mix = registry.load_traffic(cell["traffic"])
    chips = int(cell["chips"])
    harness.enable_cache(jax, ROOT)
    devices, peaks = harness.check_chip(jax, chips)
    served = harness.build(jax, cfg, mix, chips, args.seed, devices)
    if mix.get("warm_seconds"):
        harness.drive(served, mix, mix["warm_seconds"],
                      args.seed + 1).settle(60.0)
    hlo = scopes.executor_hlo(jax, served.session)
    names = scopes.hlo_scopes(hlo)
    prefetched = scopes.prefetched_scopes(hlo, names)
    program_layers = [(cl.layer_id, cl.kind)
                      for cl in served.acc.program.layers]
    dtype_bytes = np.dtype(cfg.get("build", {}).get("dtype",
                                                    "float32")).itemsize
    ids = [d.id for d in served.devices]
    session = served.session
    windows, last_on = [], None
    for i in range(2 * args.pairs):
        on = (i % 2 == 1) == ((i // 2) % 2 == 0)
        # as run.py after set-up: what came before lives on, frozen
        events = None
        gc.collect()
        gc.freeze()
        before = session.stats.snapshot()
        with harness.GcWatch() as gcw, scopes.capture(
                names, session if on else None) as events:
            log = harness.drive(served, mix, tracing.TRACE_SECONDS,
                                args.seed + 2 + i, spans=events["host"])
            win = session.stats.snapshot() - before
        log.settle(60.0)
        done = np.sort([d for d in log.done if d is not None])
        longest = float(np.max(np.diff(done))) * 1e3 if len(done) > 1 \
            else None
        base = tracing.reduce(events, ids)
        lat = log.latencies_ms()
        rec = {
            "spans": on, "window": i,
            "answered_per_s": log.answered_by(log.t_end)
            / tracing.TRACE_SECONDS,
            "latency_p50_ms": float(np.percentile(lat, 50)),
            "latency_p95_ms": float(np.percentile(lat, 95)),
            "batches": win.batches,
            "stage_us_per_image": win.stage_ns / 1e3 / max(win.submitted, 1),
            "assemble_us_per_batch": win.assemble_ns / 1e3
            / max(win.batches, 1),
            "launch_us_per_batch": win.launch_ns / 1e3 / max(win.batches, 1),
            "deliver_us_per_batch": win.deliver_ns / 1e3
            / max(win.batches, 1),
            "queue_wait_p95_ms": win.wait_p95_ms(),
            "wait_samples": win.wait_hist.count,
            "dispatched_rows": win.dispatched_rows,
            "gc_collections": gcw.n, "gc_longest_ms": gcw.longest_ms,
            "longest_gap_between_answers_ms": longest,
            "stalled": longest is None or longest > STALL_MS,
        }
        if base:
            rec.update(
                idle_share=100.0 * (1 - base["busy_s"] / base["window_s"]),
                step_device_ms=base["module_s"] * 1e3 / max(win.batches, 1),
                idle_gaps=base["idle_gaps"])
        layered = scopes.reduce_scopes(events, ids) if base else None
        if layered and win.batches:
            rows = (win.dispatched_rows + win.padded_rows) / win.batches
            costs = scopes.layer_costs(served.model, served.sizes,
                                       round(rows), dtype_bytes,
                                       program_layers)
            rec.update(
                scoped_share=layered["scoped_share"],
                device_ops=layered["device_ops"],
                layers=scopes.layer_rows(layered["scope_s"], win.batches,
                                         costs, peaks[cfg["compute"]],
                                         peaks["hbm_bytes_per_s"],
                                         prefetched))
        if on:
            last_on = events
        windows.append(rec)
        print(json.dumps(rec), flush=True)
    session.close()
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps({"workload": args.workload, "seed": args.seed,
                               "prefetched": sorted(prefetched),
                               "windows": windows, "events": last_on}))
    for on in (False, True):
        sel = [w for w in windows if w["spans"] == on]
        calm = [w for w in sel if not w["stalled"]]
        print(f"spans {'on' if on else 'off'}: {len(sel)} windows, "
              f"{len(sel) - len(calm)} stalled; answered/s "
              f"{[round(w['answered_per_s'], 1) for w in sel]}, p95 ms "
              f"{[round(w['latency_p95_ms'], 2) for w in sel]}, idle % "
              f"{[round(w.get('idle_share', -1), 2) for w in sel]}")
        for label, ws in (("all", sel), ("not stalled", calm)):
            if ws:
                print(f"  medians, {label}: answered/s "
                      f"{np.median([w['answered_per_s'] for w in ws]):.1f},"
                      f" p95 ms "
                      f"{np.median([w['latency_p95_ms'] for w in ws]):.2f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
