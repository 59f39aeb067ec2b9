#!/usr/bin/env python3
"""Record the small device trace that the reduction's tests read.

    python3 bench/tests/record_trace.py   # on a TPU; writes tests/data/

Serves a tiny VGG16 (channels / 16, 32x32 inputs) through the session for
a fraction of a second under ``trace.capture``, as a traced window of the
harness does, and writes the plain form cut to the window (with 2 ms on
each side) to ``tests/data/trace_v5e.json``.
"""
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main() -> int:
    import jax

    from bench import harness, registry
    from bench import trace as tracing

    devices, _ = harness.check_chip(jax, 1)
    base = [HERE, registry.BENCH_DIR]
    cfg = registry.load_config("tiny", base)
    mix = registry.load_traffic("offline-small", base)
    served = harness.build(jax, cfg, mix, 1, 5, devices, base)
    harness.drive(served, mix, 0.3, 5).settle()
    with tracing.capture() as events:
        log = harness.drive(served, mix, 0.05, 5, spans=events["host"])
    log.settle()
    served.session.close()
    w = [e for e in events["host"] if e[0] == tracing.WINDOW_SPAN][0]
    lo, hi = w[1] - 2e6, w[1] + w[2] + 2e6
    keep = lambda evs: [e for e in evs if lo <= e[1] <= hi]  # noqa: E731
    small = {"devices": {k: {kk: keep(vv) for kk, vv in v.items()}
                         for k, v in events["devices"].items()},
             "host": keep(events["host"])}
    out = HERE / "data" / "trace_v5e.json"
    out.write_text(json.dumps(small))
    print(f"wrote {out}: {len(small['host'])} host spans, "
          f"{ {k: len(v['ops']) for k, v in small['devices'].items()} } ops; "
          f"reduced: {tracing.reduce(small, [devices[0].id])}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
