#!/usr/bin/env python3
"""Record the small scoped device trace that ``test_scopes.py`` reads.

    python3 bench/tests/record_scoped_trace.py   # on a TPU; writes tests/data/

As ``record_trace.py``, but through ``scopes.capture``: the session's own
spans (``session.*``) join the benchmark's. Serves a tiny VGG16 (channels /
16, 32x32 inputs) for a fraction of a second and writes the plain form cut
to the window (with 2 ms on each side), with the executor calls counted in
it and the compiled text of the executor (``hlo``, from which
``scopes.hlo_scopes`` gives each op its layer scope), to
``tests/data/trace_v5e_scoped.json``.
"""
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main() -> int:
    import jax

    from bench import harness, registry, scopes

    # as run.py: the compile cache on, so that executor_hlo reads the
    # very executable the session runs
    harness.enable_cache(jax, ROOT)
    devices, _ = harness.check_chip(jax, 1)
    base = [HERE, registry.BENCH_DIR]
    cfg = registry.load_config("tiny", base)
    mix = registry.load_traffic("offline-small", base)
    served = harness.build(jax, cfg, mix, 1, 5, devices, base)
    harness.drive(served, mix, 0.3, 5).settle()
    before = served.session.stats.snapshot()
    hlo = scopes.executor_hlo(jax, served.session)
    names = scopes.hlo_scopes(hlo)
    with scopes.capture(names, served.session) as events:
        log = harness.drive(served, mix, 0.05, 5, spans=events["host"])
        win = served.session.stats.snapshot() - before
    log.settle()
    served.session.close()
    w = [e for e in events["host"] if e[0] == "bench.window"][0]
    lo, hi = w[1] - 2e6, w[1] + w[2] + 2e6

    def keep(dev):
        return {"ops": [e for e in dev["ops"] if lo <= e[1] <= hi],
                "modules": [e for e in dev["modules"] if lo <= e[1] <= hi]}
    small = {"devices": {k: keep(v) for k, v in events["devices"].items()},
             "host": [e for e in events["host"] if lo <= e[1] <= hi],
             "calls": win.batches, "rows": win.dispatched_rows, "hlo": hlo}
    out = HERE / "data" / "trace_v5e_scoped.json"
    out.write_text(json.dumps(small))
    scopes.attach_scopes(small, names)
    print(f"wrote {out}: {len(small['host'])} host spans, "
          f"{ {k: len(v['ops']) for k, v in small['devices'].items()} } ops, "
          f"{win.batches} calls; scoped: "
          f"{scopes.reduce_scopes(small, [devices[0].id])}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
