#!/usr/bin/env python
"""AOT round-trip smoke: save -> FRESH process -> load -> serve.

    PYTHONPATH=src python tools/aot_smoke.py

Two child interpreters run in turn; this parent never imports JAX, so each
child in turn may hold the device. The first child builds a small model,
serves one warmed session (recording the fresh ``compile_ms`` and the
per-request outputs), and writes an AOT bundle
(``Accelerator.save_program(..., aot=True)``). The second — a genuinely cold
process, the autoscaling-event case the artifact layer exists for — loads
the bundle, serves the same requests, and reports its ``SessionStats``. The
smoke fails if the warm process compiled anything (``compile_ms`` must be
exactly 0), if any output differs BITWISE from the first child's, or if the
warm start is not faster than the fresh compile. CI's fast tier runs this on
every PR.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile

BUCKETS = (1, 2, 4)


def _requests():
    import numpy as np
    rng = np.random.default_rng(1)
    return [rng.standard_normal((16, 16, 3)).astype(np.float32)
            for _ in range(8)]


def _child(mode: str, bundle: str, out_path: str):
    """``fresh``: build, serve, save the bundle; ``warm``: load, serve."""
    import numpy as np

    from repro import api
    from repro.core import perf_model as pm
    from repro.core.hybrid_conv import ConvSpec, FCSpec, PoolSpec

    if mode == "fresh":
        specs = [ConvSpec("c1", 16, 16, 3, 8), PoolSpec("p1", 16, 16, 8),
                 FCSpec("fc", 8 * 8 * 8, 10, relu=False)]
        acc = api.Accelerator.build(specs, target=pm.V5E, batch=4, seed=0)
    else:
        # same stand-in weights the fresh build(seed=0) generated
        with open(os.path.join(bundle, "program.json")) as f:
            doc = json.load(f)
        specs = [api._spec_from_dict(d) for d in doc["specs"]]
        acc = api.Accelerator.from_program(
            bundle, params=api.random_params(specs, seed=0))
    with acc.serve(max_batch=4, buckets=BUCKETS, warmup=True) as s:
        outs = [np.asarray(y).tolist() for y in s.run_many(_requests())]
        st = s.stats
    if mode == "fresh":
        acc.save_program(bundle, aot=True, buckets=BUCKETS)
    with open(out_path, "w") as f:
        json.dump({"compile_ms": st.compile_ms,
                   "warm_load_ms": st.warm_load_ms, "outs": outs}, f)


def _run_child(mode: str, bundle: str, out_path: str) -> dict | None:
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(repo, "src"), env.get("PYTHONPATH", "")])
    r = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child", mode, bundle,
         out_path], capture_output=True, text=True, env=env, timeout=600)
    if r.returncode != 0:
        print(f"FAIL: {mode} child process died\nstdout:\n{r.stdout}\n"
              f"stderr:\n{r.stderr}", file=sys.stderr)
        return None
    with open(out_path) as f:
        return json.load(f)


def main() -> int:
    with tempfile.TemporaryDirectory() as tmp:
        bundle = os.path.join(tmp, "bundle")
        fresh = _run_child("fresh", bundle, os.path.join(tmp, "fresh.json"))
        if fresh is None:
            return 1
        warm = _run_child("warm", bundle, os.path.join(tmp, "warm.json"))
        if warm is None:
            return 1

    ok = True
    if warm["compile_ms"] != 0.0:
        print(f"FAIL: warm process compiled "
              f"({warm['compile_ms']:.1f}ms != 0)", file=sys.stderr)
        ok = False
    if not warm["warm_load_ms"] > 0.0:
        print("FAIL: warm process reported no warm-load time — the bundle "
              "was not used", file=sys.stderr)
        ok = False
    # JSON round-trips each float32 output exactly, so list equality is
    # bitwise equality
    for i, (a, b) in enumerate(zip(fresh["outs"], warm["outs"])):
        if a != b:
            print(f"FAIL: request {i} differs between fresh and warm-loaded "
                  f"executors (bitwise)", file=sys.stderr)
            ok = False
            break
    fresh_compile_ms = fresh["compile_ms"]
    ratio = warm["warm_load_ms"] / max(fresh_compile_ms, 1e-9)
    print(f"aot smoke: fresh compile {fresh_compile_ms:.0f}ms, warm load "
          f"{warm['warm_load_ms']:.0f}ms ({ratio:.2f}x), outputs bitwise "
          f"{'OK' if ok else 'MISMATCH'}")
    if ratio >= 1.0:
        print("FAIL: warm load is not faster than the fresh compile",
              file=sys.stderr)
        ok = False
    print(f"aot smoke: {'OK' if ok else 'FAIL'}")
    return 0 if ok else 1


if __name__ == "__main__":
    if len(sys.argv) == 5 and sys.argv[1] == "--child":
        _child(*sys.argv[2:])
        sys.exit(0)
    sys.exit(main())
