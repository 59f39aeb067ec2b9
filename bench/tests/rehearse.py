"""Drive the whole harness without a chip, for the tests: a temporary
``BENCHMARK.json`` names cells of the tiny configurations under
``tests/configs`` (never cells of the real benchmark), the look for a TPU is
replaced, a fault can be planted in the served path underneath, and a device
plane the CPU's profile lacks can be laid into the trace.

    python -m bench.tests.rehearse <cell> <chips> [fault]

prints the result line; the four-chip rehearsal runs it in a process of its
own, started with four virtual CPU devices.
"""
from __future__ import annotations

import json
import sys
import tempfile
import time
from pathlib import Path

from bench import harness, registry

HERE = Path(__file__).resolve().parent
BASE = [HERE, registry.BENCH_DIR]
# tiny cells: name -> (configuration, traffic)
CELLS = {
    "tiny.offline": ("tiny", "offline-small"),
    "tiny.server": ("tiny", "server-slow"),
    "tiny-int8.offline": ("tiny-int8", "offline-small"),
    # mixes of data alone that the loops already serve
    "tiny.single-stream": ("tiny", "single-stream"),
    "tiny.server-bursty": ("tiny", "server-bursty"),
    "tiny.overload": ("tiny", "overload"),
    # a second network, its configuration and reference test files alone
    "tiny-resnet18.offline": ("tiny-resnet18", "offline-small"),
}
# arithmetic only, never reported
FAKE_PEAKS = {"bf16": 1e12, "int8": 2e12, "hbm_bytes_per_s": 1e11}
CALLS = 10      # executor calls in a laid device plane


def cpu_check(jax, chips):
    return jax.devices()[:chips], FAKE_PEAKS


def write_root(tmp: Path, chips: int) -> Path:
    """A checkout-like root whose ``BENCHMARK.json`` holds the tiny cells
    and the real benchmark's metrics, each given to the tiny cells of the
    same loop type."""
    real = registry.load_benchmark()
    loop_of = {c["name"]: registry.load_traffic(c["traffic"])["loop"]
               for c in real["workloads"]}
    cells = [{"name": n, "config": c, "traffic": t, "chips": chips,
              "why": "rehearsal"} for n, (c, t) in CELLS.items()]
    tiny_loop = {c["name"]: registry.load_traffic(c["traffic"], BASE)["loop"]
                 for c in cells}
    bench = dict(real, workloads=cells)
    for group in ("end_to_end", "per_layer"):
        bench[group] = []
        for m in real[group]:
            m = dict(m)
            if "workloads" in m:
                loops = {loop_of[w] for w in m["workloads"]}
                m["workloads"] = [n for n, lp in tiny_loop.items()
                                  if lp in loops]
            bench[group].append(m)
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


def with_device_plane(attach):
    """``scopes.attach_scopes`` that first lays, where the trace has no
    device, one in the window as a TPU's would read: ``CALLS`` executor
    calls, each a module in the first half of its share of the window
    holding one op per scoped instruction of ``names``, in layer order. It
    stands in for the device events only, so the scoped reduction, the
    reference's ``layer_costs`` and the metrics run as on a chip."""
    from bench import scopes
    from bench import trace as tracing

    def attach_with_plane(events, names):
        window = [h for h in events["host"] if h[0] == tracing.WINDOW_SPAN]
        if names and window and not events["devices"]:
            _, w0, wdur = window[0]
            order = sorted(names, key=lambda n: scopes._layer_id(names[n]))
            share = wdur // CALLS
            op = share // 2 // len(order)
            ops, modules = [], []
            for k in range(CALLS):
                t = w0 + k * share
                modules.append(["jit_traced", t, op * len(order)])
                ops += [[n, t + i * op, op] for i, n in enumerate(order)]
            events["devices"]["0"] = {"ops": ops, "modules": modules}
        attach(events, names)
    return attach_with_plane


def plant(fault: str | None):
    """Break the served path underneath the harness."""
    if fault is None:
        return
    import numpy as np

    from repro import api
    S = api.ServingSession
    if fault == "answer":            # one answer altered where it is made
        to_host = S._to_host

        def altered(self, y):
            y = np.array(to_host(self, y))
            y[0] = y[0] * 1.5 + 0.1
            return y
        S._to_host = altered
    elif fault == "half_batch":      # half of each batch left out
        run = S._run_bucket

        def half(self, x):
            x = np.array(x)
            x[x.shape[0] // 2:] = 0
            return run(self, x)
        S._run_bucket = half
    elif fault == "exchange":        # shards never gathered: device 0's only
        to_host = S._to_host

        def first_shard(self, y):
            shards = getattr(y, "addressable_shards", [])
            if len(shards) > 1:
                part = np.asarray(shards[0].data)
                y = np.concatenate([part] * len(shards))
            return to_host(self, y)
        S._to_host = first_shard
    elif fault == "control":         # the reference, at the control's
        build = harness.build        # precision, in the program's place
        harness.build = lambda jax, cfg, *a, **k: _control_in_place(
            build(jax, cfg, *a, **k), cfg["control"])
    else:
        raise ValueError(f"unknown fault {fault!r}")


def _control_in_place(served, control: str):
    """Answer the session's requests with the configuration's control: the
    plain reference with every matmul operand rounded to the precision
    below the configuration's, on the served weights."""
    from concurrent.futures import Future

    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import correct
    act, weight = correct.CONTROLS[control]
    fwd = jax.jit(lambda p, x: served.model.forward(
        p, x, served.sizes, act=act, weight=weight))

    def submit_many(xs, **_):
        futs = []
        for row in np.asarray(fwd(served.params, jnp.asarray(np.stack(xs)))):
            futs.append(Future())
            futs[-1].set_result(row)
        return futs
    served.session.submit = lambda x, **_: submit_many([x])[0]
    served.session.submit_many = submit_many
    return served


def run(cell: str, chips: int = 1, *, seed: int = 2 ** 33 + 5,
        seconds: float = 1.0, trace: bool = False,
        fault: str | None = None) -> dict:
    t0 = time.monotonic()
    plant(fault)
    with tempfile.TemporaryDirectory() as tmp:
        root = write_root(Path(tmp), chips)
        return harness.run_cell(cell, seed, seconds, trace, t_process=t0,
                                root=root, base=BASE, device_check=cpu_check)


if __name__ == "__main__":
    fault = sys.argv[3] if len(sys.argv) > 3 else None
    out = run(sys.argv[1], int(sys.argv[2]), fault=fault)
    print(json.dumps(out))
