"""Quickstart: the HybridDNN framework API end-to-end on a reduced VGG16.

The paper's whole design flow is one call — DSE (Sec. 5) -> compile to the
128-bit ISA (Sec. 4.1) -> validate the hazard schedule once -> the cached
jitted executor:

    acc = api.Accelerator.build(specs, target=pm.V5E, batch=4)
    logits = acc(x)

Any DSE backend goes through the same ``Target`` protocol, so the paper's
FPGA devices and the TPU target are interchangeable here. The script also
exercises the save/load path (reuse a compiled Program without re-running
DSE) and the batching ``ServingSession``.

  PYTHONPATH=src python examples/quickstart.py
"""
import os
import tempfile

import jax
import numpy as np

from repro import api
from repro.core import perf_model as pm
from repro.models import vgg


def main():
    img, scale = 32, 16
    specs = vgg.network_specs(img=img, scale=scale, n_classes=10)
    x = np.random.default_rng(0).standard_normal(
        (2, img, img, 3)).astype(np.float32)

    # -- the 5-line flow: DSE -> compile -> validate -> execute -------------
    acc = api.Accelerator.build(specs, target=pm.V5E, batch=2)
    logits = acc(x)
    print(acc.summary())
    print(f"logits: {logits.shape}\n")

    # -- lowering optimizer: opt_level=1 (default) fuses each layer's
    # per-block loop into one PE dispatch; opt_level=0 is the literal
    # per-block reference lowering it is tested against. Reuse acc's plans:
    # same schedule by construction, and no redundant second DSE search.
    acc_ref = api.Accelerator.build(specs, plans=acc.plans, batch=2,
                                    params=acc.params, opt_level=0)
    err = float(np.max(np.abs(np.asarray(acc_ref(x)) - np.asarray(logits))))
    print(f"opt_level=1 (fused) vs opt_level=0 (blocked): "
          f"max |diff| = {err:.2e}")
    assert err < 1e-5

    # -- one Target protocol, three DSE backends ----------------------------
    for target in (pm.VU9P, pm.PYNQ_Z1):
        r = target.run_dse(specs)
        n_wino = sum(p.mode == "wino" for s, p in zip(specs, r.plans)
                     if isinstance(s, vgg.ConvSpec))
        print(f"{target.name}: PI={r.hw.pi} PO={r.hw.po} PT={r.hw.pt} "
              f"NI={r.hw.ni} | {n_wino}/13 CONVs Winograd "
              f"({r.candidates_searched} candidates)")
    acc_fpga = api.Accelerator.build(specs, target=pm.PYNQ_Z1, batch=2,
                                     params=acc.params)
    err = float(np.max(np.abs(np.asarray(acc_fpga(x)) - np.asarray(logits))))
    print(f"FPGA-planned vs TPU-planned logits: max |diff| = {err:.2e}\n")
    assert err < 5e-3

    # -- save the compiled Program; reload without re-running the DSE -------
    with tempfile.TemporaryDirectory() as d:
        path = acc.save_program(os.path.join(d, "vgg16_reduced.json"))
        acc2 = api.Accelerator.from_program(path, params=acc.params)
        same = np.array_equal(np.asarray(acc2(x)), np.asarray(logits))
        print(f"saved + reloaded Program ({acc2.n_instructions} "
              f"instructions): bitwise-equal logits = {same}")
        assert same

    # -- batched serving: single-image requests coalesce on the queue, and
    # the pipelined dispatch overlaps batch i+1's staging with batch i's
    # device compute -------------------------------------------------------
    with acc.serve(max_batch=4, warmup=True) as session:
        outs = session.run_many([x[i % 2] for i in range(8)])
        jax.block_until_ready(outs[-1])
        # coalesced device batches may differ in shape from the batch-2
        # reference call -> float-associativity tolerance, not bitwise
        ok = all(np.allclose(np.asarray(o), np.asarray(logits[i % 2]),
                             atol=1e-5, rtol=1e-5)
                 for i, o in enumerate(outs))
        print(f"ServingSession: {session.stats.requests} requests in "
              f"{session.stats.batches} device batches "
              f"({session.stats.padded_rows} padded rows, whole-session "
              f"latency p50 {session.stats.p50_ms():.2f}ms "
              f"p95 {session.stats.p95_ms():.2f}ms); "
              f"rows match = {ok}")
        assert ok
    print("OK")


if __name__ == "__main__":
    main()
