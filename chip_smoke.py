#!/usr/bin/env python3
"""Chip smoke test: serve full-width VGG16 end to end on a TPU v5e.

    python chip_smoke.py             # one chip: the xla and pallas fp32 phases
    python chip_smoke.py --chips 4   # four chips: the sharded session only

Drives the normal serving path — ``Accelerator.build`` (DSE -> one
``Program`` -> schedule validation) -> cached jitted executor ->
``ServingSession`` — at VGG16's published widths (224x224x3 in, 1000
classes, fp32, batch 8) with fan-in-scaled random weights made from
``--seed``, and checks every answer against the spec-chain oracle
``resnet.reference_forward`` run at ``highest`` matmul precision.

Everything runs in this one process, which holds the chip. Without a TPU
whose ``device_kind`` the v5e peaks table knows, the script exits non-zero
before it builds anything; it never falls back to the CPU, to Pallas
interpret mode or to the session's XLA degradation path. The last line of
stdout is one JSON object, ``{"ok": true, "device": {...}}``, printed only
when every phase passed; a failed phase raises and the script exits
non-zero.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

# The comparison bars. On the TPU an fp32 matmul or convolution at default
# precision runs on one bf16 MXU pass: both operands are rounded to bf16
# (unit roundoff 2^-9 ~ 2e-3), which leaves each layer's output off by
# ~3e-3 of its scale (sqrt(2) * 2^-9 for random-sign dot products), while
# the reference runs at ``highest``. Over VGG16's 16 layers those errors
# compound to 2-3% of the output scale: on a TPU v5e the sound runs read
# at most 0.036 (max) and 0.032 (rms), at batch 32. Faults planted in the
# served model at batch 8 read 0.041-0.049 rms for a dropped bias or one
# layer's weights 3% high, 0.24 for flipped filter taps and 5.2 for a
# sign error in a Winograd coefficient; a fault under ~3% rms sits inside
# the bf16 noise and cannot be told from it at this precision.
MAX_REL_TOL = 5e-2      # max|diff| / max|ref|
RMS_REL_TOL = 4e-2      # rms(diff) / rms(ref)
TOP1_MIN = 0.9          # share of requests whose top-1 class agrees
IMG, N_CLASSES, BATCH = 224, 1000, 8
N_REQUESTS = 4 * BATCH  # per steady round of the one-chip phases


def fail(msg: str):
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(1)


def log(msg: str):
    print(msg, flush=True)


class CacheEvents:
    """Counts JAX persistent-compilation-cache hits and misses (a miss is
    written back only when its compile took longer than JAX's threshold)."""

    def __init__(self, jax):
        self.hits = self.misses = 0

        def listen(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        jax.monitoring.register_event_listener(listen)


def with_biases(params, seed):
    """``params`` with seeded nonzero biases, so that a dropped or
    misplaced bias moves the logits: layer l's bias is a tenth of the rms
    its pre-activations would have on a unit-variance input (2^(-l/2):
    fan-in-scaled weights keep the second moment, ReLU halves it)."""
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.default_rng(seed)
    return [(w, jnp.asarray(0.1 * 2 ** (-l / 2)
                            * rng.standard_normal(b.shape), jnp.float32))
            for l, (w, b) in enumerate(params)]


def check_outputs(name, y, ref):
    import numpy as np
    if y.shape != ref.shape:
        raise AssertionError(f"{name}: output shape {y.shape} != {ref.shape}")
    if not np.all(np.isfinite(y)):
        raise AssertionError(f"{name}: non-finite outputs")
    d = y - ref
    r = {"max_rel": float(np.max(np.abs(d)) / np.max(np.abs(ref))),
         "rms_rel": float(np.sqrt(np.mean(d ** 2) / np.mean(ref ** 2))),
         "top1": float(np.mean(y.argmax(-1) == ref.argmax(-1)))}
    log(f"  {name}: max|diff| / max|ref| = {r['max_rel']!r} (bar "
        f"{MAX_REL_TOL}), rms(diff) / rms(ref) = {r['rms_rel']!r} (bar "
        f"{RMS_REL_TOL}), top-1 agreement {r['top1']!r} (bar {TOP1_MIN}), "
        f"max|diff| = {float(np.max(np.abs(d)))!r}")
    if not (r["max_rel"] <= MAX_REL_TOL and r["rms_rel"] <= RMS_REL_TOL
            and r["top1"] >= TOP1_MIN):
        raise AssertionError(f"{name}: outputs off the reference: {r}")


def check_ledger(name, st, n_requests):
    log(f"  {name} ledger: submitted {st.submitted}, completed "
        f"{st.requests}, errors {st.errors}, shed {st.shed}, isolated "
        f"{st.isolated}, retries {st.retries}, degraded {st.degraded}, "
        f"batches {st.batches}, padded rows {st.padded_rows}, "
        f"compile_ms {st.compile_ms!r}, device_batches "
        f"{dict(sorted(st.device_batches.items()))}")
    bad = {k: getattr(st, k) for k in ("errors", "shed", "isolated",
                                       "degraded") if getattr(st, k)}
    if st.submitted != n_requests or st.requests != n_requests or bad:
        raise AssertionError(
            f"{name}: session ledger off: submitted {st.submitted}, "
            f"completed {st.requests} of {n_requests}, nonzero {bad}")


def reference_logits(jax, specs, params, xs):
    """The spec-chain oracle at ``highest`` precision, one batch at a time."""
    import numpy as np
    from repro.models import resnet
    with jax.default_matmul_precision("highest"):
        fwd = jax.jit(lambda p, x: resnet.reference_forward(p, x, specs))
        t0 = time.monotonic()
        outs = [np.asarray(fwd(params, xs[i:i + BATCH]))
                for i in range(0, len(xs), BATCH)]
    log(f"reference (spec chain, highest precision): "
        f"{(time.monotonic() - t0) * 1e3!r} ms incl. compile")
    return np.concatenate(outs)


def serve_phase(jax, backend, specs, params, target, xs, ref, rounds):
    """Build, serve and check one fp32 backend on the first chip."""
    import numpy as np
    from repro import api
    log(f"== phase {backend} fp32 ==")
    t0 = time.monotonic()
    acc = api.Accelerator.build(specs, target=target, batch=BATCH,
                                params=params, backend=backend)
    log(f"  build (DSE + compile + validate): "
        f"{(time.monotonic() - t0) * 1e3!r} ms, "
        f"{acc.n_instructions} instructions")
    rt = acc.runtime
    n = len(xs)
    t0 = time.monotonic()
    with acc.serve(max_batch=BATCH, buckets=(BATCH,), warmup=True) as s:
        log(f"  session open + warmup compile: "
            f"{(time.monotonic() - t0) * 1e3!r} ms "
            f"(compile_ms {s.stats.compile_ms!r})")
        if backend == "pallas":
            entry, dparams = rt.executor_entry(BATCH, acc.input_dtype,
                                               donate_input=True)
            if entry.interpret is not False:
                raise AssertionError(
                    f"pallas executor resolved interpret={entry.interpret}")
            x_spec = jax.ShapeDtypeStruct((BATCH, IMG, IMG, 3),
                                          acc.input_dtype)
            hlo = entry.fn.lower(dparams, x_spec).compile().as_text()
            n_calls = hlo.count("tpu_custom_call")
            log(f"  compiled executor: interpret={entry.interpret}, "
                f"{n_calls} tpu_custom_call sites in its HLO")
            if not n_calls:
                raise AssertionError("pallas executor HLO has no "
                                     "tpu_custom_call")
        t0 = time.monotonic()
        futs = [s.submit(x) for x in xs[:BATCH]]
        first = np.stack([f.result() for f in futs])
        log(f"  first request batch (submit -> results): "
            f"{(time.monotonic() - t0) * 1e3!r} ms")
        t0 = time.monotonic()
        for _ in range(rounds):
            outs = np.stack(s.run_many(list(xs)))
        dt = time.monotonic() - t0
        log(f"  steady: {rounds * n} requests in {dt * 1e3!r} ms = "
            f"{dt * 1e3 / (rounds * n / BATCH)!r} ms/batch{BATCH} "
            f"(host clock, run_many)")
        check_outputs(f"{backend} first batch", first, ref[:BATCH])
        check_outputs(f"{backend} session", outs, ref)
        check_ledger(backend, s.stats, BATCH + rounds * n)
    if rt.cache.stats.fallbacks:
        raise AssertionError(
            f"{backend}: program cache counted {rt.cache.stats.fallbacks} "
            f"degraded-backend entries")


def sharded_phase(jax, specs, params, target, devices, seed, n_chips):
    """Full-width VGG16 over a 4-chip batch mesh vs the one-chip session."""
    import numpy as np
    from repro import api
    from repro.launch.mesh import make_fleet_mesh
    batch = BATCH * n_chips
    rng = np.random.default_rng(seed + 1)
    xs = rng.standard_normal((2 * batch, IMG, IMG, 3)).astype(np.float32)
    log(f"== phase sharded: {n_chips} chips, batch {batch} ==")
    acc = api.Accelerator.build(specs, target=target, batch=batch,
                                params=params)
    mesh = make_fleet_mesh(n_chips)
    outs = {}
    for name, m in (("one-chip", None), ("sharded", mesh)):
        t0 = time.monotonic()
        with acc.serve(max_batch=batch, buckets=(batch,), warmup=True,
                       mesh=m) as s:
            t_open = (time.monotonic() - t0) * 1e3
            t0 = time.monotonic()
            outs[name] = np.stack(s.run_many(list(xs)))
            dt = time.monotonic() - t0
            log(f"  {name}: open + warmup {t_open!r} ms; {len(xs)} requests "
                f"in {dt * 1e3!r} ms = {dt * 1e3 / (len(xs) / batch)!r} "
                f"ms/batch{batch}")
            check_ledger(name, s.stats, len(xs))
            used = set(s.stats.device_batches)
        want = ({int(d.id) for d in mesh.devices.flat} if m is not None
                else {int(devices[0].id)})
        if used != want:
            raise AssertionError(f"{name}: batches ran on devices {used}, "
                                 f"expected {want}")
    # the session's device ledger is bookkeeping: also look at where the
    # sharded executor's output shards actually live
    entry, dparams = acc.runtime.executor_entry(
        batch, acc.input_dtype, donate_input=True, mesh=mesh)
    x = jax.device_put(xs[:batch], jax.NamedSharding(
        mesh, jax.sharding.PartitionSpec(tuple(mesh.axis_names))))
    shards = {int(sh.device.id): sh.data.shape
              for sh in entry(dparams, x).addressable_shards}
    log(f"  sharded executor output shards: {shards}")
    if (set(shards) != {int(d.id) for d in mesh.devices.flat}
            or set(shards.values()) != {(BATCH, N_CLASSES)}):
        raise AssertionError(f"sharded output not split over the {n_chips} "
                             f"chips: {shards}")
    for d in devices[:n_chips]:
        stats = d.memory_stats() or {}
        log(f"  device {d.id}: peak_bytes_in_use "
            f"{stats.get('peak_bytes_in_use')!r}")
    check_outputs("sharded vs one-chip", outs["sharded"], outs["one-chip"])
    ref = reference_logits(jax, specs, params, xs)
    check_outputs("sharded vs reference", outs["sharded"], ref)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: the xla and pallas phases on the first chip; "
                         "4: only the sharded session over four chips")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rounds", type=int, default=3,
                    help=f"steady rounds of {N_REQUESTS} requests "
                         f"(one-chip phases)")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    try:
        import jax
        import numpy as np
        from repro import api
        from repro.core import perf_model as pm
        from repro.launch.compile_cache import enable_compile_cache
        from repro.models import vgg
    except ImportError as e:
        fail(f"cannot import the repro package from src/ next to this "
             f"script: {e}")

    cache_dir = enable_compile_cache()
    cache = CacheEvents(jax)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"no TPU: JAX's first device is {dev.platform!r} "
             f"({dev.device_kind!r}); this check runs only on the chip")
    try:
        target = pm.tpu_target_for(dev)
    except ValueError as e:
        fail(str(e))
    if len(devices) < args.chips:
        fail(f"--chips {args.chips} needs {args.chips} devices, JAX sees "
             f"{len(devices)}")
    log(f"device: {dev.platform} {dev.device_kind!r} x{len(devices)}; "
        f"peaks table: {target.name}; compile cache: {cache_dir}")

    specs = vgg.network_specs(img=IMG, scale=1, n_classes=N_CLASSES)
    t0 = time.monotonic()
    params = with_biases(api.random_params(specs, seed=args.seed), args.seed)
    log(f"random fan-in-scaled params with biases (seed {args.seed}): "
        f"{sum(w.size + b.size for w, b in params)} values in "
        f"{(time.monotonic() - t0) * 1e3!r} ms")

    if args.chips > 1:
        sharded_phase(jax, specs, params, target, devices, args.seed,
                      args.chips)
    else:
        rng = np.random.default_rng(args.seed + 1)
        xs = rng.standard_normal(
            (N_REQUESTS, IMG, IMG, 3)).astype(np.float32)
        ref = reference_logits(jax, specs, params, xs)
        for backend in ("xla", "pallas"):
            serve_phase(jax, backend, specs, params, target, xs, ref,
                        args.rounds)
    log(f"compile cache: {cache.hits} hits, {cache.misses} misses "
        f"in {cache_dir}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
