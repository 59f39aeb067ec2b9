"""Table 4 reproduction: end-to-end VGG16 throughput (GOPS).

* paper-faithful: the FPGA DSE re-derives the paper's configurations and the
  Eq. 6-15 latency model reproduces the published GOPS (VU9P 3375.7 /
  PYNQ-Z1 83.3).
* hybrid-vs-spatial-only: the paper's headline 1.8x-class gain, measured by
  forcing all-Spatial plans through the same model.
* TPU analog: the hardware-adapted model's GOPS for the v5e target.
* runtime rows: interpreter vs cached-jitted executor, the full-network
  single-Program path vs the legacy segmented path, the lowering optimizer
  (``opt_level=1`` fused whole-layer dispatches) vs the literal per-block
  lowering, the batching pipelined ``ServingSession`` queue vs direct
  ``rt.run`` loops, the sharded-fleet serving row (shard_map'd executors
  over forced host devices + continuous-vs-bucketed scheduling), the
  Pallas PE backend vs the XLA lowering, and the quantized int8 accelerator
  vs fp32 (throughput ratio + top-1 agreement on reduced VGG16 and
  ResNet-18) — the runtime + serving rows are written to a
  ``BENCH_table4_vgg16.json`` artifact for CI; ``tools/bench_compare.py``
  schema-checks it and diffs against the committed file as a regression
  tripwire.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time

from repro.core import perf_model as pm
from repro.core.dse import DSEResult, run_fpga_dse, run_tpu_dse
from repro.launch.compile_cache import without_compile_cache
from repro.models.vgg import conv_specs, conv_segments, network_specs

PAPER_GOPS = {"VU9P": 3375.7, "PYNQ-Z1": 83.3}


def _gops(specs, total_latency):
    return sum(2 * s.macs for s in specs) / 1e9 / total_latency


def _spatial_only_latency(target, specs, hw) -> float:
    t_inst = dataclasses.replace(target, bw=target.bw / hw.ni)
    total = 0.0
    for spec in specs:
        best = min(
            pm.fpga_layer_latency(t_inst, spec, hw.pi, hw.po, hw.pt, hw.m,
                                  "spat", df)
            for df in ("is", "ws"))
        total += best / hw.ni
    return total


def run() -> list[dict]:
    specs = conv_specs()
    rows = []
    for target, name in ((pm.VU9P, "VU9P"), (pm.PYNQ_Z1, "PYNQ-Z1")):
        r: DSEResult = run_fpga_dse(target, specs)
        gops = _gops(specs, r.total_latency)
        err = abs(gops - PAPER_GOPS[name]) / PAPER_GOPS[name] * 100
        rows.append({
            "bench": "table4_vgg16", "name": f"{name}/hybrid",
            "config": f"PI{r.hw.pi}_PO{r.hw.po}_PT{r.hw.pt}_NI{r.hw.ni}",
            "gops": round(gops, 1), "paper": PAPER_GOPS[name],
            "err_pct": round(err, 2),
            "wino_layers": sum(p.mode == "wino" for p in r.plans),
        })
        spat_lat = _spatial_only_latency(target, specs, r.hw)
        gops_spat = _gops(specs, spat_lat)
        rows.append({
            "bench": "table4_vgg16", "name": f"{name}/spatial_only",
            "gops": round(gops_spat, 1),
            "hybrid_speedup": round(gops / gops_spat, 2),
        })
    rt = run_tpu_dse(specs, batch=8)
    rows.append({
        "bench": "table4_vgg16", "name": "v5e/tpu_dse",
        "config": f"bm{rt.hw.bm}_bk{rt.hw.bk}_bn{rt.hw.bn}_m{rt.hw.m}",
        "gops": round(8 * _gops(specs, rt.total_latency), 1),
        "wino_layers": sum(p.mode == "wino" for p in rt.plans),
    })
    runtime_rows = run_runtime_comparison()
    runtime_rows += run_single_vs_segmented()
    runtime_rows += run_fused_vs_blocked()
    runtime_rows += run_serving_queue()
    runtime_rows += run_fleet_sharded()
    runtime_rows += run_pallas_vs_xla()
    runtime_rows += run_resnet18_single_program()
    runtime_rows += run_int8_vs_fp32()
    runtime_rows += run_aot_cold_start()
    runtime_rows += run_fault_injection()
    _write_artifact(runtime_rows)
    return rows + runtime_rows


def _write_artifact(rows: list[dict],
                    artifact: str = "BENCH_table4_vgg16.json"):
    with open(artifact, "w") as f:
        json.dump(rows, f, indent=2)
    print(f"wrote {os.path.abspath(artifact)}")


def run_runtime_comparison(*, img: int = 32, scale: int = 16, batch: int = 2,
                           iters: int = 10) -> list[dict]:
    """Interpreter vs cached-jitted-executor wall clock on the reduced VGG16
    stack — the validate-once/trace-many payoff measured end-to-end.

    Plans alternate Winograd/Spatial so the comparison exercises both CONV
    modes, the U-space weight path, and the WINO<->SPAT layout reorders.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.compiler import LayerPlan, compile_network
    from repro.core.hybrid_conv import max_pool2d
    from repro.core.runtime import HybridRuntime

    specs = conv_specs(img=img, scale=scale)
    plans = [LayerPlan("wino" if i % 2 == 0 else "spat", "is" if i % 2 else "ws",
                       m=2, g_k=2, g_h=2) for i, _ in enumerate(specs)]
    rng = np.random.default_rng(0)
    params = [(jnp.asarray(rng.standard_normal((s.r, s.s, s.c, s.k)),
                           jnp.float32) * (s.r * s.s * s.c) ** -0.5,
               jnp.zeros((s.k,), jnp.float32)) for s in specs]
    x = jnp.asarray(rng.standard_normal((batch, img, img, specs[0].c)),
                    jnp.float32)

    jit_rts, strict_rts, idx = [], [], 0
    for n in conv_segments():
        program = compile_network(specs[idx:idx + n], plans[idx:idx + n])
        for strict, dst in ((False, jit_rts), (True, strict_rts)):
            r = HybridRuntime(program, strict=strict)
            r.load_params(params[idx:idx + n])
            dst.append(r)
        idx += n

    def request(rts, x):
        for r in rts:
            x = max_pool2d(r.run(x))
        return x

    # warm BOTH paths before timing so neither side pays first-use XLA op
    # compilation inside the measured region
    y_jit = jax.block_until_ready(request(jit_rts, x))   # validate + compile
    jax.block_until_ready(request(strict_rts, x))
    t0 = time.monotonic()
    for _ in range(iters):
        y_jit = jax.block_until_ready(request(jit_rts, x))
    t_jit = (time.monotonic() - t0) / iters

    t0 = time.monotonic()
    y_int = jax.block_until_ready(request(strict_rts, x))
    t_int = time.monotonic() - t0
    err = float(jnp.max(jnp.abs(y_jit - y_int)))

    return [{
        "bench": "table4_vgg16", "name": "runtime/jit_vs_interpreter",
        "config": f"img{img}_scale{scale}_batch{batch}",
        "interp_ms": round(t_int * 1e3, 1),
        "jit_ms": round(t_jit * 1e3, 2),
        "speedup": round(t_int / t_jit, 1),
        "max_abs_diff": err,
    }]


def _alternating_plans(specs):
    """Fixed wino/spat-alternating CONV plans — pins the schedule so the
    runtime rows measure execution, not DSE variance."""
    from repro.core.compiler import LayerPlan
    from repro.core.hybrid_conv import ConvSpec

    ci, plans = 0, []
    for s in specs:
        if isinstance(s, ConvSpec):
            plans.append(LayerPlan("wino" if ci % 2 == 0 else "spat",
                                   "is" if ci % 2 else "ws", m=2,
                                   g_k=2, g_h=2))
            ci += 1
        else:
            plans.append(None)
    return plans


def run_single_vs_segmented(*, img: int = 32, scale: int = 16, batch: int = 2,
                            iters: int = 10) -> list[dict]:
    """Full-network ISA payoff: the whole reduced VGG16 (13 CONV + 5 POOL +
    3 FC) as ONE Program vs the legacy per-segment Programs with host-side
    maxpool/FC glue — end-to-end wall clock on the cached jitted executors.

    ``run()`` writes this row (plus the serving row) to
    ``BENCH_table4_vgg16.json`` so CI can archive it as a run artifact.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import api

    specs = network_specs(img=img, scale=scale, n_classes=10)
    plans = _alternating_plans(specs)
    acc = api.Accelerator.build(specs, plans=plans, seed=0, batch=batch)
    acc_seg = api.Accelerator.build(specs, plans=plans, params=acc.params,
                                    batch=batch, segmented=True)
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (batch, img, img, 3)), jnp.float32)

    y_single = jax.block_until_ready(acc(x))        # validate + jit both
    y_seg = jax.block_until_ready(acc_seg(x))
    t0 = time.monotonic()
    for _ in range(iters):
        y_single = jax.block_until_ready(acc(x))
    t_single = (time.monotonic() - t0) / iters
    t0 = time.monotonic()
    for _ in range(iters):
        y_seg = jax.block_until_ready(acc_seg(x))
    t_seg = (time.monotonic() - t0) / iters

    return [{
        "bench": "table4_vgg16", "name": "runtime/single_vs_segmented",
        "config": f"img{img}_scale{scale}_batch{batch}",
        "n_instructions": acc.n_instructions,
        "single_program_ms": round(t_single * 1e3, 2),
        "segmented_ms": round(t_seg * 1e3, 2),
        "speedup": round(t_seg / t_single, 2),
        "max_abs_diff": float(jnp.max(jnp.abs(y_single - y_seg))),
    }]


def _jaxpr_ops(jaxpr) -> int:
    """Primitive-equation count, recursing into nested (pjit/scan) bodies —
    the graph-size metric the lowering optimizer is judged on."""
    n = 0
    for eq in jaxpr.eqns:
        n += 1
        for v in eq.params.values():
            for vv in (v if isinstance(v, (list, tuple)) else (v,)):
                if hasattr(vv, "jaxpr"):
                    n += _jaxpr_ops(vv.jaxpr)
    return n


@without_compile_cache()
def run_fused_vs_blocked(*, img: int = 32, scale: int = 16, batch: int = 2,
                         iters: int = 20) -> list[dict]:
    """Lowering-optimizer payoff on the full reduced VGG16 (13 CONV +
    5 POOL + 3 FC, ONE Program): ``opt_level=1`` (whole-layer fused
    dispatches) vs ``opt_level=0`` (the literal per-block lowering) —
    steady-state wall clock, trace+compile time, and traced-graph op count
    (``jax.make_jaxpr`` equation count), plus max |diff| between the two.

    Plans alternate Winograd/Spatial with g_h=2/g_k=2 so every layer has a
    real block structure to fuse (4 COMP blocks per CONV layer).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import api
    from repro.core.compiler import compile_network
    from repro.core.executor import (
        compile_executor,
        lower_program,
        to_dram_params,
        validate_schedule,
    )

    specs = network_specs(img=img, scale=scale, n_classes=10)
    plans = _alternating_plans(specs)
    program = compile_network(specs, plans)
    stats = validate_schedule(program)
    params = api.random_params(specs, seed=0)
    dram = to_dram_params(program, params)
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (batch, img, img, 3)), jnp.float32)

    out: dict = {"bench": "table4_vgg16", "name": "runtime/fused_vs_blocked",
                 "config": f"img{img}_scale{scale}_batch{batch}"}
    execs, ys = {}, {}
    for lvl, tag in ((1, "fused"), (0, "blocked")):
        ex = compile_executor(program, stats=stats, opt_level=lvl)
        t0 = time.monotonic()                 # first call: trace + compile
        ys[tag] = jax.block_until_ready(ex(dram, x))
        out[f"{tag}_trace_compile_ms"] = round(
            (time.monotonic() - t0) * 1e3, 1)
        out[f"{tag}_jaxpr_ops"] = _jaxpr_ops(jax.make_jaxpr(
            lower_program(program, opt_level=lvl))(dram, x).jaxpr)
        execs[tag] = ex
    # interleaved best-of-rounds: a single long loop per level charges
    # whichever runs first for machine warm-up — alternating short rounds
    # and keeping each level's best is robust to drift either way
    wall = {"fused": float("inf"), "blocked": float("inf")}
    for _ in range(3):
        for tag, ex in execs.items():
            t0 = time.monotonic()
            for _ in range(iters):
                jax.block_until_ready(ex(dram, x))
            wall[tag] = min(wall[tag], (time.monotonic() - t0) / iters)
    out["fused_ms"] = round(wall["fused"] * 1e3, 2)
    out["blocked_ms"] = round(wall["blocked"] * 1e3, 2)
    out["speedup"] = round(wall["blocked"] / wall["fused"], 2)
    out["jaxpr_op_reduction"] = round(
        out["blocked_jaxpr_ops"] / out["fused_jaxpr_ops"], 2)
    out["max_abs_diff"] = float(jnp.max(jnp.abs(ys["fused"]
                                                - ys["blocked"])))
    return [out]


def run_pallas_vs_xla(*, img: int = 32, scale: int = 16, batch: int = 2,
                      iters: int = 5) -> list[dict]:
    """PE-backend comparison on the cached jitted executor: the same reduced
    VGG16 Program lowered through the XLA ops vs the Pallas PE kernels
    (``Accelerator.build(..., backend="pallas")``), with max |diff|.

    On CPU/CI the Pallas path runs in interpret mode, so ``pallas_ms`` there
    measures the fallback, not kernel performance — the row's job off-TPU is
    the numerical-parity evidence and keeping the path exercised; on real
    TPU it becomes the kernel-vs-XLA speed row. ``backend_mode`` records
    which of the two was measured.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import api

    specs = network_specs(img=img, scale=scale, n_classes=10)
    plans = _alternating_plans(specs)
    acc_xla = api.Accelerator.build(specs, plans=plans, seed=0, batch=batch)
    acc_pal = api.Accelerator.build(specs, plans=plans, params=acc_xla.params,
                                    batch=batch, backend="pallas")
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (batch, img, img, 3)), jnp.float32)

    y_xla = jax.block_until_ready(acc_xla(x))       # trace + compile both
    y_pal = jax.block_until_ready(acc_pal(x))
    t0 = time.monotonic()
    for _ in range(iters):
        y_xla = jax.block_until_ready(acc_xla(x))
    t_xla = (time.monotonic() - t0) / iters
    t0 = time.monotonic()
    for _ in range(iters):
        y_pal = jax.block_until_ready(acc_pal(x))
    t_pal = (time.monotonic() - t0) / iters

    on_tpu = jax.default_backend() == "tpu"
    return [{
        "bench": "table4_vgg16", "name": "runtime/pallas_vs_xla",
        "config": f"img{img}_scale{scale}_batch{batch}",
        "backend_mode": "tpu" if on_tpu else "cpu_interpret",
        "xla_ms": round(t_xla * 1e3, 2),
        "pallas_ms": round(t_pal * 1e3, 2),
        "pallas_over_xla": round(t_pal / t_xla, 2),
        "max_abs_diff": float(jnp.max(jnp.abs(y_xla - y_pal))),
    }]


def run_resnet18_single_program(*, img: int = 64, scale: int = 8,
                                batch: int = 2, iters: int = 10
                                ) -> list[dict]:
    """Residual-workload row: the reduced ResNet-18 (20 CONV + 8 ELTWISE_ADD
    + 1 POOL + 1 FC, skip tensors held live across each block by the DRAM
    planner) as ONE Program on the cached jitted executor — steady-state
    wall clock and GOPS, with the strict per-instruction interpreter and the
    spec-chain reference oracle as the numerical cross-checks.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.runtime import HybridRuntime
    from repro.models import resnet

    specs = resnet.resnet18_specs(img, scale, n_classes=10)
    t0 = time.monotonic()
    acc = resnet.accelerator(img=img, scale=scale, n_classes=10, batch=batch)
    t_build = time.monotonic() - t0
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (batch, img, img, 3)), jnp.float32)

    y = jax.block_until_ready(acc(x))
    t0 = time.monotonic()
    for _ in range(iters):
        y = jax.block_until_ready(acc(x))
    t_exec = (time.monotonic() - t0) / iters

    strict = HybridRuntime(acc.program, strict=True)
    strict.load_params(acc.params)
    y_strict = strict.run(x)
    y_ref = resnet.reference_forward(acc.params, x, specs)
    macs = sum(s.macs for s in specs)
    return [{
        "bench": "table4_vgg16", "name": "runtime/resnet18_single_program",
        "config": f"img{img}_scale{scale}_batch{batch}",
        "n_instructions": acc.n_instructions,
        "n_eltwise": sum(strict.stats[k] for k in ("eltwise",)),
        "build_ms": round(t_build * 1e3, 1),
        "exec_ms": round(t_exec * 1e3, 2),
        "gops": round(2 * macs * batch / 1e9 / t_exec, 1),
        "strict_bitwise": bool(jnp.array_equal(y, y_strict)),
        "max_abs_diff_ref": float(jnp.max(jnp.abs(y - y_ref))),
    }]


def run_int8_vs_fp32(*, img: int = 32, scale: int = 16, batch: int = 2,
                     n_eval: int = 256, n_calib: int = 256,
                     iters: int = 10) -> list[dict]:
    """Quantized-inference row: the int8 accelerator (calibrated sidecar,
    int8 PEs with the fused requantize+ReLU epilogue, int8-aware DSE) vs
    the fp32 build of the same reduced VGG16 — steady-state wall clock,
    plus top-1 agreement on ``n_eval`` images for BOTH reduced VGG16 and
    reduced ResNet-18, the executor-vs-strict-interpreter bitwise check on
    the int8 path, and the dequantized-logit error vs fp32.

    The agreement models are ``scale=4`` VGG16 and ``scale=8`` ResNet-18
    (minmax observer, ``n_calib`` calibration images): per-tensor int8
    activation grids need enough channels for rounding noise to
    self-average, and at ``scale=16`` the narrowest VGG layers are FOUR
    channels wide — a breakdown regime no calibration fixes (measured
    ~0.90 agreement there vs >=0.98 at scale=4). The timing pair stays at
    the table's ``scale=16`` config so the wall-clock row is comparable
    with the rest of the bench.

    ``backend_mode`` records where the ratio was measured: on a CPU host
    XLA *emulates* int8 MACs in wider arithmetic, so ``int8_speedup``
    there measures emulation cost, not the packed-MAC win — the regression
    guard only gates the ratio on hardware with real int8 paths, exactly
    like ``pallas_vs_xla``'s interpret-mode caveat. The parity metric is
    named ``dequant_max_abs_err`` (NOT ``max_abs_diff``): ~1e-1 logit
    error is the quantization design point, not a numerical regression.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import api
    from repro.models import resnet

    rng = np.random.default_rng(0)
    x_np = rng.standard_normal((batch, img, img, 3)).astype(np.float32)
    specs = network_specs(img=img, scale=scale, n_classes=10)
    acc32 = api.Accelerator.build(specs, target=pm.V5E, seed=0, batch=batch)
    acc8 = api.Accelerator.build(specs, target=pm.V5E, seed=0, batch=batch,
                                 params=acc32.params, dtype="int8",
                                 calib=x_np)
    x = jnp.asarray(x_np)
    y32 = jax.block_until_ready(acc32(x))      # trace + compile both
    y8 = jax.block_until_ready(acc8(x))

    # interleaved best-of-rounds (same rationale as run_fused_vs_blocked)
    wall = {"fp32": float("inf"), "int8": float("inf")}
    for _ in range(3):
        for tag, acc in (("fp32", acc32), ("int8", acc8)):
            t0 = time.monotonic()
            for _ in range(iters):
                jax.block_until_ready(acc(x))
            wall[tag] = min(wall[tag], (time.monotonic() - t0) / iters)

    # int8 executor must match the strict int8 interpreter BITWISE —
    # integer accumulation is exact, so any lowering rewrite that broke
    # the requantize ordering would show up here as a hard False
    y8_raw = acc8._request(x)
    y8_strict = acc8.strict_request()(x)
    bitwise = bool(jnp.array_equal(y8_raw, y8_strict))

    # top-1 agreement: fp32 vs int8 argmax over the eval set, one pair of
    # builds per model at the agreement configs documented above
    calib = rng.standard_normal((n_calib, img, img, 3)).astype(np.float32)
    xe = jnp.asarray(rng.standard_normal(
        (n_eval, img, img, 3)), jnp.float32)

    def _agreement(aspecs) -> tuple[float, bool]:
        a32 = api.Accelerator.build(aspecs, target=pm.V5E, seed=0,
                                    batch=batch)
        a8 = api.Accelerator.build(aspecs, target=pm.V5E, seed=0,
                                   batch=batch, params=a32.params,
                                   dtype="int8", calib=calib,
                                   observer="minmax")
        agree = float(jnp.mean(
            jnp.argmax(a8(xe), -1) == jnp.argmax(a32(xe), -1)))
        bit = bool(jnp.array_equal(a8._request(a8.quant.quantize_input(xe)),
                                   a8.strict_request()(xe)))
        return agree, bit

    agree_vgg, v_bitwise = _agreement(
        network_specs(img=img, scale=4, n_classes=10))
    agree_resnet, r_bitwise = _agreement(
        resnet.resnet18_specs(img=img, scale=8, n_classes=10))

    on_tpu = jax.default_backend() == "tpu"
    return [{
        "bench": "table4_vgg16", "name": "runtime/int8_vs_fp32",
        "config": (f"img{img}_scale{scale}_batch{batch}"
                   f"_eval{n_eval}_calib{n_calib}"),
        "backend_mode": "tpu" if on_tpu else "cpu",
        "fp32_ms": round(wall["fp32"] * 1e3, 2),
        "int8_ms": round(wall["int8"] * 1e3, 2),
        "int8_speedup": round(wall["fp32"] / wall["int8"], 2),
        "top1_agreement_vgg16": agree_vgg,
        "top1_agreement_resnet18": agree_resnet,
        "executor_interp_bitwise": bitwise and v_bitwise and r_bitwise,
        "dequant_max_abs_err": float(jnp.max(jnp.abs(y8 - y32))),
    }]


@without_compile_cache()
def run_serving_queue(*, img: int = 32, scale: int = 16, batch: int = 8,
                      n_requests: int = 128) -> list[dict]:
    """ServingSession throughput: single-image requests coalesced by the
    padding-bucketed batching queue vs direct ``rt.run`` loops.

    ``direct_b{batch}_rps`` is the best case the session must sustain (the
    caller already batched perfectly); ``direct_b1_rps`` is what unbatched
    serving actually gets per request — the gap between the two is the
    batching payoff the queue recovers for independent single-image
    requests. With the pipelined dispatch (batch i+1 staged while batch i
    executes) the session is expected to *beat* the direct pre-batched
    loop (``session_vs_direct_batched`` >= 1.0), since the direct loop
    host-syncs between batches. The row also records the session's
    trace+compile time and steady-state p50/p95 request latency.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import api

    specs = network_specs(img=img, scale=scale, n_classes=10)
    plans = _alternating_plans(specs)
    acc = api.Accelerator.build(specs, plans=plans, seed=0, batch=batch)
    rng = np.random.default_rng(0)
    xb = jnp.asarray(rng.standard_normal((batch, img, img, 3)), jnp.float32)
    x1 = xb[:1]

    yb = jax.block_until_ready(acc(xb))             # warm both batch shapes
    jax.block_until_ready(acc(x1))
    iters = max(1, n_requests // batch)

    # materialize the request list up front — clients arrive with their own
    # host arrays; slicing xb per request inside the timed region would
    # charge the session for 64 jax dispatch calls the direct loop never pays
    reqs = [np.asarray(xb[i % batch]) for i in range(n_requests)]
    yb_np = np.asarray(yb)
    # interleaved best-of-rounds: direct loop and session alternate inside
    # each round so shared-machine load hits both sides alike — a single
    # long measurement per side charges whichever ran during a noisy
    # stretch for the whole comparison
    direct_bN_rps = direct_b1_rps = session_rps = 0.0
    p50 = p95 = 0.0
    with acc.serve(max_batch=batch, buckets=(batch,), warmup=True) as s:
        compile_ms = s.stats.compile_ms
        s.run_many(reqs[:batch * 2])        # warm the dispatch/drain threads
        warm_batches = s.stats.batches
        for _ in range(3):
            t0 = time.monotonic()
            for _ in range(iters):
                jax.block_until_ready(acc(xb))
            direct_bN_rps = max(direct_bN_rps,
                                batch * iters / (time.monotonic() - t0))
            before = s.stats.snapshot()     # percentiles: this pass only
            t0 = time.monotonic()
            outs = s.run_many(reqs)
            jax.block_until_ready(outs[-1])
            rps = n_requests / (time.monotonic() - t0)
            if rps > session_rps:
                session_rps = rps
                window = s.stats.snapshot() - before
                p50, p95 = window.p50_ms(), window.p95_ms()
            t0 = time.monotonic()
            for _ in range(n_requests // 2):
                jax.block_until_ready(acc(x1))
            direct_b1_rps = max(
                direct_b1_rps, (n_requests // 2) / (time.monotonic() - t0))
        err = max(float(np.max(np.abs(np.asarray(o) - yb_np[i % batch])))
                  for i, o in enumerate(outs))
        n_batches = (s.stats.batches - warm_batches) // 3
        padded = s.stats.padded_rows

    return [{
        "bench": "table4_vgg16", "name": "serving/batched_queue",
        "scheduler": "continuous",
        "config": f"img{img}_scale{scale}_maxbatch{batch}_n{n_requests}",
        "session_rps": round(session_rps, 1),
        f"direct_b{batch}_rps": round(direct_bN_rps, 1),
        "direct_b1_rps": round(direct_b1_rps, 1),
        "session_vs_direct_batched": round(session_rps / direct_bN_rps, 2),
        "session_vs_direct_single": round(session_rps / direct_b1_rps, 2),
        "device_batches": n_batches, "padded_rows": padded,
        "compile_ms": round(compile_ms, 1),
        "latency_p50_ms": round(p50, 2),
        "latency_p95_ms": round(p95, 2),
        "max_abs_diff": err,
    }]


@without_compile_cache()
def run_aot_cold_start(*, img: int = 32, scale: int = 16,
                       batch: int = 8, n_req: int = 32) -> list[dict]:
    """AOT cold-start row: a session loading the serialized-executable
    bundle (``save_program(..., aot=True)``) vs a session compiling the same
    program from its ``program.json`` — the autoscaling-event number the
    artifact layer exists for.

    Both sides run in this process, which holds the device: each starts
    from ``jax.clear_caches()`` and a fresh ``ProgramCache``, with the
    persistent compilation cache off. The row records the cold side's
    ``compile_ms``, the warm side's ``warm_load_ms`` (its ``compile_ms``
    must be 0 — enforced here), their ratio (gated lower-is-better by
    ``tools/bench_compare.py``; target <= 0.10), session-ready
    wall clocks (from ``from_program`` to the last answer, in an already
    started process: interpreter and JAX start-up are not in them), and
    the max |diff|
    between the two sides' outputs — bitwise 0.0 by construction, since a
    deserialized executable IS the compiled program.
    """
    import tempfile

    import jax
    import numpy as np

    from repro import api
    from repro.core.program_cache import ProgramCache

    specs = network_specs(img=img, scale=scale, n_classes=10)
    plans = _alternating_plans(specs)
    acc = api.Accelerator.build(specs, plans=plans, seed=0, batch=batch)
    rng = np.random.default_rng(0)
    reqs = [rng.standard_normal((img, img, 3)).astype(np.float32)
            for _ in range(n_req)]

    def _serve(path):
        jax.clear_caches()
        t0 = time.monotonic()
        acc2 = api.Accelerator.from_program(path, params=acc.params,
                                            cache=ProgramCache())
        with acc2.serve(max_batch=batch, buckets=(batch,),
                        warmup=True) as s:
            outs = [np.asarray(y) for y in s.run_many(reqs)]
            ready_ms = (time.monotonic() - t0) * 1e3
            st = s.stats
        return {"compile_ms": st.compile_ms,
                "warm_load_ms": st.warm_load_ms, "ready_ms": ready_ms,
                "outs": outs}

    with tempfile.TemporaryDirectory() as tmp:
        bundle = os.path.join(tmp, "bundle")
        acc.save_program(bundle, aot=True, buckets=(batch,))
        cold = _serve(os.path.join(bundle, "program.json"))
        warm = _serve(bundle)

    if warm["compile_ms"] != 0.0:
        raise RuntimeError(f"warm side compiled "
                           f"({warm['compile_ms']:.1f}ms != 0) — the AOT "
                           f"bundle was not used")
    diff = max(float(np.abs(a - b).max())
               for a, b in zip(cold["outs"], warm["outs"]))
    return [{
        "bench": "table4_vgg16", "name": "serving/aot_cold_start",
        "config": f"img{img}_scale{scale}_batch{batch}",
        "platform": jax.devices()[0].platform,
        "cold_compile_ms": round(cold["compile_ms"], 1),
        "warm_load_ms": round(warm["warm_load_ms"], 1),
        "warm_over_cold_compile_ratio": round(
            warm["warm_load_ms"] / cold["compile_ms"], 3),
        "cold_ready_ms": round(cold["ready_ms"], 1),
        "warm_ready_ms": round(warm["ready_ms"], 1),
        "max_abs_diff": diff,
    }]


# self-contained subprocess body for the fleet row: four forced CPU host
# devices need a fresh interpreter, and that child runs on the CPU only
# (JAX_PLATFORMS=cpu), so it never competes for a chip the parent holds
_FLEET_SHARDED_SUBPROC = r"""
import json, os, time
import numpy as np
import jax, jax.numpy as jnp
from repro import api
from repro.launch.mesh import make_fleet_mesh
from repro.models.vgg import network_specs
from repro.core.compiler import LayerPlan
from repro.core.hybrid_conv import ConvSpec

img, scale, batch, n_req = 32, 16, 8, 96
specs = network_specs(img=img, scale=scale, n_classes=10)
ci, plans = 0, []
for s in specs:
    if isinstance(s, ConvSpec):
        plans.append(LayerPlan("wino" if ci % 2 == 0 else "spat",
                               "is" if ci % 2 else "ws", m=2, g_k=2, g_h=2))
        ci += 1
    else:
        plans.append(None)
acc = api.Accelerator.build(specs, plans=plans, seed=0, batch=batch)
mesh = make_fleet_mesh()
ndev = int(np.prod(mesh.devices.shape))
rng = np.random.default_rng(0)
reqs = [rng.standard_normal((img, img, 3)).astype(np.float32)
        for _ in range(n_req)]

def measure(mesh_arg):
    best, outs = 0.0, None
    with acc.serve(max_batch=batch, buckets=(batch,), mesh=mesh_arg,
                   warmup=True) as s:
        s.run_many(reqs[:2 * batch])            # warm threads + executor
        for _ in range(3):
            t0 = time.monotonic()
            o = s.run_many(reqs)
            jax.block_until_ready(o[-1])
            rps = n_req / (time.monotonic() - t0)
            if rps > best:
                best, outs = rps, o
    return best, outs

rps_1, outs_1 = measure(None)
rps_n, outs_n = measure(mesh)
parity = max(float(np.abs(np.asarray(a) - np.asarray(b)).max())
             for a, b in zip(outs_1, outs_n))

# pallas under sharding: each shard is an ordinary single-device trace, so
# the Pallas PE kernels run inside the mapped region (interpret mode on CPU)
acc_pal = api.Accelerator.build(specs, plans=plans, params=acc.params,
                                batch=batch, backend="pallas")
with acc_pal.serve(max_batch=batch, buckets=(batch,), mesh=mesh,
                   warmup=True) as sp:
    outs_p = sp.run_many(reqs[:batch])
pallas_diff = max(float(np.abs(np.asarray(a) - np.asarray(b)).max())
                  for a, b in zip(outs_1[:batch], outs_p))

# bursty trace: continuous batching vs the legacy fixed-bucket window.
# Unsharded on purpose — isolates the scheduler from the sharding cost.
def bursty(scheduler):
    rngb = np.random.default_rng(1)
    sizes = [int(rngb.integers(2, 7)) for _ in range(24)]
    total, best = sum(sizes), 0.0
    with acc.serve(max_batch=batch, buckets=(batch,), max_wait_ms=1.0,
                   scheduler=scheduler, warmup=True) as s:
        s.run_many(reqs[:2 * batch])
        for _ in range(3):
            futs, i = [], 0
            t0 = time.monotonic()
            for sz in sizes:
                futs += s.submit_many([reqs[(i + j) % n_req]
                                       for j in range(sz)])
                i += sz
                time.sleep(0.0025)              # burst gap
            for f in futs:
                f.result()
            best = max(best, total / (time.monotonic() - t0))
        padded = s.stats.padded_rows
    return best, padded

cont_rps, cont_padded = bursty("continuous")
buck_rps, buck_padded = bursty("bucketed")

print("FLEET_ROW:" + json.dumps({
    "config": f"img{img}_scale{scale}_maxbatch{batch}_n{n_req}",
    "n_devices": ndev,
    "host_cores": os.cpu_count() or 1,
    "session_rps_1dev": round(rps_1, 1),
    "session_rps_4dev": round(rps_n, 1),
    "rps_scaling": round(rps_n / rps_1, 2),
    "continuous_rps": round(cont_rps, 1),
    "bucketed_rps": round(buck_rps, 1),
    "continuous_vs_bucketed": round(cont_rps / buck_rps, 2),
    "continuous_padded_rows": cont_padded,
    "bucketed_padded_rows": buck_padded,
    "pallas_sharded_max_abs_diff": pallas_diff,
    "max_abs_diff": parity,
}))
"""


def run_fleet_sharded(*, n_devices: int = 4) -> list[dict]:
    """Sharded fleet serving row: the shard_map'd executor variant splitting
    each device batch over ``n_devices`` forced host devices, measured
    against the same session on one device, plus the continuous-vs-bucketed
    scheduler comparison on a bursty arrival trace and the Pallas-under-
    sharding parity evidence.

    Runs in a subprocess on the CPU platform (``JAX_PLATFORMS=cpu``) with
    ``--xla_force_host_platform_device_count``: a CPU row wherever the
    benchmark runs, never a second process reaching for the chip. On a
    single-core host the 4-device row CANNOT show real scaling — four
    shard computations time-slice one core — so the row records
    ``host_cores`` alongside ``rps_scaling`` and the regression guard
    (``tools/bench_compare.py``) only gates scaling when the host has the
    cores to parallelize; multi-core CI regenerates the row with real
    speedup. ``continuous_vs_bucketed`` and both parity metrics are
    load-independent and meaningful everywhere.
    """
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={n_devices}")
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(repo, "src")
    r = subprocess.run([sys.executable, "-c", _FLEET_SHARDED_SUBPROC],
                       capture_output=True, text=True, env=env, timeout=900)
    if r.returncode != 0:
        raise RuntimeError(f"fleet_sharded subprocess failed:\n"
                           f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}")
    line = next(l for l in r.stdout.splitlines()
                if l.startswith("FLEET_ROW:"))
    row = json.loads(line[len("FLEET_ROW:"):])
    row = {"bench": "table4_vgg16", "name": "serving/fleet_sharded",
           "platform": "cpu", **row}
    return [row]


def run_fault_injection(*, img: int = 32, scale: int = 16, batch: int = 4,
                        n_requests: int = 40) -> list[dict]:
    """Fault-tolerant serving row: what poisoned-batch isolation costs.

    The same request stream is served twice through one warmed session
    configuration: once clean, once with ~10% of the requests *cursed*
    (a deterministic :class:`FaultSpec` fails every batch containing them
    at the ``execute`` site, forcing the bisect-and-retry recovery). The
    row records:

    * ``survived`` / ``accounting_balanced`` — the liveness invariant
      under load: every future resolved, ``submitted == completed +
      errors + shed``;
    * ``isolation_overhead_ratio`` — faulty-pass wall clock over the
      clean pass (lower is better; both passes run back-to-back in one
      process, so the ratio is machine-load-independent);
    * ``p95_clean_ms`` / ``p95_faulty_ms`` — tail latency with and
      without 10% faults;
    * ``innocent_max_abs_diff`` — innocents co-batched with an offender
      against the clean pass. The bisection retries re-run the same
      compiled executor at the same bucket size and row offsets, so this
      is REQUIRED to be exactly 0.0 (bitwise), not merely small.
    """
    import numpy as np

    from repro import api
    from repro.serving import FaultPlan, FaultSpec

    specs = network_specs(img=img, scale=scale, n_classes=10)
    acc = api.Accelerator.build(specs, seed=0, batch=batch)
    rng = np.random.default_rng(0)
    xs = rng.standard_normal((n_requests, img, img, 3)).astype(np.float32)
    cursed = tuple(range(0, n_requests, 10))        # every 10th request
    # request ids are session-global and the timed stream runs after a
    # 2*batch-request pipeline warmup, so the cursed specs bind to the
    # warmup-offset ids
    plan = FaultPlan([FaultSpec(site="execute", kind="error",
                                requests=(c + 2 * batch,),
                                message=f"cursed request {c}")
                      for c in cursed])

    def _pass(fault_plan):
        with acc.serve(max_batch=batch, buckets=(batch,), max_wait_ms=2.0,
                       warmup=True, fault_plan=fault_plan) as s:
            s.run_many(list(xs[:2 * batch]))        # warm pipeline threads
            before = s.stats.snapshot()
            t0 = time.monotonic()
            futs = [s.submit(x) for x in xs]
            outs = []
            for f in futs:
                try:
                    outs.append(np.asarray(f.result(timeout=120)))
                except Exception as e:  # noqa: BLE001 — typed resolution
                    outs.append(e)
            dt = time.monotonic() - t0
            resolved = all(f.done() for f in futs)
        # read once the session has closed: the drain side counts a batch
        # just after resolving its futures
        return outs, dt, s.stats.snapshot() - before, resolved

    clean_outs, t_clean, st_clean, _ = _pass(None)
    faulty_outs, t_faulty, st_faulty, resolved = _pass(plan)
    balanced = (st_faulty.submitted
                == st_faulty.requests + st_faulty.errors + st_faulty.shed)
    innocent_diff = max(
        float(np.max(np.abs(f - c)))
        for i, (f, c) in enumerate(zip(faulty_outs, clean_outs))
        if i not in cursed)
    offenders_isolated = all(isinstance(faulty_outs[i], Exception)
                             for i in cursed)
    return [{
        "bench": "table4_vgg16", "name": "serving/fault_injection",
        "config": (f"img{img}_scale{scale}_maxbatch{batch}_n{n_requests}_"
                   f"cursed{len(cursed)}"),
        "fault_rate": round(len(cursed) / n_requests, 3),
        "survived": bool(resolved and balanced),
        "accounting_balanced": bool(balanced),
        "offenders_isolated": bool(offenders_isolated),
        "retries": st_faulty.retries, "isolated": st_faulty.isolated,
        "isolation_overhead_ratio": round(t_faulty / t_clean, 2),
        "p95_clean_ms": round(st_clean.p95_ms(), 2),
        "p95_faulty_ms": round(st_faulty.p95_ms(), 2),
        "innocent_max_abs_diff": innocent_diff,
    }]
