"""Serving entrypoints.

LM serving (batched prefill + decode with a KV/SSM cache):

  PYTHONPATH=src python -m repro.launch.serve --arch mamba2-130m --reduced \
      --batch 4 --prompt-len 32 --gen 16

CNN serving through the HybridDNN pipeline — DSE -> compile -> validated,
cached, jitted executor (the paper's Fig. 1 flow end-to-end):

  PYTHONPATH=src python -m repro.launch.serve --arch vgg16 --reduced \
      --batch 8 --iters 20
  PYTHONPATH=src python -m repro.launch.serve --model resnet18 --reduced \
      --batch 4 --iters 20
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.parallel.sharding import make_rules, use_rules
from repro.train import steps as steps_lib


def serve(arch: str, *, reduced: bool = True, batch: int = 4,
          prompt_len: int = 32, gen: int = 16, seed: int = 0,
          greedy: bool = True):
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    mesh = make_host_mesh()
    rules = make_rules(mesh)
    rng = np.random.default_rng(seed)

    with use_rules(rules):
        params = steps_lib.init_params(jax.random.PRNGKey(seed), cfg)
    prefill_fn, decode_fn = steps_lib.make_serve_steps(cfg)

    max_len = prompt_len + gen
    cache = steps_lib.init_cache(cfg, batch, max_len)
    extras = {}
    if cfg.family == "vlm":
        extras["image_embeds"] = jnp.asarray(rng.standard_normal(
            (batch, cfg.n_image_tokens, cfg.d_model)), cfg.jnp_dtype)
    if cfg.family == "audio":
        from repro.models import whisper
        frames = jnp.asarray(rng.standard_normal(
            (batch, cfg.n_audio_frames, cfg.d_model)), cfg.jnp_dtype)
        with use_rules(rules):
            extras["enc_out"] = whisper.encode(params, frames, cfg)

    def _prefill(params, tokens, cache, extras):
        with use_rules(rules):
            return prefill_fn(params, tokens, cache, extras)

    def _decode(params, token, cache, pos, extras):
        with use_rules(rules):
            return decode_fn(params, token, cache, pos, extras)

    jit_prefill = jax.jit(_prefill, donate_argnums=(2,))
    jit_decode = jax.jit(_decode, donate_argnums=(2,))

    prompts = rng.integers(0, cfg.vocab_size, (batch, prompt_len),
                           dtype=np.int32)
    t0 = time.monotonic()
    logits, cache = jit_prefill(params, jnp.asarray(prompts), cache, extras)
    t_prefill = time.monotonic() - t0

    outs = []
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    t0 = time.monotonic()
    for i in range(gen):
        outs.append(np.asarray(tok)[:, 0])
        logits, cache = jit_decode(params, tok, cache,
                                   jnp.int32(prompt_len + i), extras)
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    t_decode = time.monotonic() - t0
    gen_tokens = np.stack(outs, 1)
    print(f"prefill {prompt_len} toks x{batch}: {t_prefill*1e3:.1f}ms; "
          f"decode {gen} steps: {t_decode/gen*1e3:.1f}ms/tok")
    return gen_tokens


# Back-compat aliases: both lived here before the ``repro.api`` façade
# (PR 3); benchmarks and tests import them from this module.
from repro.api import build_segmented_request  # noqa: E402,F401
from repro.api import random_params as make_vgg_params  # noqa: E402,F401

CNN_TARGETS = {"tpu": "V5E", "vu9p": "VU9P", "pynq": "PYNQ_Z1"}


def serve_cnn(arch: str = "vgg16", *, reduced: bool = True, batch: int = 8,
              iters: int = 20, seed: int = 0, compare_interpreter: bool = False,
              segmented: bool = False, target: str = "tpu",
              session: bool = False, backend: str = "xla",
              opt_level: int = 1, mesh: str = "host",
              scheduler: str = "continuous", dtype: str = "float32",
              deadline_ms: float | None = None,
              queue_limit: int | None = None):
    """CNN inference through the full HybridDNN pipeline — now a thin driver
    over ``repro.api``.

    ``Accelerator.build`` runs the DSE (per-layer mode/dataflow/m/g_h/g_k
    over the WHOLE model), lowers all 21 layers to ONE 128-bit instruction
    stream, validates the schedule ONCE, and serves every request from the
    cached jitted executor — steady-state requests never touch the Python
    interpreter. ``target`` picks the DSE backend through the unified
    ``Target`` protocol (``tpu``/``vu9p``/``pynq``). ``segmented=True``
    keeps the legacy multi-Program path for comparison, and ``session=True``
    additionally drives requests through the batching (pipelined-dispatch)
    ``ServingSession``. ``backend="pallas"`` serves through the Pallas PE
    kernels (compiled on a TPU, interpret mode elsewhere) instead of the
    XLA lowering; ``opt_level=0`` disables the lowering optimizer (literal
    per-block lowering — the reference the fused default is tested
    against).
    ``dtype="int8"`` serves the quantized accelerator (post-training
    calibration on the request distribution, int8 PEs with fused
    requantize, int8-aware DSE — see ``docs/ARCHITECTURE.md``).
    """
    from repro import api
    from repro.core import perf_model as pm
    from repro.core.program_cache import default_cache
    from repro.models import resnet, vgg

    if arch not in ("vgg16", "resnet18"):
        raise ValueError(f"CNN serving supports 'vgg16' (the paper's case "
                         f"study) and 'resnet18' (the residual workload), "
                         f"got {arch!r}")
    if target not in CNN_TARGETS:
        raise ValueError(f"--target must be one of {sorted(CNN_TARGETS)}")
    if segmented and arch == "resnet18":
        raise ValueError(
            "--segmented is the legacy conv-segment path (host-side maxpool "
            "glue between linear CONV runs) — a residual topology has no "
            "such segmentation; resnet18 serves single-Program only")
    iters = max(1, iters)
    dse_target = getattr(pm, CNN_TARGETS[target])
    if target == "tpu" and jax.devices()[0].platform == "tpu":
        # serving on a chip: plan against the peaks of the chip that is
        # there, and refuse one the table does not know
        dse_target = pm.tpu_target_for(jax.devices()[0])
    img, scale = (64, 8) if reduced else (224, 1)
    n_classes = 10 if reduced else 1000
    if arch == "resnet18":
        specs = resnet.resnet18_specs(img=img, scale=scale,
                                      n_classes=n_classes)
    else:
        specs = vgg.network_specs(img=img, scale=scale, n_classes=n_classes)
    rng = np.random.default_rng(seed + 1)
    x_np = rng.standard_normal((batch, img, img, 3)).astype(np.float32)
    t0 = time.monotonic()
    # int8 calibrates on the request distribution itself — the serving
    # analog of calibrating on a training-set slice
    acc = api.Accelerator.build(specs, target=dse_target, batch=batch,
                                seed=seed, segmented=segmented,
                                backend=backend, opt_level=opt_level,
                                dtype=dtype,
                                calib=x_np if dtype == "int8" else None)
    t_build = time.monotonic() - t0
    print(acc.summary())
    print(f"build (DSE+compile+validate): {t_build * 1e3:.0f}ms; "
          f"PE backend: {backend}; opt_level: {opt_level}; dtype: {dtype}")

    x = jnp.asarray(x_np)
    t0 = time.monotonic()
    y = jax.block_until_ready(acc(x))          # first request: jit trace
    t_first = time.monotonic() - t0
    t0 = time.monotonic()
    for _ in range(iters):                     # steady state: cache hits only
        y = jax.block_until_ready(acc(x))
    t_steady = (time.monotonic() - t0) / max(1, iters)
    macs = sum(s.macs for s in specs)
    gops = 2 * macs * batch / 1e9 / t_steady
    cache = default_cache()
    print(f"first request (jit): {t_first * 1e3:.1f}ms; "
          f"steady: {t_steady * 1e3:.2f}ms/batch{batch} "
          f"({gops:.1f} GOPS); cache hits={cache.stats.hits} "
          f"misses={cache.stats.misses}")
    if session:
        mesh_arg = None if mesh == "none" else mesh
        with acc.serve(max_batch=batch, buckets=(batch,), warmup=True,
                       mesh=mesh_arg, scheduler=scheduler,
                       deadline_ms=deadline_ms,
                       queue_limit=queue_limit) as s:
            n_req = batch * iters
            # materialize requests host-side before timing, like real
            # clients arriving with their own arrays
            reqs = [np.asarray(x[i % batch]) for i in range(n_req)]
            t0 = time.monotonic()
            outs = s.run_many(reqs)
            jax.block_until_ready(outs[-1])
            dt = time.monotonic() - t0
            st = s.stats
            print(f"ServingSession[{scheduler}, mesh={mesh}]: {n_req} "
                  f"requests in {dt * 1e3:.1f}ms "
                  f"({n_req / dt:.1f} req/s, {st.batches} device "
                  f"batches, {st.padded_rows} padded rows, "
                  f"occupancy {st.occupancy():.3f}; whole session: "
                  f"latency p50 {st.p50_ms():.2f}ms "
                  f"p95 {st.p95_ms():.2f}ms, "
                  f"queue wait p50 {st.wait_p50_ms():.2f}ms "
                  f"p95 {st.wait_p95_ms():.2f}ms, "
                  f"staging {st.stage_ns / 1e3 / max(st.submitted, 1):.1f}"
                  f"us/request; "
                  f"compile {st.compile_ms:.0f}ms "
                  f"warm-load {st.warm_load_ms:.0f}ms)")
            per_dev = ", ".join(f"{d}: {n}" for d, n in
                                sorted(st.device_batches.items()))
            print(f"  per-device batches: {{{per_dev}}}")
            # failure-model counters: the liveness ledger (submitted ==
            # completed + errors + shed, enforced by the fault suite)
            print(f"  failure model: submitted {st.submitted} = "
                  f"completed {st.requests} + errors {st.errors} + "
                  f"shed {st.shed}; deadline_exceeded "
                  f"{st.deadline_exceeded}, retries {st.retries}, "
                  f"isolated {st.isolated}, degraded {st.degraded}, "
                  f"watchdog restarts {st.watchdog_restarts}")
    if compare_interpreter:
        strict_request = acc.strict_request()
        jax.block_until_ready(strict_request(x))   # warm XLA op caches
        t0 = time.monotonic()
        y_i = jax.block_until_ready(strict_request(x))
        t_interp = time.monotonic() - t0
        if acc.quant is not None:       # both paths emit int8: compare in
            y_i = acc.quant.dequantize_output(y_i)   # the dequantized space
        err = float(jnp.max(jnp.abs(y - y_i)))
        print(f"interpreter: {t_interp * 1e3:.1f}ms/batch "
              f"({t_interp / t_steady:.1f}x slower than cached executor; "
              f"max |diff| {err:.2e})")
    return np.asarray(y)


def main():
    ap = argparse.ArgumentParser()
    # --model is the CNN-serving spelling of the same knob (resnet18/vgg16)
    ap.add_argument("--arch", "--model", dest="arch", required=True)
    # BooleanOptionalAction so --no-reduced actually reaches full-size mode
    # (a bare store_true with default=True made it unreachable)
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--iters", type=int, default=20,
                    help="steady-state requests to time (CNN serving)")
    ap.add_argument("--compare-interpreter", action="store_true")
    ap.add_argument("--segmented", action="store_true",
                    help="legacy multi-Program CNN path (one Program per "
                         "CONV segment, host-side maxpool/FC glue)")
    ap.add_argument("--target", default="tpu", choices=sorted(CNN_TARGETS),
                    help="DSE backend for CNN serving (unified Target "
                         "protocol: TPU v5e or the paper's FPGA devices)")
    ap.add_argument("--session", action="store_true",
                    help="also drive requests through the batching "
                         "ServingSession (host-mesh sharded)")
    ap.add_argument("--mesh", default="host", choices=("none", "host"),
                    help="ServingSession device mesh: 'host' shards device "
                         "batches over every local device via shard_map; "
                         "'none' keeps single-device dispatch")
    ap.add_argument("--scheduler", default="continuous",
                    choices=("continuous", "bucketed"),
                    help="ServingSession admission policy: 'continuous' "
                         "keeps admitting while the device pipeline is "
                         "busy; 'bucketed' is the legacy fixed window")
    ap.add_argument("--backend", default="xla", choices=("xla", "pallas"),
                    help="PE implementation the executor lowers through "
                         "(pallas: compiled kernels on a TPU, the Pallas "
                         "interpreter elsewhere)")
    ap.add_argument("--dtype", default="float32",
                    choices=("float32", "int8"),
                    help="CNN serving precision: int8 builds the quantized "
                         "accelerator (calibrated sidecar, int8 PEs with "
                         "fused requantize, int8-aware DSE)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request deadline for the ServingSession: "
                         "requests not completed in time fail with "
                         "DeadlineExceeded instead of waiting forever")
    ap.add_argument("--queue-limit", type=int, default=None,
                    help="bound the ServingSession's pending queue; "
                         "overflow requests are shed with Overloaded "
                         "(explicit backpressure instead of unbounded "
                         "memory growth)")
    ap.add_argument("--opt-level", type=int, default=1, choices=(0, 1),
                    help="lowering-optimizer level: 1 fuses each layer's "
                         "per-block loop into one PE dispatch where "
                         "provably equivalent; 0 keeps the literal "
                         "per-block lowering")
    args = ap.parse_args()
    enable_compile_cache()
    if args.arch.startswith("vgg") or args.arch.startswith("resnet"):
        y = serve_cnn(args.arch, reduced=args.reduced, batch=args.batch,
                      iters=args.iters,
                      compare_interpreter=args.compare_interpreter,
                      segmented=args.segmented, target=args.target,
                      session=args.session, backend=args.backend,
                      opt_level=args.opt_level, mesh=args.mesh,
                      scheduler=args.scheduler, dtype=args.dtype,
                      deadline_ms=args.deadline_ms,
                      queue_limit=args.queue_limit)
        print("logits:", y.shape)
        return
    toks = serve(args.arch, reduced=args.reduced, batch=args.batch,
                 prompt_len=args.prompt_len, gen=args.gen)
    print("generated token grid:\n", toks)


if __name__ == "__main__":
    main()
