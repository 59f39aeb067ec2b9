"""``repro.api`` — one façade for the paper's full design flow (Fig. 1).

The paper's headline contribution is a *framework*: model + hardware target
in, deployed accelerator out. This module is that framework's user surface:

    from repro import api
    from repro.core import perf_model as pm
    from repro.models import vgg

    specs = vgg.network_specs(img=64, scale=8, n_classes=10)
    acc = api.Accelerator.build(specs, target=pm.V5E, batch=8)
    logits = acc(x)                 # cached, validated, jitted executor
    print(acc.summary())            # per-layer mode/dataflow/latency table

``Accelerator.build`` runs the DSE (Sec. 5) through the unified ``Target``
protocol — any object with ``run_dse(specs, batch)`` works, so ``pm.V5E``
and the ``pm.FPGATarget`` instances dispatch identically — compiles ONE
``Program`` (Sec. 4.1), validates the hazard schedule once, and returns a
callable accelerator whose requests hit the cached jitted executor.

``Accelerator.save_program`` / ``Accelerator.from_program`` persist the
compiled instruction stream (plus specs/plans and the DSE verdict) so a
deployment can skip the DSE; the loader recompiles and verifies the stream
bit-exactly.

``ServingSession`` (via ``Accelerator.serve()``) is the paper's NI-instances
analog on the host mesh: a continuous-batching request queue that coalesces
single-image requests into device batches (admitting late arrivals while the
device pipeline is busy, deadline-capped), pads stragglers up to a fixed set
of bucket sizes (so the jit cache holds one executor per bucket), and
optionally shards full buckets over a device mesh via the shard_map'd
executor variant — with BOTH backends, since each shard is an ordinary
single-device trace. ``Fleet`` stacks several sessions over one process,
one program cache, and one FIFO-fair device-slot pool for multi-model
tenancy.

``backend="xla" | "pallas"`` (on ``build``, ``from_program``, and inherited
by sessions) selects the PE implementation every CONV/FC block lowers
through — the XLA ops (the default) or the Pallas PE kernels (compiled on
a TPU, interpret mode on the CPU). See ``docs/ARCHITECTURE.md`` for
the plug-in table and ``docs/API.md`` for the full reference.
"""
from __future__ import annotations

import dataclasses
import itertools
import json
import logging
import os
import threading
import time
import warnings
from collections import deque
from contextlib import contextmanager
from concurrent.futures import Future, InvalidStateError
from typing import Any, Protocol, Sequence, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import perf_model as pm
from repro.core.compiler import NO_PLAN, LayerPlan, Program, compile_network
from repro.core.dse import DSEResult, FPGACandidate, TPUCandidate
from repro.core.hybrid_conv import (
    ConvSpec,
    DepthwiseSpec,
    EltwiseSpec,
    FCSpec,
    PoolSpec,
)
from repro.core.runtime import HybridRuntime
from repro.quant import QuantSidecar, quantize_params
from repro.quant import calibrate as quant_calibrate
from repro.serving import (
    DeadlineExceeded,
    DeadlineTable,
    NumericsError,
    Overloaded,
    PipelineCrashed,
    ThreadSupervisor,
)
from repro.serving.telemetry import LogHistogram, SpanRecorder

PROGRAM_FORMAT = "hybriddnn-program/v1"

log = logging.getLogger("repro.serving")


class ProgramLoadError(ValueError):
    """A saved program/bundle that cannot be loaded: truncated or non-JSON
    file, unknown format version, instruction-stream or quant-sidecar
    digest mismatch. Subclasses ``ValueError`` so pre-existing callers that
    catch the broad class keep working; new callers should catch this."""


@contextmanager
def _expected_donation_noise():
    """ServingSession opts into best-effort input donation: when a bucket's
    input buffer has no same-shape reuse inside the executor (e.g. the
    entry layout transform changes its shape immediately), XLA warns at
    compile time and keeps a copy — expected by design. Suppress exactly
    that message around the session's own compile sites only, so a user's
    own ``jax.jit(..., donate_argnums=...)`` diagnostics stay visible.

    ``warnings.catch_warnings`` mutates process-global filter state and is
    not thread-safe, so this is a no-op off the main thread: a cold bucket
    compiled lazily in the dispatch worker emits the (harmless, one-time)
    note rather than risk corrupting a user thread's filter stack."""
    if threading.current_thread() is not threading.main_thread():
        yield
        return
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable",
            category=UserWarning)
        yield


@runtime_checkable
class Target(Protocol):
    """Anything that can run the paper's DSE for a layer chain.

    ``pm.TPUTarget`` and ``pm.FPGATarget`` both implement this, so callers
    never branch on ``run_tpu_dse`` vs ``run_fpga_dse`` — they hand any
    target instance to ``Accelerator.build``.
    """

    def run_dse(self, specs, batch: int = 1) -> DSEResult: ...


def random_params(specs: Sequence[Any], seed: int = 0) -> list:
    """Random ``[(w, b), ...]`` for every parameterized layer (CONV, FC and
    DEPTHWISE; POOL and ELTWISE carry no params), fan-in scaled — the
    stand-in for trained weights throughout the repo."""
    rng = np.random.default_rng(seed)
    params = []
    for s in specs:
        if isinstance(s, ConvSpec):
            w = jnp.asarray(rng.standard_normal((s.r, s.s, s.c, s.k)),
                            jnp.float32) * (s.r * s.s * s.c) ** -0.5
            params.append((w, jnp.zeros((s.k,), jnp.float32)))
        elif isinstance(s, DepthwiseSpec):
            w = jnp.asarray(rng.standard_normal((s.r, s.s, 1, s.c)),
                            jnp.float32) * (s.r * s.s) ** -0.5
            params.append((w, jnp.zeros((s.c,), jnp.float32)))
        elif isinstance(s, FCSpec):
            w = jnp.asarray(rng.standard_normal((s.d_in, s.d_out)),
                            jnp.float32) * s.d_in ** -0.5
            params.append((w, jnp.zeros((s.d_out,), jnp.float32)))
    return params


def _conv_segments_of(specs) -> list[int]:
    """Consecutive-CONV run lengths between maxpools (VGG16: [2,2,3,3,3]).

    The segmented request glues segments with a host-side maxpool, so the
    chain must be ``(CONV+ POOL)+ FC*`` — anything else (trailing CONVs
    without a pool, a pool before any CONV, CONVs after the FC tail) gets a
    descriptive error instead of an opaque crash downstream."""
    segments, run, seen_fc = [], 0, False
    for s in specs:
        if isinstance(s, (EltwiseSpec, DepthwiseSpec)):
            raise ValueError(
                f"segmented path: {type(s).__name__} {s.name!r} — residual "
                f"adds and depthwise convs need the single-Program path "
                f"(segmented=False); the legacy glue only handles "
                f"(CONV+ POOL)+ FC*")
        if isinstance(s, ConvSpec):
            if s.inp_from is not None:
                raise ValueError(
                    f"segmented path: CONV {s.name!r} reroutes its input "
                    f"(inp_from={s.inp_from}) — skip wiring needs the "
                    f"single-Program path (segmented=False)")
            if seen_fc:
                raise ValueError("segmented path: CONV after the FC tail")
            run += 1
        elif isinstance(s, PoolSpec):
            if seen_fc:
                raise ValueError("segmented path: POOL after the FC tail")
            if run == 0:
                raise ValueError(
                    "segmented path: maxpool without a preceding CONV "
                    "segment — the chain must be (CONV+ POOL)+ FC*")
            segments.append(run)
            run = 0
        else:
            seen_fc = True
    if run:
        raise ValueError(
            "segmented path: trailing CONV segment without a maxpool — "
            "use the single-Program path (segmented=False) for this chain")
    if not segments:
        raise ValueError("segmented path: no CONV+POOL segment in the chain")
    return segments


def build_segmented_request(specs, plans, params, *, strict: bool = False,
                            cache=None, backend: str = "xla",
                            interpret: bool | None = None,
                            opt_level: int = 1):
    """The legacy multi-Program path: one compiled Program per CONV segment,
    host-side 2x2 maxpool glue between segments, and the FC tail outside
    the runtime. Kept as ``Accelerator.build(..., segmented=True)``;
    asserted numerically identical to the single-Program path in
    ``tests/test_integration.py``. ``strict=True`` builds the per-segment
    runtimes on the per-instruction interpreter instead of the cached
    jitted executor; ``cache`` overrides the process-global program cache
    for every segment runtime; ``backend``/``interpret`` select the PE
    implementation for the segment runtimes AND the host-side FC tail;
    ``opt_level`` is the lowering-optimizer level of each segment
    executor."""
    from repro.core.executor import resolve_backend, resolve_opt_level
    from repro.core.hybrid_conv import dense, max_pool2d

    resolve_backend(backend, interpret)   # reject bad combos before building
    resolve_opt_level(opt_level)

    # params align with the non-pool specs, in network order
    nonpool = [s for s in specs if not isinstance(s, PoolSpec)]
    assert len(nonpool) == len(params)
    conv_specs = [s for s in specs if isinstance(s, ConvSpec)]
    conv_plans = [p for s, p in zip(specs, plans) if isinstance(s, ConvSpec)]
    conv_params = [p for s, p in zip(nonpool, params)
                   if isinstance(s, ConvSpec)]
    pool_specs = [s for s in specs if isinstance(s, PoolSpec)]
    fc_specs = [s for s in nonpool if isinstance(s, FCSpec)]
    fc_params = [p for s, p in zip(nonpool, params) if isinstance(s, FCSpec)]

    runtimes, idx, n_instr = [], 0, 0
    for n in _conv_segments_of(specs):
        program = compile_network(conv_specs[idx:idx + n],
                                  conv_plans[idx:idx + n])
        rt = HybridRuntime(program, strict=strict, cache=cache,
                           backend=backend, interpret=interpret,
                           opt_level=opt_level)
        rt.load_params(conv_params[idx:idx + n])
        runtimes.append(rt)
        n_instr += len(program.instructions)
        idx += n

    assert len(pool_specs) == len(runtimes), \
        "segmented path expects one maxpool after each CONV segment"

    def request(x):
        for rt, ps in zip(runtimes, pool_specs):
            x = max_pool2d(rt.run(x), ps.window, ps.stride)
        x = x.reshape(x.shape[0], -1)
        for s, (w, b) in zip(fc_specs, fc_params):
            x = dense(x, w, b, relu=s.relu,
                      use_pallas=backend == "pallas", interpret=interpret)
        return x

    return request, runtimes, n_instr


# ---------------------------------------------------------------------------
# Program (de)serialization helpers
# ---------------------------------------------------------------------------

_SPEC_KINDS = {"conv": ConvSpec, "pool": PoolSpec, "fc": FCSpec,
               "eltwise": EltwiseSpec, "dw": DepthwiseSpec}


def _spec_to_dict(spec) -> dict:
    kind = next(k for k, cls in _SPEC_KINDS.items()
                if type(spec) is cls)
    return {"kind": kind, **dataclasses.asdict(spec)}


def _spec_from_dict(d: dict):
    d = dict(d)
    return _SPEC_KINDS[d.pop("kind")](**d)


def _hw_to_dict(hw) -> dict:
    if isinstance(hw, TPUCandidate):
        return {"type": "tpu", **dataclasses.asdict(hw)}
    if isinstance(hw, FPGACandidate):
        return {"type": "fpga", **dataclasses.asdict(hw)}
    return {"type": "other", "repr": repr(hw)}


def _hw_from_dict(d: dict):
    d = dict(d)
    typ = d.pop("type")
    if typ == "tpu":
        return TPUCandidate(**d)
    if typ == "fpga":
        return FPGACandidate(**d)
    return d.get("repr")


def _fmt_t(seconds: float) -> str:
    if seconds < 1e-3:
        return f"{seconds * 1e6:8.1f} us"
    if seconds < 1.0:
        return f"{seconds * 1e3:8.2f} ms"
    return f"{seconds:8.3f} s "


# ---------------------------------------------------------------------------
# The façade
# ---------------------------------------------------------------------------

class Accelerator:
    """A built accelerator: DSE verdict + ONE compiled Program + the cached,
    validated, jitted executor behind ``__call__``.

    Construct with :meth:`build` (the full flow) or :meth:`from_program`
    (reuse a saved instruction stream, skipping the DSE). ``backend``
    selects the PE implementation the executor lowers each CONV/FC block
    through — ``"xla"`` (default) or ``"pallas"`` (the Pallas TPU kernels,
    compiled on a TPU, interpret mode on the CPU unless overridden) — see
    ``docs/ARCHITECTURE.md``.

    Instances are callable: ``acc(x)`` runs one inference request through
    the cached executor. :meth:`summary` prints the per-layer DSE verdict,
    :meth:`save_program` / :meth:`from_program` persist/restore the
    compiled stream, and :meth:`serve` opens a batching
    :class:`ServingSession`.
    """

    def __init__(self, *, specs, plans, params, request, target=None,
                 batch: int = 1, program: Program | None = None,
                 runtime: HybridRuntime | None = None,
                 dse: DSEResult | None = None, segmented: bool = False,
                 segment_runtimes: list | None = None,
                 backend: str = "xla", interpret: bool | None = None,
                 opt_level: int = 1, quant=None):
        self.specs = list(specs)
        self.plans = list(plans)
        self.params = params
        self.target = target
        self.batch = batch
        self.program = program
        self.runtime = runtime
        self.dse = dse
        self.segmented = segmented
        self.segment_runtimes = segment_runtimes
        self.backend = backend
        self.interpret = interpret
        self.opt_level = opt_level
        self.quant = quant          # QuantSidecar for int8 accelerators
        self._request = request

    # -- construction -------------------------------------------------------
    @classmethod
    def build(cls, specs, target: Target = pm.V5E, *, batch: int = 8,
              params: list | None = None, seed: int = 0,
              plans: Sequence[LayerPlan | None] | None = None,
              segmented: bool = False, strict: bool = False,
              cache=None, backend: str = "xla",
              interpret: bool | None = None,
              opt_level: int = 1, dtype: str = "float32",
              calib=None, observer: str = "percentile") -> "Accelerator":
        """DSE -> compile -> validate, in one call.

        ``target`` is any :class:`Target` (``pm.V5E``, ``pm.VU9P``,
        ``pm.PYNQ_Z1``, or a custom instance). ``plans`` overrides the DSE
        (skips it entirely — useful for benchmarks pinning a schedule).
        ``params`` defaults to :func:`random_params`. ``segmented=True``
        builds the legacy multi-Program path instead (one Program per CONV
        segment, host-side glue); ``strict=True`` runs the per-instruction
        interpreter instead of the cached executor.

        ``backend="pallas"`` routes every CONV/FC block through the Pallas
        PE kernels instead of the XLA ops; ``interpret`` overrides the
        Pallas interpret-mode resolution (``None`` = from the device the
        executor runs on: compiled on a TPU, interpret mode elsewhere).
        ``opt_level`` selects the lowering optimizer — ``1`` (default) collapses each layer's per-block loop
        into one whole-layer PE dispatch where provably equivalent, ``0``
        keeps the literal per-block lowering (the reference). Backend and
        opt_level both join the program-cache key, so the same Program
        serves every variant side by side.

        ``dtype="int8"`` builds a fully quantized accelerator: the DSE
        plans against the target's int8 variant (Winograd gated off — no
        int8 U-space transform), ``calib`` (an (n, H, W, C) array or list
        of batches; defaults to seeded random data) drives post-training
        calibration into a ``repro.quant.QuantSidecar``, params are
        quantized per-tensor symmetric (int8 weights, int32 bias), and
        every path — cached executor, strict interpreter, Pallas PEs —
        runs int8 GEMMs with a fused requantize+ReLU epilogue. ``observer``
        picks the activation-range estimator (``"percentile"`` default,
        or ``"minmax"``). The accelerator stays float-in/float-out:
        ``__call__`` quantizes inputs by the calibrated input scale and
        dequantizes the int8 logits (a positive per-tensor rescale, so
        top-1 is taken on the same ordering the device computed).
        """
        specs = list(specs)
        if dtype not in ("float32", "int8"):
            raise ValueError(f"unsupported dtype {dtype!r}: expected "
                             f"'float32' or 'int8'")
        if dtype == "int8" and segmented:
            raise ValueError("segmented accelerators are fp32-only — the "
                             "int8 path needs the single-Program runtime "
                             "(the sidecar is keyed to one schedule)")
        dse = None
        if plans is None:
            if not isinstance(target, Target):
                raise TypeError(
                    f"target {target!r} does not implement the Target "
                    f"protocol (needs a run_dse(specs, batch) method) — pass "
                    f"e.g. pm.V5E, pm.VU9P, pm.PYNQ_Z1, or supply plans=")
            # dtype is only passed when quantizing, so custom fp32 targets
            # that predate the dtype parameter keep working unchanged
            dse = (target.run_dse(specs, batch=batch, dtype=dtype)
                   if dtype != "float32"
                   else target.run_dse(specs, batch=batch))
            plans = list(dse.plans)
        else:
            plans = list(plans)
        if params is None:
            params = random_params(specs, seed)

        quant = None
        if dtype == "int8":
            if calib is None:
                # stand-in calibration data, seeded like random_params: real
                # deployments pass a slice of the training set instead
                s0 = specs[0]
                shape = ((8, s0.d_in) if isinstance(s0, FCSpec)
                         else (8, s0.h, s0.w, s0.c))
                calib = np.random.default_rng(seed + 1).standard_normal(
                    shape).astype(np.float32)
            quant = quant_calibrate(specs, params, calib, observer=observer)
            params = quantize_params(specs, params, quant)

        if segmented:
            request, seg_rts, _ = build_segmented_request(
                specs, plans, params, strict=strict, cache=cache,
                backend=backend, interpret=interpret, opt_level=opt_level)
            return cls(specs=specs, plans=plans, params=params,
                       request=request, target=target, batch=batch, dse=dse,
                       segmented=True, segment_runtimes=seg_rts,
                       backend=backend, interpret=interpret,
                       opt_level=opt_level)

        program = compile_network(specs, plans)
        rt = HybridRuntime(program, strict=strict, cache=cache,
                           backend=backend, interpret=interpret,
                           opt_level=opt_level, quant=quant)
        rt.load_params(params)
        if not strict:
            rt.cache.validate(program)   # schedule check once, at build time
        return cls(specs=specs, plans=plans, params=params, request=rt.run,
                   target=target, batch=batch, program=program, runtime=rt,
                   dse=dse, backend=backend, interpret=interpret,
                   opt_level=opt_level, quant=quant)

    # -- inference ----------------------------------------------------------
    def __call__(self, x):
        """One inference request. ``x``: (n, H, W, C) for CONV-first models,
        (n, D) for FC-first. Steady-state calls are cache hits only.
        Quantized accelerators are float-in/float-out: float inputs are
        quantized by the calibrated input scale (already-int8 inputs pass
        through) and the int8 logits are dequantized back to fp32."""
        if self.quant is not None:
            y = self._request(jnp.asarray(x))   # runtime quantizes floats
            return self.quant.dequantize_output(y)
        return self._request(jnp.asarray(x, self.input_dtype))

    @property
    def input_dtype(self):
        if self.params:
            return self.params[0][0].dtype
        return jnp.float32

    @property
    def input_shape(self) -> tuple[int, ...]:
        """Shape of ONE request item (no batch dim)."""
        s0 = self.specs[0]
        if isinstance(s0, FCSpec):
            return (s0.d_in,)
        return (s0.h, s0.w, s0.c)

    @property
    def n_instructions(self) -> int:
        if self.program is not None:
            return len(self.program.instructions)
        return sum(len(rt.program.instructions)
                   for rt in self.segment_runtimes or [])

    def strict_request(self):
        """A per-instruction-interpreter request fn over the same Program(s)
        and params — the hazard-faithful baseline for comparisons. Always
        runs the XLA PE, regardless of this accelerator's ``backend``, so
        it can serve as the numerical oracle for the Pallas path too. For
        quantized accelerators the interpreter carries the same sidecar, so
        its int8 outputs are bitwise-comparable to the raw executor's."""
        if self.segmented:
            return build_segmented_request(
                self.specs, self.plans, self.params, strict=True)[0]
        rt = HybridRuntime(self.program, strict=True, quant=self.quant)
        rt.load_params(self.params)
        return rt.run

    # -- reporting ----------------------------------------------------------
    def _hw_desc(self) -> str:
        if self.dse is None:
            return "plans supplied (no DSE)"
        hw = self.dse.hw
        if isinstance(hw, TPUCandidate):
            return (f"blocks=({hw.bm},{hw.bk},{hw.bn}) m={hw.m} | DSE over "
                    f"{self.dse.candidates_searched} candidates")
        if isinstance(hw, FPGACandidate):
            return (f"PI={hw.pi} PO={hw.po} PT={hw.pt} NI={hw.ni} | DSE over "
                    f"{self.dse.candidates_searched} candidates")
        return str(hw)

    def summary(self) -> str:
        """Per-layer plan/latency table — the DSE verdict, human-readable."""
        # target is an instance with .name, or the bare name string a
        # from_program-restored accelerator carries
        tname = (self.target if isinstance(self.target, str)
                 else getattr(self.target, "name", None)) or "-"
        kind_of = {ConvSpec: "conv", PoolSpec: "pool", FCSpec: "fc",
                   EltwiseSpec: "eltwise", DepthwiseSpec: "dw"}
        head = (f"{len(self.specs)} layers as "
                + (f"{len(self.segment_runtimes)} segment Programs + host "
                   f"glue" if self.segmented else
                   f"ONE Program ({self.n_instructions} instructions)"))
        lines = [f"Accelerator[{tname}]: {head}",
                 f"  {self._hw_desc()}, batch={self.batch}",
                 f"  {'layer':<12}{'kind':<9}{'dtype':<9}{'mode':<6}"
                 f"{'df':<4}{'m':>2}{'g_h':>5}{'g_k':>5}"
                 f"  {'latency':>11}{'share':>8}"]
        lats = self.dse.layer_latencies if self.dse else None
        total = self.dse.total_latency if self.dse else None
        for i, (s, p) in enumerate(zip(self.specs, self.plans)):
            kind = kind_of[type(s)]
            p = p or NO_PLAN
            mode, df, m = (p.mode, p.dataflow, str(p.m)) \
                if kind == "conv" else ("-", "-", "-")
            gh, gk = ((str(p.g_h), str(p.g_k)) if kind == "conv"
                      else ("-", "-"))
            # precision per layer: "int8+rq" = int8 math with the fused
            # requantize epilogue, "int8" = scale-passthrough (pool)
            if self.quant is None:
                dt = "fp32"
            else:
                dt = ("int8+rq" if self.quant.layers[i].requantize
                      else "int8")
            lat = _fmt_t(lats[i]) if lats else "          -"
            share = (f"{100 * lats[i] / total:6.1f}%"
                     if lats and total else "      -")
            lines.append(f"  {s.name:<12}{kind:<9}{dt:<9}{mode:<6}{df:<4}"
                         f"{m:>2}{gh:>5}{gk:>5}  {lat}{share}")
        if total is not None:
            macs = sum(s.macs for s in self.specs)
            scale = self.batch if isinstance(self.dse.hw, TPUCandidate) else 1
            gops = 2.0 * macs * scale / total / 1e9
            lines.append(f"  est. total {_fmt_t(total).strip()} "
                         f"({gops:.1f} effective GOPS)")
        return "\n".join(lines)

    # -- persistence --------------------------------------------------------
    def save_program(self, path: str, *, aot: bool = False,
                     buckets: Sequence[int] | None = None) -> str:
        """Persist the compiled instruction stream + specs/plans + DSE
        verdict as JSON, so :meth:`from_program` can rebuild this
        accelerator without re-running the DSE. Params are NOT saved (they
        are the model's weights — supply them at load time).

        ``aot=True`` writes a **bundle directory** instead of a single
        file: ``program.json`` (the same document) plus ``aot/`` holding
        one serialized XLA executable per warmed entry — every serving
        ``bucket`` with input donation (the :class:`ServingSession` hot
        path; defaults to the session's power-of-two buckets up to
        ``self.batch``) and the direct-call entry at ``self.batch``. A
        bundle loaded by :meth:`from_program` serves its first request
        without tracing OR compiling; see ``repro.core.aot`` for the keying
        and fallback semantics."""
        if self.program is None:
            raise ValueError("segmented accelerators hold multiple Programs; "
                             "save_program supports the single-Program path")
        doc = {
            "format": PROGRAM_FORMAT,
            "target": (self.target if isinstance(self.target, str)
                       else getattr(self.target, "name", None)),
            "batch": self.batch,
            "specs": [_spec_to_dict(s) for s in self.specs],
            "plans": [dataclasses.asdict(cl.plan)
                      for cl in self.program.layers],
            "instructions": self.program.instruction_image().tolist(),
            "dse": None if self.dse is None else {
                "hw": _hw_to_dict(self.dse.hw),
                "layer_latencies": [float(v)
                                    for v in self.dse.layer_latencies],
                "total_latency": float(self.dse.total_latency),
                "candidates_searched": self.dse.candidates_searched,
            },
            # the quant sidecar rides ALONGSIDE the instruction stream (the
            # 128-bit words are untouched — int8 never changes the ISA);
            # its digest is bound to this schedule so a sidecar pasted from
            # a different calibration or program is rejected at load
            "quant": None if self.quant is None else {
                "sidecar": self.quant.to_dict(),
                "digest": self.quant.digest(self.program.schedule_key()),
            },
        }
        if not aot:
            with open(path, "w") as f:
                json.dump(doc, f)
            return path
        rt = self.runtime
        if rt is None or rt.strict:
            raise ValueError("aot=True needs the cached-executor runtime — "
                             "strict-interpreter accelerators have no "
                             "compiled executable to export")
        os.makedirs(path, exist_ok=True)
        with open(os.path.join(path, "program.json"), "w") as f:
            json.dump(doc, f)
        aot_dir = os.path.join(path, "aot")
        if buckets is None:
            buckets, b = [], 1
            while b < self.batch:
                buckets.append(b)
                b *= 2
            buckets.append(self.batch)
        in_shape = tuple(self.input_shape)
        dt = self.input_dtype
        for b in sorted({int(b) for b in buckets}):
            # the serving hot path: per-bucket executors donate their
            # staged input buffer
            rt.export_aot(aot_dir, (b, *in_shape), dt, donate_input=True)
        # the direct acc(x) path: batch-sized, no donation
        rt.export_aot(aot_dir, (self.batch, *in_shape), dt,
                      donate_input=False)
        return path

    @classmethod
    def from_program(cls, path: str, *, params: list | None = None,
                     strict: bool = False, cache=None, backend: str = "xla",
                     interpret: bool | None = None,
                     opt_level: int = 1) -> "Accelerator":
        """Rebuild an accelerator from :meth:`save_program` output — no DSE.

        The layer chain is recompiled from the saved specs/plans and the
        resulting stream is verified bit-exact against the saved instruction
        image; a mismatch (compiler/schedule drift) raises ``ValueError``
        rather than serving from a stream that was never validated.

        ``params`` is required: saved programs carry no weights, and
        silently substituting random ones would make a reloaded deployment
        serve garbage — pass ``api.random_params(specs, seed)`` explicitly
        if stand-in weights are what you want. ``backend``/``interpret``/
        ``opt_level`` select the PE implementation and lowering-optimizer
        level exactly as in :meth:`build` — the saved stream is agnostic to
        both, so one artifact deploys to every variant.

        ``path`` may also be an AOT bundle directory written by
        ``save_program(..., aot=True)``: the instruction image loads from
        its ``program.json`` and the runtime warm-starts executors from the
        serialized executables in ``aot/`` — skipping trace AND compile —
        whenever the full artifact key (including this host's device kind
        and jax version) matches; stale artifacts fall back to a fresh
        compile with the reason logged on ``repro.aot``.

        Malformed input — truncated/non-JSON file, unknown format version,
        instruction-stream mismatch, quant-sidecar digest bound to a
        different schedule — raises :class:`ProgramLoadError`.
        """
        if params is None:
            raise ValueError(
                "saved programs carry no weights — pass params=[...] "
                "(api.random_params(specs, seed) for stand-ins)")
        aot_dir = None
        doc_path = path
        if os.path.isdir(path):
            doc_path = os.path.join(path, "program.json")
            if not os.path.exists(doc_path):
                raise ProgramLoadError(
                    f"{path}: directory is not an AOT bundle — no "
                    f"program.json inside")
            d = os.path.join(path, "aot")
            aot_dir = d if os.path.isdir(d) else None
        try:
            with open(doc_path) as f:
                doc = json.load(f)
        except json.JSONDecodeError as e:
            raise ProgramLoadError(
                f"{doc_path}: truncated or not JSON ({e}) — the save was "
                f"interrupted or the file corrupted in transit") from e
        if doc.get("format") != PROGRAM_FORMAT:
            raise ProgramLoadError(
                f"{doc_path}: not a {PROGRAM_FORMAT} file "
                f"(format={doc.get('format')!r})")
        specs = [_spec_from_dict(d) for d in doc["specs"]]
        plans = [LayerPlan(**d) for d in doc["plans"]]
        program = compile_network(specs, plans)
        image = np.asarray(doc["instructions"], np.uint32).reshape(-1, 4)
        if not np.array_equal(program.instruction_image(), image):
            raise ProgramLoadError(
                f"{doc_path}: saved instruction stream does not match its "
                f"recompilation (compiler or schedule drift) — re-run "
                f"Accelerator.build and save again")
        quant = None
        if doc.get("quant"):
            q = doc["quant"]
            quant = QuantSidecar.from_dict(q["sidecar"])
            if quant.digest(program.schedule_key()) != q.get("digest"):
                raise ProgramLoadError(
                    f"{doc_path}: quant sidecar digest does not match this "
                    f"program's schedule — the sidecar was edited or "
                    f"belongs to a different calibration/program; re-run "
                    f"Accelerator.build(dtype='int8') and save again")
            # accept either fp32 weights (quantized here, deterministically
            # — the sidecar fixes every scale) or pre-quantized int8 ones
            if np.asarray(params[0][0]).dtype != np.int8:
                params = quantize_params(specs, params, quant)
        dse = None
        if doc.get("dse"):
            d = doc["dse"]
            dse = DSEResult(hw=_hw_from_dict(d["hw"]), plans=plans,
                            layer_latencies=d["layer_latencies"],
                            total_latency=d["total_latency"],
                            candidates_searched=d["candidates_searched"])
        rt = HybridRuntime(program, strict=strict, cache=cache,
                           backend=backend, interpret=interpret,
                           opt_level=opt_level, quant=quant,
                           aot_dir=aot_dir)
        rt.load_params(params)
        if not strict:
            rt.cache.validate(program)
        return cls(specs=specs, plans=plans, params=params, request=rt.run,
                   target=doc.get("target"), batch=doc.get("batch", 1),
                   program=program, runtime=rt, dse=dse,
                   backend=backend, interpret=interpret,
                   opt_level=opt_level, quant=quant)

    # -- serving ------------------------------------------------------------
    def serve(self, **kwargs) -> "ServingSession":
        """Open a :class:`ServingSession` over this accelerator — a
        padding-bucketed request-batching queue (see the class docs).
        ``mesh="host"`` shards batches over all local devices."""
        if kwargs.get("mesh") == "host":
            from repro.launch.mesh import make_host_mesh
            kwargs["mesh"] = make_host_mesh()
        return ServingSession(self, **kwargs)


# ---------------------------------------------------------------------------
# Serving: the request-batching queue (NI-instances analog)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SessionStats:
    requests: int = 0        # requests completed
    batches: int = 0         # executor invocations
    padded_rows: int = 0     # zero rows added to reach a bucket size
    dispatched_rows: int = 0  # real (non-pad) rows sent to the device(s)
    # -- failure model (see docs/ARCHITECTURE.md "Failure model") ----------
    # the accounting invariant every session maintains and the chaos soak
    # asserts: submitted == requests + errors + shed. A request lands in
    # exactly one of the three; deadline_exceeded is the subset of errors
    # failed by the deadline enforcer, isolated the subset quarantined
    # individually (poisoned-batch bisection or a numerics guard hit).
    submitted: int = 0           # requests accepted by submit()/run_many()
    errors: int = 0              # requests resolved with an exception
    deadline_exceeded: int = 0   # ... of which: missed their deadline_ms
    shed: int = 0                # refused at admission (queue_limit)
    retries: int = 0             # bisection re-dispatches after a failure
    isolated: int = 0            # requests individually quarantined
    degraded: int = 0            # batches recovered on the XLA fallback
    watchdog_restarts: int = 0   # pipeline restarts after a dead thread
    # first-use cost per bucket, split by how the executor came to exist so
    # the AOT warm-start win is measurable: compile_ms counts buckets that
    # traced + XLA-compiled in this process (warmup or first use);
    # warm_load_ms counts buckets whose executable deserialized from an AOT
    # bundle (repro.core.aot) — disk read + load + first dispatch, no
    # compile. One bucket lands in exactly one of the two.
    compile_ms: float = 0.0
    warm_load_ms: float = 0.0
    # device id -> batches dispatched there. A sharded batch counts once on
    # EVERY device it spans; a single-device batch counts on its one device
    # — so the table reads as per-device occupancy of the fleet.
    device_batches: dict = dataclasses.field(default_factory=dict)
    # whole-life timing, always on: per-request submit -> result latency
    # and submit -> dispatch queue wait (the scheduler-health metric:
    # continuous batching keeps it bounded by the batching window even
    # under backpressure), in log-spaced bins that lose no sample however
    # long the session lives (repro.serving.telemetry)
    latency_hist: LogHistogram = dataclasses.field(
        default_factory=LogHistogram)
    wait_hist: LogHistogram = dataclasses.field(default_factory=LogHistogram)
    # nanoseconds the host spent per phase, summed: staging on the callers'
    # threads (validate, int8 quantize, copy), assembling and launching on
    # the dispatch side, scattering results (callbacks included) on the
    # drain side. stage_ns is written under the session's admission lock,
    # the rest under _lat_lock.
    stage_ns: int = 0
    assemble_ns: int = 0
    launch_ns: int = 0
    deliver_ns: int = 0
    # counter writes from several threads and histogram reads share
    # _lat_lock
    _lat_lock: threading.Lock = dataclasses.field(
        default_factory=threading.Lock, repr=False)

    def bump(self, name: str, k: int = 1):
        """Thread-safe counter increment — the failure counters are bumped
        from the worker, drain, supervisor AND caller threads, and a bare
        ``+=`` read-modify-write can drop updates across them, which would
        break the exact-accounting invariant the chaos soak asserts."""
        with self._lat_lock:
            setattr(self, name, getattr(self, name) + k)

    def snapshot(self) -> "SessionStats":
        """A copy of every counter and histogram. Two snapshots difference
        to the stats of the window between them (``later - earlier``),
        whose percentiles read that window alone."""
        with self._lat_lock:
            return dataclasses.replace(
                self, latency_hist=self.latency_hist.copy(),
                wait_hist=self.wait_hist.copy(),
                device_batches=dict(self.device_batches),
                _lat_lock=threading.Lock())

    def __sub__(self, earlier: "SessionStats") -> "SessionStats":
        out = {}
        for f in dataclasses.fields(self):
            if f.name == "_lat_lock":
                continue
            a, b = getattr(self, f.name), getattr(earlier, f.name)
            out[f.name] = ({k: v - b.get(k, 0) for k, v in a.items()}
                           if isinstance(a, dict) else a - b)
        return SessionStats(**out)

    def _pct(self, hist: LogHistogram, q: float) -> float:
        with self._lat_lock:
            return hist.percentile(q)

    def p50_ms(self) -> float:
        """Median submit-to-result latency over the session's life (or a
        snapshot difference's window), to within one 4.4% bin."""
        return self._pct(self.latency_hist, 0.50)

    def p95_ms(self) -> float:
        """95th-percentile submit-to-result latency, as ``p50_ms``."""
        return self._pct(self.latency_hist, 0.95)

    def wait_p50_ms(self) -> float:
        """Median queue wait (submit -> dispatch), as ``p50_ms``."""
        return self._pct(self.wait_hist, 0.50)

    def wait_p95_ms(self) -> float:
        """95th-percentile queue wait, as ``p50_ms``."""
        return self._pct(self.wait_hist, 0.95)

    def occupancy(self) -> float:
        """Real-row fraction of all dispatched device rows (1.0 = no
        padding waste). The continuous-batching scheduler's win over fixed
        buckets on bursty traffic shows up here first."""
        total = self.dispatched_rows + self.padded_rows
        return self.dispatched_rows / total if total else 1.0


class _SlotPool:
    """FIFO-fair counting semaphore over device-pipeline slots.

    Each :class:`ServingSession` bounds its outstanding device batches with
    one of these (the classic triple buffer: one syncing, one executing,
    one staged). A :class:`Fleet` shares ONE pool across every tenant
    session, so device time round-robins between models: dispatch workers
    queue FIFO for the next free slot, and a model that just dispatched
    re-queues behind its peers — the paper's NI-instances arbitration,
    host-side.
    """

    def __init__(self, capacity: int):
        if capacity < 1:
            raise ValueError("slot pool capacity must be >= 1")
        self.capacity = int(capacity)
        self._free = self.capacity
        self._cv = threading.Condition()
        self._waiters: deque = deque()
        self._subscribers: list[threading.Condition] = []

    def subscribe(self, cv: threading.Condition):
        """Register a condition to notify on every release — session
        admitters sleep on their own ``_cv`` while the pipeline is full, so
        a freed slot must wake them there."""
        with self._cv:
            self._subscribers.append(cv)

    def available(self) -> bool:
        """Lock-free hint (admission heuristics only, never correctness)."""
        return self._free > 0

    def busy(self) -> bool:
        """Lock-free hint: any slot taken — the device (pool-wide, across a
        Fleet's tenants) still has dispatched work in flight."""
        return self._free < self.capacity

    def acquire(self, cancelled=None) -> bool:
        """Block for a slot; returns True once acquired. ``cancelled`` (a
        nullary predicate, polled while waiting) lets a dispatch worker
        abandon the wait when its pipeline generation is retired — without
        it, a worker queued on a pool whose holder crashed would block a
        watchdog restart forever. Returns False when cancelled."""
        token = object()
        with self._cv:
            self._waiters.append(token)
            while self._free <= 0 or self._waiters[0] is not token:
                if cancelled is not None and cancelled():
                    self._waiters.remove(token)
                    self._cv.notify_all()   # next in line may now be eligible
                    return False
                self._cv.wait(None if cancelled is None else 0.05)
            self._waiters.popleft()
            self._free -= 1
            if self._free > 0:
                self._cv.notify_all()   # next waiter in line may also go
            return True

    def release(self):
        with self._cv:
            # clamp: watchdog crash-recovery frees slots on behalf of dead
            # threads; if a presumed-dead thread still manages a release,
            # the pool must not inflate past its capacity
            self._free = min(self._free + 1, self.capacity)
            self._cv.notify_all()
        for cv in self._subscribers:
            with cv:
                cv.notify_all()


class _Request:
    """One staged request flowing through the session pipeline."""

    __slots__ = ("x", "single", "fut", "t_submit", "rid", "deadline",
                 "deadline_ms", "off")

    def __init__(self, x, single: bool, fut: Future | None,
                 t_submit: float, rid: int,
                 deadline: float | None = None,
                 deadline_ms: float | None = None):
        self.x = x                    # staged host array (k, *input_shape)
        self.single = single          # un-batched submit: scatter row 0
        self.fut = fut                # None on run_many's inline bulk path
        self.t_submit = t_submit
        self.rid = rid                # session-unique id (fault targeting)
        self.deadline = deadline      # absolute monotonic, None = none
        self.deadline_ms = deadline_ms
        self.off = 0                  # row offset inside its staged bucket


class ServingSession:
    """Padding-bucketed request-batching queue over the cached executor,
    with pipelined dispatch.

    Callers ``submit()`` single items (H, W, C) or small batches
    (n, H, W, C) and get a ``Future``; a dispatch worker coalesces pending
    requests into device batches of at most ``max_batch`` items, pads each
    batch up to the nearest size in ``buckets`` (so the jit cache holds one
    executor per bucket instead of one per observed batch size), runs the
    accelerator's cached executor directly (no per-request DRAM dict work),
    and scatters the rows back to the futures in submission order.

    The hot path is **pipelined**, the software analog of the paper's
    LOAD/COMP/SAVE overlap: the dispatch worker launches device batch i+1
    while batch i is still in flight (JAX dispatch is asynchronous), and a
    separate drain thread blocks on completed batches and resolves their
    futures — the host-side numpy staging of one batch overlaps the device
    compute of the previous one. Staging uses two preallocated numpy
    buffers per bucket, reused alternately; a buffer is free for refill as
    soon as its batch is dispatched, because ``jnp.asarray`` copies
    host->device. Outstanding device batches are hard-capped at 3 (one
    being synced by the drain thread, one executing, one freshly staged —
    triple buffering), so the session never runs unboundedly ahead of the
    device. Per-bucket executors donate their input buffer (the staged
    device array is never reused), so steady-state batches allocate no
    fresh activation input.

    The session inherits the accelerator's PE ``backend`` and lowering
    ``opt_level``: per-bucket executors are fetched through
    ``HybridRuntime.executor_entry``, which keys the program cache on
    ``(schedule, bucket, dtype, backend, interpret, opt_level, donate,
    mesh)`` — an ``Accelerator.build(..., backend="pallas")`` session
    serves every request through the Pallas PE kernels.

    ``mesh``: a ``jax.sharding.Mesh`` — device batches whose bucket size is
    a multiple of the device count run through the **shard_map'd executor
    variant** (batch axis split over every mesh axis, weights replicated
    once at session start), the paper's NI-instances analog. Because each
    shard replays the whole per-shard program locally, this works for
    ``backend="pallas"`` too — GSPMD can't split the custom call, but
    inside the mapped region there is nothing left to split. Straggler
    buckets that don't divide by the device count fall back to the
    single-device executor, so both entry families coexist in one cache.

    ``scheduler`` selects the admission policy:

    * ``"continuous"`` (default) — continuous batching: the admitter fills
      the next in-flight device batch straight from the pending queue. The
      batching window (``max_wait_ms``) only caps the wait while a device
      slot is FREE; while the pipeline is full the admitter keeps admitting
      into the open batch instead of cutting it (dispatch is impossible
      anyway), so batches grow to fill devices under backpressure and
      padding collapses on bursty traffic.
    * ``"bucketed"`` — the legacy fixed-window policy: cut the batch when
      the window expires regardless of pipeline state, pad up to the
      bucket. Kept as the reference the scheduler tests compare against.

    ``stats`` records, besides request/batch counts, the trace+compile
    time spent on warmup and first-use buckets (``compile_ms``), per-device
    batch counts (``device_batches``), padding ``occupancy()``, and over
    the session's whole life: log-spaced histograms of per-request
    submit-to-result latency (``latency_hist``; ``p50_ms()`` /
    ``p95_ms()``) and queue wait (``wait_hist``; ``wait_p50_ms()`` /
    ``wait_p95_ms()``), each to within one 4.4% bin, and the host's busy
    nanoseconds per phase (``stage_ns``, ``assemble_ns``, ``launch_ns``,
    ``deliver_ns``). ``stats.snapshot()`` copies them; two snapshots
    difference to one window's stats. ``record_spans()`` records the host
    spans of each request and device batch on the ``time.time_ns`` clock,
    for laying over a profiler trace of the device.

    ``slot_pool`` shares the device-pipeline slots with other sessions — a
    :class:`Fleet` passes one pool to every tenant model so device slots
    round-robin between them; standalone sessions get a private pool of 3.

    **Failure model** (full semantics in ``docs/ARCHITECTURE.md``):

    * ``deadline_ms`` (session default, overridable per ``submit``) — a
      request whose result has not drained by its deadline resolves with
      :class:`repro.serving.DeadlineExceeded` instead of hanging; the
      continuous admitter caps its coalescing hold at the earliest
      deadline in the open batch.
    * ``queue_limit`` + ``on_overload`` (``"shed"`` | ``"block"``) —
      bounded admission: past the limit, ``"shed"`` returns a future
      pre-failed with :class:`repro.serving.Overloaded`; ``"block"``
      makes ``submit`` wait for queue space.
    * poisoned-batch isolation — a failed coalesced batch is bisected and
      re-dispatched at the SAME bucket size with the excluded rows zeroed
      in place, so innocent co-batched requests still succeed
      **bitwise-identically** to a fault-free run; the offender fails with
      the causal exception (``stats.retries`` / ``stats.isolated``).
    * graceful backend degradation — on a ``backend="pallas"`` execution
      failure the whole batch is re-dispatched once through the XLA
      lowering (``stats.degraded``) before bisection, mirroring the AOT
      warn-and-recompile path.
    * ``guard_numerics`` — per-request NaN/Inf quarantine at drain time
      (:class:`repro.serving.NumericsError`); finite co-batched results
      still resolve.
    * supervision — a per-session supervisor thread enforces deadlines and
      watches the dispatch/drain threads (``is_alive`` + the
      ``HeartbeatMonitor``-based hang detector when ``hang_after_s`` is
      set). A dead thread fails every queued/in-flight future with
      :class:`repro.serving.PipelineCrashed` (causal exception chained),
      frees the dead thread's device slots and restarts the pipeline
      (``stats.watchdog_restarts``); ``close()`` stays idempotent through
      all of it.
    * ``fault_plan`` — a :class:`repro.serving.FaultPlan` wired into the
      pipeline boundaries for deterministic fault injection (tests/CI).

    The accounting invariant across all of the above:
    ``stats.submitted == stats.requests + stats.errors + stats.shed``
    once every accepted future has resolved.
    """

    SCHEDULERS = ("continuous", "bucketed")

    def __init__(self, acc: Accelerator, *, max_batch: int = 8,
                 buckets: Sequence[int] | None = None, mesh=None,
                 max_wait_ms: float = 5.0, warmup: bool = False,
                 scheduler: str = "continuous",
                 slot_pool: _SlotPool | None = None,
                 deadline_ms: float | None = None,
                 queue_limit: int | None = None,
                 on_overload: str = "shed",
                 guard_numerics: bool = False,
                 fault_plan=None,
                 supervise: bool = True,
                 hang_after_s: float | None = None):
        if max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        if scheduler not in self.SCHEDULERS:
            raise ValueError(f"unknown scheduler {scheduler!r}: expected "
                             f"one of {self.SCHEDULERS}")
        if on_overload not in ("shed", "block"):
            raise ValueError(f"on_overload must be 'shed' or 'block', "
                             f"got {on_overload!r}")
        if queue_limit is not None and queue_limit < 1:
            raise ValueError("queue_limit must be >= 1")
        self.acc = acc
        self.scheduler = scheduler
        self.max_batch = int(max_batch)
        if buckets is None:
            buckets, b = [], 1
            while b < self.max_batch:
                buckets.append(b)
                b *= 2
            buckets.append(self.max_batch)
        self.buckets = tuple(sorted({int(b) for b in buckets}))
        if self.buckets[-1] < self.max_batch or self.buckets[0] < 1:
            raise ValueError(
                f"buckets {self.buckets} must cover max_batch={max_batch}")
        self.stats = SessionStats()
        # resolve once: input_dtype/input_shape are properties that walk
        # the param tree — too costly to re-derive on every submit()
        self._in_dtype = np.dtype(acc.input_dtype)
        self._in_shape = tuple(acc.input_shape)
        # quantized accelerators keep the session float-in/float-out:
        # floats are quantized host-side at staging (so the device batch is
        # int8 end to end) and int8 logits dequantized at drain
        self._quant = acc.quant
        self._single_rank = len(self._in_shape)
        self._max_wait = max(0.0, max_wait_ms) / 1e3
        self._pending: deque = deque()
        self._cv = threading.Condition()
        self._closed = False

        # -- failure model state --------------------------------------------
        self._deadline_default = (None if deadline_ms is None
                                  else max(0.0, float(deadline_ms)))
        self.queue_limit = queue_limit
        self.on_overload = on_overload
        self._guard_numerics = bool(guard_numerics)
        self._faults = fault_plan
        self._rid_counter = itertools.count()
        self._batch_seq = itertools.count()
        self._spans: SpanRecorder | None = None   # see record_spans()
        self._deadlines = DeadlineTable()
        self._backend_tag = getattr(acc, "backend", "xla") or "xla"
        self._fallback_entries: dict[int, Any] = {}  # lazy XLA degradation
        self._fallback_lock = threading.Lock()
        # pipeline generation: bumped by the watchdog on restart; stale
        # threads check it and stand down without touching shared state
        self._gen = 0
        self._life_lock = threading.Lock()   # serializes restart vs close
        self._closed_done = False
        self._worker_exited_clean = False
        # slot bookkeeping the watchdog uses to free a dead thread's slots:
        # flags only ever flip in the owning thread, and are only read by
        # the watchdog after that thread is confirmed dead/joined
        self._worker_holds_slot = False
        self._drain_popped_unreleased = False
        # the group a pipeline thread is actively working on, visible so a
        # crash mid-dispatch / mid-deliver (group popped from the shared
        # deques, held only in the thread's locals) cannot strand futures:
        # the watchdog fails whatever a confirmed-dead thread left here
        self._worker_group: list | None = None
        self._drain_group: list | None = None
        self._thread_exc: BaseException | None = None   # causal, for restart
        self._sup = (ThreadSupervisor(("dispatch", "drain"),
                                      hang_after_s=hang_after_s)
                     if supervise else None)
        self._sup_cv = threading.Condition()
        self._sup_stop = False
        self._sup_thread: threading.Thread | None = None

        # hot path: one cached executor entry per bucket (validated once,
        # lowered once per bucket), donating the staged input buffer.
        # Falls back to acc(x) for segmented / strict accelerators.
        self._entries: dict[int, Any] = {}
        self._sharded_entries: dict[int, Any] = {}
        self._params = None
        self._params_sharded = None
        rt = acc.runtime
        if rt is not None and not rt.strict:
            # donation is best-effort (see the module-level warnings filter).
            # With an AOT bundle the deserialize happens HERE, inside
            # executor_entry -> cache.get — count it as warm-load time so
            # the stats line shows where the cold start went
            for b in self.buckets:
                t0 = time.monotonic()
                self._entries[b], self._params = rt.executor_entry(
                    b, acc.input_dtype, donate_input=True)
                if getattr(self._entries[b], "aot_loaded", False):
                    self.stats.warm_load_ms += (time.monotonic() - t0) * 1e3

        self._mesh = mesh
        self._n_devices = 1
        self._fleet_device_ids: tuple[int, ...] = (
            int(jax.devices()[0].id),)      # where unsharded batches land
        self._local_device_ids = self._fleet_device_ids
        if mesh is not None:
            self._n_devices = int(np.prod(mesh.devices.shape))
            if self._n_devices > 1 and self._params is None:
                # refuse rather than silently serve unsharded: sharding
                # needs the direct executor-entry hot path
                raise ValueError(
                    "mesh sharding requires the single-Program cached "
                    "executor path — segmented/strict accelerators can't "
                    "shard over the mesh")
            if self._n_devices > 1:
                # sharded executor variants for every bucket the mesh
                # divides evenly; stragglers keep the single-device entries.
                # Works for backend="pallas" too: each shard runs the whole
                # per-shard program locally under shard_map, so there is no
                # custom call left for GSPMD to split.
                for b in self.buckets:
                    if b % self._n_devices == 0:
                        self._sharded_entries[b], _ = rt.executor_entry(
                            b, acc.input_dtype, donate_input=True, mesh=mesh)
                if not self._sharded_entries:
                    raise ValueError(
                        f"no bucket in {self.buckets} divides evenly over "
                        f"the mesh's {self._n_devices} devices — sharded "
                        f"serving would never engage")
                # weights replicated once at session start; the separate
                # unsharded copy stays for straggler buckets (a replicated
                # array handed to the single-device jit would reshard on
                # every call)
                self._params_sharded = jax.device_put(
                    self._params,
                    jax.NamedSharding(mesh, jax.sharding.PartitionSpec()))
                self._x_sharding = jax.NamedSharding(
                    mesh, jax.sharding.PartitionSpec(tuple(mesh.axis_names)))
                self._fleet_device_ids = tuple(
                    int(d.id) for d in mesh.devices.flat)
                self._local_device_ids = (self._fleet_device_ids[0],)

        # completion pipeline: dispatched-but-unresolved batches, FIFO.
        # The slot pool bounds every outstanding device batch — the one the
        # drain thread is syncing, one executing, and one freshly staged —
        # the classic triple-buffer pipeline. The drainer holds its slot
        # until the host sync completes, so this is a hard device-memory
        # cap, not a soft target. A Fleet passes one shared pool so its
        # tenant models round-robin the same slots.
        self._inflight: deque = deque()
        self._inflight_cv = threading.Condition()
        # serializes staging+dispatch between the worker thread and
        # run_many's inline bulk path (both cycle the staging ring)
        self._dispatch_mutex = threading.Lock()
        self._slots = slot_pool if slot_pool is not None else _SlotPool(3)
        self._slots.subscribe(self._cv)   # full-pipeline admitters sleep
                                          # on _cv; wake them on slot free

        # host staging: a ring of numpy buffers per bucket, one per pipeline
        # slot, cycled per dispatch. The ring size MUST be >= the slot
        # capacity: a buffer is only refilled once its batch's slot has been
        # released (drained), so even if jax's CPU device_put zero-copies an
        # aligned host buffer instead of copying, no refill can race an
        # in-flight execution still reading it. (Two buffers against a
        # 3-deep pipeline let batch i+2 clobber batch i's input mid-run —
        # observed as rare wrong-row outputs under load.)
        self._staging = {
            b: [np.empty((b, *acc.input_shape),
                         np.dtype(acc.input_dtype))
                for _ in range(self._slots.capacity)]
            for b in self.buckets}
        self._staging_flip: dict[int, int] = {b: 0 for b in self.buckets}
        # run_many's inline bulk path gets its OWN ring: the worker and the
        # bulk path each release slots FIFO within themselves but interleave
        # arbitrarily across threads, so a shared ring could refill a buffer
        # whose batch is still in flight on the other path (lazily built —
        # most sessions never bulk-run every bucket)
        self._staging_bulk: dict[int, list] = {}
        self._bulk_flip: dict[int, int] = {}

        self._warm: set[int] = set()
        if warmup:   # pre-trace every bucket so first requests don't stall
            with _expected_donation_noise():
                for b in self.buckets:
                    z = jnp.zeros((b, *acc.input_shape), acc.input_dtype)
                    t0 = time.monotonic()
                    jax.block_until_ready(self._run_bucket(z))
                    self._count_first_use(b, t0)
                    self._warm.add(b)

        self._start_pipeline_threads()
        if supervise:
            self._sup_thread = threading.Thread(
                target=self._supervise, daemon=True,
                name="hybriddnn-serving-watchdog")
            self._sup_thread.start()

    def _start_pipeline_threads(self):
        """(Re)start the dispatch + drain pair for the current generation.
        Thread targets take the generation by value: a restarted pipeline
        must never process state a stale thread still thinks it owns."""
        gen = self._gen
        self._worker_exited_clean = False
        self._dispatch_thread = threading.Thread(
            target=self._worker, args=(gen,), daemon=True,
            name=f"hybriddnn-serving-g{gen}")
        self._drain_thread = threading.Thread(
            target=self._drainer, args=(gen,), daemon=True,
            name=f"hybriddnn-serving-drain-g{gen}")
        self._dispatch_thread.start()
        self._drain_thread.start()

    # -- client side --------------------------------------------------------
    def _stage(self, x) -> tuple[np.ndarray, bool]:
        """Validate + host-stage one request (no jax dispatch, no locks)."""
        x = np.asarray(x)
        if self._quant is not None and np.issubdtype(x.dtype, np.floating):
            # round-and-clip by the calibrated input scale — a bare dtype
            # cast would TRUNCATE floats toward zero and skip the clip
            x = np.clip(
                np.round(x.astype(np.float32)
                         / np.float32(self._quant.input_scale)),
                -127, 127).astype(self._in_dtype)
        else:
            x = np.asarray(x, self._in_dtype)
        if x.ndim == self._single_rank:
            x, single = x[None], True
        elif x.ndim == self._single_rank + 1:
            single = False
        else:
            raise ValueError(
                f"request rank {x.ndim} does not match input shape "
                f"{self._in_shape} (+ optional batch dim)")
        if not 1 <= x.shape[0] <= self.max_batch:
            raise ValueError(
                f"request batch {x.shape[0]} must be between 1 and "
                f"max_batch={self.max_batch}")
        if tuple(x.shape[1:]) != self._in_shape:
            # reject here, not in the worker: a malformed item would fail
            # the batch assembly and poison every co-batched request
            raise ValueError(
                f"request item shape {tuple(x.shape[1:])} does not match "
                f"the accelerator input shape {self.acc.input_shape}")
        return x, single

    def _make_request(self, x, fut: Future | None, now: float,
                      deadline_ms: float | None) -> _Request:
        """Stage + wrap one request; assigns its session-unique id and
        resolves its absolute deadline. The fault harness's ``staging``
        site fires here, on the caller's thread, against a private copy of
        the staged array (corruption must never alias the caller's
        buffer)."""
        xs, single = self._stage(x)
        rid = next(self._rid_counter)
        if self._faults is not None:
            xs = self._faults.visit(
                "staging", payload=np.array(xs), requests=(rid,),
                rows={rid: (0, xs.shape[0])})
        dl_ms = (self._deadline_default if deadline_ms is None
                 else max(0.0, float(deadline_ms)))
        dl = None if dl_ms is None else now + dl_ms / 1e3
        return _Request(xs, single, fut, now, rid, dl, dl_ms)

    def _queue_full(self) -> bool:
        """Caller holds ``_cv``. Compacts already-resolved (deadline-
        expired/cancelled) entries out of the queue before refusing —
        a dead request must not occupy admission capacity."""
        if len(self._pending) < self.queue_limit:
            return False
        self._pending = deque(
            r for r in self._pending
            if r.fut is None or not r.fut.done())
        return len(self._pending) >= self.queue_limit

    def _enqueue(self, reqs: list[_Request], stage_ns: int):
        """Admission control: bounded queue with shed-or-block overflow,
        deadline registration, exact ``submitted`` accounting."""
        st = self.stats
        notify_sup = False
        with self._cv:
            if self._closed:
                raise RuntimeError("ServingSession is closed")
            st.stage_ns += stage_ns
            for req in reqs:
                if self.queue_limit is not None and self._queue_full():
                    if self.on_overload == "block":
                        while self._queue_full() and not self._closed:
                            self._cv.wait(0.05)
                        if self._closed:
                            raise RuntimeError("ServingSession is closed")
                    else:
                        st.bump("submitted")
                        st.bump("shed")
                        req.fut.set_exception(Overloaded(
                            f"pending queue at queue_limit="
                            f"{self.queue_limit}; request shed"))
                        continue
                st.bump("submitted")
                self._pending.append(req)
                if req.deadline is not None:
                    if self._deadlines.add(req.deadline, req):
                        notify_sup = True
            self._cv.notify()
        if notify_sup and self._sup_thread is not None:
            with self._sup_cv:   # new earliest deadline: shorten the nap
                self._sup_cv.notify_all()

    def _stage_all(self, xs, deadline_ms: float | None, fut=Future
                   ) -> tuple[list[_Request], int]:
        """Stage + wrap the requests of one call on the caller's thread;
        returns them and the nanoseconds it took (one ``session.stage``
        span, ``ref`` = the first request id)."""
        t0 = time.perf_counter_ns()
        now = time.monotonic()
        reqs = [self._make_request(x, fut(), now, deadline_ms) for x in xs]
        dt = time.perf_counter_ns() - t0
        spans = self._spans
        if spans is not None and reqs:
            spans.add("session.stage", dt, reqs[0].rid)
        return reqs, dt

    @contextmanager
    def record_spans(self):
        """Record the session's host spans while the block runs; yields the
        :class:`repro.serving.telemetry.SpanRecorder`. Off by default, and
        then each span site costs one ``is None`` test.

        Spans, on the ``time.time_ns`` clock: ``session.stage`` (caller's
        thread: validate, int8 quantize, copy; one per ``submit`` /
        ``submit_many`` / ``run_many`` call), then per device batch
        ``session.admit`` (the dispatch side's coalescing hold, first
        request taken to the cut), ``session.slot_wait`` (waiting for a
        pipeline slot), ``session.assemble`` (the staging buffer),
        ``session.launch`` (device put and executor enqueue),
        ``session.sync`` (the drain side's wait for the device, plus int8
        dequantize) and ``session.deliver`` (scattering rows to the
        futures, their callbacks included)."""
        if self._spans is not None:
            raise RuntimeError("this session is already recording spans")
        self._spans = rec = SpanRecorder()
        try:
            yield rec
        finally:
            self._spans = None

    def submit(self, x, *, deadline_ms: float | None = None) -> Future:
        """Enqueue one request; returns a Future of the result (a single
        item's logits for single-item requests, a batch for batched ones).

        The request is staged host-side (numpy): no jax dispatch happens on
        the caller's thread — the dispatch worker launches one device call
        per coalesced bucket. ``deadline_ms`` overrides the session default
        for this request: past it, the future resolves with
        :class:`repro.serving.DeadlineExceeded` rather than waiting for a
        result. When the session has a ``queue_limit`` and the queue is
        full, ``on_overload="shed"`` returns a future pre-failed with
        :class:`repro.serving.Overloaded`; ``"block"`` waits for space."""
        reqs, stage_ns = self._stage_all([x], deadline_ms)
        self._enqueue(reqs, stage_ns)
        return reqs[0].fut

    def submit_many(self, xs, *, deadline_ms: float | None = None
                    ) -> list[Future]:
        """Enqueue a whole request list under ONE lock acquisition.

        Per-request ``submit`` wakes the dispatch worker once per call —
        for a burst of hundreds of already-materialized requests that lock
        traffic alone costs more than a device batch. Validation happens
        before anything enqueues, so a malformed request poisons nothing.
        """
        reqs, stage_ns = self._stage_all(xs, deadline_ms)
        self._enqueue(reqs, stage_ns)
        return [r.fut for r in reqs]

    def __call__(self, x):
        """Synchronous convenience: submit + wait."""
        return self.submit(x).result()

    def run_many(self, xs) -> list:
        """Run a whole request list; returns results in request order.

        Bulk traffic takes an inline pipelined path: the calling thread
        stages and dispatches full device batches itself (same executor
        entries, same slot pool, same stats), keeping up to the pool's
        capacity in flight and syncing oldest-first. Skipping the
        worker/drain thread handoff matters on small hosts: two context
        switches per ~5ms batch is a few percent of throughput — the
        difference between beating the caller-batched direct loop and
        trailing it. Concurrent ``submit()`` traffic stays correct (the
        dispatch mutex serializes staging; the shared slot pool keeps
        device arbitration FIFO-fair), it just isn't co-batched with the
        bulk run."""
        reqs, stage_ns = self._stage_all(xs, None, fut=lambda: None)
        if not reqs:
            return []
        with self._cv:
            if self._closed:
                raise RuntimeError("ServingSession is closed")
            self.stats.stage_ns += stage_ns
        self.stats.bump("submitted", len(reqs))
        # cut [start, end) item groups of <= max_batch rows
        groups, start, n = [], 0, 0
        for i, r in enumerate(reqs):
            k = r.x.shape[0]
            if n + k > self.max_batch:
                groups.append((start, i, n))
                start, n = i, 0
            n += k
        groups.append((start, len(reqs), n))
        out: list = [None] * len(reqs)
        errs: list[Exception] = []
        inflight: deque = deque()   # (start, end, y, bucket, buf, seq)

        def _deliver_bulk(s0, outcomes):
            st = self.stats
            for i, (r, ok, val) in enumerate(outcomes):
                if ok:
                    gexc = self._guard(r, val)
                    if gexc is None:
                        out[s0 + i] = val[0] if r.single else val
                        st.bump("requests")
                        continue
                    st.bump("isolated")
                    val = gexc
                errs.append(val)
                st.bump("errors")

        def _sync_oldest():
            s0, e0, y, bucket, buf, seq = inflight.popleft()
            group = reqs[s0:e0]
            try:
                if self._faults is not None:
                    self._faults.visit(
                        "drain", requests=[r.rid for r in group])
                y_np = self._sync(y, seq)        # host sync (+ dequant)
            except Exception as exc:  # noqa: BLE001 — recover per request
                # recover BEFORE releasing the slot: the staging ring must
                # not refill ``buf`` until the bisection has re-read it
                try:
                    _deliver_bulk(s0, self._recover(group, bucket, buf, exc))
                finally:
                    self._slots.release()
                return
            self._slots.release()
            done_t = time.monotonic()
            self.stats.bump("batches")
            _deliver_bulk(
                s0, [(r, True, y_np[r.off:r.off + r.x.shape[0]])
                     for r in group])
            st = self.stats
            with st._lat_lock:
                for r in group:
                    st.latency_hist.add((done_t - r.t_submit) * 1e3)

        try:
            for s0, e0, n in groups:
                if len(inflight) >= self._slots.capacity:
                    _sync_oldest()   # never self-deadlock on the pool
                group = reqs[s0:e0]
                seq = next(self._batch_seq)
                self._slots.acquire()
                bucket = buf = None
                try:
                    with self._dispatch_mutex:
                        bucket, buf = self._stage_group(group, n, seq,
                                                        bulk=True)
                    y = self._launch(bucket, buf, group, seq)
                except Exception as e:  # noqa: BLE001 — recover per request
                    try:
                        if buf is None:
                            raise    # staging failed: nothing to recover
                        _deliver_bulk(
                            s0, self._recover(group, bucket, buf, e))
                    finally:
                        self._slots.release()
                    continue
                except BaseException:
                    self._slots.release()
                    raise
                inflight.append((s0, e0, y, bucket, buf, seq))
        finally:
            while inflight:     # release EVERY held slot even on error
                try:
                    _sync_oldest()
                except Exception as e:  # noqa: BLE001 — keep draining
                    errs.append(e)
        if errs:
            self._raise_joined(errs)
        return out

    @staticmethod
    def _raise_joined(errs: list[Exception]):
        """Raise the first error; the rest are attached as notes (3.11+)
        and ``secondary_errors``, and logged — a multi-slot failure must
        not silently swallow every error after the first."""
        first, rest = errs[0], errs[1:]
        for e in rest:
            log.error("serving: additional in-flight batch failure "
                      "(suppressed by %r): %r", first, e)
            if hasattr(first, "add_note"):   # pragma: no cover — py3.11+
                first.add_note(f"additionally failed: {e!r}")
        first.secondary_errors = tuple(rest)
        raise first

    def close(self):
        """Drain and shut down. Idempotent, and safe mid-failure: a
        pipeline that crashed (dead worker/drain thread) cannot strand
        ``close`` — joins are bounded, a missing drain sentinel is
        re-queued, and whatever is left queued/in-flight afterwards is
        failed with :class:`repro.serving.PipelineCrashed` and its device
        slots returned to the pool."""
        with self._life_lock:
            if self._closed_done:
                return
            with self._cv:
                self._closed = True
                self._cv.notify_all()
            self._dispatch_thread.join(timeout=60.0)
            if not self._worker_exited_clean:
                # the worker died without queueing the drain sentinel
                # (crashed or stale): queue it so the drainer can exit
                with self._inflight_cv:
                    self._inflight.append(None)
                    self._inflight_cv.notify_all()
            self._drain_thread.join(timeout=60.0)
            exc = PipelineCrashed("ServingSession closed while its "
                                  "pipeline was down")
            exc.__cause__ = self._thread_exc
            self._fail_all_queued(exc)
            self._closed_done = True
        if self._sup_thread is not None:
            with self._sup_cv:
                self._sup_stop = True
                self._sup_cv.notify_all()
            self._sup_thread.join(timeout=10.0)

    def _fail_all_queued(self, exc):
        """Fail every queued + in-flight request and return their pipeline
        slots. Only called with the pipeline threads dead or joined (close
        after join; watchdog after gen retirement), so the deques are not
        concurrently drained."""
        with self._cv:
            pending = list(self._pending)
            self._pending.clear()
            self._cv.notify_all()
        with self._inflight_cv:
            items = [it for it in self._inflight if it is not None]
            self._inflight.clear()
            self._inflight_cv.notify_all()
        for _ in range(len(items)):
            self._slots.release()
        # a dead thread's locals: its held slot, and the group it popped
        # from the shared deques but never handed off/delivered — without
        # collecting these, a crash mid-dispatch or mid-deliver would
        # strand futures forever (the liveness invariant's hardest case)
        stranded = []
        if not self._dispatch_thread.is_alive():
            if self._worker_holds_slot:
                self._worker_holds_slot = False
                self._slots.release()
            if self._worker_group:
                stranded.extend(self._worker_group)
                self._worker_group = None
        if not self._drain_thread.is_alive():
            if self._drain_popped_unreleased:
                self._drain_popped_unreleased = False
                self._slots.release()
            if self._drain_group:
                stranded.extend(self._drain_group)
                self._drain_group = None
        for it in items:
            for r in it[0]:
                self._reject_req(r, exc)
        for r in stranded:
            self._reject_req(r, exc)
        for r in pending:
            self._reject_req(r, exc)
        return len(items) + (1 if stranded else 0), len(pending)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # -- dispatch side ------------------------------------------------------
    def _take_group(self, gen: int):
        """Admit pending requests into one device batch (<= max_batch).

        ``"bucketed"``: the legacy fixed window — cut when ``max_wait_ms``
        expires, whatever the pipeline is doing. ``"continuous"``: the
        window only caps the wait while the device pipeline is IDLE — while
        any batch is still in flight, cutting a partial group early buys
        nothing (it would only queue behind the in-flight work) and wastes
        device time on padding, so the admitter keeps folding arrivals into
        the open batch until the pipeline drains or the batch fills. A hard
        cap (several windows) bounds the hold so a co-tenant model that
        keeps the shared slot pool busy can never starve a straggler —
        past it the group is cut and padded like the legacy path. The
        drainer wakes us (via the slot pool's subscriber hook) the moment a
        slot frees; the short wait below is only a backstop against a
        missed wakeup.

        Failure-model extensions: already-resolved requests (deadline
        expired / cancelled while queued) are dropped instead of admitted;
        the coalescing hold is additionally capped at the earliest
        deadline in the open batch (holding past it would guarantee a
        ``DeadlineExceeded``); and a retired generation (watchdog restart)
        hands its partial batch back to the queue and stands down.

        Returns ``(group, n, stale, hold_ns)``, ``hold_ns`` the coalescing
        hold from the first request taken to the cut (0 with spans off).
        """
        continuous = self.scheduler == "continuous"
        with self._cv:
            while (not self._pending and not self._closed
                   and self._gen == gen):
                self._beat("dispatch")
                self._cv.wait(0.25)
            if self._gen != gen:
                return None, 0, True, 0
            if not self._pending:
                return None, 0, False, 0    # closed and drained
            # the hold is timed only for the session.admit span
            t_hold = time.perf_counter_ns() if self._spans is not None else 0
            group, n = [], 0
            deadline = time.monotonic() + self._max_wait
            hard_deadline = deadline + 8 * self._max_wait
            while True:
                while (self._pending
                       and n + self._pending[0].x.shape[0] <= self.max_batch):
                    r = self._pending.popleft()
                    if r.fut is not None and r.fut.done():
                        continue     # expired/cancelled while queued
                    group.append(r)
                    n += r.x.shape[0]
                self._cv.notify_all()    # queue shrank: wake blocked admitters
                if (n >= self.max_batch or self._pending or self._closed
                        or self._gen != gen):
                    break                # full, head won't fit, or draining
                dls = [r.deadline for r in group if r.deadline is not None]
                batch_cap = min(dls) if dls else None
                now = time.monotonic()
                if batch_cap is not None and now >= batch_cap:
                    break                # earliest deadline reached: cut
                if (continuous and self._slots.busy() and now < hard_deadline
                        and (batch_cap is None or now < batch_cap)):
                    self._cv.wait(0.005)     # device busy: keep admitting
                    continue
                timeout = deadline - now
                if batch_cap is not None:
                    timeout = min(timeout, batch_cap - now)
                if timeout <= 0:
                    break                # batching window expired
                self._cv.wait(timeout)
            if self._gen != gen:
                # retired mid-take: hand the batch to the new pipeline
                self._pending.extendleft(reversed(group))
                return None, 0, True, 0
            return (group, n, False,
                    time.perf_counter_ns() - t_hold if t_hold else 0)

    def _to_host(self, y) -> np.ndarray:
        """Host-sync one device batch; dequantize int8 logits to fp32.

        Dequantization is gated on the ARRAY dtype, not just the session:
        the ``acc(x)`` fallback path (segmented/strict accelerators)
        already returns dequantized fp32, and rescaling it twice would
        corrupt every co-batched result."""
        y_np = np.asarray(y)
        if self._quant is not None and y_np.dtype == np.int8:
            return (y_np.astype(np.float32)
                    * np.float32(self._quant.output_scale))
        return y_np

    def _sync(self, y, seq: int) -> np.ndarray:
        """``_to_host`` as the ``session.sync`` span of batch ``seq``."""
        spans = self._spans
        if spans is None:
            return self._to_host(y)
        t0 = time.perf_counter_ns()
        try:
            return self._to_host(y)
        finally:
            spans.add("session.sync", time.perf_counter_ns() - t0, seq)

    def _run_bucket(self, x):
        """Place one staged batch on its device(s) and run it. A bucket
        with a sharded entry goes straight onto the mesh, split over the
        batch axis, so no shard is staged through the first device."""
        b = x.shape[0]
        entry = self._sharded_entries.get(b)
        if entry is not None:
            return entry(self._params_sharded,
                         jax.device_put(x, self._x_sharding))
        entry = self._entries.get(b)
        if entry is not None:
            return entry(self._params, jnp.asarray(x))
        return self.acc(x)

    def _stage_group(self, group, n, seq: int, *, bulk: bool = False):
        """Assemble one device batch into the staging ring — no dispatch.

        Assembly is numpy into a preallocated staging ring (one buffer per
        pipeline slot — see ``__init__``): per-op jax dispatch dominates at
        this granularity (8 expand_dims + concat + 8 slices per batch), so
        the queue would otherwise run slower than the direct loop it exists
        to beat. Records each request's row offset (``req.off``) so a
        failed batch can be bisected at the same offsets. Returns
        ``(bucket, buf)``; ``_launch`` dispatches it.
        """
        t0 = time.perf_counter_ns()
        bucket = next(b for b in self.buckets if b >= n)
        if bulk:
            ring = self._staging_bulk.get(bucket)
            if ring is None:
                ring = self._staging_bulk[bucket] = [
                    np.empty_like(self._staging[bucket][0])
                    for _ in range(self._slots.capacity)]
                self._bulk_flip[bucket] = 0
            flips = self._bulk_flip
        else:
            ring, flips = self._staging[bucket], self._staging_flip
        buf = ring[flips[bucket]]
        flips[bucket] = (flips[bucket] + 1) % len(ring)
        off = 0
        for r in group:
            k = r.x.shape[0]
            buf[off:off + k] = r.x
            r.off = off
            off += k
        if bucket > n:
            buf[n:] = 0
        now = time.monotonic()
        dev_ids = (self._fleet_device_ids
                   if bucket in self._sharded_entries
                   else self._local_device_ids)
        st = self.stats
        dt = time.perf_counter_ns() - t0
        with st._lat_lock:
            st.padded_rows += bucket - n
            st.dispatched_rows += n
            for r in group:
                st.wait_hist.add((now - r.t_submit) * 1e3)
            for d in dev_ids:
                st.device_batches[d] = st.device_batches.get(d, 0) + 1
            st.assemble_ns += dt
        spans = self._spans
        if spans is not None:
            spans.add("session.assemble", dt, seq)
            spans.batches[seq] = tuple(r.rid for r in group)
        return bucket, buf

    def _launch(self, bucket, buf, group, seq: int):
        """Launch a staged batch — no host sync. The fault harness's
        ``dispatch`` and ``execute`` sites fire here; the drain thread (or
        the bulk path) syncs the returned in-flight device result."""
        t_launch = time.perf_counter_ns()
        if self._faults is not None:
            rids = [r.rid for r in group]
            self._faults.visit("dispatch", requests=rids)
            buf = self._faults.visit(
                "execute", payload=buf, requests=rids,
                rows={r.rid: (r.off, r.x.shape[0]) for r in group},
                backend=self._backend_tag)
        first_use = bucket not in self._warm
        t0 = time.monotonic()
        # the staging ring guarantees this buffer is not refilled until its
        # slot drains, so placing it may copy OR zero-copy-alias it safely
        if first_use:
            with _expected_donation_noise():   # compile happens in this call
                y = self._run_bucket(buf)
            self._count_first_use(bucket, t0)
            self._warm.add(bucket)
        else:
            y = self._run_bucket(buf)
        dt = time.perf_counter_ns() - t_launch
        self.stats.bump("launch_ns", dt)
        spans = self._spans
        if spans is not None:
            spans.add("session.launch", dt, seq)
        return y

    # -- failure handling ---------------------------------------------------
    def _beat(self, name: str):
        if self._sup is not None:
            self._sup.beat(name)

    def _guard(self, req: _Request, rows):
        """``guard_numerics``: the NumericsError for non-finite output rows
        of this request, else None."""
        if not self._guard_numerics:
            return None
        rows = np.asarray(rows)
        if (np.issubdtype(rows.dtype, np.floating)
                and not np.all(np.isfinite(rows))):
            return NumericsError(
                f"request {req.rid}: non-finite values in its output rows "
                f"quarantined (guard_numerics=True)")
        return None

    def _reject_req(self, req: _Request, exc: BaseException) -> bool:
        """Resolve ``req`` with ``exc``; True when THIS call resolved it.
        The set_exception winner does the error accounting, so a request
        racing the deadline enforcer against the drain thread is counted
        exactly once."""
        if req.fut is None:
            return False    # bulk path: run_many accounts for it inline
        try:
            req.fut.set_exception(exc)
        except InvalidStateError:
            return False
        st = self.stats
        with st._lat_lock:
            st.errors += 1
            if isinstance(exc, DeadlineExceeded):
                st.deadline_exceeded += 1
        return True

    def _resolve_req(self, req: _Request, rows) -> bool:
        """Resolve ``req`` with its output rows (numerics-guarded); True
        when this call delivered the result."""
        gexc = self._guard(req, rows)
        if gexc is not None:
            if self._reject_req(req, gexc):
                self.stats.bump("isolated")
            return False
        try:
            req.fut.set_result(rows[0] if req.single else rows)
        except InvalidStateError:
            return False    # expired/cancelled first; already accounted
        return True

    def _deliver(self, group, y_np, seq: int):
        """Scatter a drained batch's rows to its futures + count it."""
        t0 = time.perf_counter_ns()
        done_t = time.monotonic()
        lats = []
        for r in group:
            rows = y_np[r.off:r.off + r.x.shape[0]]
            if self._resolve_req(r, rows):
                lats.append((done_t - r.t_submit) * 1e3)
        dt = time.perf_counter_ns() - t0
        spans = self._spans
        if spans is not None:
            spans.add("session.deliver", dt, seq)
        st = self.stats
        with st._lat_lock:
            st.batches += 1
            st.requests += len(lats)
            for ms in lats:
                st.latency_hist.add(ms)
            st.deliver_ns += dt

    def _deliver_outcomes(self, group, outcomes):
        """Resolve per-request recovery outcomes ``(req, ok, rows|exc)``."""
        done_t = time.monotonic()
        lats = []
        for r, ok, val in outcomes:
            if ok:
                if self._resolve_req(r, val):
                    lats.append((done_t - r.t_submit) * 1e3)
            else:
                self._reject_req(r, val)
        st = self.stats
        with st._lat_lock:
            st.requests += len(lats)
            for ms in lats:
                st.latency_hist.add(ms)

    def _fallback_entry(self, bucket: int):
        """The lazily-compiled XLA degradation executor for ``bucket`` —
        same Program, same params, ``backend="xla"`` keyed separately in
        the program cache. Raises for strict/segmented accelerators (no
        cached-entry hot path to degrade onto)."""
        with self._fallback_lock:
            pair = self._fallback_entries.get(bucket)
            if pair is None:
                rt = self.acc.runtime
                if rt is None or rt.strict or not self._entries:
                    raise RuntimeError("no XLA fallback entry available")
                pair = rt.executor_entry(bucket, self.acc.input_dtype,
                                         donate_input=False, backend="xla")
                self._fallback_entries[bucket] = pair
            return pair

    def _execute_staged(self, bucket, buf, group, *, fallback: bool = False):
        """Synchronously execute an already-staged buffer — the recovery
        path (XLA degradation and bisection retries). Re-visits the fault
        plan's ``execute`` site so request-bound ("cursed") faults keep
        firing on retry and the bisection converges on the offender."""
        if self._faults is not None:
            buf = self._faults.visit(
                "execute", payload=buf, requests=[r.rid for r in group],
                rows={r.rid: (r.off, r.x.shape[0]) for r in group},
                backend="xla" if fallback else self._backend_tag)
        if fallback:
            entry, params = self._fallback_entry(bucket)
            y = entry(params, jnp.asarray(buf))
        else:
            y = self._run_bucket(buf)
        return self._to_host(y)

    def _recover(self, group, bucket, buf, exc):
        """Per-request outcomes for a failed device batch.

        Order of escalation: (1) a ``backend="pallas"`` failure re-runs the
        WHOLE batch once through the XLA lowering (``stats.degraded``) —
        the kernel-level analog of the AOT warn-and-recompile path; (2)
        bisection — re-dispatch each half **at the same bucket size with
        the other half's rows zeroed in place**, recursing into halves
        that still fail until the offender is alone. Same bucket + same
        row offsets means the innocent rows run through the *identical*
        compiled executor at identical positions, so their results are
        bitwise-identical to a fault-free run (changing the bucket would
        change the lowering and drift the floats). Runs on the thread that
        detected the failure while the batch's pipeline slot is still held
        (the staging buffer must survive the re-reads).

        Returns ``[(req, ok, rows_or_exc), ...]`` in group order.
        """
        if self._backend_tag == "pallas":
            try:
                y_np = self._execute_staged(bucket, buf, group,
                                            fallback=True)
                self.stats.bump("degraded")
                log.warning(
                    "serving: batch of %d requests re-dispatched on the "
                    "XLA backend after a pallas failure: %r",
                    len(group), exc)
                return [(r, True, y_np[r.off:r.off + r.x.shape[0]])
                        for r in group]
            except Exception as e2:  # noqa: BLE001 — fall through to bisect
                log.warning("serving: XLA fallback also failed (%r); "
                            "bisecting the batch", e2)
        return self._bisect(group, bucket, buf, exc)

    def _bisect(self, group, bucket, buf, exc):
        if len(group) == 1:
            self.stats.bump("isolated")
            log.warning("serving: request %d isolated as the batch "
                        "offender: %r", group[0].rid, exc)
            return [(group[0], False, exc)]
        mid = len(group) // 2
        outcomes = []
        for part in (group[:mid], group[mid:]):
            part_buf = np.zeros_like(buf)
            for r in part:
                k = r.x.shape[0]
                part_buf[r.off:r.off + k] = buf[r.off:r.off + k]
            self.stats.bump("retries")
            try:
                y_np = self._execute_staged(bucket, part_buf, part)
            except Exception as e:  # noqa: BLE001 — recurse on the half
                outcomes.extend(self._bisect(part, bucket, part_buf, e))
                continue
            outcomes.extend((r, True, y_np[r.off:r.off + r.x.shape[0]])
                            for r in part)
        return outcomes

    def _count_first_use(self, bucket: int, t0: float):
        """Attribute a bucket's first-use stall to ``warm_load_ms`` when its
        executor deserialized from an AOT bundle (no compile happened —
        this is the warm-start cost), to ``compile_ms`` otherwise. Sharded
        entries always compile in-process (AOT binaries would pin one
        host's device ids), so they count as compile."""
        dt = (time.monotonic() - t0) * 1e3
        entry = (None if bucket in self._sharded_entries
                 else self._entries.get(bucket))
        if getattr(entry, "aot_loaded", False):
            self.stats.warm_load_ms += dt
        else:
            self.stats.compile_ms += dt

    def _worker(self, gen: int):
        """Dispatch loop: batch i+1 is staged and launched while batch i is
        still executing on the device (the drain thread owns completion).

        Crash containment: any escaping exception (including the fault
        harness's ``ThreadKilled``, a BaseException) is recorded as the
        causal ``_thread_exc`` and the thread dies — the supervisor
        detects the dead thread, fails stranded futures and restarts the
        pipeline under a new generation. A retired (stale-generation)
        worker hands unstarted work back to the queue and stands down
        without touching shared pipeline state."""
        try:
            while True:
                group, n, stale, hold_ns = self._take_group(gen)
                if stale:
                    return
                if group is None:
                    with self._inflight_cv:   # closed: wake the drain thread
                        self._inflight.append(None)
                        self._inflight_cv.notify_all()
                    self._worker_exited_clean = True
                    return
                if not group:
                    continue    # every admitted request had already expired
                # the group now lives only in this thread: publish it so the
                # watchdog can fail its futures if we die before handoff
                self._worker_group = group
                self._beat("dispatch")
                seq = next(self._batch_seq)
                spans = self._spans
                if spans is not None:
                    spans.add("session.admit", hold_ns, seq)
                t_wait = time.perf_counter_ns() if spans is not None else 0
                # acquire the pipeline slot BEFORE launching, so at most
                # pool-capacity device batches are ever outstanding — across
                # the whole Fleet when the pool is shared. The wait is
                # cancellable on generation retirement: a wedged pool (its
                # holder crashed) must not block the watchdog restart.
                acquired = self._slots.acquire(
                    cancelled=lambda: self._gen != gen)
                if spans is not None:
                    spans.add("session.slot_wait",
                              time.perf_counter_ns() - t_wait, seq)
                if not acquired:
                    with self._cv:
                        self._pending.extendleft(reversed(group))
                    self._worker_group = None
                    return
                self._worker_holds_slot = True
                bucket = buf = None
                try:
                    with self._dispatch_mutex:
                        bucket, buf = self._stage_group(group, n, seq)
                    y = self._launch(bucket, buf, group, seq)
                except Exception as e:  # noqa: BLE001 — recover per request
                    try:
                        outcomes = (self._recover(group, bucket, buf, e)
                                    if buf is not None else None)
                    finally:
                        self._slots.release()
                        self._worker_holds_slot = False
                    if outcomes is None:    # staging failed: nothing staged
                        self._fail_group(group, e)
                    else:
                        self._deliver_outcomes(group, outcomes)
                    self._worker_group = None
                    continue
                retired = False
                with self._inflight_cv:
                    if self._gen != gen:
                        retired = True    # watchdog owns cleanup now
                    else:
                        self._inflight.append((group, y, bucket, buf, seq))
                        self._worker_holds_slot = False
                        self._worker_group = None
                        self._inflight_cv.notify_all()
                if retired:
                    self._slots.release()
                    self._worker_holds_slot = False
                    with self._cv:
                        self._pending.extendleft(reversed(group))
                    self._worker_group = None
                    return
        except BaseException as e:  # noqa: BLE001 — watchdog handles it
            self._thread_exc = e
            log.error("serving: dispatch worker died: %r", e)

    # -- completion side ----------------------------------------------------
    def _drainer(self, gen: int):
        """Completion loop: block on the oldest in-flight batch, scatter its
        rows back to the futures in submission order. The batch is PEEKED,
        synced, and only then released — releasing the dispatch slot before
        the host sync would let a third batch launch (and its staging
        buffer be refilled) while this one may still be executing, breaking
        the documented in-flight bound of the slot pool.

        A sync failure triggers per-request recovery (XLA degradation /
        bisection — see ``_recover``) BEFORE the slot is released, while
        the staged buffer is still guaranteed intact. A retired generation
        abandons its peeked batch untouched: after the generation bump the
        watchdog owns every in-flight item, and a stale pop/release here
        would double-free its slot."""
        try:
            while True:
                with self._inflight_cv:
                    while not self._inflight and self._gen == gen:
                        self._beat("drain")
                        self._inflight_cv.wait(0.25)
                    if self._gen != gen:
                        return
                    item = self._inflight[0]     # peek: slot stays occupied
                if item is None:
                    return
                self._beat("drain")
                group, y, bucket, buf, seq = item
                exc = None
                try:
                    if self._faults is not None:
                        self._faults.visit(
                            "drain", requests=[r.rid for r in group])
                    y_np = self._sync(y, seq)  # the one host sync per batch
                                               # (+ dequant for int8)
                except Exception as e:  # noqa: BLE001 — device error lands here
                    exc = e
                outcomes = (None if exc is None
                            else self._recover(group, bucket, buf, exc))
                with self._inflight_cv:
                    if self._gen != gen or not self._inflight:
                        return               # retired mid-sync: abandon
                    self._inflight.popleft()     # only this thread pops
                    self._drain_popped_unreleased = True
                    self._drain_group = group    # local-only until delivered
                    self._inflight_cv.notify_all()
                self._slots.release()            # batch done: free the slot
                self._drain_popped_unreleased = False
                if outcomes is not None:
                    self._deliver_outcomes(group, outcomes)
                else:
                    self._deliver(group, y_np, seq)
                self._drain_group = None
        except BaseException as e:  # noqa: BLE001 — watchdog handles it
            self._thread_exc = e
            log.error("serving: drain thread died: %r", e)

    def _fail_group(self, group, e):
        for r in group:
            self._reject_req(r, e)

    # -- supervision --------------------------------------------------------
    def _supervise(self):
        """Watchdog loop (own thread): enforce request deadlines and watch
        the pipeline threads. Sleeps until the earliest registered
        deadline (or a 50ms poll tick), fails due requests with
        ``DeadlineExceeded``, and triggers a pipeline restart when a
        dispatch/drain thread is dead — or silent past ``hang_after_s``
        while the session has work."""
        while True:
            with self._sup_cv:
                if self._sup_stop:
                    return
                timeout = 0.05
                nxt = self._deadlines.next_at()
                if nxt is not None:
                    timeout = min(timeout, max(0.001, nxt - time.monotonic()))
                self._sup_cv.wait(timeout)
                if self._sup_stop:
                    return
            now = time.monotonic()
            expired = False
            for req in self._deadlines.pop_due(now):
                if req.fut is not None and not req.fut.done():
                    if self._reject_req(req, DeadlineExceeded(
                            f"request {req.rid} missed its "
                            f"{req.deadline_ms:.1f}ms deadline")):
                        expired = True
            if expired:
                with self._cv:
                    self._cv.notify_all()    # free queue space / admitters
            if self._closed:
                continue    # keep enforcing deadlines until close() stops us
            if self._sup is not None:
                with self._cv:
                    busy = bool(self._pending)
                if not busy:
                    with self._inflight_cv:
                        busy = any(it is not None for it in self._inflight)
                self._sup.update_busy(busy, now=now)
                hung = self._sup.hung(now=now)
            else:
                hung = []
            dead = [name for name, t
                    in (("dispatch", self._dispatch_thread),
                        ("drain", self._drain_thread))
                    if not t.is_alive()]
            if dead or hung:
                self._restart_pipeline(hung)

    def _restart_pipeline(self, hung):
        """Retire the current pipeline generation, fail every queued and
        in-flight future with ``PipelineCrashed`` (causal exception
        chained), return the dead threads' device slots to the pool, and
        start fresh dispatch/drain threads. Serialized against ``close``
        by ``_life_lock``; re-validates liveness under the lock so a
        concurrent clean shutdown is never mistaken for a crash."""
        with self._life_lock:
            if self._closed or self._sup_stop or self._closed_done:
                return
            old = (self._dispatch_thread, self._drain_thread)
            dead = [name for name, t in zip(("dispatch", "drain"), old)
                    if not t.is_alive()]
            if not dead and not hung:
                return
            causal = self._thread_exc
            exc = PipelineCrashed(
                f"pipeline thread(s) {dead or hung} "
                f"{'died' if dead else 'hung'}; the watchdog failed this "
                f"request and restarted the pipeline")
            exc.__cause__ = causal
            with self._cv:
                self._gen += 1           # retire survivors
                self._cv.notify_all()
            with self._inflight_cv:
                self._inflight_cv.notify_all()
            for t in old:
                t.join(timeout=15.0)
            n_inflight, n_pending = self._fail_all_queued(exc)
            self._thread_exc = None
            self.stats.bump("watchdog_restarts")
            log.warning(
                "serving: watchdog restarted the pipeline (gen %d) after "
                "%s %s; failed %d in-flight batch(es) + %d queued "
                "request(s) with PipelineCrashed (causal: %r)",
                self._gen, dead or hung, "died" if dead else "hung",
                n_inflight, n_pending, causal)
            if self._sup is not None:
                self._sup.update_busy(False)     # re-arm hang detection
            self._start_pipeline_threads()


# ---------------------------------------------------------------------------
# Fleet: multi-model tenancy over one process / one device pool
# ---------------------------------------------------------------------------

class Fleet:
    """Several :class:`Accelerator` models served from ONE process over one
    device pool — the paper's NI-instances analog taken to a rack.

    Each model gets its own :class:`ServingSession` (own pending queue, own
    staging buffers, own stats), but every session shares:

    * **one device-slot pool** — the in-flight pipeline slots are a single
      FIFO-fair pool, so device time round-robins between tenant models
      instead of one model's burst starving the rest;
    * **one program cache** — accelerators built against the process-global
      ``core.program_cache.default_cache()`` (the default) land their
      executors side by side in it, keyed by schedule/backend/mesh, so two
      models never recompile each other's entries away by identity;
    * **one mesh** (optional) — full buckets of every model shard over the
      same devices via the shard_map'd executor variant.

    ::

        fleet = api.Fleet({"vgg16": acc_vgg, "resnet18": acc_res},
                          mesh="host", max_batch=8)
        fut = fleet.submit("resnet18", x)       # routed to that model
        y = fleet("vgg16", x)                   # submit + wait

    Per-model outputs are bitwise-stable under tenancy: a model's requests
    run through exactly the cached executor entries its standalone session
    would use — co-tenancy only changes *when* a batch gets a device slot,
    never what it computes (asserted in ``tests/test_fleet_serving.py``).
    """

    def __init__(self, accelerators, *, mesh=None, max_batch: int = 8,
                 buckets: Sequence[int] | None = None,
                 max_wait_ms: float = 5.0, warmup: bool = False,
                 scheduler: str = "continuous", max_inflight: int = 3,
                 deadline_ms: float | None = None,
                 queue_limit: int | None = None,
                 on_overload: str = "shed",
                 guard_numerics: bool = False,
                 fault_plan=None,
                 supervise: bool = True,
                 hang_after_s: float | None = None):
        items = dict(accelerators)
        if not items:
            raise ValueError("Fleet needs at least one named Accelerator")
        if mesh == "host":
            from repro.launch.mesh import make_host_mesh
            mesh = make_host_mesh()
        self.mesh = mesh
        self._pool = _SlotPool(max_inflight)
        self.sessions: dict[str, ServingSession] = {}
        for name, acc in items.items():
            # the failure model is per-session (each tenant gets its own
            # deadlines/queue bound/watchdog) over the SHARED slot pool —
            # a tenant's watchdog restart returns its dead pipeline's
            # slots so co-tenants never lose pool capacity
            self.sessions[name] = ServingSession(
                acc, max_batch=max_batch, buckets=buckets, mesh=mesh,
                max_wait_ms=max_wait_ms, warmup=warmup, scheduler=scheduler,
                slot_pool=self._pool, deadline_ms=deadline_ms,
                queue_limit=queue_limit, on_overload=on_overload,
                guard_numerics=guard_numerics, fault_plan=fault_plan,
                supervise=supervise, hang_after_s=hang_after_s)

    @property
    def models(self) -> tuple[str, ...]:
        return tuple(self.sessions)

    def _session(self, model: str) -> ServingSession:
        try:
            return self.sessions[model]
        except KeyError:
            raise ValueError(f"unknown model {model!r}: fleet serves "
                             f"{sorted(self.sessions)}") from None

    def submit(self, model: str, x) -> Future:
        """Enqueue one request for ``model``; returns its Future."""
        return self._session(model).submit(x)

    def __call__(self, model: str, x):
        """Synchronous convenience: submit + wait."""
        return self.submit(model, x).result()

    def run_many(self, requests) -> list:
        """``requests``: iterable of ``(model, x)`` pairs. Every request is
        submitted first — so co-tenant models contend for device slots the
        way live traffic would — then gathered in submission order."""
        pairs = [(m, x) for m, x in requests]
        by_model: dict[str, list] = {}
        for m, x in pairs:
            by_model.setdefault(m, []).append(x)
        futs_by_model = {m: iter(self._session(m).submit_many(xs))
                         for m, xs in by_model.items()}
        futs = [next(futs_by_model[m]) for m, _ in pairs]
        return [f.result() for f in futs]

    def stats(self) -> dict[str, SessionStats]:
        """Per-model :class:`SessionStats`, keyed by model name."""
        return {name: s.stats for name, s in self.sessions.items()}

    def close(self):
        for s in self.sessions.values():
            s.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

