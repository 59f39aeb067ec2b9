"""Two-phase program execution: validate once, trace many.

The ``HybridRuntime`` interpreter replays the 128-bit ISA stream one Python
dispatch at a time — one hazard check and one ``staging.at[].set()`` per
instruction — which is faithful to the hardware handshake FIFOs (Sec. 4.1)
but caps end-to-end inference at Python speed. This module splits that job
into the two phases the paper's accelerator actually has:

* **Phase 1 — schedule validation** (:func:`validate_schedule`): replay the
  instruction stream against *symbolic* buffer state only (slot tags, block
  sets — no tensors). This enforces the identical handshake-FIFO discipline
  as the interpreter — LOAD over a live slot, COMP before its LOADs, SAVE
  before COMP, a missing final SAVE all raise :class:`HazardError` — and
  produces the same pipeline-statistics counters. It runs once per
  ``Program``; the hardware analog is the one-time bitstream/schedule check
  before the stream is burned into instruction memory.

* **Phase 2 — lowering** (:func:`lower_program`): turn the validated
  schedule into a pure function ``execute(params, x) -> y`` made only of
  ``lax``/``jnp`` ops with static Python control flow — per-layer blocked
  compute (the same row-group/k-group blocks the COMP instructions name)
  assembled with ``concatenate`` instead of per-instruction dict staging.
  The result is ``jax.jit``-compatible and is cached per
  ``(Program, batch, dtype)`` by :mod:`repro.core.program_cache`.

Both phases cover the full-network ISA: POOL and FC blocks validate under
the same slot-tag discipline as COMP (input slot for POOL; input slot,
weight slot and bias buffer for FC) and lower through the shared
:func:`pool_forward` / :func:`fc_forward` helpers the interpreter also
calls, so an entire model — CONVs, maxpools, FC tail — executes as one
jitted function.

Numerical contract: for a stream that passes validation, the lowered
function computes block-for-block the same math as the interpreter (same
halo slicing, same horizontal padding, same U-space weight pre-transform,
same dtype casts), so outputs agree to float-associativity tolerance.

Backends: lowering emits each block's compute through one of two PE
implementations, selected by ``backend=``:

* ``"xla"`` (default) — plain ``lax``/``jnp`` ops. GSPMD-partitionable, so
  the lowered function can live inside a pjit-sharded model.
* ``"pallas"`` — the Pallas PE kernels (``kernels/spatial_conv`` for
  Spatial CONV, ``kernels/winograd`` + ``kernels/gemm`` for Winograd CONV,
  ``kernels/gemm`` for FC). ``interpret=None`` resolves from the device the
  executor runs on (``kernels.common.interpret_default``): compiled kernels
  on a TPU, interpret mode elsewhere, so the same Program runs in the CPU
  test suite; pass ``interpret=False`` to force compiled lowering.

Both backends lower the identical blocked schedule — only the per-block PE
changes — and are asserted equal (to tolerance) over full reduced VGG16 in
``tests/test_backend_pallas.py``. POOL blocks always lower through
``lax.reduce_window``: pooling is comparisons, not PE MACs, in the paper's
architecture (Sec. 4.2). See ``docs/ARCHITECTURE.md``.

Lowering optimizer (``opt_level``): the literal per-block lowering above is
faithful to the COMP stream but wasteful as a *software* dataflow — every
block re-materializes its vertical halo (``jnp.pad`` + slice) and the
per-(row, k) blocks reassemble through fusion-blocking ``concatenate``
chains, so XLA sees G_H x G_K small convolutions per layer instead of one.
``opt_level=1`` (the default) runs :func:`analyze_program` before tracing:
a CONV layer whose blocks are *provably equivalent* to one whole-layer
dispatch — every COMP block carries the same RELU bit, the k-groups
contiguously tile [0, K), the row groups contiguously tile the output
height (halos are always spec-derived, see :func:`slice_input_rows`) —
collapses to a single PE call over the full weight image. A layer whose
RELU bits differ between blocks cannot fuse (the stream is authoritative);
when its k-groups are equal-sized it lowers to a stacked-weight batched
form (one vmapped PE call + a static per-block RELU mask) instead of the
concat chain, and anything else falls back to the literal blocked lowering.
``opt_level=0`` keeps the literal lowering everywhere — the reference the
optimizer is tested against. The chosen level joins the program-cache key,
so fused and blocked executors of one Program coexist. On this container's
CPU backend the fused lowering is bitwise-equal to the blocked one (and to
the strict interpreter) — asserted in ``tests/test_opt_lowering.py`` and
measured in the ``runtime/fused_vs_blocked`` bench row.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import layouts
from repro.core.compiler import CompiledLayer, Program
from repro.core.hybrid_conv import (
    dense,
    depthwise_conv2d,
    hybrid_conv2d,
    max_pool2d,
    same_pad,
)
from repro.core.isa import Opcode, unpack_dw_geom, unpack_fc_dims
from repro.core.winograd import transform_weights, winograd_apply_pretransformed
from repro.quant.execute import qconv2d, qdense, qdepthwise, qeltwise
from repro.quant.sidecar import LayerQuant, QuantSidecar


class HazardError(RuntimeError):
    """Instruction-stream hazard: the handshake FIFO discipline was violated.

    Shared by the interpreter and the validation pass (``runtime.py``
    re-exports this class so existing ``except HazardError`` sites keep
    working).
    """


BACKENDS = ("xla", "pallas")
OPT_LEVELS = (0, 1)


def resolve_opt_level(opt_level: int) -> int:
    """Validate the lowering-optimizer level (0 = literal per-block
    lowering, 1 = fused whole-layer lowering where provably equivalent)."""
    if opt_level not in OPT_LEVELS:
        raise ValueError(
            f"unknown opt_level {opt_level!r}: expected one of {OPT_LEVELS}")
    return int(opt_level)


def resolve_backend(backend: str, interpret: bool | None, mesh=None
                    ) -> tuple[str, bool | None]:
    """Normalize a ``(backend, interpret)`` pair to its effective value.

    ``interpret`` only means something on the Pallas backend; ``None`` there
    resolves from the device the executor runs on — the first device of
    ``mesh``, else JAX's default device: compiled kernels on a TPU, interpret
    mode on any other platform. Passing a non-None ``interpret`` with
    ``backend="xla"`` is a contradiction — the XLA lowering would silently ignore it and the
    caller would believe the Pallas interpret path was exercised — so it
    raises instead. The resolved pair is what joins the program-cache key,
    so a resolved ``None`` and the equivalent explicit value share a cache
    entry.
    """
    if backend not in BACKENDS:
        raise ValueError(
            f"unknown backend {backend!r}: expected one of {BACKENDS}")
    if backend == "xla":
        if interpret is not None:
            raise ValueError(
                f"interpret={interpret!r} has no effect with backend='xla' "
                f"— pass backend='pallas' or drop interpret")
        return "xla", None
    if interpret is None:
        from repro.kernels.common import interpret_default
        device = None if mesh is None else mesh.devices.flat[0]
        return "pallas", interpret_default(device)
    return "pallas", bool(interpret)


def _fresh_stats() -> dict[str, int]:
    return {"load_inp": 0, "load_wgt": 0, "load_bias": 0,
            "comp": 0, "pool": 0, "fc": 0, "eltwise": 0, "dw": 0,
            "save": 0, "inp_words": 0, "wgt_words": 0}


# ---------------------------------------------------------------------------
# Phase 1: schedule validation (symbolic replay, no tensors)
# ---------------------------------------------------------------------------

def validate_schedule(program: Program) -> dict[str, int]:
    """Replay the hazard/FIFO discipline once, without any compute.

    Mirrors ``HybridRuntime``'s checks exactly — the tags that the
    interpreter attaches to tensor payloads are tracked here on their own.
    Returns the pipeline statistics counters (same keys as
    ``HybridRuntime.stats``); raises :class:`HazardError` on the first
    violation.
    """
    stats = _fresh_stats()
    inp_tags: list[tuple | None] = [None, None]
    wgt_tags: list[tuple | None] = [None, None]
    bias_tag: tuple | None = None
    out_blocks: set[tuple[int, int]] = set()
    saved_any = False
    cur_layer = -1

    def flush(layer_id: int):
        if out_blocks:
            raise HazardError(
                f"layer {layer_id}: {len(out_blocks)} COMP blocks never SAVEd")
        if not saved_any:
            raise HazardError(f"layer {layer_id}: no SAVE executed")

    for ins in program.instructions:
        cl = program.layers[ins.layer_id]
        if ins.layer_id != cur_layer:
            if cur_layer >= 0:
                flush(cur_layer)
            cur_layer = ins.layer_id
            out_blocks = set()
            saved_any = False

        op = ins.opcode
        if op == Opcode.LOAD_BIAS:
            bias_tag = (ins.layer_id,)
            stats["load_bias"] += 1
        elif op == Opcode.LOAD_INP:
            ih, slot = ins.buff_base >> 1, ins.buff_base & 1
            inp_tags[slot] = (ins.layer_id, ih)
            stats["load_inp"] += 1
            stats["inp_words"] += ins.size
        elif op == Opcode.LOAD_WGT:
            kg, slot = ins.buff_base >> 1, ins.buff_base & 1
            wgt_tags[slot] = (ins.layer_id, kg)
            stats["load_wgt"] += 1
            stats["wgt_words"] += ins.size
        elif op == Opcode.COMP:
            ih = ins.size & 0xFFF
            kg = (ins.size >> 12) & 0xFFF
            islot = (ins.size >> 24) & 1
            wslot = (ins.size >> 25) & 1
            if inp_tags[islot] != (ins.layer_id, ih):
                raise HazardError(
                    f"COMP L{ins.layer_id} row-group {ih}: input slot "
                    f"{islot} holds {inp_tags[islot]}")
            if wgt_tags[wslot] != (ins.layer_id, kg):
                raise HazardError(
                    f"COMP L{ins.layer_id} k-group {kg}: weight slot "
                    f"{wslot} holds {wgt_tags[wslot]}")
            if bias_tag != (ins.layer_id,):
                raise HazardError(f"COMP L{ins.layer_id}: stale bias buffer")
            out_blocks.add((ih, kg))
            stats["comp"] += 1
        elif op == Opcode.POOL:
            islot = ins.buff_base & 1
            cfg = (ins.pool_window, ins.pool_stride)
            if cfg != (cl.spec.window, cl.spec.stride):
                raise HazardError(
                    f"POOL L{ins.layer_id}: word0 window/stride {cfg} "
                    f"disagree with compiled spec "
                    f"({cl.spec.window}, {cl.spec.stride})")
            if inp_tags[islot] != (ins.layer_id, 0):
                raise HazardError(
                    f"POOL L{ins.layer_id}: input slot {islot} holds "
                    f"{inp_tags[islot]}")
            out_blocks.add((0, 0))
            stats["pool"] += 1
        elif op == Opcode.FC:
            islot = ins.buff_base & 1
            wslot = (ins.buff_base >> 1) & 1
            dims = unpack_fc_dims(ins.size)
            if dims != (cl.spec.d_in, cl.spec.d_out):
                raise HazardError(
                    f"FC L{ins.layer_id}: word3 dims {dims} disagree with "
                    f"compiled spec ({cl.spec.d_in}, {cl.spec.d_out})")
            if inp_tags[islot] != (ins.layer_id, 0):
                raise HazardError(
                    f"FC L{ins.layer_id}: input slot {islot} holds "
                    f"{inp_tags[islot]}")
            if wgt_tags[wslot] != (ins.layer_id, 0):
                raise HazardError(
                    f"FC L{ins.layer_id}: weight slot {wslot} holds "
                    f"{wgt_tags[wslot]}")
            if bias_tag != (ins.layer_id,):
                raise HazardError(f"FC L{ins.layer_id}: stale bias buffer")
            out_blocks.add((0, 0))
            stats["fc"] += 1
        elif op == Opcode.ELTWISE_ADD:
            pslot = ins.buff_base & 1
            sslot = (ins.buff_base >> 1) & 1
            n_el = cl.spec.h * cl.spec.w * cl.spec.c
            if ins.size != n_el:
                raise HazardError(
                    f"ELTWISE L{ins.layer_id}: word3 element count "
                    f"{ins.size} disagrees with compiled spec ({n_el})")
            if ins.dram_base != cl.skip_addr:
                raise HazardError(
                    f"ELTWISE L{ins.layer_id}: word2 skip base "
                    f"{ins.dram_base} disagrees with compiled skip operand "
                    f"({cl.skip_addr})")
            if inp_tags[pslot] != (ins.layer_id, 0):
                raise HazardError(
                    f"ELTWISE L{ins.layer_id}: primary input slot {pslot} "
                    f"holds {inp_tags[pslot]}")
            if inp_tags[sslot] != (ins.layer_id, 1):
                raise HazardError(
                    f"ELTWISE L{ins.layer_id}: skip input slot {sslot} "
                    f"holds {inp_tags[sslot]}")
            out_blocks.add((0, 0))
            stats["eltwise"] += 1
        elif op == Opcode.DEPTHWISE_CONV:
            islot = ins.buff_base & 1
            wslot = (ins.buff_base >> 1) & 1
            geom = unpack_dw_geom(ins.size)
            if geom != (cl.spec.r, cl.spec.s, cl.spec.stride):
                raise HazardError(
                    f"DEPTHWISE L{ins.layer_id}: word3 geometry {geom} "
                    f"disagrees with compiled spec "
                    f"({cl.spec.r}, {cl.spec.s}, {cl.spec.stride})")
            if inp_tags[islot] != (ins.layer_id, 0):
                raise HazardError(
                    f"DEPTHWISE L{ins.layer_id}: input slot {islot} holds "
                    f"{inp_tags[islot]}")
            if wgt_tags[wslot] != (ins.layer_id, 0):
                raise HazardError(
                    f"DEPTHWISE L{ins.layer_id}: weight slot {wslot} holds "
                    f"{wgt_tags[wslot]}")
            if bias_tag != (ins.layer_id,):
                raise HazardError(
                    f"DEPTHWISE L{ins.layer_id}: stale bias buffer")
            out_blocks.add((0, 0))
            stats["dw"] += 1
        elif op == Opcode.SAVE:
            ih = ins.size & 0xFFF
            kg = (ins.size >> 12) & 0xFFF
            if cl.kind != "conv":
                need = [(0, 0)]
            elif cl.plan.dataflow == "is":
                need = [(ih, g) for g in range(len(cl.k_groups))]
            else:
                need = [(ih, kg)]
            for key in need:
                if key not in out_blocks:
                    raise HazardError(
                        f"SAVE L{ins.layer_id} block {key} not computed")
                out_blocks.discard(key)
            saved_any = True
            stats["save"] += 1
        else:
            raise ValueError(op)

    if cur_layer >= 0:
        flush(cur_layer)
    else:
        raise HazardError("empty instruction stream")
    return stats


# ---------------------------------------------------------------------------
# Phase 2: lowering to a pure, traceable function
# ---------------------------------------------------------------------------

def slice_input_rows(cl: CompiledLayer, x_nhwc: jax.Array, ih: int) -> jax.Array:
    """Static-slice the input rows (plus halo) for output row group ``ih``.

    Shared with the interpreter (``HybridRuntime._load_input_group``
    delegates here) so the two paths can never drift. Everything is
    Python-int static, so the slice lowers to a plain XLA slice.
    """
    r0, r1 = cl.row_groups[ih]
    return slice_input_span(cl, x_nhwc, r0, r1)


def slice_input_span(cl: CompiledLayer, x_nhwc: jax.Array,
                     r0: int, r1: int) -> jax.Array:
    """Input rows (plus spec-derived halo) for output rows ``[r0, r1)``.

    The fused lowering calls this with the whole output height — the same
    arithmetic a single-row-group plan would produce, which is what makes
    whole-layer fusion provably equivalent to the blocked assembly.
    """
    spec = cl.spec
    pad = (same_pad(spec.h, spec.r, spec.stride)[0]
           if spec.padding.upper() == "SAME" else 0)
    in_lo = r0 * spec.stride - pad
    in_hi = (r1 - 1) * spec.stride + spec.r - pad
    pad_top = max(0, -in_lo)
    pad_bot = max(0, in_hi - spec.h)
    sl = x_nhwc[:, max(0, in_lo):min(spec.h, in_hi)]
    if pad_top or pad_bot:
        sl = jnp.pad(sl, ((0, 0), (pad_top, pad_bot), (0, 0), (0, 0)))
    return sl


def width_pad(cl: CompiledLayer) -> tuple[int, int]:
    """Horizontal conv padding (vertical halo is materialized by the slice)."""
    if cl.spec.padding.upper() == "SAME":
        return same_pad(cl.spec.w, cl.spec.s, cl.spec.stride)
    return (0, 0)


def conv_block_forward(cl: CompiledLayer, x_slab: jax.Array,
                       w_grp: jax.Array, b_grp: jax.Array, relu: bool,
                       *, backend: str = "xla",
                       interpret: bool | None = None,
                       quant: LayerQuant | None = None,
                       k_range: tuple[int, int] | None = None) -> jax.Array:
    """One COMP block on the selected PE backend.

    ``x_slab`` is the row-group slice (halo included, vertical padding
    materialized); ``w_grp`` the k-group slice of the DRAM weight image
    (U-space for Winograd). Shared by the lowered executor and the strict
    interpreter's COMP handler so the two paths route through one PE
    implementation per backend. ``quant`` switches the block to the int8
    PE (``repro.quant.execute``): int8 in/weights, int32 accumulate, fused
    requantize(+ReLU) epilogue — spatial mode only (the DSE keeps Winograd
    plans off quantized builds). When ``w_grp``/``b_grp`` are a k-group
    slice of the layer, ``k_range=(lo, hi)`` slices a per-channel
    multiplier to match (a per-tensor scalar is slice-invariant).
    """
    spec, plan = cl.spec, cl.plan
    dtype = x_slab.dtype
    wpad = width_pad(cl)
    if quant is not None:
        if plan.mode == "wino":
            raise ValueError(
                f"layer {cl.layer_id}: Winograd plans cannot execute int8 "
                f"(the U-space transform is fp-only) — rebuild with "
                f"dtype='int8' so the DSE falls back to spatial")
        mult = quant.multiplier
        if k_range is not None and np.ndim(mult):
            mult = mult[k_range[0]:k_range[1]]
        return qconv2d(x_slab, w_grp, b_grp, mult=mult,
                       stride=spec.stride, padding=((0, 0), wpad),
                       relu=relu, use_pallas=backend == "pallas",
                       interpret=interpret)
    if plan.mode == "wino":
        x_p = jnp.pad(x_slab, ((0, 0), (0, 0), wpad, (0, 0)))
        if backend == "pallas":
            from repro.kernels.winograd import (
                winograd_apply_pretransformed_pallas,
            )
            return winograd_apply_pretransformed_pallas(
                x_p, w_grp, b_grp, m=plan.m, relu=relu, padding="VALID",
                dataflow=plan.dataflow, out_dtype=dtype, interpret=interpret)
        return winograd_apply_pretransformed(
            x_p, w_grp, b_grp, plan.m, relu=relu,
            padding="VALID", out_dtype=dtype)
    # the XLA lowering is dataflow-oblivious (and hybrid_conv2d now rejects
    # a dataflow/interpret that cannot take effect), so only forward the
    # plan's dataflow to the Pallas PE
    pallas = backend == "pallas"
    return hybrid_conv2d(
        x_slab, w_grp, b_grp, mode="spat",
        dataflow=plan.dataflow if pallas else "is", stride=spec.stride,
        relu=relu, padding=((0, 0), wpad),
        use_pallas=pallas, interpret=interpret,
        out_dtype=dtype)


# ---------------------------------------------------------------------------
# Lowering optimizer: per-layer block-structure analysis (opt_level=1)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LayerLowering:
    """The optimizer's verdict for one CONV layer.

    ``kind``:

    * ``"fused"``  — one whole-layer PE dispatch (uniform RELU bit across
      every COMP block, k-groups contiguously tile [0, K), row groups
      contiguously tile the output height). ``relu`` holds the uniform bit.
    * ``"stacked"`` — RELU bits differ between blocks but the groups still
      tile contiguously and the k-groups are equal-sized: one vmapped PE
      call over stacked weight groups, RELU applied through a static
      per-block mask (``relu_blocks[kg][ih]``) — no concat chain.
    * ``"block"``  — not provably reducible (non-contiguous groups from a
      hand-built stream, unequal k-group sizes with mixed RELU bits, or the
      Pallas backend where vmapping the PE kernel is not supported): keep
      the literal per-block lowering. ``reason`` says why.
    * ``"single"`` — the opcode is already one dispatch by construction
      (ELTWISE_ADD two-source add, DEPTHWISE_CONV grouped conv): nothing to
      fuse, the verdict is explicit so the optimizer's coverage is total.
    """
    kind: str
    relu: bool | None = None
    relu_blocks: tuple[tuple[bool, ...], ...] | None = None
    reason: str = ""


def _tiles_contiguously(groups, total: int) -> bool:
    lo = 0
    for a, b in groups:
        if a != lo or b <= a:
            return False
        lo = b
    return lo == total


def _stream_overrides(program: Program):
    """Per-block RELU bits and POOL configs, read off the instruction
    stream — the stream is authoritative over the compiled specs."""
    relu_bits: dict[tuple[int, int, int], bool] = {}
    pool_cfg: dict[int, tuple[int, int]] = {}
    for ins in program.instructions:
        if ins.opcode == Opcode.COMP:
            ih = ins.size & 0xFFF
            kg = (ins.size >> 12) & 0xFFF
            relu_bits[(ins.layer_id, ih, kg)] = ins.relu_flag
        elif ins.opcode in (Opcode.FC, Opcode.ELTWISE_ADD,
                            Opcode.DEPTHWISE_CONV):
            relu_bits[(ins.layer_id, 0, 0)] = ins.relu_flag
        elif ins.opcode == Opcode.POOL:
            pool_cfg[ins.layer_id] = (ins.pool_window, ins.pool_stride)
    return relu_bits, pool_cfg


def analyze_layer(cl: CompiledLayer, relu_of, *,
                  backend: str = "xla") -> LayerLowering:
    """Decide how one CONV layer may lower under ``opt_level=1``.

    ``relu_of(ih, kg)`` is the effective RELU bit of that COMP block (the
    stream's bit, falling back to the spec for blocks the stream omits).
    Fusion is claimed only when the whole-layer dispatch is provably the
    same math as the blocked assembly; anything unprovable keeps the
    literal lowering.
    """
    ho, _ = cl.spec.out_hw
    if not _tiles_contiguously(cl.row_groups, ho):
        return LayerLowering("block", reason="row groups do not tile H")
    if not _tiles_contiguously(cl.k_groups, cl.spec.k):
        return LayerLowering("block", reason="k-groups do not tile K")
    bits = {(ih, kg): bool(relu_of(ih, kg))
            for ih in range(len(cl.row_groups))
            for kg in range(len(cl.k_groups))}
    uniq = set(bits.values())
    if len(uniq) == 1:
        return LayerLowering("fused", relu=uniq.pop())
    if backend == "pallas":
        return LayerLowering(
            "block", reason="mixed RELU bits: Pallas PE is not vmapped")
    sizes = {hi - lo for lo, hi in cl.k_groups}
    if len(sizes) != 1:
        return LayerLowering(
            "block", reason="mixed RELU bits over unequal k-group sizes")
    relu_blocks = tuple(
        tuple(bits[(ih, kg)] for ih in range(len(cl.row_groups)))
        for kg in range(len(cl.k_groups)))
    return LayerLowering("stacked", relu_blocks=relu_blocks,
                         reason="mixed RELU bits")


def analyze_program(program: Program, *, backend: str = "xla",
                    relu_bits: dict | None = None
                    ) -> dict[int, LayerLowering]:
    """The optimizer pass: one :class:`LayerLowering` verdict per layer
    that lowers through the PE — CONV layers get the fused/stacked/block
    analysis; ELTWISE and DEPTHWISE layers get an explicit ``"single"``
    verdict (one dispatch by construction; POOL and FC likewise but
    predate the verdict table and stay implicit). Pure static analysis
    over the instruction stream + compiled geometry — runs once per
    lowering, before any tracing. ``relu_bits`` lets a caller that already
    decoded the stream (``lower_program``) share the one walk."""
    if relu_bits is None:
        relu_bits, _ = _stream_overrides(program)
    out = {}
    for cl in program.layers:
        if cl.kind == "eltwise":
            out[cl.layer_id] = LayerLowering(
                "single", reason="ELTWISE_ADD is one two-source dispatch")
            continue
        if cl.kind == "dw":
            out[cl.layer_id] = LayerLowering(
                "single", reason="DEPTHWISE_CONV is one grouped-conv "
                                 "dispatch")
            continue
        if cl.kind != "conv":
            continue
        out[cl.layer_id] = analyze_layer(
            cl,
            lambda ih, kg, cl=cl: relu_bits.get((cl.layer_id, ih, kg),
                                                cl.spec.relu),
            backend=backend)
    return out


def _layer_forward_fused(cl: CompiledLayer, w_eff: jax.Array,
                         bias: jax.Array, x: jax.Array, relu: bool, *,
                         backend: str, interpret: bool | None,
                         quant: LayerQuant | None = None) -> jax.Array:
    """One whole-layer PE dispatch — the blocked assembly collapsed to a
    single virtual block covering all rows and the full weight image.
    Valid under ``quant`` too: integer accumulation is exact, so the fused
    int32 sums equal the per-block sums bit for bit and the elementwise
    requantize epilogue commutes with the block partition."""
    ho, _ = cl.spec.out_hw
    x_slab = slice_input_span(cl, x, 0, ho)
    blk = conv_block_forward(cl, x_slab, w_eff, bias, relu,
                             backend=backend, interpret=interpret,
                             quant=quant)
    return blk[:, :ho]


def _layer_forward_stacked(cl: CompiledLayer, w_eff: jax.Array,
                           bias: jax.Array, x: jax.Array,
                           lowering: LayerLowering, *, backend: str,
                           interpret: bool | None) -> jax.Array:
    """Stacked-weight batched form: one vmapped PE call over the k-groups
    plus a static per-block RELU mask — replaces the concat chain for
    layers whose RELU bits differ between blocks."""
    ho, _ = cl.spec.out_hw
    n_kg = len(cl.k_groups)
    kg_sz = cl.k_groups[0][1] - cl.k_groups[0][0]
    x_slab = slice_input_span(cl, x, 0, ho)
    # (..., K) -> (G_K, ..., kg_sz): contiguous k-groups become the vmap axis
    w_st = jnp.moveaxis(w_eff.reshape(*w_eff.shape[:-1], n_kg, kg_sz), -2, 0)
    b_st = bias.reshape(n_kg, kg_sz)
    blks = jax.vmap(lambda w, b: conv_block_forward(
        cl, x_slab, w, b, False, backend=backend, interpret=interpret)
    )(w_st, b_st)                                   # (G_K, N, H', W, kg_sz)
    blks = blks[:, :, :ho]
    mask = np.zeros((n_kg, ho), bool)               # static: trace constant
    for kg in range(n_kg):
        for ih, (r0, r1) in enumerate(cl.row_groups):
            mask[kg, r0:r1] = lowering.relu_blocks[kg][ih]
    blks = jnp.where(jnp.asarray(mask)[:, None, :, None, None],
                     jnp.maximum(blks, 0), blks)
    y = jnp.moveaxis(blks, 0, -2)                   # (N, ho, W, G_K, kg_sz)
    return y.reshape(*y.shape[:-2], n_kg * kg_sz)


def _layer_forward(cl: CompiledLayer, w_eff: jax.Array, bias: jax.Array,
                   x_stored: jax.Array, relu_of, *, backend: str = "xla",
                   interpret: bool | None = None,
                   lowering: LayerLowering | None = None,
                   quant: LayerQuant | None = None) -> jax.Array:
    """One layer as blocked compute over the compiled (row, k) groups.

    ``w_eff`` is the DRAM-resident weight image: U-space ``(PT, PT, C, K)``
    for Winograd layers, raw ``(R, S, C, K)`` for Spatial — exactly what
    ``HybridRuntime.load_params`` stores. ``relu_of(ih, kg)`` is the COMP
    instruction's RELU bit for that block (the stream is authoritative, not
    the spec — the interpreter obeys ``ins.relu_flag`` and so must we).
    ``lowering`` is the optimizer's verdict (``None`` = the literal blocked
    lowering, the ``opt_level=0`` reference).
    """
    spec = cl.spec
    x = layouts.load_view(x_stored, cl.inp_layout, hw=(spec.h, spec.w))
    dtype = x_stored.dtype

    # the stacked form masks ReLU AFTER the PE call — wrong under quant,
    # where ReLU must precede the requantize epilogue; keep the literal
    # blocked lowering for those (rare mixed-RELU) layers instead
    if quant is not None and lowering is not None \
            and lowering.kind == "stacked":
        lowering = None

    if lowering is not None and lowering.kind == "fused":
        y = _layer_forward_fused(cl, w_eff, bias, x, lowering.relu,
                                 backend=backend, interpret=interpret,
                                 quant=quant).astype(dtype)
    elif lowering is not None and lowering.kind == "stacked":
        y = _layer_forward_stacked(cl, w_eff, bias, x, lowering,
                                   backend=backend,
                                   interpret=interpret).astype(dtype)
    else:
        row_slabs = []
        for ih, (r0, r1) in enumerate(cl.row_groups):
            x_slab = slice_input_rows(cl, x, ih)
            k_blocks = []
            for kg, (lo, hi) in enumerate(cl.k_groups):
                blk = conv_block_forward(
                    cl, x_slab, w_eff[..., lo:hi], bias[lo:hi],
                    relu_of(ih, kg), backend=backend, interpret=interpret,
                    quant=quant, k_range=(lo, hi))
                k_blocks.append(blk[:, :r1 - r0].astype(dtype))
            row_slabs.append(k_blocks[0] if len(k_blocks) == 1
                             else jnp.concatenate(k_blocks, axis=-1))
        y = (row_slabs[0] if len(row_slabs) == 1
             else jnp.concatenate(row_slabs, 1))
    if cl.out_layout == "wino":
        y = layouts.save_transform(y, "wino", cl.out_m)
    return y


def pool_forward(cl: CompiledLayer, x_stored: jax.Array,
                 window: int, stride: int) -> jax.Array:
    """One POOL block: identity LOAD view -> max pool, NHWC out.

    The SAVE-side layout reorder (``out_layout == "wino"``) is applied by
    the caller — the interpreter's layer flush or the lowered executor —
    exactly as for CONV layers. Shared by both paths so they can never
    drift.
    """
    x = layouts.load_view(x_stored, cl.inp_layout, hw=(cl.spec.h, cl.spec.w))
    return max_pool2d(x, window=window, stride=stride)


def fc_forward(cl: CompiledLayer, w: jax.Array, bias: jax.Array,
               x_stored: jax.Array, relu: bool, *, backend: str = "xla",
               interpret: bool | None = None,
               quant: LayerQuant | None = None) -> jax.Array:
    """One FC layer: identity LOAD view, flatten, run the dense PE.

    ``load_view`` honors ``inp_layout`` so a hand-built stream whose
    previous layer stored tile-major WINO still flattens in NHWC order
    (compiler-emitted programs always store SPAT before FC). Shared by the
    interpreter and the lowered executor; ``backend="pallas"`` routes the
    matmul through the shared ``kernels/gemm`` PE (the int8 GEMM variant
    when ``quant`` is set).
    """
    x = layouts.load_view(x_stored, cl.inp_layout)
    x = x.reshape(x.shape[0], -1)
    if quant is not None:
        return qdense(x, w, bias, mult=quant.multiplier, relu=relu,
                      use_pallas=backend == "pallas", interpret=interpret)
    return dense(x, w, bias, relu=relu, use_pallas=backend == "pallas",
                 interpret=interpret)


def eltwise_forward(cl: CompiledLayer, x_stored: jax.Array,
                    skip_stored: jax.Array, relu: bool,
                    quant: LayerQuant | None = None) -> jax.Array:
    """One ELTWISE_ADD block: two identity LOAD views -> add (+ ReLU).

    ``x_stored``/``skip_stored`` are the producers' STORED tensors (the
    compiler records each operand's layout on the CompiledLayer); like POOL,
    the add is element-parallel VPU work on both backends. Shared by the
    interpreter and the lowered executor so the residual-add math can never
    drift between paths. Under ``quant`` the two int8 operands carry
    different scales, so the add runs through ``qeltwise`` (dequantize into
    output units, add, ReLU, requantize).
    """
    hw = (cl.spec.h, cl.spec.w)
    a = layouts.load_view(x_stored, cl.inp_layout, hw=hw)
    b = layouts.load_view(skip_stored, cl.skip_layout, hw=hw)
    if quant is not None:
        return qeltwise(a, b, quant, relu)
    y = a.astype(jnp.float32) + b.astype(jnp.float32)
    if relu:
        y = jnp.maximum(y, 0.0)
    return y.astype(x_stored.dtype)


def depthwise_forward(cl: CompiledLayer, w: jax.Array, bias: jax.Array,
                      x_stored: jax.Array, relu: bool,
                      quant: LayerQuant | None = None) -> jax.Array:
    """One DEPTHWISE_CONV block: identity LOAD view -> per-channel conv.

    Depthwise conv is VPU work, not an MXU GEMM — like POOL it lowers
    through the same XLA grouped-conv op on both backends (see
    docs/ARCHITECTURE.md); ``quant`` swaps in the int32-accumulating
    grouped conv + requantize epilogue. Shared by the interpreter and the
    lowered executor.
    """
    x = layouts.load_view(x_stored, cl.inp_layout, hw=(cl.spec.h, cl.spec.w))
    if quant is not None:
        return qdepthwise(x, w, bias, mult=quant.multiplier,
                          stride=cl.spec.stride, padding=cl.spec.padding,
                          relu=relu)
    return depthwise_conv2d(
        x, w, bias, stride=cl.spec.stride, padding=cl.spec.padding,
        relu=relu, out_dtype=x_stored.dtype)


def n_param_layers(program: Program) -> int:
    """Layers that carry (w, bias) params — CONV, FC and DEPTHWISE; POOL
    and ELTWISE have none."""
    return sum(cl.kind not in ("pool", "eltwise") for cl in program.layers)


def check_param_count(program: Program, params: list):
    if len(params) != n_param_layers(program):
        raise ValueError(
            f"expected {n_param_layers(program)} (w, bias) entries — one per "
            f"CONV/FC/DEPTHWISE layer in network order, POOL and ELTWISE "
            f"layers carry no params — got {len(params)}")


def to_dram_params(program: Program, params: list) -> list:
    """Raw ``[(w, bias), ...]`` (one entry per *parameterized* layer — CONV
    and FC; POOL layers carry no params) -> the DRAM weight image the
    executor consumes: U-space ``(PT, PT, C, K)`` for Winograd CONV layers,
    raw for Spatial CONV and FC — identical to what
    ``HybridRuntime.load_params`` stores. Pure jax, so it is differentiable
    and may run host-side (once, the paper's offline transform) or inside a
    caller's own trace.
    """
    check_param_count(program, params)
    out = []
    it = iter(params)
    for cl in program.layers:
        if cl.kind in ("pool", "eltwise"):
            continue
        w, b = next(it)
        if cl.kind == "conv" and cl.plan.mode == "wino":
            assert cl.spec.r == 3 and cl.spec.s == 3, \
                "runtime pre-transform supports r=s=3 (VGG family)"
            w = transform_weights(w, cl.plan.m)
        out.append((w, b))
    return out


def layer_scope(cl: CompiledLayer) -> str:
    """The ``jax.named_scope`` of one ISA layer's ops: ``L{layer_id}:{kind}``,
    with ``conv.spat``/``conv.wino`` naming a CONV layer's PE mode. A
    profiler trace carries it in each op's metadata, so device time can be
    put down to the layer. Metadata only: it changes no optimized HLO."""
    kind = f"conv.{cl.plan.mode}" if cl.kind == "conv" else cl.kind
    return f"L{cl.layer_id}:{kind}"


def lower_program(program: Program, *, backend: str = "xla",
                  interpret: bool | None = None, opt_level: int = 1,
                  quant: QuantSidecar | None = None
                  ) -> Callable[[list, jax.Array], jax.Array]:
    """Lower a validated schedule to ``execute(params, x_nhwc) -> y_nhwc``.

    ``params`` is the per-layer **DRAM weight image** — pre-transformed to
    U-space for Winograd layers (see :func:`to_dram_params`). Keeping the
    transform out of the traced function means steady-state calls never
    redo weight work: jit treats params as arguments, so anything computed
    from them inside the trace would re-execute every call.

    ``backend`` selects the per-block PE ("xla" or "pallas", see the module
    docstring); ``interpret`` is the Pallas interpret-mode override
    (``None`` = resolved from the default device). ``opt_level=1``
    (default) runs the lowering optimizer (:func:`analyze_program`) and
    emits the fused / stacked forms
    for layers where they are provably equivalent; ``opt_level=0`` keeps
    the literal per-block lowering everywhere.

    ``quant`` (a :class:`repro.quant.QuantSidecar`) lowers every
    parameterized block through the int8 PE instead — params must then be
    the quantized image (``repro.quant.quantize_params``) and ``x_nhwc``
    int8 at the sidecar's input scale. The schedule, blocking, and
    liveness walk are untouched: quantization changes each block's
    arithmetic, never the program.
    """
    backend, interpret = resolve_backend(backend, interpret)
    opt_level = resolve_opt_level(opt_level)
    for cl in program.layers:
        if cl.kind == "conv" and cl.plan.mode == "wino":
            if quant is not None:
                raise ValueError(
                    f"layer {cl.layer_id}: Winograd plans cannot execute "
                    f"int8 — plan with the dtype='int8' DSE (wino falls "
                    f"back to spatial)")
            assert cl.spec.r == 3 and cl.spec.s == 3, \
                "runtime pre-transform supports r=s=3 (VGG family)"

    # the stream's COMP/FC RELU bits and POOL window/stride are the
    # authority (the compiler sets them from the spec, but hand-built or
    # decoded streams may differ per block)
    relu_bits, pool_cfg = _stream_overrides(program)
    lowerings = (analyze_program(program, backend=backend,
                                 relu_bits=relu_bits)
                 if opt_level >= 1 else {})

    # dataflow wiring, resolved statically: which producer each layer reads
    # (the stash below holds every tensor a not-yet-executed consumer still
    # needs — a skip tensor stays live across its residual block exactly as
    # the compiler's DRAM planner keeps it live) and when each producer's
    # entry retires (so the traced stash mirrors the planner's liveness
    # instead of pinning every activation to the end of the network)
    last_use: dict[int, int] = {}
    for cl in program.layers:
        srcs = {cl.primary_src()}
        if cl.kind == "eltwise":
            srcs.add(cl.skip_src)
        for src in srcs:
            last_use[src] = cl.layer_id

    def execute(params: list, x_nhwc: jax.Array) -> jax.Array:
        cl0 = program.layers[0]
        x = x_nhwc
        if cl0.inp_layout == "wino":
            # the input's SAVE-side reorder is the first layer's work
            with jax.named_scope(layer_scope(cl0)):
                x = layouts.save_transform(x, "wino", cl0.plan.m)
        stash: dict[int, jax.Array] = {-1: x}   # produced, still-live fmaps
        pi = 0
        y = x
        for cl in program.layers:
            with jax.named_scope(layer_scope(cl)):
                y, pi = _lower_layer(cl, params, pi, stash)
            stash[cl.layer_id] = y
            for src in list(stash):
                if last_use.get(src, -2) <= cl.layer_id and src != cl.layer_id:
                    del stash[src]
        return y

    def _lower_layer(cl, params, pi, stash):
        """One layer's ops, its SAVE-side reorder included; returns the
        stored output and the next param index."""
        x_in = stash[cl.primary_src()]
        lq = quant.layers[cl.layer_id] if quant is not None else None
        relu00 = relu_bits.get((cl.layer_id, 0, 0), cl.spec.relu) \
            if cl.kind != "pool" else False
        if cl.kind == "pool":
            window, stride = pool_cfg.get(
                cl.layer_id, (cl.spec.window, cl.spec.stride))
            y = pool_forward(cl, x_in, window, stride)
        elif cl.kind == "eltwise":
            y = eltwise_forward(cl, x_in, stash[cl.skip_src], relu00,
                                quant=lq)
        elif cl.kind == "fc":
            w_eff, b = params[pi]
            pi += 1
            y = fc_forward(cl, w_eff, b, x_in, relu00,
                           backend=backend, interpret=interpret,
                           quant=lq)
        elif cl.kind == "dw":
            w_eff, b = params[pi]
            pi += 1
            y = depthwise_forward(cl, w_eff, b, x_in, relu00, quant=lq)
        else:
            w_eff, b = params[pi]
            pi += 1
            y = _layer_forward(
                cl, w_eff, b, x_in,
                lambda ih, kg, cl=cl: relu_bits.get((cl.layer_id, ih, kg),
                                                    cl.spec.relu),
                backend=backend, interpret=interpret,
                lowering=lowerings.get(cl.layer_id), quant=lq)
        # _layer_forward applies the SAVE-side layout reorder itself;
        # the single-dispatch kinds store what the consumer's LOAD wants
        if cl.kind != "conv" and cl.out_layout == "wino":
            y = layouts.save_transform(y, "wino", cl.out_m)
        return y, pi

    return execute


# ---------------------------------------------------------------------------
# Compiled executor: validation + lowering + jit, with trace accounting
# ---------------------------------------------------------------------------

def mesh_key(mesh) -> tuple | None:
    """Hashable topology key for a device mesh (``None`` = unmapped).

    Shape, axis names AND the flat device ids all join the key: two meshes
    over the same shape but different devices (or the same devices in a
    different order) lower to different per-shard programs, so they must not
    share a cache entry. This is what lets sharded and single-device
    executors of one Program coexist in :mod:`repro.core.program_cache`.
    """
    if mesh is None:
        return None
    return (tuple(mesh.devices.shape), tuple(mesh.axis_names),
            tuple(int(d.id) for d in mesh.devices.flat))


def mesh_device_count(mesh) -> int:
    """Total devices spanned by ``mesh`` (1 for ``None``)."""
    if mesh is None:
        return 1
    return int(np.prod(mesh.devices.shape))


@dataclasses.dataclass
class CompiledExecutor:
    """A jitted executor for one ``(Program, batch, dtype, backend,
    opt_level, donate_input, mesh)`` entry."""
    program: Program
    stats: dict[str, int]          # schedule-validation pipeline counters
    fn: Callable                   # jitted execute(params, x)
    _trace_count: list
    backend: str = "xla"           # resolved PE backend ("xla" | "pallas")
    interpret: bool | None = None  # resolved Pallas interpret mode
    opt_level: int = 1             # lowering-optimizer level (0 = literal)
    donate_input: bool = False     # x buffer donated through jax.jit
    mesh_key: tuple | None = None  # shard_map topology (None = single-device)
    aot_loaded: bool = False       # fn is a deserialized AOT executable
                                   # (core/aot.py): already compiled, never
                                   # traces — trace_count stays 0

    @property
    def trace_count(self) -> int:
        """How many times the underlying function was traced (retrace probe)."""
        return self._trace_count[0]

    def __call__(self, params: list, x_nhwc: jax.Array) -> jax.Array:
        """``params`` is the DRAM weight image (see :func:`to_dram_params`)."""
        return self.fn(params, x_nhwc)


def compile_executor(program: Program,
                     stats: dict[str, int] | None = None, *,
                     backend: str = "xla",
                     interpret: bool | None = None,
                     opt_level: int = 1,
                     donate_input: bool = False,
                     mesh=None,
                     quant: QuantSidecar | None = None) -> CompiledExecutor:
    """Validate (unless pre-validated stats are supplied), lower, and jit.

    ``backend``/``interpret`` select the per-block PE and ``opt_level`` the
    lowering-optimizer level (see :func:`lower_program`); the resolved
    values are recorded on the returned executor so cache introspection can
    tell the paths apart. ``donate_input=True`` donates the activation
    buffer (``x``) through ``jax.jit`` — only safe when the caller never
    reuses the array it passed in (the pipelined ``ServingSession`` stages
    a fresh device array per batch, so it opts in; the general ``run`` path
    must not, since callers commonly re-invoke with the same input).

    ``mesh`` builds the **sharded executor variant**: the lowered function
    is wrapped in ``jax.shard_map`` over the batch axis,
    split across every mesh axis — params replicated, ``x``/``y`` sharded
    on dim 0. Each device runs the *whole per-shard program locally*, so
    the Pallas PE kernels work under sharding (GSPMD cannot partition an
    opaque Pallas custom call, but inside the mapped region there is
    nothing left to partition — every shard is an ordinary single-device
    trace). The batch must divide evenly by the mesh's device count; the
    program cache enforces this at ``get`` time where the batch is known.
    """
    if stats is None:
        stats = validate_schedule(program)
    backend, interpret = resolve_backend(backend, interpret, mesh)
    opt_level = resolve_opt_level(opt_level)
    execute = lower_program(program, backend=backend, interpret=interpret,
                            opt_level=opt_level, quant=quant)
    if mesh is not None and mesh_device_count(mesh) > 1:
        from jax.sharding import PartitionSpec

        batch_spec = PartitionSpec(tuple(mesh.axis_names))
        # check_vma=False: pallas_call outputs carry no varying-manual-axes
        # annotation, and the xla lowering needs no replication check either
        execute = jax.shard_map(execute, mesh=mesh,
                            in_specs=(PartitionSpec(), batch_spec),
                            out_specs=batch_spec, check_vma=False)
    trace_count = [0]

    def traced(params, x):
        trace_count[0] += 1     # Python side effect: fires at trace time only
        return execute(params, x)

    return CompiledExecutor(
        program=program, stats=dict(stats),
        fn=jax.jit(traced, donate_argnums=(1,) if donate_input else ()),
        _trace_count=trace_count, backend=backend, interpret=interpret,
        opt_level=opt_level, donate_input=bool(donate_input),
        mesh_key=mesh_key(mesh))
