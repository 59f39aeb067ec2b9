"""Light-weight runtime: executes a HybridDNN instruction stream (Sec. 3 (4)).

Two execution paths share one hazard contract:

* ``strict=True`` — the original functional interpreter of the 128-bit ISA.
  It models the accelerator's on-chip state — ping-pong input/weight buffers,
  a bias buffer and the accumulating output buffer — and enforces the
  handshake-FIFO hazard discipline of Sec. 4.1 *per instruction*: COMP
  validates that the buffer slots it addresses hold the (layer, group) data
  its operands require, and SAVE validates that every block it flushes was
  produced. A mis-scheduled stream — LOAD overwriting a live slot, COMP
  before its LOADs, SAVE before COMP — raises ``HazardError`` rather than
  silently computing garbage.

* default — the **validate-once, trace-many** path (``core/executor.py``):
  the same hazard discipline runs once per ``Program`` as a symbolic
  schedule-validation pass (same ``HazardError``s, same ``stats`` counters),
  then a pure jitted ``execute(params, x)`` — cached per
  ``(Program, batch, dtype)`` in ``core/program_cache.py`` — does the math
  as a static dataflow with no Python-level dispatch. This is how the
  hardware runs: the stream is checked when it is written, not re-checked
  every inference.

DRAM is a word-addressed store (dict base-address -> tensor). Winograd-mode
weights live in DRAM pre-transformed to U-space (Sec. 4.2.3), so LOAD_WGT
traffic matches Eq. 9. The SAVE stage applies the layout reorder for the next
layer's mode (Sec. 4.3) once the layer's last block lands.

The full-network ISA (POOL/FC/ELTWISE_ADD/DEPTHWISE_CONV opcodes) runs a
whole model — CONVs, maxpools, residual adds, depthwise convs and the FC
classifier tail — from ONE instruction stream: POOL validates its input
slot like COMP and produces the pooled block; FC and DEPTHWISE_CONV
additionally check the weight slot and bias buffer; ELTWISE_ADD checks TWO
input slots (primary in slot tag (L, 0), the planner-kept skip operand in
(L, 1)) plus its word2 skip DRAM base and word3 element count; all flow
through the same SAVE/flush path, so every layer kind obeys one hazard
discipline in both execution paths.

Both paths also share one per-block PE dispatch
(``executor.conv_block_forward`` / ``executor.fc_forward``), so the
``backend="xla" | "pallas"`` knob selects the same PE implementation whether
the stream is interpreted per-instruction or lowered to the jitted executor.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax.numpy as jnp
import numpy as np

from repro.core import layouts
from repro.core.compiler import CompiledLayer, Program
from repro.core.executor import (  # noqa: F401  (HazardError re-export)
    HazardError,
    _fresh_stats,
    check_param_count,
    conv_block_forward,
    depthwise_forward,
    eltwise_forward,
    fc_forward,
    pool_forward,
    resolve_backend,
    resolve_opt_level,
    slice_input_rows,
    width_pad,
)
from repro.core.isa import (
    Instruction,
    Opcode,
    unpack_dw_geom,
    unpack_fc_dims,
)
from repro.core.winograd import transform_weights


@dataclasses.dataclass
class _Slot:
    tag: tuple | None = None
    data: Any = None


class HybridRuntime:
    """Executes a compiled :class:`~repro.core.compiler.Program` against
    DRAM-resident params and input.

    Parameters
    ----------
    program:
        The compiled instruction stream plus per-layer geometry.
    backend:
        PE implementation for CONV/FC blocks — ``"xla"`` (default,
        GSPMD-partitionable ``lax`` ops) or ``"pallas"`` (the Pallas PE
        kernels in ``repro.kernels``). Applies to BOTH the cached jitted
        executor and the strict interpreter, which share one per-block
        compute helper per backend. ``use_pallas=True`` is the legacy
        spelling of ``backend="pallas"``.
    interpret:
        Pallas interpret-mode override. ``None`` (default) resolves from
        the device the executor runs on: compiled kernels on a TPU,
        interpret mode elsewhere, so the same Program runs in the CPU test
        suite. A non-None value with the XLA backend raises
        ``ValueError`` (it would otherwise be silently meaningless).
    opt_level:
        Lowering-optimizer level for the cached jitted executor: ``1``
        (default) fuses each layer's per-block loop into a whole-layer PE
        dispatch where provably equivalent; ``0`` keeps the literal
        per-block lowering (the reference). The strict interpreter is
        per-instruction by definition and ignores the knob. Joins the
        program-cache key.
    strict:
        ``True`` replays the stream per-instruction (hazard-faithful
        interpreter); default is the validate-once cached jitted executor.
    cache:
        A :class:`~repro.core.program_cache.ProgramCache` override;
        defaults to the process-global cache.
    quant:
        A :class:`repro.quant.QuantSidecar` switches every parameterized
        block (both paths) to the int8 PE dispatch. Params must then be
        the quantized image (``repro.quant.quantize_params``); a floating
        input is quantized at the sidecar's input scale on entry, and the
        output is the network's int8 logits (dequantize with
        ``quant.dequantize_output``). Joins the program-cache key via the
        sidecar digest.
    """

    def __init__(self, program: Program, use_pallas: bool = False,
                 interpret: bool | None = None, strict: bool = False,
                 cache=None, backend: str | None = None,
                 opt_level: int = 1, quant=None,
                 aot_dir: str | None = None):
        if backend is None:
            backend = "pallas" if use_pallas else "xla"
        # validate eagerly; keep the unresolved pair (the cache resolves
        # interpret at lookup so TPU-vs-CPU auto-selection stays late-bound)
        resolve_backend(backend, interpret)
        self.program = program
        self.backend = backend
        self.use_pallas = backend == "pallas"
        self.interpret = interpret
        self.opt_level = resolve_opt_level(opt_level)
        self.quant = quant
        self.strict = strict
        # AOT artifact bundle directory (core/aot.py): every cache lookup
        # this runtime makes may warm-load its serialized executable from
        # here instead of re-tracing + re-compiling
        self.aot_dir = aot_dir
        self._cache = cache
        self.dram: dict[int, Any] = {}
        self._raw_params: list[tuple[Any, Any]] | None = None
        # pipeline statistics (4-stage pipeline occupancy model) — same
        # counter keys as the executor's schedule-validation pass
        self.stats = _fresh_stats()

    @property
    def cache(self):
        if self._cache is None:
            from repro.core.program_cache import default_cache
            self._cache = default_cache()
        return self._cache

    # -- DRAM management ----------------------------------------------------
    def load_params(self, params: list[tuple[Any, Any]]):
        """params: [(w, bias), ...] — one entry per *parameterized* layer
        (CONV, FC and DEPTHWISE, in network order; POOL and ELTWISE layers
        carry no params). Winograd CONV layers store U-space weights."""
        check_param_count(self.program, params)
        self._raw_params = [tuple(p) for p in params]
        it = iter(params)
        for cl in self.program.layers:
            if cl.kind in ("pool", "eltwise"):
                continue
            w, b = next(it)
            if cl.kind == "conv" and cl.plan.mode == "wino":
                assert cl.spec.r == 3 and cl.spec.s == 3, \
                    "runtime pre-transform supports r=s=3 (VGG family)"
                self.dram[cl.wgt_addr] = transform_weights(w, cl.plan.m)
            else:
                self.dram[cl.wgt_addr] = w
            self.dram[cl.bias_addr] = b

    def dram_params(self) -> list[tuple[Any, Any]]:
        """The DRAM weight image ``load_params`` built — U-space for Winograd
        CONV layers, raw for Spatial/FC; one entry per parameterized layer."""
        if self._raw_params is None:
            raise RuntimeError("load_params must be called first")
        return [(self.dram[cl.wgt_addr], self.dram[cl.bias_addr])
                for cl in self.program.layers
                if cl.kind not in ("pool", "eltwise")]

    def executor_entry(self, batch: int, dtype, *,
                       donate_input: bool = False, mesh=None,
                       backend: str | None = None):
        """The cached jitted executor + DRAM weight image for (batch, dtype).

        The serving hot path: a caller holding a fixed parameter set (e.g.
        ``api.ServingSession``) invokes ``entry(params, x)`` directly,
        skipping the per-request DRAM dict writes ``run`` performs. Schedule
        validation still runs (once per schedule key, cached).
        ``donate_input=True`` hands back an executor that donates the
        activation buffer — only for callers that never reuse the array
        they pass (the pipelined serving queue). ``mesh`` requests the
        shard_map'd executor variant (batch split over every mesh axis,
        Pallas PEs running per-shard); the batch must divide evenly by the
        mesh's device count.

        ``backend`` overrides the runtime's own backend for this one entry
        — the serving layer's graceful-degradation path re-dispatches a
        failed Pallas batch through ``backend="xla"``. An override resets
        ``interpret`` (a Pallas-only knob the XLA lowering would reject)
        and skips the AOT artifact dir (keyed for the primary backend;
        probing it would only log spurious stale-artifact warnings) — the
        DRAM weight image is shared, since backend selection changes the
        lowering, never the weights."""
        if self.strict:
            raise RuntimeError(
                "strict interpreter mode has no cached executor entry")
        params = self.dram_params()
        self.stats = self.cache.validate(self.program)
        is_fallback = backend is not None and backend != self.backend
        entry = self.cache.get(
            self.program, batch=batch, dtype=dtype,
            param_dtypes=tuple(jnp.dtype(w.dtype).name for w, _ in params),
            backend=self.backend if backend is None else backend,
            interpret=self.interpret if not is_fallback else None,
            opt_level=self.opt_level, donate_input=donate_input, mesh=mesh,
            quant=self.quant,
            aot_dir=self.aot_dir if not is_fallback else None,
            fallback=is_fallback)
        return entry, params

    def export_aot(self, aot_dir: str, x_shape, dtype, *,
                   donate_input: bool = False) -> str:
        """AOT-compile the executor for input shape ``x_shape`` (batch
        leading) and persist the serialized executable into ``aot_dir``,
        keyed by the full program-cache key + device/version fingerprint
        (see ``core/aot.py``). Returns the artifact digest. Lowering runs
        against ``ShapeDtypeStruct`` stand-ins — no device math at export
        time."""
        from repro.core import aot
        from repro.core.executor import compile_executor
        from repro.core.program_cache import cache_key

        batch = int(x_shape[0])
        entry, params = self.executor_entry(batch, dtype,
                                            donate_input=donate_input)
        if getattr(entry, "aot_loaded", False):
            # a deserialized executable cannot be re-lowered — rebuild a
            # jit-stage executor so re-exporting a warm-loaded runtime to a
            # new bundle directory still works
            entry = compile_executor(
                self.program, stats=self.stats, backend=self.backend,
                interpret=self.interpret, opt_level=self.opt_level,
                donate_input=donate_input, quant=self.quant)
        key = cache_key(
            self.program, batch=batch, dtype=dtype,
            param_dtypes=tuple(jnp.dtype(w.dtype).name for w, _ in params),
            backend=self.backend, interpret=self.interpret,
            opt_level=self.opt_level, donate_input=donate_input,
            quant=self.quant)
        return aot.save_entry(aot_dir, entry, params, tuple(x_shape), dtype,
                              key)

    def write_input(self, x_nhwc):
        cl0 = self.program.layers[0]
        if cl0.inp_layout == "wino":
            x_nhwc = layouts.save_transform(x_nhwc, "wino", cl0.plan.m)
        self.dram[cl0.inp_addr] = x_nhwc

    # -- execution ----------------------------------------------------------
    def run(self, x_nhwc=None):
        """Validate + execute the program; returns the last layer's output.

        Default: one-time schedule validation (cached per Program) + the
        jitted executor. ``strict=True``: the per-instruction interpreter.
        """
        if self.strict:
            return self._run_interpreter(x_nhwc)
        if self._raw_params is None:
            raise RuntimeError("load_params must be called before run()")
        x_nhwc = self._maybe_quantize_input(x_nhwc)
        if x_nhwc is not None:
            self.write_input(x_nhwc)       # same DRAM contract as strict mode
        else:
            cl0 = self.program.layers[0]
            stored = self.dram[cl0.inp_addr]
            if cl0.kind == "fc":           # FC-first: flat activation, no hw
                x_nhwc = stored.reshape(stored.shape[0], -1)
            else:
                x_nhwc = layouts.load_view(stored, cl0.inp_layout,
                                           hw=(cl0.spec.h, cl0.spec.w))
        # the executor consumes the DRAM weight image load_params already
        # built (U-space for wino) — no per-request weight work; POOL
        # layers carry no params.  executor_entry validates the schedule
        # (HazardError on bad streams; cached per schedule key).
        entry, params = self.executor_entry(x_nhwc.shape[0], x_nhwc.dtype)
        y = entry(params, x_nhwc)
        self.dram[self.program.layers[-1].out_addr] = y
        return y

    def _maybe_quantize_input(self, x_nhwc):
        """Quantized runtimes accept fp inputs for convenience: quantize at
        the sidecar's input scale (a no-op for already-int8 inputs)."""
        if self.quant is not None and x_nhwc is not None \
                and jnp.issubdtype(jnp.asarray(x_nhwc).dtype, jnp.floating):
            return self.quant.quantize_input(x_nhwc)
        return x_nhwc

    def _run_interpreter(self, x_nhwc=None):
        x_nhwc = self._maybe_quantize_input(x_nhwc)
        if x_nhwc is not None:
            self.write_input(x_nhwc)
        inp_slots = [_Slot(), _Slot()]
        wgt_slots = [_Slot(), _Slot()]
        bias_buf = _Slot()
        out_blocks: dict[tuple[int, int], Any] = {}
        cur_layer = -1
        staging = None           # NHWC assembly of the current layer's output

        for ins in self.program.instructions:
            cl = self.program.layers[ins.layer_id]
            if ins.layer_id != cur_layer:
                if cur_layer >= 0:
                    self._flush_layer(self.program.layers[cur_layer], staging,
                                      out_blocks)
                cur_layer = ins.layer_id
                staging = None
                out_blocks = {}

            op = ins.opcode
            if op == Opcode.LOAD_BIAS:
                bias_buf = _Slot((ins.layer_id,), self.dram[ins.dram_base])
                self.stats["load_bias"] += 1
            elif op == Opcode.LOAD_INP:
                ih, slot = ins.buff_base >> 1, ins.buff_base & 1
                if cl.kind in ("pool", "fc", "dw", "eltwise"):
                    # identity load of the stored tensor (the forward
                    # helpers apply the layout view themselves); ELTWISE
                    # reads TWO operands, each by the DRAM base its own
                    # LOAD_INP names — primary (ih 0) from cl.inp_addr,
                    # skip (ih 1) from the planner-kept cl.skip_addr
                    data = self.dram[ins.dram_base]
                else:
                    data = self._load_input_group(cl, ih)
                inp_slots[slot] = _Slot((ins.layer_id, ih), data)
                self.stats["load_inp"] += 1
                self.stats["inp_words"] += ins.size
            elif op == Opcode.LOAD_WGT:
                kg, slot = ins.buff_base >> 1, ins.buff_base & 1
                lo, hi = cl.k_groups[kg]
                w = self.dram[ins.dram_base][..., lo:hi]
                wgt_slots[slot] = _Slot((ins.layer_id, kg), w)
                self.stats["load_wgt"] += 1
                self.stats["wgt_words"] += ins.size
            elif op == Opcode.COMP:
                ih = ins.size & 0xFFF
                kg = (ins.size >> 12) & 0xFFF
                islot = (ins.size >> 24) & 1
                wslot = (ins.size >> 25) & 1
                if inp_slots[islot].tag != (ins.layer_id, ih):
                    raise HazardError(
                        f"COMP L{ins.layer_id} row-group {ih}: input slot "
                        f"{islot} holds {inp_slots[islot].tag}")
                if wgt_slots[wslot].tag != (ins.layer_id, kg):
                    raise HazardError(
                        f"COMP L{ins.layer_id} k-group {kg}: weight slot "
                        f"{wslot} holds {wgt_slots[wslot].tag}")
                if bias_buf.tag != (ins.layer_id,):
                    raise HazardError(f"COMP L{ins.layer_id}: stale bias buffer")
                blk = self._compute(cl, inp_slots[islot].data,
                                    wgt_slots[wslot].data,
                                    bias_buf.data, ih, kg, ins)
                out_blocks[(ih, kg)] = blk
                self.stats["comp"] += 1
            elif op == Opcode.POOL:
                islot = ins.buff_base & 1
                cfg = (ins.pool_window, ins.pool_stride)
                if cfg != (cl.spec.window, cl.spec.stride):
                    raise HazardError(
                        f"POOL L{ins.layer_id}: word0 window/stride {cfg} "
                        f"disagree with compiled spec "
                        f"({cl.spec.window}, {cl.spec.stride})")
                if inp_slots[islot].tag != (ins.layer_id, 0):
                    raise HazardError(
                        f"POOL L{ins.layer_id}: input slot {islot} holds "
                        f"{inp_slots[islot].tag}")
                out_blocks[(0, 0)] = pool_forward(
                    cl, inp_slots[islot].data, ins.pool_window,
                    ins.pool_stride)
                self.stats["pool"] += 1
            elif op == Opcode.FC:
                islot = ins.buff_base & 1
                wslot = (ins.buff_base >> 1) & 1
                dims = unpack_fc_dims(ins.size)
                if dims != (cl.spec.d_in, cl.spec.d_out):
                    raise HazardError(
                        f"FC L{ins.layer_id}: word3 dims {dims} disagree "
                        f"with compiled spec ({cl.spec.d_in}, {cl.spec.d_out})")
                if inp_slots[islot].tag != (ins.layer_id, 0):
                    raise HazardError(
                        f"FC L{ins.layer_id}: input slot {islot} holds "
                        f"{inp_slots[islot].tag}")
                if wgt_slots[wslot].tag != (ins.layer_id, 0):
                    raise HazardError(
                        f"FC L{ins.layer_id}: weight slot {wslot} holds "
                        f"{wgt_slots[wslot].tag}")
                if bias_buf.tag != (ins.layer_id,):
                    raise HazardError(f"FC L{ins.layer_id}: stale bias buffer")
                out_blocks[(0, 0)] = fc_forward(
                    cl, wgt_slots[wslot].data, bias_buf.data,
                    inp_slots[islot].data, ins.relu_flag,
                    backend=self.backend, interpret=self.interpret,
                    quant=self._layer_quant(cl))
                self.stats["fc"] += 1
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
                        f"{ins.dram_base} disagrees with compiled skip "
                        f"operand ({cl.skip_addr})")
                if inp_slots[pslot].tag != (ins.layer_id, 0):
                    raise HazardError(
                        f"ELTWISE L{ins.layer_id}: primary input slot "
                        f"{pslot} holds {inp_slots[pslot].tag}")
                if inp_slots[sslot].tag != (ins.layer_id, 1):
                    raise HazardError(
                        f"ELTWISE L{ins.layer_id}: skip input slot {sslot} "
                        f"holds {inp_slots[sslot].tag}")
                out_blocks[(0, 0)] = eltwise_forward(
                    cl, inp_slots[pslot].data, inp_slots[sslot].data,
                    ins.relu_flag, quant=self._layer_quant(cl))
                self.stats["eltwise"] += 1
            elif op == Opcode.DEPTHWISE_CONV:
                islot = ins.buff_base & 1
                wslot = (ins.buff_base >> 1) & 1
                geom = unpack_dw_geom(ins.size)
                if geom != (cl.spec.r, cl.spec.s, cl.spec.stride):
                    raise HazardError(
                        f"DEPTHWISE L{ins.layer_id}: word3 geometry {geom} "
                        f"disagrees with compiled spec "
                        f"({cl.spec.r}, {cl.spec.s}, {cl.spec.stride})")
                if inp_slots[islot].tag != (ins.layer_id, 0):
                    raise HazardError(
                        f"DEPTHWISE L{ins.layer_id}: input slot {islot} "
                        f"holds {inp_slots[islot].tag}")
                if wgt_slots[wslot].tag != (ins.layer_id, 0):
                    raise HazardError(
                        f"DEPTHWISE L{ins.layer_id}: weight slot {wslot} "
                        f"holds {wgt_slots[wslot].tag}")
                if bias_buf.tag != (ins.layer_id,):
                    raise HazardError(
                        f"DEPTHWISE L{ins.layer_id}: stale bias buffer")
                out_blocks[(0, 0)] = depthwise_forward(
                    cl, wgt_slots[wslot].data, bias_buf.data,
                    inp_slots[islot].data, ins.relu_flag,
                    quant=self._layer_quant(cl))
                self.stats["dw"] += 1
            elif op == Opcode.SAVE and cl.kind != "conv":
                if (0, 0) not in out_blocks:
                    raise HazardError(
                        f"SAVE L{ins.layer_id} block (0, 0) not computed")
                staging = out_blocks.pop((0, 0))
                self.stats["save"] += 1
            elif op == Opcode.SAVE:
                ih = ins.size & 0xFFF
                kg = (ins.size >> 12) & 0xFFF
                ho, wo = cl.spec.out_hw
                if staging is None:
                    n = self._batch(cl)
                    staging = jnp.zeros((n, ho, wo, cl.spec.k),
                                        self._dtype(cl))
                if cl.plan.dataflow == "is":
                    # one SAVE per row group: all K groups must be computed
                    need = [(ih, g) for g in range(len(cl.k_groups))]
                else:
                    need = [(ih, kg)]
                for key in need:
                    if key not in out_blocks:
                        raise HazardError(
                            f"SAVE L{ins.layer_id} block {key} not computed")
                r0, r1 = cl.row_groups[ih]
                if cl.plan.dataflow == "is":
                    row = jnp.concatenate(
                        [out_blocks.pop((ih, g)) for g in
                         range(len(cl.k_groups))], axis=-1)
                    staging = staging.at[:, r0:r1].set(row.astype(staging.dtype))
                else:
                    c0, c1 = cl.k_groups[kg]
                    staging = staging.at[:, r0:r1, :, c0:c1].set(
                        out_blocks.pop((ih, kg)).astype(staging.dtype))
                self.stats["save"] += 1
            else:
                raise ValueError(op)

        if cur_layer >= 0:
            self._flush_layer(self.program.layers[cur_layer], staging,
                              out_blocks)
        last = self.program.layers[-1]
        return self.dram[last.out_addr]

    # -- helpers ------------------------------------------------------------
    def _batch(self, cl: CompiledLayer) -> int:
        x = self.dram[cl.inp_addr]
        return x.shape[0]

    def _dtype(self, cl: CompiledLayer):
        return self.dram[cl.inp_addr].dtype

    def _input_nhwc(self, cl: CompiledLayer):
        x = self.dram[cl.inp_addr]
        return layouts.load_view(x, cl.inp_layout, hw=(cl.spec.h, cl.spec.w))

    def _load_input_group(self, cl: CompiledLayer, ih: int):
        """Slice the input rows (plus halo) needed for output rows group ih.

        Delegates to the executor's helper so the interpreter and the jitted
        path share one copy of the halo arithmetic."""
        return slice_input_rows(cl, self._input_nhwc(cl), ih)

    def _layer_quant(self, cl: CompiledLayer):
        return self.quant.layers[cl.layer_id] if self.quant is not None \
            else None

    def _compute(self, cl: CompiledLayer, x_slab, w_grp, bias, ih, kg, ins):
        lo, hi = cl.k_groups[kg]
        # one shared per-block PE dispatch (executor.conv_block_forward) so
        # the interpreter and the lowered executor can never drift — the
        # backend knob routes both through the same XLA or Pallas PE
        blk = conv_block_forward(
            cl, x_slab, w_grp, bias[lo:hi], ins.relu_flag,
            backend=self.backend, interpret=self.interpret,
            quant=self._layer_quant(cl), k_range=(lo, hi))
        r0, r1 = cl.row_groups[ih]
        return blk[:, :r1 - r0]

    def _flush_layer(self, cl: CompiledLayer, staging, out_blocks):
        if out_blocks:
            raise HazardError(
                f"layer {cl.layer_id}: {len(out_blocks)} COMP blocks never SAVEd")
        if staging is None:
            raise HazardError(f"layer {cl.layer_id}: no SAVE executed")
        if cl.out_layout == "wino":
            self.dram[cl.out_addr] = layouts.save_transform(
                staging, "wino", cl.out_m)
        else:
            self.dram[cl.out_addr] = staging


def run_program(program: Program, params, x_nhwc, **kw):
    rt = HybridRuntime(program, **kw)
    rt.load_params(params)
    return rt.run(x_nhwc)
