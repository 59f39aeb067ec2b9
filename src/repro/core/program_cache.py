"""Compiled-program cache: one jitted executor per
``(Program, batch, dtype, backend, opt_level, donate)``.

Keying rules
------------
The cache key is ``(program.schedule_key(), batch, dtype, param_dtypes,
backend, interpret, opt_level, donate_input)``:

* ``schedule_key()`` (see ``core/compiler.py``) is a content hash over the
  encoded 128-bit instruction stream plus the per-layer geometry (spec, plan,
  row/k groups, layouts). Two ``Program`` objects with identical schedules —
  e.g. recompiled from the same specs/plans — share one cache entry; any
  change to an instruction or a group boundary produces a new key.
* ``batch``, ``dtype`` and (when supplied) the per-layer weight dtypes pin
  the trace: jit would silently retrace on a new input shape/dtype or a
  changed param dtype, so they are part of the key to make (re)compilation
  an observable, counted event rather than a hidden stall.
* ``backend`` ("xla" | "pallas") and the *resolved* Pallas interpret flag
  join the key because they change the lowering itself — the same schedule
  lowered through the XLA ops and through the Pallas PE kernels are two
  different compiled artifacts. ``interpret=None`` is resolved from the
  executor's device (interpret mode off-TPU) *before* keying, so a
  resolved ``None`` and the equivalent explicit value share one entry.
* ``opt_level`` (0 = literal per-block lowering, 1 = the lowering
  optimizer's fused/stacked forms — see ``core/executor.py``) joins the key
  for the same reason: the two levels are different compiled artifacts, and
  keeping both keyed lets the reference lowering serve side by side with
  the optimized one (the property tests rely on exactly this).
* ``donate_input`` joins the key because donation is part of the jitted
  function's signature — a donating executor invalidates the caller's
  input buffer, so it must never be handed to a caller that didn't ask.
* ``mesh`` (keyed by topology: shape, axis names and flat device ids — see
  ``executor.mesh_key``) selects the **sharded executor variant**: the
  lowered function wrapped in ``shard_map`` over the batch axis, so the
  Pallas PEs run per-shard inside the mapped region. ``None`` (the default)
  is the single-device executor; sharded and unsharded entries of one
  Program coexist side by side, which is what lets a serving session keep
  straggler buckets on one device while full buckets span the fleet.

Schedule validation runs **once per schedule key** (not per entry): executors
for new batch sizes of an already-validated program reuse the cached
validation stats. Entries are LRU-evicted beyond ``maxsize``; the validation
side table is bounded too — when the last executor entry of a schedule is
evicted its validation stats go with it, and the table itself is LRU-capped
at ``validated_maxsize`` so validate-only callers cannot grow it without
limit.

Full-network Programs (POOL/FC opcodes) need no special keying: the encoded
stream and per-layer geometry already cover the new layer kinds, so the key
rules are unchanged — a whole-model Program is just one more schedule key.
"""
from __future__ import annotations

import dataclasses
import threading
from collections import OrderedDict

import jax.numpy as jnp

from repro.core.compiler import Program
from repro.core.executor import (
    CompiledExecutor,
    compile_executor,
    mesh_device_count,
    mesh_key,
    resolve_backend,
    resolve_opt_level,
    validate_schedule,
)


@dataclasses.dataclass
class CacheStats:
    hits: int = 0
    misses: int = 0
    evictions: int = 0
    validated_evictions: int = 0    # validation-stat entries dropped
    aot_loads: int = 0              # misses served from a disk artifact
    fallbacks: int = 0              # degraded-backend entry requests (the
                                    # serving layer's pallas->XLA recovery
                                    # path; hits AND misses both count)


def cache_key(program: Program, *, batch: int, dtype,
              param_dtypes: tuple = (), backend: str = "xla",
              interpret: bool | None = None, opt_level: int = 1,
              donate_input: bool = False, mesh=None, quant=None) -> tuple:
    """The cache-key tuple for one executor request, in resolved form.

    Pure and deterministic across processes for equal inputs: every
    component is either a content digest (``schedule_key``, the quant
    digest) or a resolved scalar — this is what lets the AOT artifact
    layer (``core/aot.py``) reuse the exact same identity on disk, and what
    the key-stability property tests pin down.
    """
    backend, interpret = resolve_backend(backend, interpret, mesh)
    opt_level = resolve_opt_level(opt_level)
    if mesh is not None and mesh_device_count(mesh) == 1:
        mesh = None
    return (program.schedule_key(), int(batch), jnp.dtype(dtype).name,
            tuple(param_dtypes), backend, interpret, opt_level,
            bool(donate_input), mesh_key(mesh),
            quant.digest() if quant is not None else None)


class ProgramCache:
    """LRU cache of :class:`CompiledExecutor` keyed by (schedule, batch, dtype)."""

    def __init__(self, maxsize: int = 64, validated_maxsize: int | None = None):
        self.maxsize = maxsize
        # the validation side table holds one small counters dict per
        # schedule; 4x the entry budget comfortably covers every schedule
        # with live entries plus validate-only callers, while still bounding
        # a pathological stream of distinct programs
        self.validated_maxsize = (4 * maxsize if validated_maxsize is None
                                  else validated_maxsize)
        self.stats = CacheStats()
        self._entries: OrderedDict[tuple, CompiledExecutor] = OrderedDict()
        self._validated: OrderedDict[str, dict[str, int]] = OrderedDict()
        self._lock = threading.Lock()

    def __len__(self) -> int:
        return len(self._entries)

    @property
    def validated_size(self) -> int:
        """Schedules with cached validation stats (bounded, see class docs)."""
        return len(self._validated)

    def validate(self, program: Program) -> dict[str, int]:
        """Hazard-check ``program`` once per schedule key; return counters."""
        key = program.schedule_key()
        with self._lock:
            stats = self._validated.get(key)
            if stats is not None:
                self._validated.move_to_end(key)
        if stats is None:
            stats = validate_schedule(program)   # raises HazardError
            with self._lock:
                self._validated[key] = stats
                self._validated.move_to_end(key)
                self._evict_validated_locked()
        return dict(stats)

    def _evict_validated_locked(self):
        """LRU-bound the validation side table; never drop a schedule that
        still has live executor entries (re-validating it would be wasted
        work and would skew the once-per-schedule contract)."""
        if len(self._validated) <= self.validated_maxsize:
            return
        live = {k[0] for k in self._entries}
        for skey in list(self._validated):
            if len(self._validated) <= self.validated_maxsize:
                break
            if skey in live:
                continue
            del self._validated[skey]
            self.stats.validated_evictions += 1

    def get(self, program: Program, *, batch: int, dtype,
            param_dtypes: tuple = (), backend: str = "xla",
            interpret: bool | None = None, opt_level: int = 1,
            donate_input: bool = False, mesh=None,
            quant=None, aot_dir: str | None = None,
            fallback: bool = False) -> CompiledExecutor:
        """The jitted executor for ``program`` at this
        batch/dtype/backend/opt_level/mesh (compile on miss).

        ``param_dtypes`` (one name per layer's weight) joins the key when
        weights may not share the input dtype — otherwise jit would silently
        retrace on the changed param dtypes behind a counted "hit".
        ``backend``/``interpret`` select the per-block PE lowering,
        ``opt_level`` the lowering-optimizer level, and ``donate_input``
        whether the executor donates the activation buffer (see
        ``core/executor.py``); all join the key in resolved form. ``mesh``
        requests the shard_map'd executor variant (batch axis split over
        every mesh axis, params replicated) keyed by mesh topology — the
        batch must divide evenly by the mesh's device count. ``quant`` (a
        ``repro.quant.QuantSidecar``) lowers through the int8 PE and joins
        the key by content digest — the int8 dtype alone is not enough,
        since two calibrations of one network bake different requantize
        multipliers into the trace.

        ``aot_dir`` names an AOT artifact bundle (``core/aot.py``): on a
        cache miss the serialized executable keyed by this exact request
        (plus the device/version fingerprint) is loaded from disk instead
        of re-traced and re-compiled; any stale or missing artifact falls
        back to the fresh compile with the reason logged on ``repro.aot``.
        Mesh-sharded variants never load from disk — their binaries would
        pin one host's device ids.

        ``fallback`` marks a graceful-degradation request (the serving
        layer re-keying a failed Pallas batch onto the XLA lowering).
        Degraded entries need no special treatment here — ``backend`` is
        already part of the key, so the healthy and fallback executors
        coexist — but the flag is counted (``stats.fallbacks``) so
        operators can see degradation traffic at the cache, not just per
        session.
        """
        if fallback:
            with self._lock:
                self.stats.fallbacks += 1
        backend, interpret = resolve_backend(backend, interpret, mesh)
        opt_level = resolve_opt_level(opt_level)
        # a 1-device mesh lowers identically to no mesh — normalize before
        # keying so the two spellings share one entry
        if mesh is not None and mesh_device_count(mesh) == 1:
            mesh = None
        n_dev = mesh_device_count(mesh)
        if n_dev > 1 and batch % n_dev:
            raise ValueError(
                f"sharded executor: batch {batch} does not divide evenly "
                f"over the mesh's {n_dev} devices — pad the batch to a "
                f"multiple (the serving session's bucket fallback) or drop "
                f"the mesh for this batch size")
        key = cache_key(program, batch=batch, dtype=dtype,
                        param_dtypes=param_dtypes, backend=backend,
                        interpret=interpret, opt_level=opt_level,
                        donate_input=donate_input, mesh=mesh, quant=quant)
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None:
                self._entries.move_to_end(key)
                self.stats.hits += 1
                return entry
        stats = self.validate(program)
        entry = None
        if aot_dir is not None and mesh is None:
            from repro.core import aot
            fn = aot.load_entry(aot_dir, key)
            if fn is not None:
                entry = CompiledExecutor(
                    program=program, stats=dict(stats), fn=fn,
                    _trace_count=[0], backend=backend, interpret=interpret,
                    opt_level=opt_level, donate_input=bool(donate_input),
                    mesh_key=None, aot_loaded=True)
                self.stats.aot_loads += 1
        if entry is None:
            entry = compile_executor(program, stats=stats, backend=backend,
                                     interpret=interpret, opt_level=opt_level,
                                     donate_input=donate_input, mesh=mesh,
                                     quant=quant)
        with self._lock:
            # re-check: a racing thread may have compiled the same key while
            # we were outside the lock — first insert wins so every caller
            # holds the same CompiledExecutor identity
            existing = self._entries.get(key)
            if existing is not None:
                self.stats.hits += 1
                return existing
            self._entries[key] = entry
            self.stats.misses += 1
            while len(self._entries) > self.maxsize:
                old_key, _ = self._entries.popitem(last=False)
                self.stats.evictions += 1
                # evict the schedule's validation stats alongside its last
                # executor entry — a dead schedule must not pin host memory
                skey = old_key[0]
                if (skey in self._validated
                        and not any(k[0] == skey for k in self._entries)):
                    del self._validated[skey]
                    self.stats.validated_evictions += 1
        return entry

    def clear(self):
        with self._lock:
            self._entries.clear()
            self._validated.clear()
            self.stats = CacheStats()


_default = ProgramCache()


def default_cache() -> ProgramCache:
    """The process-wide cache used by ``HybridRuntime`` unless one is passed."""
    return _default
