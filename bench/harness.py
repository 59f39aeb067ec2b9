"""One run of one cell: set-up, the measured window, the comparison with the
plain reference, and the result line.

The system under test is driven on its normal path: ``Accelerator.build``
(DSE -> one ``Program`` -> schedule validation) -> cached jitted executor ->
``ServingSession`` through ``Accelerator.serve``. The benchmark passes it the
model's layer specs, the seeded weights, the serving batch and, for a
quantized configuration, its dtype and calibration inputs; the program
chooses everything else (backend, lowering, scheduler, PE modes) by default.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import math
import os
import sys
import time
import types
from collections.abc import Callable
from pathlib import Path

import numpy as np

from bench import correct, registry, scopes
from bench import trace as tracing
from bench.peaks import peaks_for

COUNTERS = ("submitted", "requests", "errors", "shed", "batches",
            "dispatched_rows", "padded_rows")


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def check_chip(jax, chips: int):
    """The devices the cell runs on and their peaks. Exits non-zero, before
    anything is measured, without a TPU, on a ``device_kind`` the peaks
    table does not know, or with fewer chips than the cell asks for."""
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"bench: no TPU: JAX's first device is "
                         f"{dev.platform!r} ({dev.device_kind!r})")
    try:
        peaks = peaks_for(dev.device_kind)
    except LookupError as e:
        raise SystemExit(f"bench: {e}") from None
    if len(devices) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX sees "
                         f"{len(devices)}")
    return devices[:chips], peaks


def rng(seed: int, stream: int) -> np.random.Generator:
    """Host randomness for one purpose (``stream``) of one seed."""
    return np.random.default_rng([stream, seed % 2 ** 64])


def enable_cache(jax, root) -> "CacheEvents":
    """JAX's persistent compilation cache at ``<checkout>/.jax_cache``,
    handed to the program's own switch, with no minimum compile time so
    short compiles hit too."""
    from repro.launch import compile_cache
    os.environ[compile_cache.ENV_VAR] = str(root / ".jax_cache")
    where = compile_cache.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return CacheEvents(jax, where)


class CacheEvents:
    """Counts persistent-cache hits and misses from JAX's monitoring events."""

    def __init__(self, jax, where: str):
        self.where, self.hits, self.misses = where, 0, 0

        def listen(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1
        jax.monitoring.register_event_listener(listen)


@dataclasses.dataclass
class Served:
    """A built accelerator with its open session, the mix's loop, the
    weights, the input pool and what the comparison needs."""
    acc: object
    session: object
    loop: object
    params: list
    pool: np.ndarray
    model: types.ModuleType
    sizes: dict
    batch: int
    devices: list


def session_buckets(mix: dict, batch: int):
    """``(max_batch, buckets)`` of the session the mix asks for: ``"full"``
    (the configuration's batch alone), ``"powers_of_two"`` (the program's
    default up to that batch) or a list of sizes, whose largest is then the
    session's ``max_batch``."""
    buckets = mix.get("buckets", "powers_of_two")
    if buckets == "full":
        return batch, (batch,)
    if buckets == "powers_of_two":
        return batch, None
    buckets = tuple(sorted(int(b) for b in buckets))
    return buckets[-1], buckets


def build(jax, cfg: dict, mix: dict, chips: int, seed: int, devices,
          base=registry.BENCH_DIR) -> Served:
    """Set-up: weights on the device, the input pool, the accelerator and
    its warmed session."""
    from repro import api

    model = registry.load_model(cfg["reference"], base)
    loop = registry.load_loop(mix["loop"], base)
    sizes = cfg["sizes"]
    t = time.monotonic()
    key = jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF),
                             (seed >> 32) & 0xFFFFFFFF)
    params = jax.jit(lambda k: model.init_params(k, sizes))(key)
    jax.block_until_ready(params)
    say(f"weights on the device: {time.monotonic() - t:.3f} s")

    img = sizes["img"]
    pool = rng(seed, 1).standard_normal(
        (mix["pool"], img, img, 3), dtype=np.float32)
    builder_path, _, fn = cfg["network"]["builder"].partition(":")
    specs = getattr(importlib.import_module(builder_path), fn)(
        **cfg["network"]["kwargs"])

    batch, buckets = session_buckets(mix, cfg["max_batch_per_chip"] * chips)
    kwargs = dict(cfg.get("build", {}))
    if kwargs.get("dtype", "float32") != "float32":
        kwargs["calib"] = rng(seed, 2).standard_normal(
            (cfg["calibration_images"], img, img, 3), dtype=np.float32)
    t = time.monotonic()
    acc = api.Accelerator.build(specs, batch=batch, params=params, **kwargs)
    say(f"Accelerator.build: {time.monotonic() - t:.3f} s, "
        f"{acc.n_instructions} instructions")

    mesh = None
    if chips > 1:
        from repro.launch.mesh import make_fleet_mesh
        mesh = make_fleet_mesh(chips)
    t = time.monotonic()
    session = acc.serve(max_batch=batch, buckets=buckets, warmup=True,
                        mesh=mesh, **mix.get("serve", {}))
    say(f"session open + warm-up of buckets {session.buckets}: "
        f"{time.monotonic() - t:.3f} s (compile_ms "
        f"{session.stats.compile_ms:.1f})")
    return Served(acc, session, loop, params, pool, model, sizes, batch,
                  list(devices))


def drive(served: Served, mix: dict, seconds: float, seed: int, *,
          spans: list | None = None, rate: float | None = None):
    """Run the mix's loop for ``seconds``; returns its ``WindowLog``."""
    return served.loop(served.session, served.pool, seconds, mix,
                       batch=served.batch, seed=seed, spans=spans, rate=rate)


def counters(stats) -> dict:
    return {k: getattr(stats, k) for k in COUNTERS}


class GcWatch:
    """Collections of the garbage collector, and the longest, while on."""

    def __init__(self):
        self.n, self.longest_ms, self._t = 0, 0.0, None

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.monotonic()
        elif self._t is not None:
            self.n += 1
            self.longest_ms = max(self.longest_ms,
                                  (time.monotonic() - self._t) * 1e3)

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *_):
        gc.callbacks.remove(self)


@dataclasses.dataclass
class Scoped:
    """What a traced window reads the program's layer scopes with: each HLO
    instruction's scope (``names``), the scopes whose weights XLA prefetches
    across programs (``prefetched``), and ``costs(rows)``: the reference's
    ``layer_costs`` of a call on ``rows`` images, held to the program's
    layers (``None`` for a reference that gives none)."""
    names: dict
    prefetched: set
    costs: Callable[[int], list] | None


def scoping(jax, served: Served, cfg: dict) -> Scoped | None:
    """The layer scopes of the session's executor, from its compiled text
    (``scopes.executor_hlo``), or ``None`` for a session of several
    buckets: each bucket's module numbers its instructions its own way, and
    the trace does not say in which module an op ran. A reference whose
    ``layer_costs`` are not the program's layers fails here, before the
    traced window."""
    if len(served.session.buckets) != 1:
        return None
    hlo = scopes.executor_hlo(jax, served.session)
    names = scopes.hlo_scopes(hlo)
    costs = None
    layer_costs = getattr(served.model, "layer_costs", None)
    if layer_costs is not None:
        program = [(cl.layer_id, cl.kind) for cl in served.acc.program.layers]
        sizes = served.sizes
        nbytes = np.dtype(cfg.get("build", {}).get("dtype", "float32")
                          ).itemsize

        def costs(rows):
            return scopes.check_costs(layer_costs(sizes, rows, nbytes),
                                      program)
        costs(served.batch)
    return Scoped(names, scopes.prefetched_scopes(hlo, names), costs)


def window(jax, served: Served, mix: dict, seconds: float, seed: int,
           trace: bool = False, rate: float | None = None,
           scoped: Scoped | None = None):
    """One window of the mix, traced or not: ``(log, the session's
    statistics over the window (a ``SessionStats`` difference), the trace's
    events or None)``. A traced window keeps each op's scope from
    ``scoped``, and leaves the session's own spans off: they are host work
    on the session's threads, and in a cell the host bounds they would move
    ``idle_share`` (``bench/layer_profile.py`` measures them). Every
    request is then waited for, at most a minute past the window's
    close."""
    before = served.session.stats.snapshot()
    events = None
    with GcWatch() as gcw:
        if trace:
            with scopes.capture(scoped.names if scoped else {}) as events:
                log = drive(served, mix, seconds, seed, spans=events["host"],
                            rate=rate)
        else:
            log = drive(served, mix, seconds, seed, rate=rate)
    stats = served.session.stats.snapshot() - before
    log.settle(60.0)
    done = np.sort([d for d in log.done if d is not None])
    done = done[done <= log.t_end]
    stall = float(np.max(np.diff(done))) * 1e3 if len(done) > 1 else None
    say(f"window of {seconds} s: {gcw.n} collections, longest "
        f"{gcw.longest_ms:.1f} ms; longest gap between answers {stall} ms")
    return log, stats, events


def reduce_trace(events: dict, device_ids, scoped: Scoped | None, stats,
                 peak_ops: float, hbm_bytes_per_s: float) -> dict | None:
    """A traced window's device numbers, or ``None`` where the trace holds
    no window or no device events: ``trace.reduce``'s (``window_s``,
    ``busy_s``, ``module_s``, ``idle_gaps``), ``device_ops`` labelled with
    their scopes, ``scope_s`` and ``scoped_share`` (``scopes.reduce_scopes``)
    and ``layers``: ``scopes.layer_rows`` per scope over the window's
    executor calls (``stats``), or ``None`` without ``scope_s``, costs or a
    call."""
    reduced = scopes.reduce_scopes(events, device_ids)
    if reduced is None:
        return None
    reduced["layers"] = None
    if scoped and scoped.costs and reduced["scope_s"] and stats.batches:
        rows = round((stats.dispatched_rows + stats.padded_rows)
                     / stats.batches)
        reduced["layers"] = scopes.layer_rows(
            reduced["scope_s"], stats.batches, scoped.costs(rows), peak_ops,
            hbm_bytes_per_s, scoped.prefetched)
    return reduced


def say_layers(reduced: dict) -> None:
    """The traced window's table per ISA layer, on standard error."""
    say(f"traced window: {100 * reduced['scoped_share']:.2f}% of op time "
        f"scoped")
    for row in reduced["layers"] or ():
        share = ("-" if row["roofline"] is None
                 else f"{row['roofline']:.1f}%")
        say(f"  {row['scope']:<16} {row['ms_per_call']:9.4f} ms/call  "
            f"roofline {share} ({row['bound']})"
            + (f"  not measured: {row['unmeasured']}" if row["unmeasured"]
               else ""))


def memory_peak(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_process: float, root=registry.ROOT, base=registry.BENCH_DIR,
             device_check=check_chip) -> dict:
    """One run of the cell ``name``; returns the result line's object.

    Each metric's ``read(ctx)`` gets: ``seconds``, ``setup_s``, ``chips``,
    ``config``, ``traffic``; the measured window's ``log``
    (``loadgen.WindowLog``), ``latencies_ms``, ``completed_in_window``,
    ``counters`` (``COUNTERS``' deltas) and ``stats`` (the session's
    ``SessionStats`` difference over it: every counter, phase time and
    histogram); ``flops_per_image``, ``peak_ops``; and, in a ``--trace 1``
    run, ``trace``: ``reduce_trace``'s numbers with the traced window's
    ``counters`` and ``stats`` (``None`` where the trace holds no device
    events)."""
    bench = registry.load_benchmark(root)
    cell = registry.workload(bench, name)
    cfg = registry.load_config(cell["config"], base)
    mix = registry.load_traffic(cell["traffic"], base)
    chips = int(cell["chips"])
    entries = registry.cell_metrics(bench, name, trace)
    readers = {m["name"]: registry.load_metric(m["name"], base)
               for m in entries}

    import jax
    cache = enable_cache(jax, Path(root))
    devices, peaks = device_check(jax, chips)
    dev = devices[0]
    say(f"device: {dev.platform} {dev.device_kind!r}, {len(jax.devices())} "
        f"visible, {chips} used; compile cache {cache.where}")

    served = build(jax, cfg, mix, chips, seed, devices, base)
    if mix.get("warm_seconds"):
        drive(served, mix, mix["warm_seconds"], seed + 1).settle(60.0)
    # what set-up made lives to the end: the collector need not pass over it
    gc.collect()
    gc.freeze()
    setup_s = time.monotonic() - t_process
    say(f"set-up {setup_s:.3f} s; compile cache: {cache.hits} hits, "
        f"{cache.misses} misses")

    log, stats, _ = window(jax, served, mix, seconds, seed)
    delta = counters(stats)
    logs, reduced, traced = [log], None, None
    if trace:
        # the device is traced in a short window of its own after the
        # measured one: tracing slows the host, and a long trace is large
        scoped = scoping(jax, served, cfg)
        tlog, tstats, events = window(jax, served, mix, tracing.TRACE_SECONDS,
                                      seed + 2, True, scoped=scoped)
        logs.append(tlog)
        reduced = reduce_trace(events, [d.id for d in served.devices], scoped,
                               tstats, peaks[cfg["compute"]],
                               peaks["hbm_bytes_per_s"])
        if reduced:
            if scoped:
                say_layers(reduced)
            traced = types.SimpleNamespace(counters=counters(tstats),
                                           stats=tstats, **reduced)
    mem = memory_peak(served.devices)
    served.session.close()
    counts = {k: sum(lg.counts()[k] for lg in logs)
              for k in ("answered", "shed", "failed")}
    say(f"window: {len(log.due)} requests, {log.counts()}; session counters "
        f"{delta}; memory_peak_bytes {mem}")

    # the comparison runs after the windows, once the program's state is gone
    params, pool, model, sizes = (served.params, served.pool, served.model,
                                  served.sizes)
    del served
    gc.unfreeze()
    gc.collect()
    t = time.monotonic()
    ref = correct.logits(model, params, pool, sizes)
    answers: dict = {}
    for lg in logs:
        for k, n in lg.answers.items():
            answers[k] = answers.get(k, 0) + n
    worst = correct.worst_rms_rel(answers, ref)
    limit = cfg["correct"]["worst_rms_rel"]["limit"]
    is_correct = correct.judge(worst, counts["answered"], counts["failed"],
                               limit)
    say(f"reference over the pool of {len(pool)}: "
        f"{time.monotonic() - t:.3f} s; {len(answers)} distinct answers")

    ctx = types.SimpleNamespace(
        seconds=seconds, setup_s=setup_s, chips=chips, config=cfg,
        traffic=mix, log=log, counters=delta, stats=stats, trace=traced,
        completed_in_window=log.answered_by(log.t_end),
        latencies_ms=log.latencies_ms(),
        flops_per_image=model.flops_per_image(sizes),
        peak_ops=peaks[cfg["compute"]])
    metrics = {}
    for m in entries:
        value = readers[m["name"]](ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem}
    out = {"correct": is_correct,
           "attempted": sum(len(lg.due) for lg in logs),
           "failed": counts["failed"], "shed": counts["shed"],
           "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = reduced["busy_s"] if reduced else 0.0
        device["window_s"] = (reduced["window_s"] if reduced
                              else tracing.TRACE_SECONDS)
        if reduced:
            out["breakdown"] = {"device_ops": reduced["device_ops"],
                                "idle_gaps": reduced["idle_gaps"]}
    # a run with no answer, or a non-finite one, has no number to compare
    shown = worst if math.isfinite(worst) else None
    out["checks"] = {"worst_rms_rel": {"value": shown, "limit": limit},
                     "failed": {"value": counts["failed"], "limit": 0}}
    for k, v in out["checks"].items():
        say(f"check {k}: {v['value']!r} (limit {v['limit']!r})")
    return out
