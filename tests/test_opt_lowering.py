"""Lowering optimizer (``opt_level``): fused/stacked lowering equivalence
vs the literal per-block reference and the strict interpreter, analysis
verdicts (non-uniform RELU streams must NOT fuse), cache-key separation and
retrace behavior, the bounded validation side table, and the pipelined
``ServingSession`` stats."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.compiler import LayerPlan, compile_network
from repro.core.executor import (
    analyze_program,
    lower_program,
    resolve_opt_level,
    to_dram_params,
    validate_schedule,
)
from repro.core.hybrid_conv import ConvSpec
from repro.core.program_cache import ProgramCache
from repro.core.runtime import HybridRuntime, run_program

_TOL = dict(rtol=1e-4, atol=1e-4)
# fused vs blocked: same math, but XLA may pick a different convolution
# algorithm for small row slabs (documented in ARCHITECTURE.md; the bench
# row records ~6.5e-9 on reduced VGG16). Bitwise-equal on this container,
# but CI installs the latest jaxlib — assert a tight tolerance instead of
# pinning the algorithm choice.
_FUSE_TOL = dict(rtol=1e-6, atol=1e-6)


def _net(h=12, c=3, k=8, k2=12, padding="SAME"):
    specs = [ConvSpec("c1", h, h, c, k, padding=padding, relu=True),
             ConvSpec("c2", h - (2 if padding == "VALID" else 0),
                      h - (2 if padding == "VALID" else 0), k, k2,
                      padding=padding, relu=False)]
    params = []
    for i, s in enumerate(specs):
        kw, kb = jax.random.split(jax.random.PRNGKey(i), 2)
        params.append((
            jax.random.normal(kw, (s.r, s.s, s.c, s.k)) * 0.2,
            jax.random.normal(kb, (s.k,)) * 0.1))
    x = jax.random.normal(jax.random.PRNGKey(99), (2, h, h, c))
    return specs, params, x


from conftest import flip_first_comp as _flip_first_comp  # noqa: E402


# ---------------------------------------------------------------------------
# Analysis verdicts
# ---------------------------------------------------------------------------

def test_compiler_streams_analyze_fused():
    """Compiler-emitted streams have uniform RELU bits and contiguous
    groups -> every CONV layer fuses."""
    specs, _, _ = _net()
    for mode, df in (("spat", "is"), ("wino", "ws")):
        prog = compile_network(specs, [LayerPlan(mode, df, 2, 2, 2),
                                       LayerPlan("spat", df, 2, 3, 2)])
        verdicts = analyze_program(prog)
        assert [v.kind for v in verdicts.values()] == ["fused", "fused"]
        assert verdicts[0].relu is True and verdicts[1].relu is False


def test_nonuniform_relu_stream_does_not_fuse():
    """A hand-flipped COMP RELU bit makes the layer non-fusible: equal-size
    k-groups fall back to the stacked form, never 'fused'."""
    specs, _, _ = _net()
    prog = _flip_first_comp(compile_network(
        specs, [LayerPlan("spat", "is", 2, 2, 2),
                LayerPlan("spat", "is", 2, 2, 2)]))
    verdicts = analyze_program(prog)
    assert verdicts[0].kind == "stacked"       # must NOT fuse
    assert verdicts[1].kind == "fused"         # untouched layer still does


def test_nonuniform_relu_unequal_kgroups_stays_blocked():
    """Mixed RELU bits over unequal k-group sizes (k=10 into 3 groups ->
    4/4/2) cannot stack either: the literal blocked lowering is kept."""
    specs, params, x = _net(k=10)
    prog = _flip_first_comp(compile_network(
        specs, [LayerPlan("spat", "is", 2, 3, 2),
                LayerPlan("spat", "is", 2, 2, 2)]))
    assert [len(g) for g in [prog.layers[0].k_groups]] == [3]
    verdicts = analyze_program(prog)
    assert verdicts[0].kind == "block"
    # and the blocked fallback still matches the reference + interpreter
    y1 = run_program(prog, params, x)                       # opt_level=1
    y0 = jax.jit(lower_program(prog, opt_level=0))(
        to_dram_params(prog, params), x)
    ys = run_program(prog, params, x, strict=True)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), **_FUSE_TOL)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(ys), **_TOL)


def test_pallas_backend_never_stacks():
    """The Pallas PE is not vmapped: mixed-RELU layers stay blocked."""
    specs, _, _ = _net()
    prog = _flip_first_comp(compile_network(
        specs, [LayerPlan("spat", "is", 2, 2, 2),
                LayerPlan("spat", "is", 2, 2, 2)]))
    verdicts = analyze_program(prog, backend="pallas")
    assert verdicts[0].kind == "block"
    assert "Pallas" in verdicts[0].reason


def test_resolve_opt_level_rejects_unknown():
    specs, _, _ = _net()
    prog = compile_network(specs, [LayerPlan(), LayerPlan()])
    with pytest.raises(ValueError, match="opt_level"):
        resolve_opt_level(2)
    with pytest.raises(ValueError, match="opt_level"):
        HybridRuntime(prog, opt_level=7)
    with pytest.raises(ValueError, match="opt_level"):
        lower_program(prog, opt_level="fast")


# ---------------------------------------------------------------------------
# Numerical equivalence: opt_level=1 == opt_level=0 == strict interpreter
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode,dataflow", [("spat", "is"), ("spat", "ws"),
                                           ("wino", "is"), ("wino", "ws")])
def test_fused_matches_blocked_and_interpreter(mode, dataflow):
    specs, params, x = _net()
    other = "wino" if mode == "spat" else "spat"
    prog = compile_network(specs, [LayerPlan(mode, dataflow, 2, 2, 2),
                                   LayerPlan(other, dataflow, 2, 2, 2)])
    dram = to_dram_params(prog, params)
    validate_schedule(prog)
    y1 = jax.jit(lower_program(prog, opt_level=1))(dram, x)
    y0 = jax.jit(lower_program(prog, opt_level=0))(dram, x)
    ys = run_program(prog, params, x, strict=True)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), **_FUSE_TOL)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(ys), **_TOL)


def test_stacked_matches_blocked_and_interpreter():
    specs, params, x = _net()
    prog = _flip_first_comp(compile_network(
        specs, [LayerPlan("spat", "ws", 2, 2, 2),
                LayerPlan("wino", "is", 2, 2, 2)]))
    assert analyze_program(prog)[0].kind == "stacked"
    dram = to_dram_params(prog, params)
    validate_schedule(prog)
    y1 = jax.jit(lower_program(prog, opt_level=1))(dram, x)
    y0 = jax.jit(lower_program(prog, opt_level=0))(dram, x)
    ys = run_program(prog, params, x, strict=True)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), **_TOL)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(ys), **_TOL)
    # the flipped bit actually matters: relu-on reference differs
    ref = run_program(compile_network(
        specs, [LayerPlan("spat", "ws", 2, 2, 2),
                LayerPlan("wino", "is", 2, 2, 2)]), params, x)
    assert not np.allclose(np.asarray(y1), np.asarray(ref))


# The randomized-block-structure property test (opt_level=1 == opt_level=0
# == strict interpreter, non-uniform RELU streams never fuse) lives in
# tests/test_properties.py with the other hypothesis suites — this module
# stays importable without the optional dev dep.


# ---------------------------------------------------------------------------
# Cache behavior: opt_level keys entries, retrace probe, bounded validation
# ---------------------------------------------------------------------------

def test_opt_level_keys_cache_and_no_retrace():
    """Fused and blocked executors of one Program are separate cache
    entries, each traced exactly once across repeated fixed-shape calls."""
    specs, params, x = _net()
    prog = compile_network(specs, [LayerPlan("spat", "is", 2, 2, 2),
                                   LayerPlan("spat", "is", 2, 2, 2)])
    dram = to_dram_params(prog, params)
    cache = ProgramCache()
    e1 = cache.get(prog, batch=2, dtype=jnp.float32, opt_level=1)
    e0 = cache.get(prog, batch=2, dtype=jnp.float32, opt_level=0)
    assert e1 is not e0
    assert cache.stats.misses == 2
    for _ in range(3):
        e1(dram, x)
        e0(dram, x)
    assert e1.trace_count == 1 and e0.trace_count == 1
    assert e1.opt_level == 1 and e0.opt_level == 0
    # same key -> same entry, counted as a hit
    assert cache.get(prog, batch=2, dtype=jnp.float32, opt_level=1) is e1
    assert cache.stats.hits == 1


def test_donate_input_keys_cache_separately():
    specs, params, x = _net()
    prog = compile_network(specs, [LayerPlan("spat", "is", 2, 1, 1),
                                   LayerPlan("spat", "is", 2, 1, 1)])
    cache = ProgramCache()
    a = cache.get(prog, batch=2, dtype=jnp.float32)
    b = cache.get(prog, batch=2, dtype=jnp.float32, donate_input=True)
    assert a is not b and b.donate_input
    assert cache.stats.misses == 2


def test_validated_table_bounded_with_eviction_stats():
    """The validation side table is LRU-bounded and follows entry eviction:
    a stream of distinct programs cannot grow it without limit."""
    base_specs, _, _ = _net()
    programs = []
    for k2 in range(4, 12):      # 8 distinct schedules
        specs = [dataclasses.replace(base_specs[0], k=k2)]
        programs.append(compile_network(
            specs, [LayerPlan("spat", "is", 2, 1, 1)]))
    cache = ProgramCache(maxsize=2, validated_maxsize=3)
    for p in programs:
        cache.get(p, batch=1, dtype=jnp.float32)
    assert len(cache) == 2
    assert cache.validated_size <= 3
    assert cache.stats.evictions == len(programs) - 2
    assert cache.stats.validated_evictions >= len(programs) - 3
    # live entries' schedules keep their validation stats: a re-validate of
    # the most recent program is a side-table hit (counters unchanged)
    before = cache.stats.validated_evictions
    cache.validate(programs[-1])
    assert cache.stats.validated_evictions == before


def test_validate_only_callers_are_bounded():
    base_specs, _, _ = _net()
    cache = ProgramCache(maxsize=2, validated_maxsize=3)
    for k2 in range(4, 12):
        specs = [dataclasses.replace(base_specs[0], k=k2)]
        cache.validate(compile_network(
            specs, [LayerPlan("spat", "is", 2, 1, 1)]))
    assert cache.validated_size <= 3
    assert cache.stats.validated_evictions >= 5


# ---------------------------------------------------------------------------
# Pipelined session: stats + end-to-end inheritance of opt_level
# ---------------------------------------------------------------------------

def test_session_pipeline_stats_and_opt_level_inheritance():
    from repro import api

    specs, _, _ = _net(h=8)
    acc = api.Accelerator.build(
        specs, plans=[LayerPlan("spat", "is", 2, 2, 2),
                      LayerPlan("spat", "is", 2, 2, 2)], batch=4, seed=0)
    acc0 = api.Accelerator.build(
        specs, plans=[LayerPlan("spat", "is", 2, 2, 2),
                      LayerPlan("spat", "is", 2, 2, 2)], batch=4, seed=0,
        params=acc.params, opt_level=0)
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(5), (8, 8, 8, 3)),
                   np.float32)
    y_direct = np.asarray(acc(x[:4]))
    with acc.serve(max_batch=4, buckets=(4,), warmup=True) as s:
        assert s.stats.compile_ms > 0          # warmup trace+compile timed
        outs = s.run_many(list(x))
        np.testing.assert_allclose(np.asarray(outs[0]), y_direct[0],
                                   atol=1e-5, rtol=1e-5)
        assert s.stats.requests == 8 and s.stats.batches >= 2
        assert s.stats.latency_hist.count == 8
        assert 0 < s.stats.p50_ms() <= s.stats.p95_ms()
    # opt_level=0 session serves the reference lowering from its own entry
    with acc0.serve(max_batch=4, buckets=(4,), warmup=True) as s0:
        y0 = np.asarray(s0(x[0]))
    np.testing.assert_allclose(y0, y_direct[0], atol=1e-5, rtol=1e-5)


class _BoomOnMaterialize:
    """Stands in for an async device result whose error only surfaces at
    host materialization — np.asarray(...) in the drain thread."""

    def __array__(self, dtype=None):
        raise RuntimeError("device boom")


def test_session_error_isolation_pipelined():
    """Failures at every pipeline stage surface on the affected futures
    only, and the session keeps serving afterwards: a malformed request is
    rejected at submit, and a device-side error that only materializes in
    the drain thread fails that batch's futures without killing either
    worker thread (close() must still join cleanly)."""
    from repro import api

    specs, _, _ = _net(h=8)
    acc = api.Accelerator.build(
        specs, plans=[LayerPlan("spat", "is", 2, 1, 1),
                      LayerPlan("spat", "is", 2, 1, 1)], batch=2, seed=0)
    with acc.serve(max_batch=2, buckets=(2,)) as s:
        good = s.submit(np.zeros((8, 8, 3), np.float32))
        assert good.result(timeout=30).shape == (8, 8, specs[-1].k)
        with pytest.raises(ValueError):
            s.submit(np.zeros((4, 4, 3), np.float32))   # rejected at submit
        # inject a drain-side failure: the dispatched "result" raises only
        # when the drain thread tries to materialize it
        real_entry = s._entries[2]
        s._entries[2] = lambda params, x: _BoomOnMaterialize()
        doomed = s.submit(np.ones((8, 8, 3), np.float32))
        with pytest.raises(RuntimeError, match="device boom"):
            doomed.result(timeout=30)
        s._entries[2] = real_entry
        again = s.submit(np.ones((8, 8, 3), np.float32))
        assert again.result(timeout=30).shape == (8, 8, specs[-1].k)
    # close() returned -> both threads joined after the injected failure
