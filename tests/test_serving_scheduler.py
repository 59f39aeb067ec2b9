"""Serving-scheduler invariants under randomized arrival patterns.

Three properties, checked over arbitrary request traces:

* **routing** — every request's result is the accelerator's output for THAT
  request, whatever batch it was coalesced into (futures never swap rows);
* **no starvation** — every submitted request completes, including a lone
  straggler co-tenanting with a model that keeps the shared slot pool busy
  (the continuous admitter's hard cap);
* **exact accounting** — ``SessionStats`` row counters balance to the row:
  ``dispatched_rows`` equals the rows submitted, ``padded_rows`` equals the
  bucket slack, ``device_batches`` sums to ``batches``.

The randomized-trace tests run under hypothesis when available (CI installs
it via requirements-dev.txt); seeded fallbacks cover the same invariants
with fixed traces so the file is never skipped wholesale.
"""
import concurrent.futures
import functools
import threading
import time

import numpy as np
import pytest

from repro import api
from repro.core import perf_model as pm
from repro.core.hybrid_conv import ConvSpec, FCSpec, PoolSpec

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:       # optional dev dep; seeded fallbacks still run
    HAVE_HYPOTHESIS = False

SPECS = [ConvSpec("c1", 16, 16, 3, 8), ConvSpec("c2", 16, 16, 8, 16),
         PoolSpec("p1", 16, 16, 16), FCSpec("fc", 8 * 8 * 16, 10, relu=False)]
MAX_BATCH = 4
BUCKETS = (2, 4)


@pytest.fixture(scope="module")
def acc():
    return api.Accelerator.build(SPECS, target=pm.V5E, batch=MAX_BATCH,
                                 seed=0)


def _requests(n, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.standard_normal((16, 16, 3)).astype(np.float32)
            for _ in range(n)]


def _reference(acc, reqs):
    """Per-request reference outputs via the direct accelerator."""
    y = np.asarray(acc(np.stack(reqs)))
    return [y[i] for i in range(len(reqs))]


def _check_routing(results, refs):
    """Each result matches ITS request's reference — distinct gaussian
    inputs give outputs ~1e-2 apart, so atol=1e-4 catches any row swap."""
    for got, ref in zip(results, refs):
        np.testing.assert_allclose(np.asarray(got), ref, atol=1e-4)


def _check_accounting(stats, total_rows):
    assert stats.dispatched_rows == total_rows
    assert stats.requests == total_rows       # single-image requests
    assert stats.padded_rows >= 0
    # every dispatched batch is one bucket: total staged rows must split
    # into exactly `batches` bucket sizes
    staged = stats.dispatched_rows + stats.padded_rows
    assert stats.batches * min(BUCKETS) <= staged <= stats.batches * max(BUCKETS)
    assert sum(stats.device_batches.values()) == stats.batches
    assert stats.occupancy() == pytest.approx(
        stats.dispatched_rows / staged)
    # the whole-life histograms hold one sample per request
    assert stats.wait_hist.count == stats.dispatched_rows
    assert stats.latency_hist.count == stats.requests
    assert stats.wait_p50_ms() > 0.0
    assert stats.wait_p95_ms() >= stats.wait_p50_ms()


# Future-deadline for result waits. The timing-sensitive tests run through
# _retry_timing_flake below: on a first red the deadline widens 4x and the
# body reruns once — the 1-core CI container occasionally stalls a drain
# thread long enough to blow the tight window without any real bug.
_DEADLINE_S = 60.0


def _retry_timing_flake(test_fn):
    """Retry ONCE with a wider deadline before declaring a timing red.

    Guards only the nondeterministic failure modes of a loaded host —
    future timeouts and window-dependent assertion trips. The retry reruns
    the full body (fresh session, fresh stats), so a genuine routing or
    accounting bug still fails twice and stays red.
    """
    @functools.wraps(test_fn)
    def wrapper(*args, **kwargs):
        global _DEADLINE_S
        try:
            return test_fn(*args, **kwargs)
        except (AssertionError, TimeoutError,
                concurrent.futures.TimeoutError):
            _DEADLINE_S = 240.0
            try:
                return test_fn(*args, **kwargs)
            finally:
                _DEADLINE_S = 60.0
    return wrapper


def _run_trace(acc, trace, scheduler, seed=1):
    """Submit a (burst_size, gap_ms) trace; return (results, stats)."""
    n = sum(b for b, _ in trace)
    reqs = _requests(n, seed)
    refs = _reference(acc, reqs)
    futs, i = [], 0
    with acc.serve(max_batch=MAX_BATCH, buckets=BUCKETS, max_wait_ms=2.0,
                   scheduler=scheduler) as s:
        for burst, gap_ms in trace:
            futs += s.submit_many(reqs[i:i + burst])
            i += burst
            if gap_ms:
                time.sleep(gap_ms / 1e3)
        results = [f.result(timeout=_DEADLINE_S) for f in futs]  # no
        stats = s.stats                                          # starvation
    return results, refs, stats


# --------------------------------------------------------------------------
# seeded fallbacks — always run, no hypothesis needed
# --------------------------------------------------------------------------

@pytest.mark.parametrize("scheduler", api.ServingSession.SCHEDULERS)
@_retry_timing_flake
def test_bursty_trace_routing_and_accounting(acc, scheduler):
    trace = [(3, 1.0), (1, 0.0), (4, 2.0), (2, 1.0), (1, 3.0), (4, 0.0),
             (2, 0.0)]
    results, refs, stats = _run_trace(acc, trace, scheduler)
    _check_routing(results, refs)
    _check_accounting(stats, sum(b for b, _ in trace))


def test_deterministic_bulk_padding_exact(acc):
    """A deep pre-staged backlog groups deterministically: full buckets
    then one padded straggler — byte-exact padded_rows/batches."""
    reqs = _requests(7)
    refs = _reference(acc, reqs)
    with acc.serve(max_batch=MAX_BATCH, buckets=BUCKETS) as s:
        results = s.run_many(reqs)
        stats = s.stats
    _check_routing(results, refs)
    # 7 rows -> one full 4-batch + 3 rows padded into the 4-bucket
    assert stats.batches == 2
    assert stats.dispatched_rows == 7
    assert stats.padded_rows == 1
    assert stats.occupancy() == pytest.approx(7 / 8)
    assert sum(stats.device_batches.values()) == 2


@_retry_timing_flake
def test_mixed_submit_paths_route_correctly(acc):
    """submit / submit_many / run_many interleaved from the caller thread
    all resolve to their own rows (the inline bulk path and the worker
    share the slot pool but never each other's staging)."""
    reqs = _requests(10, seed=3)
    refs = _reference(acc, reqs)
    with acc.serve(max_batch=MAX_BATCH, buckets=BUCKETS) as s:
        f0 = s.submit(reqs[0])
        bulk = s.run_many(reqs[1:6])
        fs = s.submit_many(reqs[6:])
        results = [f0.result(timeout=_DEADLINE_S)] + list(bulk) + [
            f.result(timeout=_DEADLINE_S) for f in fs]
        stats = s.stats
    _check_routing(results, refs)
    _check_accounting(stats, 10)


@_retry_timing_flake
def test_no_starvation_under_co_tenant_flood(acc):
    """A lone request on model B completes while model A floods the shared
    pool — the continuous admitter's hard cap forces B's straggler out
    even though the device never goes idle."""
    acc_b = api.Accelerator.build(SPECS, target=pm.V5E, batch=MAX_BATCH,
                                  seed=7)
    reqs = _requests(40, seed=4)
    lone = _requests(1, seed=5)[0]
    lone_ref = _reference(acc_b, [lone])[0]
    with api.Fleet({"a": acc, "b": acc_b}, max_batch=MAX_BATCH,
                   buckets=BUCKETS, max_wait_ms=2.0) as fleet:
        flood = [fleet.submit("a", r) for r in reqs]
        lone_fut = fleet.submit("b", lone)
        got = lone_fut.result(timeout=_DEADLINE_S)   # must not starve
        for f in flood:
            f.result(timeout=_DEADLINE_S)
    np.testing.assert_allclose(np.asarray(got), lone_ref, atol=1e-4)


def test_scheduler_validation(acc):
    with pytest.raises(ValueError, match="scheduler"):
        acc.serve(scheduler="adaptive")
    with pytest.raises(ValueError, match="capacity"):
        api._SlotPool(0)


def test_fleet_validation(acc):
    with pytest.raises(ValueError, match="at least one"):
        api.Fleet({})
    with api.Fleet({"m": acc}, max_batch=MAX_BATCH, buckets=BUCKETS) as f:
        with pytest.raises(ValueError, match="unknown model"):
            f.submit("nope", _requests(1)[0])
        assert f.models == ("m",)
        assert set(f.stats()) == {"m"}


def test_fleet_round_robin_accounting(acc):
    """Two tenants, interleaved requests: per-model stats stay exact and
    per-model outputs match each model's own reference."""
    acc_b = api.Accelerator.build(SPECS, target=pm.V5E, batch=MAX_BATCH,
                                  seed=11)
    reqs_a, reqs_b = _requests(9, seed=6), _requests(5, seed=8)
    refs_a, refs_b = _reference(acc, reqs_a), _reference(acc_b, reqs_b)
    with api.Fleet({"a": acc, "b": acc_b}, max_batch=MAX_BATCH,
                   buckets=BUCKETS) as fleet:
        pairs = [("a", r) for r in reqs_a] + [("b", r) for r in reqs_b]
        results = fleet.run_many(pairs)
        st_a, st_b = fleet.stats()["a"], fleet.stats()["b"]
    _check_routing(results[:9], refs_a)
    _check_routing(results[9:], refs_b)
    assert st_a.dispatched_rows == 9
    assert st_b.dispatched_rows == 5
    assert sum(st_a.device_batches.values()) == st_a.batches
    assert sum(st_b.device_batches.values()) == st_b.batches


# --------------------------------------------------------------------------
# hypothesis: randomized arrival patterns (CI; optional locally)
# --------------------------------------------------------------------------

if HAVE_HYPOTHESIS:
    @settings(max_examples=10, deadline=None)
    @given(
        trace=st.lists(
            st.tuples(st.integers(1, MAX_BATCH), st.sampled_from(
                [0.0, 0.5, 1.5, 3.0])),
            min_size=1, max_size=8),
        scheduler=st.sampled_from(api.ServingSession.SCHEDULERS),
        seed=st.integers(0, 2 ** 16),
    )
    def test_random_arrivals_route_and_balance(trace, scheduler, seed):
        acc = _hyp_acc()
        results, refs, stats = _run_trace(acc, trace, scheduler, seed=seed)
        _check_routing(results, refs)
        _check_accounting(stats, sum(b for b, _ in trace))

    _HYP_ACC = None

    def _hyp_acc():
        """Module-cached accelerator (fixtures don't reach @given bodies)."""
        global _HYP_ACC
        if _HYP_ACC is None:
            _HYP_ACC = api.Accelerator.build(SPECS, target=pm.V5E,
                                             batch=MAX_BATCH, seed=0)
        return _HYP_ACC
