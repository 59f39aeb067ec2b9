"""Whole-life session timing (``SessionStats`` histograms, phase sums and
snapshots) and the session's host spans (``ServingSession.record_spans``)."""
import time

import numpy as np
import pytest

from repro import api
from repro.core import perf_model as pm
from repro.core.hybrid_conv import ConvSpec, FCSpec, PoolSpec
from repro.serving.telemetry import BINS_PER_OCTAVE, LogHistogram

SPECS = [ConvSpec("c1", 8, 8, 3, 8), PoolSpec("p1", 8, 8, 8),
         FCSpec("fc", 4 * 4 * 8, 10, relu=False)]
BATCH_SPANS = ("session.admit", "session.slot_wait", "session.assemble",
               "session.launch", "session.sync", "session.deliver")
BIN = 2.0 ** (1 / BINS_PER_OCTAVE)


@pytest.fixture(scope="module")
def acc():
    return api.Accelerator.build(SPECS, target=pm.V5E, batch=4, seed=0)


def _images(n, seed=0):
    return list(np.random.default_rng(seed).standard_normal(
        (n, 8, 8, 3)).astype(np.float32))


def _serve(session, xs, timeout=60.0):
    """Submit ``xs`` and wait until the drain side has counted them too
    (it counts a batch just after resolving its futures)."""
    done = session.stats.requests + len(xs)
    out = [f.result(timeout=timeout) for f in session.submit_many(xs)]
    deadline = time.monotonic() + timeout
    while session.stats.requests < done and time.monotonic() < deadline:
        time.sleep(0.001)
    assert session.stats.requests == done
    return out


@pytest.mark.parametrize("dist", ["lognormal", "uniform", "two_modes"])
def test_histogram_percentile_within_one_bin_of_numpy(dist):
    rng = np.random.default_rng(7)
    ms = {"lognormal": rng.lognormal(1.0, 1.5, 5000),
          "uniform": rng.uniform(0.01, 50.0, 5000),
          "two_modes": np.concatenate([rng.normal(2.0, 0.1, 3000),
                                       rng.normal(40.0, 2.0, 2000)])}[dist]
    h = LogHistogram()
    for v in ms:
        h.add(float(v))
    assert h.count == len(ms)
    for q in (0.05, 0.5, 0.9, 0.95, 0.99):
        want = float(np.percentile(ms, 100 * q))
        got = h.percentile(q)
        assert want / BIN <= got <= want * BIN, (q, got, want)


def test_histogram_edges_and_difference():
    h = LogHistogram()
    assert h.percentile(0.5) == 0.0          # empty reads 0, as before
    for ms in (1e-5, 0.5, 0.5, 4.4e6, 1e9):   # below 1 us .. past 71.6 min
        h.add(ms)
    assert h.count == 5 and h.counts[0] == 1 and h.counts[-1] == 2
    early = h.copy()
    h.add(7.0)
    window = h - early
    assert window.count == 1
    assert 7.0 / BIN <= window.percentile(0.5) <= 7.0 * BIN


def test_histograms_count_every_request_and_phases_are_timed(acc):
    with acc.serve(max_batch=4, buckets=(4,), warmup=True) as s:
        _serve(s, _images(10))
        s.run_many(_images(6, seed=1))
    st = s.stats
    assert st.requests == st.submitted == 16
    assert st.latency_hist.count == st.requests
    assert st.wait_hist.count == st.dispatched_rows == 16
    for name in ("stage_ns", "assemble_ns", "launch_ns", "deliver_ns"):
        assert getattr(st, name) > 0, name
    assert 0 < st.p50_ms() <= st.p95_ms()
    assert 0 < st.wait_p50_ms() <= st.wait_p95_ms()


def test_snapshot_differences_give_a_window(acc):
    with acc.serve(max_batch=4, buckets=(4,), warmup=True) as s:
        _serve(s, _images(8))
        before = s.stats.snapshot()
        _serve(s, _images(4, seed=2))
        after = s.stats.snapshot()
    window = after - before
    assert before.requests == 8 and after.requests == 12
    assert window.requests == window.submitted == 4
    assert window.dispatched_rows == window.wait_hist.count == 4
    assert window.latency_hist.count == 4
    assert window.batches == 1
    assert sum(window.device_batches.values()) == 1
    assert window.stage_ns > 0 and window.deliver_ns > 0
    # a snapshot is a copy: serving more leaves it as it was
    assert before.latency_hist.count == 8
    assert window.p95_ms() <= after.latency_hist.percentile(1.0) * BIN


def test_spans_off_by_default_and_in_order_per_batch(acc):
    with acc.serve(max_batch=4, buckets=(4,), warmup=True) as s:
        _serve(s, _images(4))                  # before recording
        t0 = time.time_ns()
        with s.record_spans() as rec:
            _serve(s, _images(8, seed=3))
        t1 = time.time_ns()
        n_recorded = len(rec.spans)
        _serve(s, _images(4, seed=4))          # after recording
        with pytest.raises(RuntimeError, match="already recording"):
            with s.record_spans():
                with s.record_spans():
                    pass
    assert len(rec.spans) == n_recorded       # nothing once it is off
    stage = [sp for sp in rec.spans if sp[0] == "session.stage"]
    assert len(stage) == 1 and stage[0][3] == 4    # first request id
    per_batch: dict = {}
    for name, start, dur, ref in rec.spans:
        assert dur >= 0 and t0 - 1_000_000 <= start <= t1
        if name != "session.stage":
            per_batch.setdefault(ref, []).append(name)
    assert len(per_batch) == 2
    for seq, names in per_batch.items():
        assert tuple(names) == BATCH_SPANS, (seq, names)
    # the assemble span links each batch to its requests
    assert sorted(rid for rids in rec.batches.values() for rid in rids) \
        == list(range(4, 12))
    assert set(rec.batches) == set(per_batch)


def test_bulk_path_spans(acc):
    with acc.serve(max_batch=4, buckets=(4,), warmup=True) as s:
        with s.record_spans() as rec:
            s.run_many(_images(8, seed=5))
    names = [sp[0] for sp in rec.spans]
    assert names.count("session.stage") == 1
    for name in ("session.assemble", "session.launch", "session.sync"):
        assert names.count(name) == 2, name
    assert len(rec.batches) == 2


def test_counts_survive_concurrent_callers(acc):
    """More submitting threads than cores, with a short switch interval:
    a lost update would leave a histogram short of its counter."""
    import sys
    import threading
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with acc.serve(max_batch=4, buckets=(1, 2, 4), warmup=True) as s:
            xs = _images(6)
            futs: list = []
            lock = threading.Lock()

            def client():
                mine = [s.submit(x) for x in xs]
                mine += s.submit_many(xs)
                with lock:
                    futs.extend(mine)
            threads = [threading.Thread(target=client) for _ in range(16)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert not any(t.is_alive() for t in threads)
            for f in futs:
                f.result(timeout=60)
    finally:
        sys.setswitchinterval(old)
    st = s.stats
    assert st.submitted == st.requests == len(futs) == 16 * 12
    assert st.latency_hist.count == st.requests
    assert st.wait_hist.count == st.dispatched_rows == st.requests
    assert sum(st.device_batches.values()) == st.batches
