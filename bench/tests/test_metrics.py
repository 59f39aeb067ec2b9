"""The per-layer metrics that read the program's layer scopes and session
statistics (``metrics/*.py``), each on a hand-built ``ctx``, with the cases
in which it finds nothing to read.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
import types

import pytest

from bench import registry
from repro.api import SessionStats


def _read(name, ctx):
    return registry.load_metric(name)(ctx)


def _trace(scope_s, batches=4, layers=None):
    return types.SimpleNamespace(scope_s=scope_s, layers=layers,
                                 counters={"batches": batches})


SCOPES = {"L0:conv.spat": 0.004, "L1:conv.wino": 0.02, "L2:pool": 0.001,
          "L3:conv.spat": 0.002, "L4:fc": 0.003}


@pytest.mark.parametrize("name,ms", [("conv_spat_step_ms", 1.5),
                                     ("conv_wino_step_ms", 5.0)])
def test_conv_step_ms_sums_its_kind_per_call(name, ms):
    ctx = types.SimpleNamespace(trace=_trace(SCOPES))
    assert _read(name, ctx) == pytest.approx(ms)
    # no trace, too little scoped, no call, no layer of that kind
    for trace in (None, _trace(None), _trace(SCOPES, batches=0),
                  _trace({"L2:pool": 0.001})):
        assert _read(name, types.SimpleNamespace(trace=trace)) is None


def _row(scope, ms, least, prefetched=False):
    share = 100.0 * least / ms
    return {"scope": scope, "ms_per_call": ms, "least_ms": least,
            "prefetched": prefetched,
            "roofline": None if prefetched or share > 100.0 else share}


def test_conv_spat_roofline_over_the_measured_spatial_rows():
    rows = [_row("L0:conv.spat", 2.0, 1.0), _row("L1:conv.wino", 5.0, 4.0),
            _row("L3:conv.spat", 1.0, 0.5),
            # weights prefetched across programs: left out, time and least
            _row("L4:conv.spat", 1.0, 3.0, prefetched=True)]
    ctx = types.SimpleNamespace(trace=_trace(SCOPES, layers=rows))
    assert _read("conv_spat_roofline", ctx) == pytest.approx(50.0)
    for trace in (None, _trace(None), _trace(SCOPES, layers=rows[1:2]),
                  _trace(SCOPES, layers=rows[3:])):
        assert _read("conv_spat_roofline",
                     types.SimpleNamespace(trace=trace)) is None


def test_conv_spat_roofline_shows_a_row_over_its_roofline():
    # L3's FLOPs or bytes counted too high: its row reads over 100% and
    # has no share in the table, but the metric still sums it, and so
    # passes 100% where the counts are wrong
    rows = [_row("L0:conv.spat", 2.0, 1.0), _row("L3:conv.spat", 1.0, 4.0)]
    assert rows[1]["roofline"] is None
    ctx = types.SimpleNamespace(trace=_trace(SCOPES, layers=rows))
    assert _read("conv_spat_roofline", ctx) == pytest.approx(500.0 / 3.0)


def _window(**fields):
    """The statistics of a window, as the harness takes them: a
    ``SessionStats`` difference of two snapshots."""
    before, after = SessionStats(), SessionStats()
    for k, v in fields.items():
        setattr(after, k, v)
    return after.snapshot() - before.snapshot()


def test_stage_us_per_image():
    ctx = types.SimpleNamespace(stats=_window(stage_ns=4_300_000,
                                              submitted=10))
    assert _read("stage_us_per_image", ctx) == pytest.approx(430.0)
    ctx = types.SimpleNamespace(stats=_window(stage_ns=0, submitted=0))
    assert _read("stage_us_per_image", ctx) is None


def test_queue_wait_p95_of_the_windows_histogram():
    before = SessionStats()
    for _ in range(100):
        before.wait_hist.add(50.0)      # waits before the window
    earlier = before.snapshot()
    for ms in [1.0] * 95 + [20.0] * 5:
        before.wait_hist.add(ms)
    stats = before.snapshot() - earlier
    ctx = types.SimpleNamespace(stats=stats)
    # the 95th rank falls between the last 1 ms wait and the first 20 ms
    # one, each read at its bin's middle (4.4% bins)
    p95 = _read("queue_wait_p95_ms.server", ctx)
    assert 1.0 < p95 < 20.0 * 1.05
    assert p95 == stats.wait_hist.percentile(0.95)
    ctx = types.SimpleNamespace(stats=_window())
    assert _read("queue_wait_p95_ms.server", ctx) is None
