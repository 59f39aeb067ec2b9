"""The benchmark's own arithmetic: FLOP count, peaks table, loading by name,
arrival schedule and the trace reduction.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
import json
import types
from pathlib import Path

import numpy as np
import pytest

from bench import harness, loadgen, registry
from bench import trace as tracing
from bench.models import vgg16
from bench.peaks import peaks_for

DATA = Path(__file__).resolve().parent / "data"


def test_vgg16_flops_per_image():
    # configuration D at 224: per stage (side, [(c_in, c_out), ...])
    stages = [(224, [(3, 64), (64, 64)]), (112, [(64, 128), (128, 128)]),
              (56, [(128, 256), (256, 256), (256, 256)]),
              (28, [(256, 512), (512, 512), (512, 512)]),
              (14, [(512, 512)] * 3)]
    macs = sum(h * h * 9 * c * k for h, layers in stages for c, k in layers)
    macs += 7 * 7 * 512 * 4096 + 4096 * 4096 + 4096 * 1000
    sizes = {"img": 224, "scale": 1, "n_classes": 1000}
    assert vgg16.flops_per_image(sizes) == 2 * macs
    assert round(2 * macs / 1e9, 2) == 30.94


def test_peaks_table_rejects_an_unknown_kind():
    assert peaks_for("TPU v5 lite")["bf16"] == 197e12
    with pytest.raises(LookupError, match="TPU v9"):
        peaks_for("TPU v9")

    def fake_jax(platform, kind, n=1):
        dev = types.SimpleNamespace(platform=platform, device_kind=kind)
        return types.SimpleNamespace(devices=lambda: [dev] * n)
    with pytest.raises(SystemExit, match="no peaks known"):
        harness.check_chip(fake_jax("tpu", "TPU v9"), 1)
    with pytest.raises(SystemExit, match="no TPU"):
        harness.check_chip(fake_jax("cpu", "cpu"), 1)
    with pytest.raises(SystemExit, match="needs 4 chips"):
        harness.check_chip(fake_jax("tpu", "TPU v5 lite"), 4)
    devices, peaks = harness.check_chip(fake_jax("tpu", "TPU v5 lite", 4), 4)
    assert len(devices) == 4 and peaks["int8"] == 393e12


def test_new_config_mix_loop_and_metric_load_by_name(tmp_path):
    for kind in ("configs", "traffic", "metrics", "models", "loops"):
        (tmp_path / kind).mkdir()
    (tmp_path / "configs" / "other-net.json").write_text(
        json.dumps({"reference": "other", "sizes": {"n": 3}}))
    (tmp_path / "traffic" / "bursty.json").write_text(
        json.dumps({"loop": "open", "rate_per_s": 10}))
    (tmp_path / "metrics" / "queue_ms.serve.py").write_text(
        "def read(ctx):\n    return ctx.x * 2\n")
    (tmp_path / "models" / "other.py").write_text("LAYERS = 3\n")
    (tmp_path / "loops" / "trickle.py").write_text(
        "def run(session, pool, seconds, mix, *, batch, seed, spans=None,\n"
        "        rate=None):\n    return [session(p) for p in pool]\n")
    base = [tmp_path, registry.BENCH_DIR]
    assert registry.load_config("other-net", base)["sizes"] == {"n": 3}
    assert registry.load_traffic("bursty", base)["rate_per_s"] == 10
    assert registry.load_metric("queue_ms.serve", base)(
        types.SimpleNamespace(x=4)) == 8
    assert registry.load_model("other", base).LAYERS == 3
    assert registry.load_loop("trickle", base)(
        lambda p: p + 1, [1, 2], 1.0, {}, batch=1, seed=0) == [2, 3]
    # the benchmark's own files stay reachable beside the new ones
    assert registry.load_traffic("offline", base)["loop"] == "closed"
    assert callable(registry.load_loop("open", base))
    with pytest.raises(FileNotFoundError):
        registry.load_config("missing", base)
    with pytest.raises(ValueError):
        registry.load_config("../configs/other-net", base)


def test_every_named_file_of_the_benchmark_loads():
    bench = registry.load_benchmark()
    for cell in bench["workloads"]:
        cfg = registry.load_config(cell["config"])
        registry.load_loop(registry.load_traffic(cell["traffic"])["loop"])
        registry.load_model(cfg["reference"])
        for trace in (False, True):
            entries = registry.cell_metrics(bench, cell["name"], trace)
            assert entries, (cell["name"], trace)
            for m in entries:
                assert callable(registry.load_metric(m["name"]))


def test_poisson_arrivals_same_set_for_every_seed():
    mix = {"arrivals": {"process": "poisson"}}
    a = loadgen.arrival_times(mix, 400, 10, 1)
    b = loadgen.arrival_times({}, 400, 10, 2 ** 33 + 1)
    assert len(a) == 4000 and not np.array_equal(a, b)
    # the same gaps, recovered from the cumulative times up to rounding
    np.testing.assert_allclose(np.sort(np.diff(a, prepend=0)),
                               np.sort(np.diff(b, prepend=0)), atol=1e-12)
    assert abs(a[-1] - 10) < 0.05


def test_mmpp_arrivals_same_amount_for_every_seed():
    mix = {"arrivals": {"process": "mmpp", "burst_factor": 3,
                        "base_dwell_s": 0.8, "burst_dwell_s": 0.2}}
    a = loadgen.arrival_times(mix, 300, 20, 1)
    b = loadgen.arrival_times(mix, 300, 20, 2 ** 33 + 1)
    assert len(a) == len(b) and not np.array_equal(a, b)
    assert np.all(np.diff(a) >= 0) and 0 <= a[0] and a[-1] <= 20
    # a burst at 3x for a fifth of the time: 300 * (0.8 + 0.2 * 3) / 1.0
    assert abs(len(a) / 20 - 420) < 10
    # the busiest 100 ms runs well above the base rate
    per = np.bincount((a / 0.1).astype(int))
    assert per.max() > 2 * 30


def _events():
    """Two devices; window [100, 200) ns on the host clock."""
    return {
        "devices": {
            "0": {"ops": [["conv", 90, 30], ["conv", 130, 20],
                          ["dot", 140, 20], ["conv", 190, 30]],
                  "modules": [["jit_exec", 90, 80], ["jit_exec", 190, 30]]},
            "1": {"ops": [["dot", 100, 50]],
                  "modules": [["jit_exec", 100, 50]]},
            "2": {"ops": [["other", 100, 100]], "modules": []},
        },
        "host": [["bench.window", 100, 100], ["bench.wait", 100, 60],
                 ["bench.submit", 160, 40]],
    }


def test_reduce_by_hand():
    r = tracing.reduce(_events(), [0, 1])
    # device 0 busy [100,120) + [130,160) + [190,200) = 60; device 1: 50
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(55e-9)
    # modules clipped: device 0 70 + 10, device 1 50
    assert r["module_s"] == pytest.approx(65e-9)
    # conv 20 + 20 + 10 = 50; dot 20 + 50 = 70; per device: / 2
    assert r["device_ops"] == [["dot", pytest.approx(35e-9)],
                               ["conv", pytest.approx(25e-9)]]
    # gaps: device 1 [150,200) mid 175 in submit; device 0 [160,190) mid
    # 175 in submit, [120,130) mid 125 in wait
    assert r["idle_gaps"] == [["bench.submit", pytest.approx(50e-9)],
                              ["bench.submit", pytest.approx(30e-9)],
                              ["bench.wait", pytest.approx(10e-9)]]


def test_reduce_finds_nothing_without_window_or_device():
    ev = _events()
    assert tracing.reduce(ev, [5]) is None
    ev["host"] = ev["host"][1:]
    assert tracing.reduce(ev, [0]) is None


def test_reduce_recorded_v5e_trace():
    ev = json.loads((DATA / "trace_v5e.json").read_text())
    dev = sorted(ev["devices"], key=lambda k: -len(ev["devices"][k]["ops"]))[0]
    r = tracing.reduce(ev, [int(dev)])
    assert r is not None
    assert 0 < r["busy_s"] <= r["window_s"]
    assert 0 < r["module_s"] <= r["window_s"]
    assert r["device_ops"] and len(r["device_ops"]) <= tracing.TOP_N
    times = [t for _, t in r["device_ops"]]
    assert times == sorted(times, reverse=True)
    assert r["idle_gaps"] and all(
        name.startswith("bench.") or name == "no bench span"
        for name, _ in r["idle_gaps"])
