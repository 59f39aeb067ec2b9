"""The whole harness, end to end on the CPU at a tiny size, with the look
for a chip replaced: sound runs come out correct, and runs with the served
path broken underneath come out not correct.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
import json
import os
import subprocess
import sys

import pytest

from bench.tests import rehearse

ROOT = rehearse.HERE.parents[1]


def _check_shape(out, trace):
    assert set(out) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert list(out)[-1] == "checks"
    assert out["device"]["platform"] == "cpu"
    if trace:
        assert {"busy_s", "window_s"} <= set(out["device"])


@pytest.mark.parametrize("cell,metrics", [
    ("tiny.offline", {"images_per_s", "setup_s"}),
    ("tiny.server", {"latency_p95_ms", "latency_p50_ms", "setup_s"}),
    ("tiny-int8.offline", {"images_per_s", "setup_s"}),
    ("tiny.single-stream", {"images_per_s", "setup_s"}),
    ("tiny.server-bursty", {"latency_p95_ms", "latency_p50_ms", "setup_s"}),
    ("tiny-resnet18.offline", {"images_per_s", "setup_s"}),
])
def test_sound_run_is_correct(cell, metrics):
    out = rehearse.run(cell)
    _check_shape(out, False)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == metrics


def test_traced_server_run_reads_host_metrics():
    out = rehearse.run("tiny.server", trace=True)
    _check_shape(out, True)
    assert out["correct"] is True
    # the CPU trace has no TPU plane: the device metrics find nothing
    assert set(out["metrics"]) == {"gen_lag_p95_ms.server",
                                   "batch_occupancy.server",
                                   "queue_wait_p95_ms.server"}
    assert 0 < out["metrics"]["batch_occupancy.server"]["value"] <= 100
    assert out["metrics"]["queue_wait_p95_ms.server"]["value"] > 0


@pytest.mark.parametrize("cell,kinds", [
    ("tiny.offline", {"conv", "pool", "fc"}),
    # a second network: its configuration and reference are test files, and
    # the harness takes its layer costs as it takes VGG16's
    ("tiny-resnet18.offline", {"conv", "pool", "eltwise", "fc"}),
])
def test_traced_run_reads_the_layer_metrics(cell, kinds, monkeypatch,
                                            capsys):
    """With a device plane laid into the CPU's trace, a traced run reads
    every op's layer scope from the executor's compiled text, holds the
    reference's layer costs to the program's layers and reports the
    per-layer metrics of its layers."""
    from bench import scopes
    monkeypatch.setattr(scopes, "attach_scopes",
                        rehearse.with_device_plane(scopes.attach_scopes))
    out = rehearse.run(cell, trace=True)
    _check_shape(out, True)
    assert out["correct"] is True, out["checks"]
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert {"step_device_ms", "step_mfu", "idle_share", "conv_spat_step_ms",
            "conv_spat_roofline", "stage_us_per_image"} <= set(m)
    assert 0 < m["conv_spat_roofline"] <= 100
    assert (m["conv_spat_step_ms"] + m.get("conv_wino_step_ms", 0)
            <= m["step_device_ms"] * (1 + 1e-9))
    assert m["stage_us_per_image"] > 0
    assert all(" [L" in name for name, _ in out["breakdown"]["device_ops"])
    # the layer table on standard error: a row per layer, of each kind
    err = capsys.readouterr().err
    assert "100.00% of op time scoped" in err
    rows = [line.split()[0] for line in err.splitlines()
            if line.startswith("  L")]
    assert {r.split(":")[1].split(".")[0] for r in rows} == kinds
    assert sorted(int(r[1:r.index(":")]) for r in rows) == list(
        range(len(rows)))


@pytest.mark.parametrize("cell", ["tiny.offline", "tiny.server",
                                  "tiny-resnet18.offline"])
@pytest.mark.parametrize("fault", ["answer", "half_batch"])
def test_broken_path_is_not_correct(cell, fault, monkeypatch):
    from repro import api
    for name in ("_to_host", "_run_bucket"):
        monkeypatch.setattr(api.ServingSession, name,
                            getattr(api.ServingSession, name))
    out = rehearse.run(cell, fault=fault)
    assert out["correct"] is False
    assert (out["checks"]["worst_rms_rel"]["value"]
            > out["checks"]["worst_rms_rel"]["limit"])


def test_overload_sheds_and_stays_correct():
    """A mix's ``serve`` keywords reach the session: with a queue limit far
    under the offered load, requests are shed, counted apart from failures,
    and the answered ones are still compared."""
    out = rehearse.run("tiny.overload")
    assert out["shed"] > 0 and out["failed"] == 0
    assert out["correct"] is True, out["checks"]


@pytest.mark.parametrize("cell", ["tiny.offline", "tiny.server",
                                  "tiny-int8.offline"])
def test_control_in_the_programs_place_is_not_correct(cell, monkeypatch):
    """The configuration's control answers every request in the program's
    place and goes through the harness's own comparison."""
    from bench import harness
    monkeypatch.setattr(harness, "build", harness.build)
    out = rehearse.run(cell, fault="control")
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["correct"] is False
    assert (out["checks"]["worst_rms_rel"]["value"]
            > out["checks"]["worst_rms_rel"]["limit"])


def _four_devices(fault=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=f"{ROOT / 'src'}{os.pathsep}{ROOT}")
    cmd = [sys.executable, "-m", "bench.tests.rehearse", "tiny.offline", "4"]
    p = subprocess.run(cmd + ([fault] if fault else []), env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stderr


def test_four_chip_rehearsal_on_virtual_devices():
    out, err = _four_devices()
    assert out["correct"] is True, out["checks"]
    assert "4 visible, 4 used" in err
    assert "buckets (32,)" in err


def test_four_chip_exchange_left_out_is_not_correct():
    out, _ = _four_devices("exchange")
    assert out["correct"] is False


def test_run_without_a_tpu_exits_nonzero_and_prints_nothing():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "vgg16-fp32.offline", "--seed", "1", "--seconds", "1"],
                       env=env, cwd=ROOT, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
