"""Integration: training loop convergence, checkpoint/restart bit-exactness,
crash recovery, elastic restore, data determinism, straggler detection."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import checkpoint as ckpt_lib
from repro.checkpoint.fault_tolerance import (
    HeartbeatMonitor, run_with_recovery,
)
from repro.data.pipeline import DataConfig, PrefetchingLoader, batch_for_step
from repro.launch.train import train


@pytest.mark.slow
def test_train_loss_decreases(tmp_path):
    losses = train("minitron-8b", reduced=True, steps=25, batch=4, seq=32,
                   ckpt_dir=None, lr=3e-3, log_every=100)
    assert losses[-1] < losses[0] - 0.5, (losses[0], losses[-1])


@pytest.mark.slow
def test_vgg_runtime_training_signal():
    """VGG16 (reduced) forward through hybrid engine produces gradients."""
    from repro.core.compiler import LayerPlan
    from repro.models import vgg
    key = jax.random.PRNGKey(0)
    params = vgg.init_params(key, img=32, scale=16, n_classes=10)
    specs = vgg.conv_specs(img=32, scale=16)
    plans = [LayerPlan("wino", "is", m=2) for _ in specs]
    x = jax.random.normal(key, (2, 32, 32, 3), jnp.float32)
    y = jnp.array([1, 3])

    def loss_fn(p):
        logits = vgg.forward(p, x, plans)
        return -jnp.mean(jax.nn.log_softmax(logits)[jnp.arange(2), y])

    loss, grads = jax.value_and_grad(loss_fn)(params)
    assert np.isfinite(float(loss))
    gnorm = sum(float(jnp.sum(jnp.abs(g))) for g in jax.tree.leaves(grads))
    assert gnorm > 0


def test_vgg_single_program_matches_segmented():
    """The full reduced VGG16 (13 CONV + 5 POOL + 3 FC) built through the
    ``repro.api`` façade as ONE Program produces the same logits as the
    legacy multi-Program path (``Accelerator.build(..., segmented=True)``:
    per-segment Programs + host-side maxpool glue + FC tail outside the
    runtime) — and the one-Program strict interpreter matches the cached
    jitted executor bitwise."""
    from repro import api
    from repro.core.compiler import LayerPlan
    from repro.core.hybrid_conv import ConvSpec
    from repro.models import vgg

    img, scale = 32, 16
    specs = vgg.network_specs(img=img, scale=scale, n_classes=10)
    # alternate wino/spat CONV plans so the one-Program path exercises the
    # POOL->WINO layout reorder and the U-space weight path, not just spat
    ci = 0
    plans = []
    for s in specs:
        if isinstance(s, ConvSpec):
            plans.append(LayerPlan("wino" if ci % 2 == 0 else "spat",
                                   "is" if ci % 2 else "ws", m=2))
            ci += 1
        else:
            plans.append(None)
    acc = api.Accelerator.build(specs, plans=plans, seed=0, batch=2)
    x = jnp.asarray(np.random.default_rng(1).standard_normal(
        (2, img, img, 3)), jnp.float32)

    y_single = acc(x)
    assert y_single.shape == (2, 10)

    # acceptance: strict interpreter == cached jitted executor, bitwise
    y_strict = acc.strict_request()(x)
    np.testing.assert_array_equal(np.asarray(y_single), np.asarray(y_strict))

    # compatibility: segmented path numerically identical (the old
    # build_segmented_request glue, now behind the façade)
    acc_seg = api.Accelerator.build(specs, plans=plans, params=acc.params,
                                    batch=2, segmented=True)
    y_seg = acc_seg(x)
    np.testing.assert_array_equal(np.asarray(y_single), np.asarray(y_seg))


@pytest.mark.slow
def test_serve_cnn_segmented_flag_matches_default():
    """serve_cnn's --segmented compatibility path end-to-end (DSE plans,
    program cache, random params) agrees with the single-Program default."""
    from repro.launch.serve import serve_cnn
    y1 = serve_cnn("vgg16", reduced=True, batch=2, iters=1, seed=3)
    y2 = serve_cnn("vgg16", reduced=True, batch=2, iters=1, seed=3,
                   segmented=True)
    np.testing.assert_array_equal(y1, y2)


@pytest.mark.slow
def test_serve_cnn_matches_direct_accelerator_build():
    """The serve entrypoint is a thin driver over the façade: a direct
    ``Accelerator.build(...)(x)`` with the same seed/batch reproduces
    serve_cnn's logits bitwise."""
    from repro import api
    from repro.core import perf_model as pm
    from repro.launch.serve import serve_cnn
    from repro.models import vgg

    y = serve_cnn("vgg16", reduced=True, batch=2, iters=1, seed=5)
    specs = vgg.network_specs(img=64, scale=8, n_classes=10)
    acc = api.Accelerator.build(specs, target=pm.V5E, batch=2, seed=5)
    x = jnp.asarray(np.random.default_rng(6).standard_normal(
        (2, 64, 64, 3)), jnp.float32)
    np.testing.assert_array_equal(y, np.asarray(acc(x)))


@pytest.mark.slow
def test_checkpoint_restart_bitexact(tmp_path):
    """Train 10; vs train 5 -> restore -> train 5: identical params."""
    d1, d2 = str(tmp_path / "a"), str(tmp_path / "b")
    train("mamba2-130m", reduced=True, steps=10, batch=2, seq=16,
          ckpt_dir=d1, ckpt_every=100, log_every=100, total_steps=10)
    train("mamba2-130m", reduced=True, steps=5, batch=2, seq=16,
          ckpt_dir=d2, ckpt_every=5, log_every=100, total_steps=10)
    train("mamba2-130m", reduced=True, steps=10, batch=2, seq=16,
          ckpt_dir=d2, ckpt_every=5, resume=True, log_every=100,
          total_steps=10)
    a = np.load(os.path.join(d1, "step_00000010", "arrays.npz"))
    b = np.load(os.path.join(d2, "step_00000010", "arrays.npz"))
    assert set(a.files) == set(b.files)
    for k in a.files:
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)


def test_crash_recovery(tmp_path):
    """A step that dies mid-run resumes from the last checkpoint."""
    calls = {"n": 0}

    def step_fn(state, step):
        calls["n"] += 1
        if step == 7 and calls["n"] == 8:    # fail once at step 7
            raise RuntimeError("simulated node failure")
        return {"x": state["x"] + 1.0}

    state, log = run_with_recovery(
        step_fn, {"x": jnp.zeros(())}, n_steps=10,
        ckpt_dir=str(tmp_path), ckpt_every=5)
    assert log["restarts"] == 1
    assert float(state["x"]) == 10.0   # every step applied exactly once


def test_elastic_restore_resharding(tmp_path):
    """Checkpoint written on one mesh restores onto a different mesh."""
    tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
    ckpt_lib.save(str(tmp_path), 3, tree)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("model",))
    sh = {"w": jax.NamedSharding(mesh, jax.sharding.PartitionSpec("model"))}
    restored, step = ckpt_lib.restore(str(tmp_path), tree, shardings=sh)
    assert step == 3
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(tree["w"]))
    assert restored["w"].sharding == sh["w"]


def test_data_determinism_and_sharding():
    cfg = DataConfig(vocab_size=100, seq_len=8, global_batch=8)
    b1 = batch_for_step(cfg, 5, shard=0, n_shards=2)
    b2 = batch_for_step(cfg, 5, shard=0, n_shards=2)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    b3 = batch_for_step(cfg, 5, shard=1, n_shards=2)
    assert not np.array_equal(b1["tokens"], b3["tokens"])
    # targets are next-token shifted
    full = batch_for_step(cfg, 0)
    np.testing.assert_array_equal(full["tokens"][:, 1:],
                                  full["targets"][:, :-1])


def test_prefetching_loader():
    cfg = DataConfig(vocab_size=50, seq_len=4, global_batch=4)
    loader = PrefetchingLoader(cfg, prefetch=2)
    seen = [next(loader) for _ in range(3)]
    loader.close()
    assert [s for s, _ in seen] == [0, 1, 2]
    ref = batch_for_step(cfg, 1)
    np.testing.assert_array_equal(seen[1][1]["tokens"], ref["tokens"])


def test_straggler_detection():
    mon = HeartbeatMonitor(n_workers=8, window=8, zscore_threshold=3.0)
    for step in range(8):
        for w in range(8):
            mon.report(w, 1.0 + (5.0 if w == 3 else 0.0), now=float(step))
    assert mon.stragglers() == [3]
    assert mon.dead(now=1000.0) == list(range(8))


def test_async_checkpoint(tmp_path):
    tree = {"a": jnp.ones((128, 128))}
    t = ckpt_lib.save(str(tmp_path), 1, tree, blocking=False)
    t.join()
    restored, step = ckpt_lib.restore(str(tmp_path), tree)
    assert step == 1
    np.testing.assert_array_equal(np.asarray(restored["a"]),
                                  np.asarray(tree["a"]))
