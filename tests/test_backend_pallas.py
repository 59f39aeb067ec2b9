"""The Pallas PE backend: numerical parity with the XLA lowering and the
strict interpreter, cache-key separation, and interpret mode off the TPU.

Tolerance contract (documented in docs/ARCHITECTURE.md): both backends
compute the same blocked schedule in fp32 accumulation, but the Pallas
kernels pad to MXU block multiples and the XLA path may reassociate
differently, so outputs agree to ~1e-4 abs/rel on fp32 — the same budget
``tests/test_executor.py`` grants the executor-vs-interpreter comparison.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core.compiler import LayerPlan, compile_network
from repro.core.executor import resolve_backend
from repro.core.hybrid_conv import ConvSpec
from repro.core.program_cache import ProgramCache
from repro.core.runtime import HybridRuntime, run_program
from repro.models import vgg

TOL = dict(rtol=1e-4, atol=1e-4)


def _reduced_vgg(img=32, scale=32, batch=2, n_classes=10, seed=0):
    """Full 21-layer reduced VGG16 (13 CONV + 5 POOL + 3 FC), tiny widths.

    The first two CONVs get multi-group plans (2x2 row/k blocks) so the
    blocked Pallas lowering is exercised; the tail runs single-block to keep
    interpret-mode trace time inside the fast-tier budget (every extra block
    is three more Pallas calls in the trace).
    """
    specs = vgg.network_specs(img=img, scale=scale, n_classes=n_classes)
    plans = []
    ci = 0
    for s in specs:
        if isinstance(s, ConvSpec):
            g = 2 if ci < 2 else 1
            plans.append(LayerPlan("wino" if ci % 2 == 0 else "spat",
                                   "is" if ci % 2 else "ws", m=2,
                                   g_k=g, g_h=g))
            ci += 1
        else:
            plans.append(None)
    params = api.random_params(specs, seed)
    x = jnp.asarray(np.random.default_rng(seed).standard_normal(
        (batch, img, img, 3)), jnp.float32)
    return specs, plans, params, x


@pytest.fixture(scope="module")
def vgg_pallas_setup():
    """One shared build of the reduced-VGG accelerators (both backends share
    one ProgramCache, so the key-separation assertions are real)."""
    specs, plans, params, x = _reduced_vgg()
    cache = ProgramCache()
    acc_xla = api.Accelerator.build(specs, plans=plans, params=params,
                                    batch=2, cache=cache)
    acc_pal = api.Accelerator.build(specs, plans=plans, params=params,
                                    batch=2, cache=cache, backend="pallas")
    return cache, acc_xla, acc_pal, x


def test_resolve_backend_contract():
    assert resolve_backend("xla", None) == ("xla", None)
    # interpret= on the XLA backend would be silently meaningless — reject
    # it, mirroring the vgg.forward use_pallas/interpret guard
    with pytest.raises(ValueError, match="backend='pallas'"):
        resolve_backend("xla", True)
    backend, interp = resolve_backend("pallas", None)
    assert backend == "pallas"
    # off the TPU, interpret=None resolves to interpret mode
    if jax.default_backend() != "tpu":
        assert interp is True
    assert resolve_backend("pallas", False) == ("pallas", False)
    with pytest.raises(ValueError, match="unknown backend"):
        resolve_backend("cuda", None)


@pytest.mark.parametrize("platform,interpret", [("tpu", False),
                                                ("cpu", True)])
def test_interpret_resolves_from_executor_device(platform, interpret):
    """interpret=None is decided by the device the executor runs on (the
    mesh's first device), not by a flag fixed when the package was
    imported: compiled kernels on a TPU, the interpreter elsewhere."""
    import types
    dev = types.SimpleNamespace(platform=platform, device_kind="x")
    mesh = types.SimpleNamespace(devices=np.array([dev], dtype=object))
    assert resolve_backend("pallas", None, mesh) == ("pallas", interpret)
    assert resolve_backend("pallas", not interpret, mesh) == (
        "pallas", not interpret)


def test_accelerator_pallas_matches_xla_and_interpreter(vgg_pallas_setup):
    """The acceptance gate: Accelerator.build(backend="pallas") over the full
    reduced VGG16 == the XLA backend == the strict interpreter, with the
    Pallas interpreter (the CPU suite) exercised by default."""
    cache, acc_xla, acc_pal, x = vgg_pallas_setup
    y_xla = np.asarray(acc_xla(x))
    y_pal = np.asarray(acc_pal(x))
    y_strict = np.asarray(acc_pal.strict_request()(x))
    assert y_pal.shape == y_xla.shape == y_strict.shape
    np.testing.assert_allclose(y_pal, y_xla, **TOL)
    np.testing.assert_allclose(y_pal, y_strict, **TOL)
    # both backends live side by side in ONE cache under distinct keys
    assert acc_pal.runtime.cache is cache
    assert cache.stats.misses == 2
    ent = acc_pal.runtime.executor_entry(2, jnp.float32)[0]
    assert ent.backend == "pallas"
    if jax.default_backend() != "tpu":
        assert ent.interpret is True    # the interpreter actually ran


def test_strict_interpreter_pallas_backend_small_net():
    """backend= applies to the per-instruction interpreter too (runtime.py's
    COMP/FC handlers share conv_block_forward/fc_forward with the executor)."""
    h = 12
    specs = [ConvSpec("c1", h, h, 3, 8, relu=True),
             ConvSpec("c2", h, h, 8, 12, relu=False)]
    plans = [LayerPlan("wino", "is", 2, 2, 2), LayerPlan("spat", "ws", 2, 1, 2)]
    params = api.random_params(specs, seed=1)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, h, h, 3))
    prog = compile_network(specs, plans)
    y_ref = run_program(prog, params, x, strict=True)
    y_pal = run_program(prog, params, x, strict=True, backend="pallas")
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref), **TOL)


def test_cache_key_separates_backends():
    h = 12
    specs = [ConvSpec("c", h, h, 3, 8)]
    plans = [LayerPlan("spat", "is", 2, 1, 1)]
    prog = compile_network(specs, plans)
    cache = ProgramCache()
    e_xla = cache.get(prog, batch=1, dtype=jnp.float32)
    e_pal = cache.get(prog, batch=1, dtype=jnp.float32, backend="pallas")
    assert e_xla is not e_pal and len(cache) == 2
    # auto-resolved interpret and the equivalent explicit value share a key
    _, resolved = resolve_backend("pallas", None)
    e_pal2 = cache.get(prog, batch=1, dtype=jnp.float32, backend="pallas",
                       interpret=resolved)
    assert e_pal2 is e_pal
    assert cache.stats.hits == 1
    with pytest.raises(ValueError, match="unknown backend"):
        cache.get(prog, batch=1, dtype=jnp.float32, backend="tpu")


def test_runtime_backend_spellings_agree():
    """backend="pallas" and the legacy use_pallas=True are the same knob."""
    h = 12
    specs = [ConvSpec("c", h, h, 3, 8)]
    prog = compile_network(specs, [LayerPlan("spat", "is", 2, 1, 1)])
    rt_a = HybridRuntime(prog, backend="pallas")
    rt_b = HybridRuntime(prog, use_pallas=True)
    assert rt_a.backend == rt_b.backend == "pallas"
    assert rt_a.use_pallas and rt_b.use_pallas
    assert HybridRuntime(prog).backend == "xla"
    with pytest.raises(ValueError, match="unknown backend"):
        HybridRuntime(prog, backend="mps")


def test_serving_session_inherits_pallas_backend(vgg_pallas_setup):
    """A session over a pallas accelerator serves through pallas entries."""
    _, _, acc, x = vgg_pallas_setup
    y_direct = np.asarray(acc(x))
    with acc.serve(max_batch=2, buckets=(2,)) as s:
        assert all(e.backend == "pallas" for e in s._entries.values())
        outs = s.run_many([np.asarray(x[0]), np.asarray(x[1])])
    np.testing.assert_allclose(np.asarray(outs[0]), y_direct[0], **TOL)
    np.testing.assert_allclose(np.asarray(outs[1]), y_direct[1], **TOL)


def test_vgg_forward_rejects_interpret_without_pallas():
    """models/vgg.py: interpret= with use_pallas=False used to be silently
    ignored — now it raises instead of faking an interpret-mode run.

    The guard fires before any parameter access, so placeholder params
    suffice (and prove the error isn't raised lazily mid-network)."""
    specs = vgg.conv_specs(img=32, scale=32)
    plans = [LayerPlan("spat", "is", 2, 1, 1) for _ in specs]
    x = jnp.zeros((1, 32, 32, 3))
    with pytest.raises(ValueError, match="use_pallas"):
        vgg.forward({}, x, plans, use_pallas=False, interpret=True)
    with pytest.raises(ValueError, match="use_pallas"):
        vgg.forward({}, x, plans, interpret=False)
