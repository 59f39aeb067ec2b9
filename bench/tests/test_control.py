"""The lower-precision controls come out not correct: the reference put in
the program's place, with every matmul operand rounded to the precision
below the configuration's, reads above each configuration's limit. Here at
the tiny size on the CPU; ``bench/control.py`` reads them on the chip at the
cells' own sizes.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
import jax
import numpy as np
import pytest

from bench import correct, harness, registry
from bench.tests import rehearse


@pytest.mark.parametrize("config,tiny", [("vgg16-fp32", "tiny"),
                                         ("vgg16-int8", "tiny-int8")])
@pytest.mark.parametrize("seed", [1, 2, 2 ** 33 + 3])
def test_control_fails_the_limit(config, tiny, seed):
    cfg = registry.load_config(config)
    small = registry.load_config(tiny, rehearse.BASE)
    assert small["control"] == cfg["control"]
    model = registry.load_model(small["reference"])
    sizes = small["sizes"]
    key = jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)
    params = jax.jit(lambda k: model.init_params(k, sizes))(key)
    pool = harness.rng(seed, 1).standard_normal(
        (16, sizes["img"], sizes["img"], 3), dtype=np.float32)
    ref = correct.logits(model, params, pool, sizes)
    ctrl = correct.logits(model, params, pool, sizes, control=cfg["control"])
    reading = float(np.max(correct.rms_rel(ctrl, ref)))
    assert reading > cfg["correct"]["worst_rms_rel"]["limit"], reading


def test_reference_is_the_programs_network():
    """The plain reference and the program's own spec-chain oracle compute
    the same network on the same weights (tiny size, CPU, float32)."""
    from repro.models import resnet, vgg
    small = registry.load_config("tiny", rehearse.BASE)
    sizes = small["sizes"]
    model = registry.load_model(small["reference"])
    params = model.init_params(jax.random.key(0), sizes)
    x = np.random.default_rng(0).standard_normal(
        (2, sizes["img"], sizes["img"], 3)).astype(np.float32)
    specs = vgg.network_specs(**small["network"]["kwargs"])
    with jax.default_matmul_precision("highest"):
        want = np.asarray(resnet.reference_forward(params, x, specs))
    got = np.asarray(model.forward(params, x, sizes))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
