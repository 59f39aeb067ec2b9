"""Winograd transform kernels + end-to-end hybrid conv vs direct conv."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.winograd import (
    mult_reduction, transform_weights, winograd_conv2d_reference,
)
from repro.kernels.spatial_conv import spatial_conv2d
from repro.kernels.spatial_conv.ref import spatial_conv2d_ref
from repro.kernels.winograd import (
    input_transform, output_transform, winograd_conv2d,
)
from repro.kernels.winograd.ref import (
    conv2d_ref, input_transform_ref, output_transform_ref,
)


@pytest.mark.parametrize("m", [2, 4])
def test_input_transform(m):
    pt = m + 2
    tiles = jax.random.normal(jax.random.PRNGKey(0), (10, pt, pt, 7))
    np.testing.assert_allclose(np.asarray(input_transform(tiles, m)),
                               np.asarray(input_transform_ref(tiles, m)),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("m", [2, 4])
@pytest.mark.parametrize("relu", [False, True])
def test_output_transform(m, relu):
    pt = m + 2
    marr = jax.random.normal(jax.random.PRNGKey(1), (pt * pt, 10, 5))
    bias = jax.random.normal(jax.random.PRNGKey(2), (5,))
    np.testing.assert_allclose(
        np.asarray(output_transform(marr, bias, m, relu=relu)),
        np.asarray(output_transform_ref(marr, bias, m, relu=relu)),
        rtol=1e-5, atol=1e-5)


CONV_CASES = [
    (1, 8, 8, 3, 4, 3),
    (2, 14, 14, 8, 16, 3),
    (1, 28, 28, 4, 8, 3),    # m=2: 196 tiles, a GEMM block 128 does not divide
    (1, 12, 10, 4, 8, 5),    # kernel decomposition 5x5
    (1, 16, 16, 3, 4, 7),    # kernel decomposition 7x7
]


@pytest.mark.parametrize("n,h,w,c,k,r", CONV_CASES)
@pytest.mark.parametrize("m", [2, 4])
def test_winograd_conv_vs_direct(n, h, w, c, k, r, m):
    kx, kw, kb = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(kx, (n, h, w, c), jnp.float32)
    g = jax.random.normal(kw, (r, r, c, k), jnp.float32) * 0.3
    b = jax.random.normal(kb, (k,), jnp.float32)
    y = winograd_conv2d(x, g, b, m=m, relu=True)
    yref = conv2d_ref(x, g, bias=b, relu=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yref),
                               rtol=3e-3, atol=3e-3)


@pytest.mark.parametrize("stride,pad", [(1, "SAME"), (2, "SAME"), (1, "VALID")])
def test_spatial_conv(stride, pad):
    kx, kw, kb = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jax.random.normal(kx, (2, 12, 12, 4), jnp.float32)
    g = jax.random.normal(kw, (3, 3, 4, 8), jnp.float32) * 0.3
    b = jax.random.normal(kb, (8,), jnp.float32)
    for df in ("is", "ws"):
        y = spatial_conv2d(x, g, b, stride=stride, padding=pad, relu=True,
                           dataflow=df)
        yref = spatial_conv2d_ref(x, g, b, stride=stride, padding=pad,
                                  relu=True)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yref),
                                   rtol=1e-4, atol=1e-4)


def test_mult_reduction_paper_claim():
    """Paper Sec 4.2.1: F(4x4,3x3) needs 36 mults vs 144 -> exactly 4x."""
    assert mult_reduction(4) == 4.0
    assert mult_reduction(2) == 2.25


def test_weight_transform_shapes():
    g = jax.random.normal(jax.random.PRNGKey(0), (3, 3, 5, 7))
    u = transform_weights(g, 4)
    assert u.shape == (6, 6, 5, 7)


def test_reference_matches_pallas():
    kx, kw = jax.random.split(jax.random.PRNGKey(2))
    x = jax.random.normal(kx, (1, 12, 12, 3), jnp.float32)
    g = jax.random.normal(kw, (3, 3, 3, 8), jnp.float32) * 0.3
    np.testing.assert_allclose(
        np.asarray(winograd_conv2d(x, g, m=4)),
        np.asarray(winograd_conv2d_reference(x, g, m=4)),
        rtol=2e-3, atol=2e-3)
