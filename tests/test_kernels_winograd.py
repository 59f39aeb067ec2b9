"""Winograd transform kernels + end-to-end hybrid conv vs direct conv."""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.winograd import (
    mult_reduction, transform_weights, winograd_apply_pretransformed,
    winograd_conv2d_reference,
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


# The XLA PE (`winograd_apply_pretransformed`) against the gather/einsum
# reference and against XLA's own convolution, both at HIGHEST precision so
# the comparison sees the algorithm and not bf16 passes. F(4,3)'s transforms
# carry coefficients up to 25 (B^T) and 8 (A^T), so fp32 rounding reaches a
# few 1e-5 on O(1) outputs; 2e-4 leaves room above that and stays far below
# any indexing or coefficient error, which moves outputs by O(0.1).
PE_CASES = [
    (1, 7, 7, 3, 5, "SAME"),
    (3, 13, 13, 5, 7, "SAME"),
    (1, 14, 14, 4, 6, "SAME"),
    (3, 28, 28, 3, 5, "SAME"),
    (1, 10, 28, 5, 3, "VALID"),   # a row slab: the executor's halo'd rows
]


@pytest.mark.parametrize("bias,relu", [(False, False), (True, True)])
@pytest.mark.parametrize("n,h,w,c,k,padding", PE_CASES)
@pytest.mark.parametrize("m", [2, 4])
def test_pretransformed_pe_matches_reference_and_conv(m, n, h, w, c, k,
                                                      padding, bias, relu):
    kx, kw, kb = jax.random.split(jax.random.PRNGKey(17 * m + h), 3)
    x = jax.random.normal(kx, (n, h, w, c), jnp.float32)
    g = jax.random.normal(kw, (3, 3, c, k), jnp.float32) * 0.3
    b = jax.random.normal(kb, (k,), jnp.float32) if bias else None
    with jax.default_matmul_precision("highest"):
        y = winograd_apply_pretransformed(x, transform_weights(g, m), b, m,
                                          relu=relu, padding=padding)
        y_ref = winograd_conv2d_reference(x, g, m=m, padding=padding)
        y_conv = jax.lax.conv_general_dilated(
            x, g, (1, 1), padding, dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=jax.lax.Precision.HIGHEST)
    if bias:
        y_ref, y_conv = y_ref + b, y_conv + b
    if relu:
        y_ref, y_conv = jnp.maximum(y_ref, 0), jnp.maximum(y_conv, 0)
    assert y.shape == y_conv.shape
    for want in (y_ref, y_conv):
        np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("m", [2, 4])
def test_pretransformed_pe_vmaps_over_k_groups(m):
    """The executor's stacked lowering vmaps the PE over k-group weights."""
    kx, kw, kb = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(kx, (2, 10, 12, 6), jnp.float32)
    u = transform_weights(jax.random.normal(kw, (3, 3, 6, 12)) * 0.3, m)
    b = jax.random.normal(kb, (12,), jnp.float32)
    u_st = jnp.moveaxis(u.reshape(*u.shape[:-1], 3, 4), -2, 0)
    b_st = b.reshape(3, 4)
    pe = functools.partial(winograd_apply_pretransformed, m=m, relu=True,
                           padding="VALID")
    got = jax.vmap(lambda w, bb: pe(x, w, bb))(u_st, b_st)
    want = jnp.stack([pe(x, u_st[g], b_st[g]) for g in range(3)])
    # same arithmetic, but the batched contractions may sum in another
    # order: a few fp32 ulps of outputs that reach about 10
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


def test_pretransformed_pe_tiles_by_static_slices():
    """The tiles come from static slices: no gather, and so no loop of
    dynamic slices, at VGG16 conv1_2's shape (batch 8, 226 halo'd rows,
    64 channels; lowered only) and at a small compiled shape."""
    def pe(x, u, b):
        return winograd_apply_pretransformed(x, u, b, 4, relu=True,
                                             padding="VALID")

    f32 = jnp.float32
    lowered = jax.jit(pe).lower(
        jax.ShapeDtypeStruct((8, 226, 226, 64), f32),
        jax.ShapeDtypeStruct((6, 6, 64, 64), f32),
        jax.ShapeDtypeStruct((64,), f32)).as_text()
    compiled = jax.jit(pe).lower(
        jnp.zeros((2, 18, 18, 8), f32), jnp.zeros((6, 6, 8, 8), f32),
        jnp.zeros((8,), f32)).compile().as_text()
    assert not re.search(r"stablehlo\.(gather|while)\b", lowered)
    assert not re.search(r"\b(gather|while)\(", compiled)
