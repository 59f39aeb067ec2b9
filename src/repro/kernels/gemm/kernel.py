"""Pallas TPU kernel: batched blocked GEMM with selectable dataflow.

Dataflow (the paper's Sec. 4.2.4, adapted to TPU grid iteration order):

* ``"is"`` (Input Stationary)  — grid ``(G, Mb, Nb, Kb)``. For a fixed input
  block-row ``m`` the kernel sweeps all weight block-columns ``n``; the input
  block's VMEM residency is reused across the ``n`` sweep (Pallas does not
  re-fetch a block whose index map is unchanged between consecutive steps).
* ``"ws"`` (Weight Stationary) — grid ``(G, Nb, Mb, Kb)``. The weight block
  column ``n`` stays resident while input block-rows stream past it.

Both orders keep ``K`` innermost so a single fp32 VMEM accumulator tile
carries the partial sums (the paper's accumulating buffer).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import resolve_interpret


def _mm_kernel(a_ref, b_ref, o_ref, acc_ref, *, n_kb: int):
    """One (g, m, n, k) grid step: acc += A[g,m,k] @ B[g,k,n]."""
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[0]  # (BM, BK)
    b = b_ref[0]  # (BK, BN)
    acc_ref[...] += jnp.dot(a, b, preferred_element_type=jnp.float32)

    @pl.when(k == n_kb - 1)
    def _flush():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def _mm_epilogue_kernel(a_ref, b_ref, bias_ref, o_ref, acc_ref, *,
                        n_kb: int, relu: bool):
    """GEMM with fused bias + optional ReLU at the accumulator flush.

    The paper adds bias in its accumulating buffer before SAVE; fusing the
    activation too saves one HBM round-trip of the pre-activation map.
    """
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[0], b_ref[0], preferred_element_type=jnp.float32)

    @pl.when(k == n_kb - 1)
    def _flush():
        out = acc_ref[...] + bias_ref[0].astype(jnp.float32)  # (1, BN) bcast
        if relu:
            out = jnp.maximum(out, 0.0)
        o_ref[0] = out.astype(o_ref.dtype)


def batched_matmul_kernel(
    a: jax.Array,           # (G, M, K)
    b: jax.Array,           # (G, K, N)
    bias: jax.Array | None = None,   # (G, N) fused epilogue, optional
    *,
    bm: int,
    bn: int,
    bk: int,
    dataflow: str = "is",   # "is" | "ws"
    relu: bool = False,
    out_dtype=jnp.float32,
    interpret: bool | None = None,
) -> jax.Array:             # (G, M, N)
    """Raw pallas_call wrapper. Shapes must already be padded to block multiples."""
    g, m, k = a.shape
    g2, k2, n = b.shape
    assert g == g2 and k == k2, (a.shape, b.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (a.shape, b.shape, bm, bn, bk)
    n_kb = k // bk

    if dataflow == "is":
        grid = (g, m // bm, n // bn, n_kb)
        a_map = lambda gi, mi, ni, ki: (gi, mi, ki)
        b_map = lambda gi, mi, ni, ki: (gi, ki, ni)
        o_map = lambda gi, mi, ni, ki: (gi, mi, ni)
        bias_map = lambda gi, mi, ni, ki: (gi, ni)
    elif dataflow == "ws":
        grid = (g, n // bn, m // bm, n_kb)
        a_map = lambda gi, ni, mi, ki: (gi, mi, ki)
        b_map = lambda gi, ni, mi, ki: (gi, ki, ni)
        o_map = lambda gi, ni, mi, ki: (gi, mi, ni)
        bias_map = lambda gi, ni, mi, ki: (gi, ni)
    else:
        raise ValueError(f"unknown dataflow {dataflow!r}")

    compiler_params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
    )
    in_specs = [
        pl.BlockSpec((1, bm, bk), a_map),
        pl.BlockSpec((1, bk, bn), b_map),
    ]
    operands = [a, b]
    if bias is None:
        kernel = functools.partial(_mm_kernel, n_kb=n_kb)
        assert not relu, "relu epilogue requires a bias operand (may be zeros)"
    else:
        kernel = functools.partial(_mm_epilogue_kernel, n_kb=n_kb, relu=relu)
        in_specs.append(pl.BlockSpec((1, bn), bias_map))
        operands.append(bias)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bm, bn), o_map),
        out_shape=jax.ShapeDtypeStruct((g, m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=compiler_params,
        interpret=resolve_interpret(interpret),
    )(*operands)
