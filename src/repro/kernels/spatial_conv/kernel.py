"""Pallas TPU kernel: the Spatial-mode PE — an im2col patch GEMM.

The paper's Spatial mode merges all ``PI x PO`` GEMM cores into one large
broadcast array (Sec. 4.2.2): a single 2-D GEMM over the im2col patch matrix
``(T, C*R*S) @ (C*R*S, K)`` with the accumulating-buffer epilogue (bias add +
optional ReLU) fused at the flush. Unlike ``kernels/gemm`` this kernel has no
leading Winograd-batch axis — Spatial conv is ONE GEMM, so the grid is the
plain blocked ``(Mb, Nb, Kb)`` iteration with the paper's two dataflows:

* ``"is"`` (Input Stationary)  — grid ``(Mb, Nb, Kb)``: a patch block-row
  stays VMEM-resident while all weight block-columns sweep past it.
* ``"ws"`` (Weight Stationary) — grid ``(Nb, Mb, Kb)``: a weight block-column
  stays resident while patch block-rows stream through.

``K`` is innermost in both orders so one fp32 VMEM scratch tile carries the
partial sums (the paper's accumulating output buffer).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import resolve_interpret


def _conv_gemm_body(p_ref, w_ref, bias_ref, o_ref, acc_ref, *,
                    n_kb: int, relu: bool):
    """One (m, n, k) grid step: acc += P[m,k] @ W[k,n]; epilogue at flush."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(p_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_kb - 1)
    def _flush():
        out = acc_ref[...] + bias_ref[...].astype(jnp.float32)  # (1, BN) bcast
        if relu:
            out = jnp.maximum(out, 0.0)
        o_ref[...] = out.astype(o_ref.dtype)


def conv_gemm_kernel(
    patches: jax.Array,     # (T, CRS) im2col patch matrix, block-padded
    weights: jax.Array,     # (CRS, K) reshaped kernel, block-padded
    bias: jax.Array,        # (K,) fp32, block-padded
    *,
    bm: int,
    bn: int,
    bk: int,
    dataflow: str = "is",   # "is" | "ws"
    relu: bool = False,
    out_dtype=jnp.float32,
    interpret: bool | None = None,
) -> jax.Array:             # (T, K)
    """Raw pallas_call wrapper. Shapes must already be padded to block multiples."""
    t, crs = patches.shape
    crs2, k = weights.shape
    assert crs == crs2, (patches.shape, weights.shape)
    assert t % bm == 0 and k % bn == 0 and crs % bk == 0, \
        (patches.shape, weights.shape, bm, bn, bk)
    n_kb = crs // bk

    if dataflow == "is":
        grid = (t // bm, k // bn, n_kb)
        p_map = lambda mi, ni, ki: (mi, ki)
        w_map = lambda mi, ni, ki: (ki, ni)
        o_map = lambda mi, ni, ki: (mi, ni)
        b_map = lambda mi, ni, ki: (0, ni)
    elif dataflow == "ws":
        grid = (k // bn, t // bm, n_kb)
        p_map = lambda ni, mi, ki: (mi, ki)
        w_map = lambda ni, mi, ki: (ki, ni)
        o_map = lambda ni, mi, ki: (mi, ni)
        b_map = lambda ni, mi, ki: (0, ni)
    else:
        raise ValueError(f"unknown dataflow {dataflow!r}")

    return pl.pallas_call(
        functools.partial(_conv_gemm_body, n_kb=n_kb, relu=relu),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), p_map),
            pl.BlockSpec((bk, bn), w_map),
            pl.BlockSpec((1, bn), b_map),
        ],
        out_specs=pl.BlockSpec((bm, bn), o_map),
        out_shape=jax.ShapeDtypeStruct((t, k), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(patches, weights, bias.reshape(1, -1))
