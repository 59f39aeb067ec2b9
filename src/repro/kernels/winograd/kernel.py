"""Pallas kernel bodies for Winograd input/output transforms.

Input transform:  tiles (PT, PT, T, C) -> V (PT^2, T, C)   [V = B^T d B]
Output transform: M (PT^2, T, K)       -> Y (m, m, T, K)   [Y = A^T M A]

Both are blocked over (tile, channel). The tile-position axes lead, so every
operand the body touches is a 2-D ``(tiles, channels)`` slab aligned to the
TPU's (8, 128) tiling. The PT x PT transform matrices are compile-time
constants, so each transform unrolls into scalar multiply-adds of whole slabs
(zero coefficients skipped) — VPU work, reductions of length 4 or 6 far below
MXU granularity, exactly like the adder trees the paper uses next to its DSP
GEMM cores. Both transforms are separable: rows first, then columns.

The output transform optionally fuses bias add + ReLU — the paper's
accumulating-buffer epilogue — saving one full HBM round-trip of the
pre-activation feature map.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.winograd import pt_for, transform_matrices
from repro.kernels.common import resolve_interpret


def _lincomb(coeffs, terms):
    """``sum_i coeffs[i] * terms[i]`` over constant coefficients, skipping
    zeros and multiplying only where the coefficient is not +-1."""
    acc = None
    for c, t in zip(coeffs, terms):
        c = float(c)
        if c == 0.0:
            continue
        if c == 1.0:
            term = t
        elif c == -1.0:
            term = -t
        else:
            term = c * t
        acc = term if acc is None else acc + term
    return acc


def _separable(left, right, grid):
    """``out[i][j] = sum_{p,q} left[i,p] * grid[p][q] * right[j,q]``."""
    rows = [[_lincomb(left[i], [grid[p][q] for p in range(len(grid))])
             for q in range(len(grid[0]))] for i in range(len(left))]
    return [[_lincomb(right[j], rows[i]) for j in range(len(right))]
            for i in range(len(left))]


def _input_transform_kernel(d_ref, v_ref, *, m: int):
    btm, _, _ = transform_matrices(m)
    pt = pt_for(m)
    d = [[d_ref[p, q].astype(jnp.float32) for q in range(pt)]
         for p in range(pt)]
    v = _separable(btm, btm, d)
    for i in range(pt):
        for j in range(pt):
            v_ref[i * pt + j] = v[i][j].astype(v_ref.dtype)


def _output_transform_kernel(m_ref, b_ref, y_ref, *, m: int, relu: bool):
    _, _, atm = transform_matrices(m)
    pt = pt_for(m)
    mm = [[m_ref[p * pt + q].astype(jnp.float32) for q in range(pt)]
          for p in range(pt)]
    y = _separable(atm, atm, mm)
    bias = b_ref[...].astype(jnp.float32)          # (1, BK) broadcast
    for i in range(m):
        for j in range(m):
            out = y[i][j] + bias
            if relu:
                out = jnp.maximum(out, 0.0)
            y_ref[i, j] = out.astype(y_ref.dtype)


def input_transform_kernel(
    tiles: jax.Array,  # (PT, PT, T, C) padded: T % bt == 0, C % bc == 0
    *,
    m: int,
    bt: int,
    bc: int,
    out_dtype=jnp.float32,
    interpret: bool | None = None,
) -> jax.Array:       # (PT^2, T, C)
    pt, _, t, c = tiles.shape
    assert pt == pt_for(m) and t % bt == 0 and c % bc == 0
    return pl.pallas_call(
        functools.partial(_input_transform_kernel, m=m),
        grid=(t // bt, c // bc),
        in_specs=[pl.BlockSpec((pt, pt, bt, bc),
                               lambda ti, ci: (0, 0, ti, ci))],
        out_specs=pl.BlockSpec((pt * pt, bt, bc), lambda ti, ci: (0, ti, ci)),
        out_shape=jax.ShapeDtypeStruct((pt * pt, t, c), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=resolve_interpret(interpret),
    )(tiles)


def output_transform_kernel(
    m_arr: jax.Array,   # (PT^2, T, K) padded
    bias: jax.Array,    # (K,)
    *,
    m: int,
    bt: int,
    bk: int,
    relu: bool = False,
    out_dtype=jnp.float32,
    interpret: bool | None = None,
) -> jax.Array:         # (m, m, T, K)
    pt2, t, k = m_arr.shape
    pt = pt_for(m)
    assert pt2 == pt * pt and t % bt == 0 and k % bk == 0
    return pl.pallas_call(
        functools.partial(_output_transform_kernel, m=m, relu=relu),
        grid=(t // bt, k // bk),
        in_specs=[
            pl.BlockSpec((pt * pt, bt, bk), lambda ti, ki: (0, ti, ki)),
            pl.BlockSpec((1, bk), lambda ti, ki: (0, ki)),
        ],
        out_specs=pl.BlockSpec((m, m, bt, bk), lambda ti, ki: (0, 0, ti, ki)),
        out_shape=jax.ShapeDtypeStruct((m, m, t, k), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=resolve_interpret(interpret),
    )(m_arr, bias.reshape(1, k))
