"""Pallas TPU kernel: int8 GEMM with int32 accumulate + requantize epilogue.

The int8 PE: A (M, K) int8 @ B (K, N) int8 accumulates exactly in an int32
VMEM tile (``preferred_element_type=jnp.int32`` feeds the MXU's widened
accumulation path), and the flush step fuses the whole quantized epilogue —
add int32 bias, optional ReLU (valid pre-rescale because zero_point = 0),
then requantize ``clip(round(acc * mult), -127, 127)`` back to int8 — so
the pre-activation int32 map never round-trips through HBM. ``mult`` rides
in as a ``(1, N)`` fp32 operand (per-OUTPUT-CHANNEL requantize multipliers
broadcast down each column), so per-channel weight quantization costs the
epilogue nothing and a scalar multiplier is just the broadcast case.

Same grid discipline as ``gemm/kernel.py``: K innermost so one accumulator
tile carries the partial sums; blocks honor the int8 minimum tile
(SUBLANE_I8=32, LANE=128). Zero padding is exact under zero_point = 0:
padded K rows contribute 0 to every dot product.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import LANE, SUBLANE_I8, resolve_interpret, round_up


def _qmm_kernel(a_ref, b_ref, bias_ref, mult_ref, o_ref, acc_ref, *,
                n_kb: int, relu: bool):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.int32)

    @pl.when(k == n_kb - 1)
    def _flush():
        acc = acc_ref[...] + bias_ref[...].astype(jnp.int32)  # (1, BN) bcast
        if relu:
            acc = jnp.maximum(acc, 0)
        y = jnp.round(acc.astype(jnp.float32) * mult_ref[...])
        o_ref[...] = jnp.clip(y, -127, 127).astype(jnp.int8)


def pick_int8_block_shapes(m: int, k: int, n: int) -> tuple[int, int, int]:
    """(bm, bk, bn) aligned to the int8 tile (32, 128), capped like fp32."""
    bm = min(round_up(m, SUBLANE_I8), 512)
    bk = min(round_up(k, LANE), 512)
    bn = min(round_up(n, LANE), 512)
    return bm, bk, bn


@functools.partial(jax.jit, static_argnames=("relu", "interpret"))
def _qmm(a, b, bias, mult_vec, *, relu: bool, interpret: bool):
    m, k = a.shape
    _, n = b.shape
    bm, bk, bn = pick_int8_block_shapes(m, k, n)

    mp, kp, np_ = round_up(m, bm), round_up(k, bk), round_up(n, bn)
    if (mp, kp) != (m, k):
        a = jnp.pad(a, ((0, mp - m), (0, kp - k)))
    if (kp, np_) != (k, n):
        b = jnp.pad(b, ((0, kp - k), (0, np_ - n)))
    bias2 = jnp.pad(bias.astype(jnp.int32), (0, np_ - n))[None]   # (1, Np)
    mult2 = jnp.pad(mult_vec, (0, np_ - n))[None]                 # (1, Np)

    n_kb = kp // bk
    out = pl.pallas_call(
        functools.partial(_qmm_kernel, n_kb=n_kb, relu=relu),
        grid=(mp // bm, np_ // bn, n_kb),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda mi, ni, ki: (mi, ki)),
            pl.BlockSpec((bk, bn), lambda mi, ni, ki: (ki, ni)),
            pl.BlockSpec((1, bn), lambda mi, ni, ki: (0, ni)),
            pl.BlockSpec((1, bn), lambda mi, ni, ki: (0, ni)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.int8),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b, bias2, mult2)
    if (mp, np_) != (m, n):
        out = out[:m, :n]
    return out


def quantized_matmul(
    a: jax.Array,            # (M, K) int8
    b: jax.Array,            # (K, N) int8
    bias: jax.Array,         # (N,)   int32
    *,
    mult,                    # in_scale * wgt_scale / out_scale — scalar
                             # (per-tensor) or (N,) (per-channel weights)
    relu: bool = False,
    interpret: bool | None = None,
) -> jax.Array:              # (M, N) int8
    assert a.dtype == jnp.int8 and b.dtype == jnp.int8, (a.dtype, b.dtype)
    m, k = a.shape
    k2, n = b.shape
    assert k == k2 and bias.shape == (n,), (a.shape, b.shape, bias.shape)
    mult_vec = jnp.broadcast_to(
        jnp.asarray(mult, jnp.float32), (n,))     # scalar -> uniform vector
    return _qmm(a, b, bias, mult_vec, relu=relu,
                interpret=resolve_interpret(interpret))
