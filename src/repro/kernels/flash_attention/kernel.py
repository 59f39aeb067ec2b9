"""Pallas TPU flash-attention kernel.

Grid ``(BH, Sq/BQ, Skv/BK)`` with the KV axis innermost ("arbitrary");
running max / sum / weighted-accumulator live in VMEM scratch across the KV
sweep — the same accumulating-buffer pattern as the GEMM PE.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


from repro.kernels.common import resolve_interpret

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
               n_kb: int, scale: float, causal: bool, bq: int, bk: int,
               kv_len: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)   # (BQ, D)
    k = k_ref[0].astype(jnp.float32)   # (BK, D)
    v = v_ref[0].astype(jnp.float32)   # (BK, D)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    if causal:
        qi = pl.program_id(1)
        rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        s = jnp.where(rows >= cols, s, NEG_INF)
    if kv_len % bk != 0:  # mask padded KV columns past the true length
        s = jnp.where(cols < kv_len, s, NEG_INF)

    m_prev = m_ref[...]                       # (BQ, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)           # (BQ, 1)
    p = jnp.exp(s - m_new)                    # (BQ, BK)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)

    @pl.when(ki == n_kb - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def flash_attention_kernel(
    q: jax.Array,   # (BH, Sq, D) padded: Sq % bq == 0
    k: jax.Array,   # (BH, Skv, D) padded: Skv % bk == 0
    v: jax.Array,   # (BH, Skv, D)
    *,
    bq: int,
    bk: int,
    causal: bool = True,
    scale: float | None = None,
    kv_len: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    bh, sq, d = q.shape
    _, skv, _ = k.shape
    assert sq % bq == 0 and skv % bk == 0
    scale = scale if scale is not None else d ** -0.5
    kv_len = skv if kv_len is None else kv_len
    n_kb = skv // bk
    grid = (bh, sq // bq, n_kb)
    kernel = functools.partial(
        _fa_kernel, n_kb=n_kb, scale=scale, causal=causal, bq=bq, bk=bk,
        kv_len=kv_len)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, bk, d), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, bk, d), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(q, k, v)
