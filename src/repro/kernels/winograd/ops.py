"""Jitted Winograd convolution assembled from Pallas stages.

Pipeline (the paper's COMP-module datapath, Sec. 4.2):

  tile extract (XLA strided slices)   — LOAD manager addressing
  -> input_transform  (Pallas)        — LOAD manager online B^T d B
  -> batched GEMM, batch PT^2 (Pallas, kernels/gemm) — the PE, Eq. 2
  -> output_transform (Pallas, fused bias+ReLU)      — SAVE manager A^T M A
  -> tile scatter (XLA reshape)       — SAVE manager layout write

Weights are transformed offline (``transform_weights``), matching Sec. 4.2.3.
Kernels with R, S > 3 use the paper's kernel-decomposition (Sec. 4.2.5).
``dataflow`` ("is"/"ws") is forwarded to the GEMM grid order.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.winograd import (
    R_WINO,
    decompose_kernel,
    pt_for,
    transform_weights,
)
from repro.kernels.common import LANE, SUBLANE, round_up
from repro.kernels.gemm.kernel import batched_matmul_kernel
from repro.kernels.winograd.kernel import (
    input_transform_kernel,
    output_transform_kernel,
)


def _pick_tile_blocks(t: int, c: int, k: int) -> tuple[int, int, int]:
    """(bt, bc, bk): tile-block, channel blocks. MXU-aligned where possible."""
    bt = min(round_up(t, SUBLANE), 256)
    bc = min(round_up(c, LANE), 256)
    bk = min(round_up(k, LANE), 256)
    return bt, bc, bk


# The transforms hold PT^2 slabs of (tiles, channels) per grid step, in and
# out, double-buffered: blocks of at most 128 x 128 keep that (36 x 64 KiB
# each way at PT = 6) well inside the compiler's default scoped-VMEM limit,
# where the GEMM's 256-wide blocks would not fit.
_TRANSFORM_BLOCK = 128


def _transform_block(b: int) -> int:
    """The largest multiple of the sublane tile, at most _TRANSFORM_BLOCK,
    that divides the GEMM block ``b``, so both grids cover the same padded
    extent (a 200-tile GEMM block gets 40-tile transform blocks)."""
    return max(d for d in range(SUBLANE, min(b, _TRANSFORM_BLOCK) + 1,
                                SUBLANE) if b % d == 0)


def _tiles_ptpt(x: jax.Array, m: int):
    """Overlapping PT x PT input tiles at stride m, tile-position axes first.

    ``x`` is already padded for a VALID conv. Returns ``(tiles, (nh, nw))``
    with tiles shaped (PT, PT, N*nh*nw, C): one strided slice per tile
    position, so no gather is needed and each (p, q) slab is a 2-D
    (tiles, channels) matrix the transform kernel reads whole.
    """
    pt = pt_for(m)
    n, h, w, c = x.shape
    nh, nw = -(-(h - R_WINO + 1) // m), -(-(w - R_WINO + 1) // m)
    hp, wp = (nh - 1) * m + pt, (nw - 1) * m + pt
    x = jnp.pad(x, ((0, 0), (0, hp - h), (0, wp - w), (0, 0)))
    rows = []
    for p in range(pt):
        row = []
        for q in range(pt):
            sl = x[:, p:p + (nh - 1) * m + 1:m, q:q + (nw - 1) * m + 1:m]
            row.append(sl.reshape(n * nh * nw, c))
        rows.append(jnp.stack(row))
    return jnp.stack(rows), (nh, nw)


def _pad_for_conv(x_nhwc, rr, ss, padding):
    """SAME/VALID input padding for a VALID rr x ss conv of the result."""
    if padding.upper() == "SAME":
        ph, pw = (rr - 1) // 2, (ss - 1) // 2
        return jnp.pad(x_nhwc, ((0, 0), (ph, rr - 1 - ph),
                                (pw, ss - 1 - pw), (0, 0)))
    if padding.upper() == "VALID":
        return x_nhwc
    raise ValueError(padding)


def _finish_output(m_acc, bias, *, m, bt, bk, relu, interpret, geom,
                   ho, wo, k, kp, out_dtype):
    """Shared SAVE-manager epilogue: Pallas A^T M A (fused bias/ReLU), then
    the tile scatter/crop back to NHWC. One copy for both entry points so
    the reshape/crop arithmetic can't drift."""
    n, nh, nw, t, tp = geom
    bias_p = jnp.pad(bias.astype(jnp.float32), (0, kp - k))
    y = output_transform_kernel(
        m_acc, bias_p, m=m, bt=_transform_block(bt), bk=_transform_block(bk),
        relu=relu, out_dtype=jnp.float32, interpret=interpret)  # (m,m,Tp,Kp)
    y = y[:, :, :t].reshape(m, m, n, nh, nw, kp).transpose(2, 3, 0, 4, 1, 5)
    y = y.reshape(n, nh * m, nw * m, kp)[:, :ho, :wo, :k]
    return y.astype(out_dtype)


def _wino_conv_piece(x, u_flat, m, t_blocks, out_dtype, dataflow, interpret):
    """One r x r sub-kernel's Winograd conv. x already padded+shifted.

    u_flat: (PT^2, Cp, Kp) transformed weights (already channel-padded).
    Returns M-space output (PT^2, T, Kp) accumulated later, plus tile geometry.
    """
    tiles, (nh, nw) = _tiles_ptpt(x, m)
    n = x.shape[0]
    _, _, t, c = tiles.shape
    bt, bc, bk = t_blocks
    tp, cp = round_up(t, bt), round_up(c, bc)
    if (tp, cp) != (t, c):
        tiles = jnp.pad(tiles, ((0, 0), (0, 0), (0, tp - t), (0, cp - c)))
    v = input_transform_kernel(
        tiles, m=m, bt=_transform_block(bt), bc=_transform_block(bc),
        out_dtype=jnp.float32, interpret=interpret)
    mm = batched_matmul_kernel(
        v, u_flat, bm=bt, bn=bk, bk=bc, dataflow=dataflow,
        out_dtype=jnp.float32, interpret=interpret)        # (PT^2, Tp, Kp)
    return mm, (n, nh, nw, t, tp)


@functools.partial(
    jax.jit,
    static_argnames=("m", "padding", "relu", "dataflow", "out_dtype", "interpret"),
)
def winograd_conv2d(
    x_nhwc: jax.Array,
    g_rsck: jax.Array,
    bias: jax.Array | None = None,
    *,
    m: int = 4,
    padding: str = "SAME",
    relu: bool = False,
    dataflow: str = "is",
    out_dtype=None,
    interpret: bool | None = None,
) -> jax.Array:
    """Winograd F(m x m, 3 x 3) convolution, stride 1, NHWC/HWIO."""
    out_dtype = out_dtype or x_nhwc.dtype
    n, h, w, c = x_nhwc.shape
    rr, ss, _, k = g_rsck.shape
    if bias is None:
        bias = jnp.zeros((k,), jnp.float32)

    x = _pad_for_conv(x_nhwc, rr, ss, padding)
    ho, wo = x.shape[1] - rr + 1, x.shape[2] - ss + 1

    if (rr, ss) == (R_WINO, R_WINO):
        pieces = [(0, 0, g_rsck)]
    else:
        pieces = decompose_kernel(g_rsck, m)
        x = jnp.pad(x, ((0, 0),
                        (0, (-(-rr // R_WINO)) * R_WINO - rr),
                        (0, (-(-ss // R_WINO)) * R_WINO - ss),
                        (0, 0)))

    # geometry is identical across pieces; block sizes from the first
    t_est = n * (-(-ho // m)) * (-(-wo // m))
    bt, bc, bk = _pick_tile_blocks(t_est, c, k)
    cp, kp = round_up(c, bc), round_up(k, bk)
    pt = pt_for(m)

    m_acc = None
    geom = None
    for (oh, ow, sub) in pieces:
        u = transform_weights(sub, m).astype(jnp.float32)  # (PT, PT, C, K)
        u = u.reshape(pt * pt, c, k)
        if (cp, kp) != (c, k):
            u = jnp.pad(u, ((0, 0), (0, cp - c), (0, kp - k)))
        xs = x[:, oh:oh + ho + R_WINO - 1, ow:ow + wo + R_WINO - 1, :]
        mm, geom = _wino_conv_piece(xs, u, m, (bt, bc, bk), out_dtype,
                                    dataflow, interpret)
        m_acc = mm if m_acc is None else m_acc + mm       # accumulate in M-space

    return _finish_output(m_acc, bias, m=m, bt=bt, bk=bk, relu=relu,
                          interpret=interpret, geom=geom, ho=ho, wo=wo,
                          k=k, kp=kp, out_dtype=out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("m", "padding", "relu", "dataflow", "out_dtype", "interpret"),
)
def winograd_apply_pretransformed_pallas(
    x_nhwc: jax.Array,
    u_ptck: jax.Array,      # (PT, PT, C, K) offline-transformed weights
    bias: jax.Array | None = None,
    *,
    m: int = 4,
    padding: str = "SAME",
    relu: bool = False,
    dataflow: str = "is",
    out_dtype=None,
    interpret: bool | None = None,
) -> jax.Array:
    """Winograd conv from U-space weights, all three stages on Pallas.

    The executor/runtime COMP path: the paper stores *transformed* weights in
    DRAM (Sec. 4.2.3), so the PE consumes U directly — no G g G^T at run
    time. Mirrors ``core.winograd.winograd_apply_pretransformed`` (the XLA
    reference) stage for stage: tile extract -> ``input_transform_kernel`` ->
    the PT^2-batched GEMM -> ``output_transform_kernel`` with the bias/ReLU
    epilogue fused. r = s = 3, stride 1.
    """
    out_dtype = out_dtype or x_nhwc.dtype
    n, h, w, c = x_nhwc.shape
    pt, _, _, k = u_ptck.shape
    assert pt == pt_for(m), (pt, m)
    if bias is None:
        bias = jnp.zeros((k,), jnp.float32)

    x = _pad_for_conv(x_nhwc, R_WINO, R_WINO, padding)
    ho, wo = x.shape[1] - R_WINO + 1, x.shape[2] - R_WINO + 1

    # same tile/GEMM pipeline as winograd_conv2d, minus the weight
    # transform — U comes from DRAM (shared _wino_conv_piece /
    # _finish_output so the tiling, block-padding and scatter/crop
    # arithmetic can't drift between the two entry points)
    t_est = n * (-(-ho // m)) * (-(-wo // m))
    bt, bc, bk = _pick_tile_blocks(t_est, c, k)
    cp, kp = round_up(c, bc), round_up(k, bk)
    u = u_ptck.astype(jnp.float32).reshape(pt * pt, c, k)
    if (cp, kp) != (c, k):
        u = jnp.pad(u, ((0, 0), (0, cp - c), (0, kp - k)))
    mm, geom = _wino_conv_piece(
        x, u, m, (bt, bc, bk), out_dtype, dataflow, interpret)
    return _finish_output(mm, bias, m=m, bt=bt, bk=bk, relu=relu,
                          interpret=interpret, geom=geom, ho=ho, wo=wo,
                          k=k, kp=kp, out_dtype=out_dtype)


def input_transform(tiles, m, **kw):
    """Padded public wrapper for the input-transform Pallas kernel:
    (T, PT, PT, C) -> (PT^2, T, C)."""
    t, pt, _, c = tiles.shape
    bt, bc, _ = map(_transform_block, _pick_tile_blocks(t, c, c))
    tp, cp = round_up(t, bt), round_up(c, bc)
    tiles = jnp.pad(tiles.transpose(1, 2, 0, 3),
                    ((0, 0), (0, 0), (0, tp - t), (0, cp - c)))
    v = input_transform_kernel(tiles, m=m, bt=bt, bc=bc, **kw)
    return v[:, :t, :c]


def output_transform(m_arr, bias, m, relu=False, **kw):
    """Padded public wrapper for the output-transform Pallas kernel:
    (PT^2, T, K), (K,) -> (T, m, m, K)."""
    pt2, t, k = m_arr.shape
    bt, _, bk = map(_transform_block, _pick_tile_blocks(t, k, k))
    tp, kp = round_up(t, bt), round_up(k, bk)
    m_arr = jnp.pad(m_arr, ((0, 0), (0, tp - t), (0, kp - k)))
    bias_p = jnp.pad(bias.astype(jnp.float32), (0, kp - k))
    y = output_transform_kernel(m_arr, bias_p, m=m, bt=bt, bk=bk, relu=relu, **kw)
    return y[:, :, :t, :k].transpose(2, 0, 1, 3)
