"""Winograd fast convolution: transforms, GEMM formulation, kernel decomposition.

Implements the paper's Sec. 4.2.1: an ``F(m x m, r x r)`` Winograd algorithm
computes an ``m x m`` output tile from an ``(m+r-1) x (m+r-1)`` input tile as

    Y = A^T [ (G g G^T) .* (B^T d B) ] A                              (Eq. 1)

and, summed over input channels, the element-wise products split into
``PT^2 = (m+r-1)^2`` *independent GEMMs* (Eq. 2):

    M[p, t, k] = sum_c V[p, t, c] * U[p, c, k]       p in [0, PT^2)

which is exactly a batched matmul with leading batch PT^2 — the paper's
PT x PT array of GEMM cores, our ``kernels/gemm`` leading grid axis.

Supported: F(2x2, 3x3) (PT=4) and F(4x4, 3x3) (PT=6), matching the paper's
``PT in {4, 6}`` constraint (Sec. 5.1). Larger kernels are handled by the
paper's kernel-decomposition method (Sec. 4.2.5): an R x S kernel is split
into ceil(R/r) x ceil(S/r) zero-padded r x r kernels whose partial outputs
accumulate at shifted offsets.

Layout conventions: feature maps NHWC, kernels HWIO (R, S, C, K).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

R_WINO = 3  # the paper's Winograd algorithms are F(m, 3)


# ---------------------------------------------------------------------------
# Transform matrices (Lavin & Gray, "Fast Algorithms for Convolutional NNs")
# ---------------------------------------------------------------------------

_F2_BT = np.array(
    [[1, 0, -1, 0],
     [0, 1, 1, 0],
     [0, -1, 1, 0],
     [0, 1, 0, -1]], dtype=np.float64)
_F2_G = np.array(
    [[1, 0, 0],
     [0.5, 0.5, 0.5],
     [0.5, -0.5, 0.5],
     [0, 0, 1]], dtype=np.float64)
_F2_AT = np.array(
    [[1, 1, 1, 0],
     [0, 1, -1, -1]], dtype=np.float64)

_F4_BT = np.array(
    [[4, 0, -5, 0, 1, 0],
     [0, -4, -4, 1, 1, 0],
     [0, 4, -4, -1, 1, 0],
     [0, -2, -1, 2, 1, 0],
     [0, 2, -1, -2, 1, 0],
     [0, 4, 0, -5, 0, 1]], dtype=np.float64)
_F4_G = np.array(
    [[1 / 4, 0, 0],
     [-1 / 6, -1 / 6, -1 / 6],
     [-1 / 6, 1 / 6, -1 / 6],
     [1 / 24, 1 / 12, 1 / 6],
     [1 / 24, -1 / 12, 1 / 6],
     [0, 0, 1]], dtype=np.float64)
_F4_AT = np.array(
    [[1, 1, 1, 1, 1, 0],
     [0, 1, -1, 2, -2, 0],
     [0, 1, 1, 4, 4, 0],
     [0, 1, -1, 8, -8, 1]], dtype=np.float64)

_MATRICES = {2: (_F2_BT, _F2_G, _F2_AT), 4: (_F4_BT, _F4_G, _F4_AT)}

# the implemented F(m, 3) transform set — the DSE's eligibility source of
# truth (ConvSpec.wino_eligible)
SUPPORTED_M = tuple(sorted(_MATRICES))


@functools.lru_cache(None)
def transform_matrices(m: int, dtype=jnp.float32):
    """Return (B^T, G, A^T) for F(m x m, 3 x 3). PT = m + r - 1.

    Cached as NUMPY arrays (trace-safe: jnp values created inside a jit
    trace would leak tracers through the lru_cache)."""
    if m not in _MATRICES:
        raise ValueError(f"F({m},{R_WINO}) unsupported; PT must be in {{4, 6}} (m in {{2, 4}})")
    bt, g, at = _MATRICES[m]
    return (np.asarray(bt, dtype), np.asarray(g, dtype), np.asarray(at, dtype))


def pt_for(m: int) -> int:
    """Input tile size PT = m + r - 1."""
    return m + R_WINO - 1


def mult_reduction(m: int, r: int = R_WINO) -> float:
    """Multiplication reduction of F(m,r) vs direct conv: (m*r)^2 / (m+r-1)^2.

    Paper example: F(4x4,3x3) needs 36 mults/tile vs 144 direct -> 4.0x.
    """
    return float((m * r) ** 2) / float((m + r - 1) ** 2)


# ---------------------------------------------------------------------------
# Weight transform (offline, Sec. 4.2.3: "offline transformation from
# pretrained DNN models")
# ---------------------------------------------------------------------------

def transform_weights(g_rsck: jax.Array, m: int) -> jax.Array:
    """U = G g G^T per (c, k): (r, r, C, K) -> (PT, PT, C, K)."""
    r, s, c, k = g_rsck.shape
    assert r == R_WINO and s == R_WINO, f"use decompose_kernel for {r}x{s}"
    _, gm, _ = transform_matrices(m, jnp.float32)
    g32 = g_rsck.astype(jnp.float32)
    u = jnp.einsum("ir,rsck,js->ijck", gm, g32, gm)
    return u.astype(g_rsck.dtype)


def decompose_kernel(g_rsck: jax.Array, m: int):
    """Paper Sec. 4.2.5 kernel decomposition for R, S > r.

    Splits an (R, S, C, K) kernel into ceil(R/r) x ceil(S/r) zero-padded
    (r, r, C, K) sub-kernels. Returns a list of (offset_h, offset_w, subkernel)
    where offsets are the input-shift at which the sub-kernel's partial conv
    output accumulates.
    """
    r = R_WINO
    rr, ss, c, k = g_rsck.shape
    nh, nw = -(-rr // r), -(-ss // r)
    pads = ((0, nh * r - rr), (0, nw * r - ss), (0, 0), (0, 0))
    gp = jnp.pad(g_rsck, pads)
    out = []
    for i in range(nh):
        for j in range(nw):
            sub = gp[i * r:(i + 1) * r, j * r:(j + 1) * r]
            out.append((i * r, j * r, sub))
    return out


# ---------------------------------------------------------------------------
# Input tiling / transform and output transform (pure-jnp reference forms;
# the Pallas fast path lives in kernels/winograd)
# ---------------------------------------------------------------------------

def tile_input(x_nhwc: jax.Array, m: int) -> tuple[jax.Array, tuple[int, int]]:
    """Partition NHWC input into overlapping PT x PT tiles with stride m.

    Input is assumed already padded for the convolution itself (i.e. a VALID
    conv of the padded input yields the desired output). Returns
    ``(tiles, (nh, nw))`` with tiles shaped (N, nh, nw, PT, PT, C); adjacent
    tiles share an (r-1)-pixel overlap, exactly the paper's partitioning.
    """
    pt = pt_for(m)
    n, h, w, c = x_nhwc.shape
    ho, wo = h - R_WINO + 1, w - R_WINO + 1  # VALID conv output size
    nh, nw = -(-ho // m), -(-wo // m)
    # pad so the tile grid covers the full output
    hp, wp = (nh - 1) * m + pt, (nw - 1) * m + pt
    x = jnp.pad(x_nhwc, ((0, 0), (0, hp - h), (0, wp - w), (0, 0)))
    # gather tiles: strided window extraction
    idx_h = (jnp.arange(nh) * m)[:, None] + jnp.arange(pt)[None, :]   # (nh, PT)
    idx_w = (jnp.arange(nw) * m)[:, None] + jnp.arange(pt)[None, :]   # (nw, PT)
    tiles = x[:, idx_h]                # (N, nh, PT, Wp, C)
    tiles = tiles[:, :, :, idx_w]      # (N, nh, PT, nw, PT, C)
    tiles = tiles.transpose(0, 1, 3, 2, 4, 5)  # (N, nh, nw, PT, PT, C)
    return tiles, (nh, nw)


def transform_input(tiles: jax.Array, m: int) -> jax.Array:
    """V = B^T d B: (N, nh, nw, PT, PT, C) -> (PT*PT, N*nh*nw, C)."""
    bt, _, _ = transform_matrices(m, jnp.float32)
    n, nh, nw, pt, _, c = tiles.shape
    v = jnp.einsum("ip,xpqc,jq->xijc", bt, tiles.reshape(-1, pt, pt, c).astype(jnp.float32), bt)
    v = v.reshape(n * nh * nw, pt * pt, c).transpose(1, 0, 2)
    return v


def transform_output(m_ptsq: jax.Array, m: int, n: int, nh: int, nw: int,
                     out_dtype=jnp.float32) -> jax.Array:
    """Y = A^T M A: (PT*PT, N*nh*nw, K) -> (N, nh*m, nw*m, K)."""
    _, _, at = transform_matrices(m, jnp.float32)
    pt2, t, k = m_ptsq.shape
    pt = pt_for(m)
    mm = m_ptsq.transpose(1, 0, 2).reshape(t, pt, pt, k).astype(jnp.float32)
    y = jnp.einsum("ip,xpqk,jq->xijk", at, mm, at)  # (t, m, m, K)
    y = y.reshape(n, nh, nw, m, m, k).transpose(0, 1, 3, 2, 4, 5)
    y = y.reshape(n, nh * m, nw * m, k)
    return y.astype(out_dtype)


def winograd_apply_pretransformed(
    x_nhwc: jax.Array,
    u_ptck: jax.Array,      # (PT, PT, C, K) offline-transformed weights
    bias: jax.Array | None,
    m: int,
    relu: bool = False,
    padding: str = "SAME",
    out_dtype=None,
) -> jax.Array:
    """Winograd conv with weights already in U-space (r = 3, stride 1).

    This is the runtime's COMP path: the paper stores *transformed* weights in
    DRAM (Sec. 4.2.3), so the PE consumes U directly.

    No gather: the padded input is split into its ``m x m`` phase planes
    ``x[:, a::m, b::m]`` by one reshape and a transpose of whole planes. A
    tile's first ``m`` rows are its own tile's phases and its last ``PT - m
    = r - 1 <= m`` the next tile's first phases, so each tile axis takes two
    unit-stride slices of the planes and one concatenate. The planes keep
    the batch beside the channels, so tiles flatten to ``(PT^2, nh nw N,
    C)`` without moving data. ``B^T d B`` and ``A^T M A`` are each one
    contraction over the ``PT^2`` tile axis with ``B^T (x) B^T`` and ``A^T
    (x) A^T``, at the GEMM's precision. A strided index ``x[:, p::m]`` would
    lower to a gather in JAX 0.9, which the TPU runs as a loop of dynamic
    slices; a stack of ``PT^2`` single slices to a chain of in-place
    updates that XLA leaves without the layer's ``op_name``.
    ``tile_input``/``transform_input``/``transform_output`` are the same
    algorithm in gather/einsum form, kept as the reference.
    """
    out_dtype = out_dtype or x_nhwc.dtype
    n, h, w, c = x_nhwc.shape
    pt, _, _, k = u_ptck.shape
    assert pt == pt_for(m), (pt, m)
    rr = R_WINO
    lo = (rr - 1) // 2 if padding.upper() == "SAME" else 0
    ho, wo = h + 2 * lo - rr + 1, w + 2 * lo - rr + 1
    nh, nw = -(-ho // m), -(-wo // m)
    # one pad: the conv's own, then out to whole (tile, phase) grids
    x = jnp.pad(x_nhwc.astype(jnp.float32),
                ((0, 0), (lo, (nh + 1) * m - h - lo),
                 (lo, (nw + 1) * m - w - lo), (0, 0)))
    planes = x.reshape(n, nh + 1, m, nw + 1, m, c).transpose(2, 4, 1, 3, 0, 5)
    # (PT, m, nh, nw + 1, N, C), then (PT, PT, nh, nw, N, C)
    rows = jnp.concatenate([planes[:, :, :nh], planes[:pt - m, :, 1:]], axis=0)
    tiles = jnp.concatenate([rows[:, :, :, :nw], rows[:, :pt - m, :, 1:]], axis=1)
    bt, _, at = transform_matrices(m, jnp.float32)
    v = jnp.einsum("ap,ptc->atc", np.kron(bt, bt),
                   tiles.reshape(pt * pt, nh * nw * n, c))
    u = u_ptck.astype(jnp.float32).reshape(pt * pt, c, k)
    mm = jnp.einsum("ptc,pck->ptk", v, u)                    # the PT^2 GEMMs
    y = jnp.einsum("ap,ptk->atk", np.kron(at, at), mm)       # (m^2, nh nw N, K)
    y = y.reshape(m, m, nh, nw, n, k).transpose(4, 2, 0, 3, 1, 5)
    y = y.reshape(n, nh * m, nw * m, k)[:, :ho, :wo]
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    if relu:
        y = jnp.maximum(y, 0.0)
    return y.astype(out_dtype)


def winograd_conv2d_reference(
    x_nhwc: jax.Array,
    g_rsck: jax.Array,
    m: int = 4,
    padding: str | tuple = "SAME",
    out_dtype=None,
) -> jax.Array:
    """End-to-end Winograd convolution (stride 1), pure jnp. Oracle + fallback.

    Handles R, S != 3 via the paper's kernel decomposition.
    """
    out_dtype = out_dtype or x_nhwc.dtype
    n, h, w, c = x_nhwc.shape
    rr, ss, _, k = g_rsck.shape

    if isinstance(padding, str):
        if padding.upper() == "SAME":
            ph, pw = (rr - 1) // 2, (ss - 1) // 2
            pad = ((ph, rr - 1 - ph), (pw, ss - 1 - pw))
        elif padding.upper() == "VALID":
            pad = ((0, 0), (0, 0))
        else:
            raise ValueError(padding)
    else:
        pad = padding
    x = jnp.pad(x_nhwc, ((0, 0), pad[0], pad[1], (0, 0)))
    ho = x.shape[1] - rr + 1
    wo = x.shape[2] - ss + 1

    if (rr, ss) == (R_WINO, R_WINO):
        pieces = [(0, 0, g_rsck)]
    else:
        pieces = decompose_kernel(g_rsck, m)
        # pad input so every shifted sub-conv sees a full window
        extra_h = (-(-rr // R_WINO)) * R_WINO - rr
        extra_w = (-(-ss // R_WINO)) * R_WINO - ss
        x = jnp.pad(x, ((0, 0), (0, extra_h), (0, extra_w), (0, 0)))

    acc = None
    for (oh, ow, sub) in pieces:
        xs = x[:, oh:oh + ho + R_WINO - 1, ow:ow + wo + R_WINO - 1, :]
        tiles, (nh, nw) = tile_input(xs, m)
        v = transform_input(tiles, m)                      # (PT^2, T, C)
        u = transform_weights(sub, m).astype(jnp.float32)  # (PT, PT, C, K)
        pt = pt_for(m)
        u = u.reshape(pt * pt, c, k)
        mm = jnp.einsum("ptc,pck->ptk", v, u)              # the PT^2 GEMMs
        y = transform_output(mm, m, n, nh, nw)             # (N, nh*m, nw*m, K)
        y = y[:, :ho, :wo, :]
        acc = y if acc is None else acc + y
    return acc.astype(out_dtype)
