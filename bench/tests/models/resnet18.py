"""Plain reference of the reduced ResNet-18 the program ships as a residual
example (He et al., "Deep Residual Learning for Image Recognition",
arXiv:1512.03385, Table 1, 18-layer, in the form the program's ISA serves):
a 3x3 stride-1 stem and a 2x2/2 max pool, four stages of two basic blocks
(3x3 convolutions; the first block of stages 2-4 strides by 2 in its first
convolution and projects its shortcut by a 1x1 stride-2 convolution), each
block closed by the residual add and a ReLU, then one dense layer over the
flattened map (no global average pool). For the CPU rehearsal of a second
network only; never a cell.

Inputs NHWC, weights HWIO and (in, out), "same" padding as ``lax`` pads it.
Straight ``jax.numpy``/``lax``: no kernels, no batching, no cache.

``sizes`` keys: ``img`` (a multiple of 16), ``scale`` (the stem's width is
``max(4, 64 // scale)``, doubled at each later stage) and ``n_classes``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

BLOCKS = (2, 2, 2, 2)


def layers(sizes: dict) -> list[tuple]:
    """One tuple per ISA layer, in the program's order: ``("conv", h, c, k,
    r, stride, relu, src)``, ``("pool", h, c)``, ``("eltwise", h, c, skip)``
    and ``("fc", d_in, d_out)``; ``src`` and ``skip`` are the index of the
    layer whose output is read (-1: the input)."""
    img, w0 = int(sizes["img"]), max(4, 64 // int(sizes["scale"]))
    out = [("conv", img, 3, w0, 3, 1, True, -1), ("pool", img, w0)]
    h, c = img // 2, w0
    for stage, n in enumerate(BLOCKS):
        width = w0 * 2 ** stage
        for block in range(n):
            block_in = len(out) - 1
            if stage > 0 and block == 0:
                out.append(("conv", h, c, width, 1, 2, False, block_in))
                skip = len(out) - 1
                out.append(("conv", h, c, width, 3, 2, True, block_in))
                h = h // 2
            else:
                skip = block_in
                out.append(("conv", h, c, width, 3, 1, True, block_in))
            out.append(("conv", h, width, width, 3, 1, False, len(out) - 1))
            out.append(("eltwise", h, width, skip))
            c = width
    out.append(("fc", h * h * c, int(sizes["n_classes"])))
    return out


def _out_side(h: int, stride: int) -> int:
    return -(-h // stride)


def flops_per_image(sizes: dict) -> int:
    """Multiplies and adds of direct convolution and the dense layer (2 per
    MAC); bias, ReLU, pooling and the residual adds are not counted."""
    n = 0
    for layer in layers(sizes):
        if layer[0] == "conv":
            _, h, c, k, r, stride, _, _ = layer
            n += 2 * _out_side(h, stride) ** 2 * r * r * c * k
        elif layer[0] == "fc":
            n += 2 * layer[1] * layer[2]
    return n


def layer_costs(sizes: dict, rows: int, dtype_bytes: int) -> list:
    """Per ISA layer, ``(kind, flops, bytes)`` of one executor call on
    ``rows`` images: FLOPs of direct convolution and the dense layer (2 per
    MAC) and one per residual add; bytes of weights and biases once plus
    each image's maps read and written, all at ``dtype_bytes``."""
    out = []
    for layer in layers(sizes):
        kind = layer[0]
        if kind == "conv":
            _, h, c, k, r, stride, _, _ = layer
            ho = _out_side(h, stride)
            flops = 2 * ho * ho * r * r * c * k
            weights, maps = r * r * c * k + k, h * h * c + ho * ho * k
        elif kind == "pool":
            _, h, c = layer
            flops, weights, maps = 0, 0, h * h * c + (h // 2) ** 2 * c
        elif kind == "eltwise":
            _, h, c, _ = layer
            flops, weights, maps = h * h * c, 0, 3 * h * h * c
        else:
            _, d_in, d_out = layer
            flops = 2 * d_in * d_out
            weights, maps = d_in * d_out + d_out, d_in + d_out
        out.append((kind, rows * flops, dtype_bytes * (weights + rows * maps)))
    return out


def init_params(key, sizes: dict) -> list:
    """Fan-in-scaled normal weights and seeded nonzero biases, one ``(w, b)``
    per convolution and the dense layer, in layer order."""
    shapes = [((l[4], l[4], l[2], l[3]), l[4] * l[4] * l[2])
              if l[0] == "conv" else ((l[1], l[2]), l[1])
              for l in layers(sizes) if l[0] in ("conv", "fc")]
    keys = jax.random.split(key, 2 * len(shapes))
    params = []
    for i, (shape, fan_in) in enumerate(shapes):
        w = jax.random.normal(keys[2 * i], shape, jnp.float32) * fan_in ** -0.5
        b = 0.1 * jax.random.normal(keys[2 * i + 1], shape[-1:], jnp.float32)
        params.append((w, b))
    return params


def forward(params, x, sizes: dict, *, act=None, weight=None):
    """Logits for the NHWC batch ``x``, matmuls at ``highest`` precision;
    ``act`` and ``weight`` map each convolution's or dense layer's operands,
    as in ``bench/models/vgg16.py``."""
    act = act or (lambda a: a)
    weight = weight or (lambda w: w)
    hi = lax.Precision.HIGHEST
    outs, pi = {-1: x}, 0
    for i, layer in enumerate(layers(sizes)):
        kind = layer[0]
        if kind == "conv":
            _, _, _, _, _, stride, relu, src = layer
            w, b = params[pi]
            pi += 1
            y = lax.conv_general_dilated(
                act(outs[src]), weight(w), (stride, stride), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=hi) + b
            y = jnp.maximum(y, 0.0) if relu else y
        elif kind == "pool":
            y = lax.reduce_window(outs[i - 1], -jnp.inf, lax.max,
                                  (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
        elif kind == "eltwise":
            y = jnp.maximum(outs[i - 1] + outs[layer[3]], 0.0)
        else:
            w, b = params[pi]
            pi += 1
            y = outs[i - 1].reshape(x.shape[0], -1)
            y = jnp.dot(act(y), weight(w), precision=hi) + b
        outs[i] = y
    return y
