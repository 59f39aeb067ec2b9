"""Plain reference of VGG16: Simonyan & Zisserman, "Very Deep Convolutional
Networks for Large-Scale Image Recognition", arXiv:1409.1556, Table 1,
configuration D.

Thirteen 3x3 convolutions (stride 1, "same" padding, bias, ReLU) in five
stages closed by 2x2/2 max pooling, then three fully connected layers (ReLU
after the first two). Inputs are NHWC, weights HWIO for convolutions and
(in, out) for the dense layers, and the pooled map is flattened in NHWC
order. Straight ``jax.numpy``/``lax``: no kernels, no batching, no cache.

``sizes`` keys: ``img`` (input side, a multiple of 32), ``scale`` (divides
every channel width and the 4096-wide dense layers, with a floor of 64 for
the latter; 1 is the published network) and ``n_classes``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

# configuration D: output channels per conv, "M" = 2x2/2 max pool
LAYOUT = (64, 64, "M", 128, 128, "M", 256, 256, 256, "M",
          512, 512, 512, "M", 512, 512, 512, "M")
FC_WIDTH = 4096


def layers(sizes: dict) -> list[tuple]:
    """``("conv", h, c, k)``, ``("pool", h, c)`` and ``("fc", d_in, d_out,
    relu)`` in order, for one image."""
    img, scale = int(sizes["img"]), int(sizes["scale"])
    out, h, c = [], img, 3
    for entry in LAYOUT:
        if entry == "M":
            out.append(("pool", h, c))
            h //= 2
        else:
            k = entry // scale
            out.append(("conv", h, c, k))
            c = k
    fc = max(64, FC_WIDTH // scale)
    dims = [h * h * c, fc, fc, int(sizes["n_classes"])]
    for i in range(3):
        out.append(("fc", dims[i], dims[i + 1], i < 2))
    return out


def flops_per_image(sizes: dict) -> int:
    """Multiplies and adds of direct convolution and the dense layers (2 per
    MAC); bias, ReLU and pooling are not counted."""
    n = 0
    for layer in layers(sizes):
        if layer[0] == "conv":
            _, h, c, k = layer
            n += 2 * h * h * 9 * c * k
        elif layer[0] == "fc":
            n += 2 * layer[1] * layer[2]
    return n


def init_params(key, sizes: dict) -> list:
    """Fan-in-scaled normal weights and seeded nonzero biases, one ``(w, b)``
    per conv and dense layer. Layer l's bias is a tenth of the rms its
    pre-activations have on a unit-variance input (2^(-l/2): fan-in scaling
    keeps the second moment and ReLU halves it), so a dropped or misplaced
    bias moves the logits."""
    params = []
    shapes = [((3, 3, l[2], l[3]), l[2] * 9) if l[0] == "conv"
              else ((l[1], l[2]), l[1])
              for l in layers(sizes) if l[0] != "pool"]
    keys = jax.random.split(key, 2 * len(shapes))
    for i, (shape, fan_in) in enumerate(shapes):
        w = jax.random.normal(keys[2 * i], shape, jnp.float32) * fan_in ** -0.5
        b = 0.1 * 2.0 ** (-i / 2) * jax.random.normal(
            keys[2 * i + 1], shape[-1:], jnp.float32)
        params.append((w, b))
    return params


def forward(params, x, sizes: dict, *, act=None, weight=None):
    """Logits for the NHWC batch ``x``. Matmuls run at ``highest``
    precision. ``act`` and ``weight``, where given, map every convolution's
    or dense layer's input and weight before it is used: the lower-precision
    controls round through them."""
    act = act or (lambda a: a)
    weight = weight or (lambda w: w)
    hi = lax.Precision.HIGHEST
    y, pi = x, 0
    for layer in layers(sizes):
        if layer[0] == "pool":
            y = lax.reduce_window(y, -jnp.inf, lax.max, (1, 2, 2, 1),
                                  (1, 2, 2, 1), "VALID")
            continue
        w, b = params[pi]
        pi += 1
        if layer[0] == "conv":
            y = lax.conv_general_dilated(
                act(y), weight(w), (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=hi) + b
            y = jnp.maximum(y, 0.0)
        else:
            y = y.reshape(y.shape[0], -1)
            y = jnp.dot(act(y), weight(w), precision=hi) + b
            if layer[3]:
                y = jnp.maximum(y, 0.0)
    return y
