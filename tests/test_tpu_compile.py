"""Every Pallas kernel on the serving path compiles for a TPU v5e, and the
XLA Winograd PE compiles there to ops that all keep their layer's scope.

Each case compiles one kernel with ``interpret=False`` at VGG16's published
widths (batch 8) for a chip that is described, not attached: the TPU
compiler refuses here what it would refuse on the chip (unaligned blocks,
too much scoped VMEM, layouts Mosaic cannot lower). Nothing runs, so these
say nothing about results or speed.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and the test workers all import this
file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.winograd import winograd_apply_pretransformed
from repro.kernels.gemm import matmul
from repro.kernels.gemm.int8 import quantized_matmul
from repro.kernels.gemm.kernel import batched_matmul_kernel
from repro.kernels.spatial_conv import spatial_conv2d
from repro.kernels.winograd.kernel import (
    input_transform_kernel,
    output_transform_kernel,
)
from repro.launch.compile_cache import without_compile_cache

N = 8   # serving batch


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    # the TPU library would otherwise write compiler and driver logs
    # under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_MIN_LOG_LEVEL", "3")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no description
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip would be written to the persistent
    # cache but could never be read back without one: keep the cache out
    with without_compile_cache():
        yield SingleDeviceSharding(topo.devices[0])


# (kernel, operand shapes/dtypes): VGG16 at 224, batch 8
CASES = {
    # Spatial PE at conv5_x: (8, 14+halo, 14, 512) x (3, 3, 512, 512)
    "spatial_conv": (
        lambda x, w, b: spatial_conv2d(x, w, b, padding=((0, 0), (1, 1)),
                                       relu=True, interpret=False),
        [((N, 16, 14, 512), jnp.float32), ((3, 3, 512, 512), jnp.float32),
         ((512,), jnp.float32)]),
    # Winograd F(4,3) at conv4_2: 8 x 7 x 7 tiles padded to 512, C = 512
    "winograd_input_transform": (
        lambda t: input_transform_kernel(t, m=4, bt=128, bc=128,
                                         interpret=False),
        [((6, 6, 512, 512), jnp.float32)]),
    "winograd_output_transform": (
        lambda mm, b: output_transform_kernel(mm, b, m=4, bt=128, bk=128,
                                              relu=True, interpret=False),
        [((36, 512, 512), jnp.float32), ((512,), jnp.float32)]),
    # the U-space GEMM: PT^2 = 36 independent (T, C) @ (C, K) products
    "winograd_uspace_gemm": (
        lambda v, u: batched_matmul_kernel(v, u, bm=256, bn=256, bk=256,
                                           interpret=False),
        [((36, 512, 512), jnp.float32), ((36, 512, 512), jnp.float32)]),
    # fc6: (8, 25088) @ (25088, 4096)
    "gemm_fp32": (
        lambda a, w: matmul(a, w, interpret=False),
        [((N, 25088), jnp.float32), ((25088, 4096), jnp.float32)]),
    "gemm_int8": (
        lambda a, w, b: quantized_matmul(a, w, b, mult=0.01, relu=True,
                                         interpret=False),
        [((N, 25088), jnp.int8), ((25088, 4096), jnp.int8),
         ((4096,), jnp.int32)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, operands = CASES[name]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in operands]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# VGG16 conv1_2 (224 + halo, C = K = 64) and conv4_1 (28 + halo, 256 -> 512)
# on the XLA Winograd PE, F(4,3), batch 8
WINO_SHAPES = [(226, 64, 64), (30, 256, 512)]
_INSTR = re.compile(r"\s*(?:ROOT )?%?([\w.-]+) = .*? ([a-z][\w-]*)\(")


@pytest.mark.parametrize("h,c,k", WINO_SHAPES)
def test_xla_winograd_pe_keeps_its_scope_on_v5e(h, c, k, one_chip):
    """Every fusion, dot, convolution and loop the TPU compiler makes of the
    PE carries the layer's ``named_scope``, so a device trace can charge its
    time to the layer (a stack of the tile slices compiled to a chain of
    in-place updates with no ``op_name``); and no gather or loop."""
    def layer(x, u, b):
        with jax.named_scope("L1:conv.wino"):
            return winograd_apply_pretransformed(x, u, b, 4, relu=True,
                                                 padding="VALID")

    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in ((N, h, h, c), (6, 6, c, k), (k,))]
    text = jax.jit(layer).lower(*args).compile().as_text()
    ops = [(m.group(1), m.group(2), line)
           for line in text[text.index("\nENTRY"):].splitlines()
           if (m := _INSTR.match(line))]
    assert not [name for name, op, _ in ops if op in ("gather", "while")]
    unscoped = [name for name, op, line in ops
                if op in ("fusion", "dot", "convolution")
                and "L1:conv.wino" not in line]
    assert not unscoped
