"""Per-ISA-layer ``jax.named_scope``s of the lowered executor: every compute
op of the optimized HLO carries the ``L{layer_id}:{kind}`` scope of the layer
it came from, and the scopes are metadata only (the optimized HLO without
metadata is the same as without scopes)."""
import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core import executor
from repro.core import perf_model as pm
from repro.core.compiler import LayerPlan, compile_network
from repro.core.hybrid_conv import ConvSpec, FCSpec, PoolSpec

# Winograd and Spatial CONV layers, a POOL and the FC tail; the first layer
# is Winograd, so the input's reorder runs inside layer 0's scope
SPECS = [ConvSpec("c1", 12, 12, 3, 8), ConvSpec("c2", 12, 12, 8, 8),
         ConvSpec("c3", 12, 12, 8, 16), PoolSpec("p1", 12, 12, 16),
         FCSpec("fc", 6 * 6 * 16, 10)]
PLANS = [LayerPlan("wino", "is", 2, 2, 2), LayerPlan("spat", "is", 2, 2, 2),
         LayerPlan("wino", "ws", 2, 2, 2), None, None]
COMPUTE_OPS = ("dot", "convolution", "fusion", "while")
_INSTR = re.compile(r"\s*(?:ROOT )?%?([\w.-]+) = .*? ([a-z][\w-]*)\(")


def _compiled_text(acc, batch=2):
    entry, params = acc.runtime.executor_entry(batch, acc.input_dtype)
    x = jnp.zeros((batch, *acc.input_shape), acc.input_dtype)
    return entry.fn.lower(params, x).compile().as_text()


def _scoped_ops(text):
    """``(hlo name, opcode, scope or None)`` of every compute op."""
    out = []
    for line in text.splitlines():
        m = _INSTR.match(line)
        if not m or m.group(2) not in COMPUTE_OPS:
            continue
        op_name = re.search(r'op_name="([^"]*)"', line)
        scope = (re.search(r"(?:^|/)(L\d+:[a-z.]+)(?:/|$)", op_name.group(1))
                 if op_name else None)
        out.append((m.group(1), m.group(2), scope and scope.group(1)))
    return out


@pytest.fixture(scope="module")
def float_acc():
    return api.Accelerator.build(SPECS, target=pm.V5E, plans=PLANS, batch=2,
                                 seed=0)


@pytest.fixture(scope="module")
def int8_acc(float_acc):
    calib = np.random.default_rng(2).standard_normal(
        (4, 12, 12, 3)).astype(np.float32)
    return api.Accelerator.build(SPECS, target=pm.V5E, batch=2, seed=0,
                                 params=float_acc.params, dtype="int8",
                                 calib=calib)


def test_layer_scope_names():
    prog = compile_network(SPECS, PLANS)
    assert [executor.layer_scope(cl) for cl in prog.layers] == [
        "L0:conv.wino", "L1:conv.spat", "L2:conv.wino", "L3:pool", "L4:fc"]


@pytest.mark.parametrize("which", ["float32", "int8"])
def test_every_compute_op_carries_its_layer_scope(which, request):
    acc = request.getfixturevalue(
        "float_acc" if which == "float32" else "int8_acc")
    expected = {executor.layer_scope(cl) for cl in acc.runtime.program.layers}
    ops = _scoped_ops(_compiled_text(acc))
    assert ops, "no compute op found in the optimized HLO"
    unscoped = [(name, op) for name, op, scope in ops if scope is None]
    assert not unscoped, f"compute ops without a layer scope: {unscoped}"
    seen = {scope for _, _, scope in ops}
    assert seen <= expected
    # every layer that computes shows up (POOL may fuse into its neighbour
    # on the CPU, so only CONV and FC layers are required)
    assert {s for s in expected if ":conv" in s or s.endswith(":fc")} <= seen
    if which == "int8":
        assert not any("wino" in s for s in expected)


def test_scopes_are_metadata_only(float_acc, monkeypatch):
    """The optimized HLO with its metadata (and the source-location tables
    after it) removed is the same as the one lowered without scopes: XLA
    optimizes the same program."""
    def strip(t):
        return re.sub(r", metadata=\{[^}]*\}", "", t.split("\nFileNames")[0])

    prog = float_acc.runtime.program
    params = executor.to_dram_params(prog, float_acc.params)
    x = jnp.zeros((2, 12, 12, 3), jnp.float32)

    def text():
        fn = jax.jit(executor.lower_program(prog))
        return fn.lower(params, x).compile().as_text()

    scoped = text()
    monkeypatch.setattr(executor.jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = text()
    assert "L0:conv.wino" in scoped and "L0:conv.wino" not in bare
    assert strip(scoped) == strip(bare)
