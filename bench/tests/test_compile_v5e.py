"""The four-chip offline cell's executor, compiled for a described TPU v5e
2x2 (no chip needed): the sharded batch-32 executor of full-width VGG16 that
``vgg16-fp32`` under ``traffic/offline.json`` serves over a four-chip
batch mesh, with the plans the DSE makes for it.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest bench/tests -q

The topology is described inside a module fixture, never at import.
"""
import functools
import os

import numpy as np
import pytest

from bench import registry

CONFIG, CHIPS = "vgg16-fp32", 4   # the four-chip offline cell's


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_MIN_LOG_LEVEL", "3")
    from jax.experimental import topologies

    from repro.launch.compile_cache import without_compile_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    with without_compile_cache():
        yield desc


def test_four_chip_executor_compiles_for_v5e_2x2(topo):
    import importlib

    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from repro.core import perf_model as pm
    from repro.core.compiler import compile_network
    from repro.core.executor import compile_executor, to_dram_params

    cfg = registry.load_config(CONFIG)
    mod, _, fn = cfg["network"]["builder"].partition(":")
    specs = getattr(importlib.import_module(mod), fn)(
        **cfg["network"]["kwargs"])
    batch = cfg["max_batch_per_chip"] * CHIPS
    program = compile_network(specs, pm.V5E.run_dse(specs, batch=batch).plans)
    mesh = Mesh(np.array(topo.devices[:CHIPS]), ("batch",))
    ex = compile_executor(program, mesh=mesh, donate_input=True)

    model = registry.load_model(cfg["reference"])
    raw = jax.eval_shape(lambda k: model.init_params(k, cfg["sizes"]),
                         jax.random.key(0))
    dram = jax.eval_shape(functools.partial(to_dram_params, program), raw)
    rep = NamedSharding(mesh, PartitionSpec())
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=rep), dram)
    img = cfg["sizes"]["img"]
    x = jax.ShapeDtypeStruct((batch, img, img, 3), np.float32,
                             sharding=NamedSharding(mesh,
                                                    PartitionSpec("batch")))
    compiled = ex.fn.lower(params, x).compile()
    mem = compiled.memory_analysis()
    per_chip = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    print(f"{CONFIG} on {CHIPS} chips: arguments {mem.argument_size_in_bytes} B, temporaries "
          f"{mem.temp_size_in_bytes} B per chip")
    assert 0 < per_chip < 16e9
