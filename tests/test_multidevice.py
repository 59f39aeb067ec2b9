"""Multi-device behaviors that need >1 device: run in a subprocess with
--xla_force_host_platform_device_count=8 so the main test process keeps its
single-device view."""
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.multidevice

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(_REPO, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_shardmap_pallas_gemm():
    """The Pallas GEMM PE under shard_map over a 2x4 mesh — the real-TPU
    distribution pattern for the kernels."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.kernels.gemm import batched_matmul
        from repro.kernels.gemm.ref import batched_matmul_ref
        mesh = make_mesh((2, 4), ("data", "model"))
        a = jax.random.normal(jax.random.PRNGKey(0), (4, 64, 64))
        b = jax.random.normal(jax.random.PRNGKey(1), (4, 64, 128))

        def local_mm(a, b):  # batch sharded over data, N sharded over model
            return batched_matmul(a, b)

        mm = jax.shard_map(local_mm, mesh=mesh,
                           in_specs=(P("data", None, None),
                                     P("data", None, "model")),
                           out_specs=P("data", None, "model"),
                           check_vma=False)  # pallas_call outputs carry no vma
        out = mm(a, b)
        ref = batched_matmul_ref(a, b)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)
        print("shard_map pallas gemm ok")
    """)


def test_sharded_train_step_runs():
    """A reduced model trains on a real 2x4 device mesh with the production
    sharding rules (params sharded, batch sharded, loss finite)."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.base import get_config
        from repro.parallel.sharding import (make_rules, param_shardings,
                                             use_rules)
        from repro.optim import adamw
        from repro.train import steps as steps_lib
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        rules = make_rules(mesh)
        cfg = get_config("minitron-8b").reduced()
        with use_rules(rules):
            params = steps_lib.init_params(jax.random.PRNGKey(0), cfg)
        params = jax.device_put(params, param_shardings(params, rules))
        opt_state = adamw.init(params)
        step = steps_lib.make_train_step(cfg, adamw.AdamWConfig(lr=1e-3))
        def wrapped(p, o, b):
            with use_rules(rules):
                return step(p, o, b)
        rng = np.random.default_rng(0)
        batch = {k: jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 16)),
                                jnp.int32) for k in ("tokens", "targets")}
        p2, o2, m = jax.jit(wrapped, donate_argnums=(0, 1))(
            params, opt_state, batch)
        assert np.isfinite(float(m["loss"]))
        print("sharded train step ok, loss", float(m["loss"]))
    """)


def test_compressed_psum_matches_mean():
    """int8 error-feedback all-reduce ~= exact mean over the DP axis."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.optim.compression import compressed_psum, init_error_state
        mesh = make_mesh((8,), ("data",))
        g = jax.random.normal(jax.random.PRNGKey(0), (8, 64))

        def body(g):
            grads = {"w": g[0]}
            err = init_error_state(grads)
            mean, new_err = compressed_psum(grads, err, ("data",))
            return mean["w"]

        out = jax.shard_map(body, mesh=mesh, in_specs=P("data", None),
                            out_specs=P())(g)
        ref = jnp.mean(g, axis=0)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=0.05, atol=0.02)
        print("compressed psum ok")
    """)


def test_sharded_serving_session_parity():
    """The shard_map'd executor variant inside a ServingSession: full
    buckets split over a 4-device fleet mesh, stragglers stay local —
    outputs match the unsharded session to fp accumulation noise."""
    _run("""
        import numpy as np
        from repro import api
        from repro.core import perf_model as pm
        from repro.core.hybrid_conv import ConvSpec, FCSpec, PoolSpec
        from repro.launch.mesh import make_fleet_mesh
        SPECS = [ConvSpec("c1", 16, 16, 3, 8), ConvSpec("c2", 16, 16, 8, 16),
                 PoolSpec("p1", 16, 16, 16),
                 FCSpec("fc", 8 * 8 * 16, 10, relu=False)]
        acc = api.Accelerator.build(SPECS, target=pm.V5E, batch=8, seed=0)
        mesh = make_fleet_mesh(4)
        rng = np.random.default_rng(0)
        reqs = [rng.standard_normal((16, 16, 3)).astype(np.float32)
                for _ in range(19)]            # 2 full buckets + straggler
        with acc.serve(max_batch=8, buckets=(4, 8)) as s:
            ref = [np.asarray(o) for o in s.run_many(reqs)]
        with acc.serve(max_batch=8, buckets=(4, 8), mesh=mesh) as s:
            got = [np.asarray(o) for o in s.run_many(reqs)]
            st = s.stats
        d = max(float(np.abs(a - b).max()) for a, b in zip(ref, got))
        assert d <= 1e-4, d
        # full 8-buckets counted on EVERY mesh device, stragglers on one
        assert len(st.device_batches) == 4, st.device_batches
        assert st.dispatched_rows == 19
        print("sharded session parity ok, max diff", d)
    """)


def test_pallas_backend_under_sharding_matches_xla():
    """backend="pallas" serves sharded: each shard is an ordinary
    single-device trace, so the Pallas PE kernels run per-shard inside the
    shard_map region — matching the XLA lowering to <= 1e-4."""
    _run("""
        import numpy as np
        from repro import api
        from repro.core import perf_model as pm
        from repro.core.hybrid_conv import ConvSpec, FCSpec, PoolSpec
        from repro.launch.mesh import make_fleet_mesh
        SPECS = [ConvSpec("c1", 16, 16, 3, 8), ConvSpec("c2", 16, 16, 8, 16),
                 PoolSpec("p1", 16, 16, 16),
                 FCSpec("fc", 8 * 8 * 16, 10, relu=False)]
        acc_x = api.Accelerator.build(SPECS, target=pm.V5E, batch=8, seed=0)
        acc_p = api.Accelerator.build(SPECS, target=pm.V5E, batch=8,
                                      params=acc_x.params, backend="pallas")
        mesh = make_fleet_mesh(4)
        rng = np.random.default_rng(0)
        reqs = [rng.standard_normal((16, 16, 3)).astype(np.float32)
                for _ in range(8)]
        with acc_x.serve(max_batch=8, buckets=(8,), mesh=mesh) as s:
            ref = [np.asarray(o) for o in s.run_many(reqs)]
        with acc_p.serve(max_batch=8, buckets=(8,), mesh=mesh) as s:
            got = [np.asarray(o) for o in s.run_many(reqs)]
        d = max(float(np.abs(a - b).max()) for a, b in zip(ref, got))
        assert d <= 1e-4, d
        print("pallas-under-sharding parity ok, max diff", d)
    """)


def test_fleet_multi_model_bitwise_stable():
    """Two models co-tenanting one Fleet (shared slot pool, shared program
    cache, shared mesh) produce BITWISE the outputs of their standalone
    sessions — tenancy changes scheduling, never computation."""
    _run("""
        import numpy as np
        from repro import api
        from repro.core import perf_model as pm
        from repro.core.hybrid_conv import ConvSpec, FCSpec, PoolSpec
        from repro.launch.mesh import make_fleet_mesh
        SPECS_A = [ConvSpec("c1", 16, 16, 3, 8),
                   ConvSpec("c2", 16, 16, 8, 16),
                   PoolSpec("p1", 16, 16, 16),
                   FCSpec("fc", 8 * 8 * 16, 10, relu=False)]
        SPECS_B = [ConvSpec("c1", 16, 16, 3, 12),
                   PoolSpec("p1", 16, 16, 12),
                   FCSpec("fc", 8 * 8 * 12, 10, relu=False)]
        acc_a = api.Accelerator.build(SPECS_A, target=pm.V5E, batch=8, seed=0)
        acc_b = api.Accelerator.build(SPECS_B, target=pm.V5E, batch=8, seed=1)
        mesh = make_fleet_mesh(4)
        rng = np.random.default_rng(0)
        reqs = [rng.standard_normal((16, 16, 3)).astype(np.float32)
                for _ in range(8)]
        with acc_a.serve(max_batch=8, buckets=(8,), mesh=mesh) as s:
            ref_a = [np.asarray(o) for o in s.run_many(reqs)]
        with acc_b.serve(max_batch=8, buckets=(8,), mesh=mesh) as s:
            ref_b = [np.asarray(o) for o in s.run_many(reqs)]
        with api.Fleet({"a": acc_a, "b": acc_b}, mesh=mesh,
                       max_batch=8, buckets=(8,)) as fleet:
            pairs = ([("a", r) for r in reqs] + [("b", r) for r in reqs])
            res = fleet.run_many(pairs)
        assert all(np.array_equal(g, r)
                   for g, r in zip(res[:8], ref_a)), "model a not bitwise"
        assert all(np.array_equal(g, r)
                   for g, r in zip(res[8:], ref_b)), "model b not bitwise"
        print("fleet multi-model bitwise ok")
    """)


def test_sharded_executor_cache_keying():
    """Mesh topology joins the program-cache key: sharded and unsharded
    executors of one Program coexist, a 1-device mesh aliases to the
    unsharded entry, and a non-dividing batch is refused."""
    _run("""
        import pytest
        from repro import api
        from repro.core import perf_model as pm
        from repro.core.hybrid_conv import ConvSpec, FCSpec
        from repro.core.program_cache import ProgramCache
        from repro.launch.mesh import make_fleet_mesh
        SPECS = [ConvSpec("c1", 16, 16, 3, 8),
                 FCSpec("fc", 16 * 16 * 8, 10, relu=False)]
        acc = api.Accelerator.build(SPECS, target=pm.V5E, batch=8, seed=0)
        cache = ProgramCache()
        prog = acc.program
        e0 = cache.get(prog, batch=8, dtype="float32")
        e4 = cache.get(prog, batch=8, dtype="float32",
                       mesh=make_fleet_mesh(4))
        e1 = cache.get(prog, batch=8, dtype="float32",
                       mesh=make_fleet_mesh(1))
        assert e4 is not e0, "mesh must join the cache key"
        assert e1 is e0, "1-device mesh must alias the unsharded entry"
        assert e4.mesh_key is not None and e0.mesh_key is None
        try:
            cache.get(prog, batch=6, dtype="float32",
                      mesh=make_fleet_mesh(4))
        except ValueError as e:
            assert "divide" in str(e)
        else:
            raise AssertionError("non-dividing batch must be refused")
        print("sharded cache keying ok")
    """)
