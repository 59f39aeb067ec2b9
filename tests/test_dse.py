"""DSE reproduces the paper's configurations and respects constraints."""
import dataclasses

import pytest

from repro.core import perf_model as pm
from repro.core.dse import (
    enumerate_fpga_candidates, run_fpga_dse, run_tpu_dse,
)
from repro.core.hybrid_conv import ConvSpec
from repro.models.vgg import conv_specs


def test_vu9p_reproduces_paper_config():
    """Paper Sec 6.1: VU9P -> PI=4, PO=4, PT=6, NI=6, all-Winograd VGG16."""
    r = run_fpga_dse(pm.VU9P, conv_specs())
    assert (r.hw.pi, r.hw.po, r.hw.pt, r.hw.ni) == (4, 4, 6, 6)
    assert all(p.mode == "wino" for p in r.plans)


def test_vu9p_gops_matches_table4():
    """Paper Table 4: 3375.7 GOPS on VU9P. Model within 5%."""
    specs = conv_specs()
    r = run_fpga_dse(pm.VU9P, specs)
    gops = sum(2 * s.macs for s in specs) / 1e9 / r.total_latency
    assert abs(gops - 3375.7) / 3375.7 < 0.05


def test_pynq_reproduces_paper_config():
    """Paper Sec 6.1: PYNQ-Z1 -> PI=4, PO=4, PT=4, one instance."""
    r = run_fpga_dse(pm.PYNQ_Z1, conv_specs())
    assert (r.hw.pi, r.hw.po, r.hw.pt, r.hw.ni) == (4, 4, 4, 1)


def test_pynq_gops_near_table4():
    """Paper Table 4: 83.3 GOPS on PYNQ-Z1 (within 10%)."""
    specs = conv_specs()
    r = run_fpga_dse(pm.PYNQ_Z1, specs)
    gops = sum(2 * s.macs for s in specs) / 1e9 / r.total_latency
    assert abs(gops - 83.3) / 83.3 < 0.10


def test_candidates_respect_resources():
    for t in (pm.VU9P, pm.PYNQ_Z1):
        for c in enumerate_fpga_candidates(t):
            assert pm.fpga_fits(t, c.pi, c.po, c.pt, c.m, c.ni)
            assert c.pi >= c.po >= 1 and c.pt in (4, 6)


def test_candidates_deduped():
    """Invariant: the candidate list is duplicate-free (the DSE's
    ``candidates_searched`` count and argmin scan rely on it) — including
    on small devices where growth stalls immediately."""
    small = dataclasses.replace(pm.PYNQ_Z1, name="small", luts=8000,
                                dsps=60, bram_18k=40)
    for t in (pm.VU9P, pm.PYNQ_Z1, small):
        cands = enumerate_fpga_candidates(t)
        assert len(cands) == len(set(cands)), t.name


@pytest.mark.slow
def test_fpga_dse_end_to_end_full_network():
    """The FPGA DSE path end-to-end over the full reduced VGG16 spec chain:
    its plans compile to ONE Program, the cached executor agrees bitwise
    with the per-instruction interpreter, and the network function matches
    the TPU-planned Program to float-associativity tolerance (per-layer
    modes may legitimately differ between the two DSE verdicts)."""
    import jax.numpy as jnp
    import numpy as np

    from repro import api
    from repro.models import vgg

    specs = vgg.network_specs(img=32, scale=16, n_classes=10)
    r_fpga = run_fpga_dse(pm.VU9P, specs)
    assert len(r_fpga.plans) == len(specs)
    params = api.random_params(specs, seed=0)
    acc_f = api.Accelerator.build(specs, target=pm.VU9P, batch=2,
                                  params=params)
    x = jnp.asarray(np.random.default_rng(2).standard_normal(
        (2, 32, 32, 3)), jnp.float32)
    y_f = np.asarray(acc_f(x))
    assert y_f.shape == (2, 10)
    np.testing.assert_array_equal(y_f, np.asarray(acc_f.strict_request()(x)))
    acc_t = api.Accelerator.build(specs, target=pm.V5E, batch=2,
                                  params=params)
    np.testing.assert_allclose(y_f, np.asarray(acc_t(x)),
                               atol=5e-3, rtol=1e-3)


def test_bandwidth_starved_prefers_spatial():
    """Paper Sec 6.2: when memory-bound, Spatial outperforms Winograd."""
    starved = dataclasses.replace(pm.PYNQ_Z1, bw=0.05e9)
    r = run_fpga_dse(starved, conv_specs())
    n_spat = sum(p.mode == "spat" for p in r.plans)
    assert n_spat > len(r.plans) // 2


def test_wino_stride_ineligible():
    spec = ConvSpec("s2", 16, 16, 4, 8, stride=2)
    r = run_fpga_dse(pm.VU9P, [spec])
    assert r.plans[0].mode == "spat"


def test_wino_kernel_ineligible_1x1_projection():
    """Regression: ``wino_eligible`` used to ignore its ``m`` argument AND
    the kernel size (a vacuous ``r >= 1`` check), so the DSE would plan
    ``wino`` for a ResNet 1x1 projection conv — whose F(m, 3) transform
    does not exist. A 1x1 (or 5x5) conv must plan ``spat`` on every target,
    and ``wino_eligible`` must reject unsupported tile sizes."""
    proj = ConvSpec("proj", 16, 16, 8, 16, r=1, s=1, stride=2, relu=False)
    five = ConvSpec("k5", 16, 16, 4, 8, r=5, s=5)
    for spec in (proj, five):
        assert not spec.wino_eligible(2) and not spec.wino_eligible(4)
        for target in (pm.VU9P, pm.PYNQ_Z1):
            r = run_fpga_dse(target, [spec])
            assert r.plans[0].mode == "spat", (spec.name, target.name)
        rt = run_tpu_dse([spec], batch=2)
        assert rt.plans[0].mode == "spat", spec.name
    # m outside the implemented transform set {2, 4} is ineligible even for
    # the canonical 3x3 stride-1 layer
    ok = ConvSpec("c3", 16, 16, 4, 8)
    assert ok.wino_eligible(2) and ok.wino_eligible(4)
    assert not ok.wino_eligible(3) and not ok.wino_eligible(6)


def test_dse_plans_residual_specs():
    """EltwiseSpec/DepthwiseSpec ride through both DSE paths: NO_PLAN rows,
    nonzero latency contribution (candidates rank on the FULL network)."""
    from repro.core.hybrid_conv import DepthwiseSpec, EltwiseSpec
    specs = [ConvSpec("c1", 16, 16, 3, 8),
             EltwiseSpec("e1", 16, 16, 8, skip_from=-1),
             DepthwiseSpec("d1", 16, 16, 8)]
    for run in (lambda s: run_fpga_dse(pm.VU9P, s),
                lambda s: run_tpu_dse(s, batch=2)):
        r = run(specs)
        assert len(r.plans) == 3
        assert r.plans[1].mode != "wino" and r.plans[2].mode != "wino"
        assert all(lat > 0 for lat in r.layer_latencies)
        conv_only = run([specs[0]])
        assert r.total_latency > conv_only.total_latency


def test_tpu_dse_vmem_constraint():
    r = run_tpu_dse(conv_specs(), batch=8)
    from repro.core.dse import enumerate_tpu_candidates
    cands = enumerate_tpu_candidates()
    assert r.hw in cands
    assert r.total_latency > 0
    # VMEM working-set bound (Eq. 4 analog) holds for the winner
    working = 4 * 2 * (r.hw.bm * r.hw.bk + r.hw.bk * r.hw.bn
                       + r.hw.bm * r.hw.bn)
    assert working <= pm.V5E.vmem_bytes // 2


@pytest.mark.parametrize("kind,known", [("TPU v5 lite", True),
                                        ("TPU v4", False), ("cpu", False)])
def test_tpu_target_for_device_kind(kind, known):
    """The peaks table is keyed by device_kind: a v5e maps to V5E, and any
    kind the table does not list is an error, not a default."""
    import types
    dev = types.SimpleNamespace(platform="tpu", device_kind=kind)
    if known:
        assert pm.tpu_target_for(dev) is pm.V5E
    else:
        with pytest.raises(ValueError, match="no peaks known"):
            pm.tpu_target_for(dev)


def test_estimated_latency_monotone_in_bandwidth():
    specs = conv_specs()
    lats = []
    for bw in (5e9, 20e9, 80e9):
        t = dataclasses.replace(pm.VU9P, bw=bw)
        lats.append(run_fpga_dse(t, specs).total_latency)
    assert lats[0] >= lats[1] >= lats[2]
