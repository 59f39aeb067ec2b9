"""Hypothesis property tests on the system's invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="optional dev dep: pip install -r requirements-dev.txt")
from hypothesis import given, settings, strategies as st

from repro.core import layouts
from repro.core.compiler import LayerPlan, compile_network
from repro.core.hybrid_conv import ConvSpec
from repro.core.isa import Instruction, Opcode, decode, decode_stream, encode_stream
from repro.core.winograd import winograd_conv2d_reference
from repro.kernels.winograd.ref import conv2d_ref
from repro.optim.compression import compress_grad, dequantize_int8

_SETTINGS = dict(max_examples=25, deadline=None)


# --------------------------------------------------------------------------
# Winograd == Spatial for arbitrary shapes (the hybrid-PE core invariant)
# --------------------------------------------------------------------------

@settings(**_SETTINGS)
@given(
    h=st.integers(4, 20), w=st.integers(4, 20),
    c=st.integers(1, 6), k=st.integers(1, 6),
    m=st.sampled_from([2, 4]), r=st.sampled_from([1, 3, 5]),
    seed=st.integers(0, 2 ** 16),
)
def test_winograd_equals_direct(h, w, c, k, m, r, seed):
    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(kx, (1, h, w, c), jnp.float32)
    g = jax.random.normal(kw, (r, r, c, k), jnp.float32) * 0.3
    y = winograd_conv2d_reference(x, g, m=m)
    yref = conv2d_ref(x, g)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yref),
                               rtol=5e-3, atol=5e-3)


# --------------------------------------------------------------------------
# ISA encode/decode round-trip is bit-exact
# --------------------------------------------------------------------------

@settings(**_SETTINGS)
@given(
    opcode=st.sampled_from(list(Opcode)),
    wino=st.booleans(), ws=st.booleans(), lw=st.booleans(),
    relu=st.booleans(),
    m=st.integers(0, 255), layer=st.integers(0, 2 ** 16 - 1),
    pw=st.integers(0, 15), ps=st.integers(0, 15),
    buff=st.integers(0, 2 ** 32 - 1), dram=st.integers(0, 2 ** 32 - 1),
    size=st.integers(0, 2 ** 32 - 1),
)
def test_isa_roundtrip(opcode, wino, ws, lw, relu, m, layer, pw, ps,
                       buff, dram, size):
    """Bit-exact across all 9 opcodes. POOL reuses the m_tile byte for
    window/stride, so the pool fields only exist on POOL instructions and
    m_tile only on the others."""
    is_pool = opcode == Opcode.POOL
    ins = Instruction(opcode, wino_flag=wino, dataflow_ws=ws,
                      layout_out_wino=lw, relu_flag=relu,
                      m_tile=0 if is_pool else m,
                      pool_window=pw if is_pool else 0,
                      pool_stride=ps if is_pool else 0,
                      layer_id=layer, buff_base=buff, dram_base=dram,
                      size=size)
    assert decode(ins.encode()) == ins


@settings(**_SETTINGS)
@given(
    d_in=st.integers(0, 2 ** 16 - 1), d_out=st.integers(0, 2 ** 16 - 1),
    relu=st.booleans(), layer=st.integers(0, 2 ** 16 - 1),
)
def test_isa_fc_dims_roundtrip(d_in, d_out, relu, layer):
    """FC packs (d_in, d_out) into word3; pack/unpack and the 128-bit
    round-trip both preserve them exactly."""
    from repro.core.isa import pack_fc_dims, unpack_fc_dims
    assert unpack_fc_dims(pack_fc_dims(d_in, d_out)) == (d_in, d_out)
    ins = Instruction(Opcode.FC, relu_flag=relu, layer_id=layer,
                      size=pack_fc_dims(d_in, d_out))
    back = decode(ins.encode())
    assert back == ins
    assert unpack_fc_dims(back.size) == (d_in, d_out)


@settings(**_SETTINGS)
@given(
    r=st.integers(1, 255), s=st.integers(1, 255), stride=st.integers(1, 255),
    relu=st.booleans(), layer=st.integers(0, 2 ** 16 - 1),
)
def test_isa_dw_geom_roundtrip(r, s, stride, relu, layer):
    """DEPTHWISE_CONV packs (r, s, stride) into word3; pack/unpack and the
    128-bit round-trip both preserve them exactly."""
    from repro.core.isa import pack_dw_geom, unpack_dw_geom
    assert unpack_dw_geom(pack_dw_geom(r, s, stride)) == (r, s, stride)
    ins = Instruction(Opcode.DEPTHWISE_CONV, relu_flag=relu, layer_id=layer,
                      size=pack_dw_geom(r, s, stride))
    back = decode(ins.encode())
    assert back == ins
    assert unpack_dw_geom(back.size) == (r, s, stride)


@pytest.mark.parametrize("geom", [(0, 3, 1), (3, 0, 1), (3, 3, 0)])
def test_isa_dw_geom_rejects_zero(geom):
    """A zero kernel extent or stride is no depthwise geometry: packing it
    raises instead of encoding an instruction no executor can run."""
    from repro.core.isa import pack_dw_geom
    with pytest.raises(ValueError):
        pack_dw_geom(*geom)


@settings(**_SETTINGS)
@given(
    pslot=st.booleans(), sslot=st.booleans(), relu=st.booleans(),
    skip_addr=st.integers(0, 2 ** 32 - 1), n_el=st.integers(0, 2 ** 32 - 1),
    layer=st.integers(0, 2 ** 16 - 1),
)
def test_isa_eltwise_two_source_roundtrip(pslot, sslot, relu, skip_addr,
                                          n_el, layer):
    """ELTWISE_ADD is the only two-DRAM-operand word: BUFF_BASE bits [0]/[1]
    name the primary/skip input slots and word2 carries the SKIP operand's
    DRAM base — all of it survives the 128-bit round-trip bit-exactly."""
    buff = (int(pslot) << 0) | (int(sslot) << 1)
    ins = Instruction(Opcode.ELTWISE_ADD, relu_flag=relu, buff_base=buff,
                      dram_base=skip_addr, size=n_el, layer_id=layer)
    back = decode(ins.encode())
    assert back == ins
    assert (back.buff_base & 1, (back.buff_base >> 1) & 1) \
        == (int(pslot), int(sslot))
    assert back.dram_base == skip_addr and back.size == n_el


@settings(**_SETTINGS)
@given(n=st.integers(0, 12), seed=st.integers(0, 999))
def test_isa_stream_roundtrip(n, seed):
    rng = np.random.default_rng(seed)
    instrs = []
    for _ in range(n):
        op = Opcode(int(rng.integers(1, 10)))
        is_pool = op == Opcode.POOL
        instrs.append(
            Instruction(op,
                        wino_flag=bool(rng.integers(2)),
                        m_tile=0 if is_pool else int(rng.integers(0, 8)),
                        pool_window=int(rng.integers(0, 16)) if is_pool else 0,
                        pool_stride=int(rng.integers(0, 16)) if is_pool else 0,
                        layer_id=int(rng.integers(0, 100)),
                        buff_base=int(rng.integers(0, 2 ** 32)),
                        dram_base=int(rng.integers(0, 2 ** 32)),
                        size=int(rng.integers(0, 2 ** 32))))
    assert decode_stream(encode_stream(instrs)) == instrs


# --------------------------------------------------------------------------
# Layout transforms invert (Sec. 4.3)
# --------------------------------------------------------------------------

@settings(**_SETTINGS)
@given(h=st.integers(1, 6), w=st.integers(1, 6), c=st.integers(1, 5),
       m=st.sampled_from([2, 4]), seed=st.integers(0, 99))
def test_layout_roundtrip(h, w, c, m, seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (2, h * m, w * m, c))
    tiled = layouts.spat_to_wino(x, m)
    assert tiled.shape == (2, h, w, m, m, c)
    np.testing.assert_array_equal(np.asarray(layouts.wino_to_spat(tiled)),
                                  np.asarray(x))


@settings(**_SETTINGS)
@given(h=st.integers(3, 17), w=st.integers(3, 17), m=st.sampled_from([2, 4]),
       seed=st.integers(0, 99))
def test_save_load_roundtrip_nondivisible(h, w, m, seed):
    """SAVE pads to tile multiples; LOAD's view crops exactly."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (1, h, w, 3))
    stored = layouts.save_transform(x, layouts.WINO, m)
    back = layouts.load_view(stored, layouts.WINO, hw=(h, w))
    np.testing.assert_array_equal(np.asarray(back), np.asarray(x))


# --------------------------------------------------------------------------
# Compiler invariants
# --------------------------------------------------------------------------

@settings(**_SETTINGS)
@given(
    n_layers=st.integers(1, 4),
    gk=st.integers(1, 3), gh=st.integers(1, 3),
    modes=st.lists(st.sampled_from(["spat", "wino"]), min_size=4, max_size=4),
    flows=st.lists(st.sampled_from(["is", "ws"]), min_size=4, max_size=4),
)
def test_compiler_group_coverage(n_layers, gk, gh, modes, flows):
    """Every layer's COMP instructions cover all (row, k) group pairs."""
    specs = [ConvSpec(f"c{i}", 16, 16, 4, 8) for i in range(n_layers)]
    plans = [LayerPlan(modes[i], flows[i], m=4, g_k=gk, g_h=gh)
             for i in range(n_layers)]
    prog = compile_network(specs, plans)
    for lid, cl in enumerate(prog.layers):
        comps = set()
        for ins in prog.instructions:
            if ins.layer_id == lid and ins.opcode == Opcode.COMP:
                comps.add((ins.size & 0xFFF, (ins.size >> 12) & 0xFFF))
        expect = {(i, j) for i in range(len(cl.row_groups))
                  for j in range(len(cl.k_groups))}
        assert comps == expect


# --------------------------------------------------------------------------
# Gradient compression: error feedback telescopes (convergence invariant)
# --------------------------------------------------------------------------

@settings(**_SETTINGS)
@given(seed=st.integers(0, 999), steps=st.integers(1, 8))
def test_error_feedback_telescopes(seed, steps):
    """sum(decoded_t) + err_T == sum(g_t): no information is lost."""
    rng = np.random.default_rng(seed)
    err = jnp.zeros((32,), jnp.float32)
    total_g = jnp.zeros((32,), jnp.float32)
    total_dec = jnp.zeros((32,), jnp.float32)
    for t in range(steps):
        g = jnp.asarray(rng.standard_normal(32), jnp.float32)
        q, scale, err = compress_grad(g, err)
        total_g = total_g + g
        total_dec = total_dec + dequantize_int8(q, scale)
    np.testing.assert_allclose(np.asarray(total_dec + err),
                               np.asarray(total_g), rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------------------
# Lowering optimizer: opt_level=1 == opt_level=0 == strict interpreter on
# randomized block structures; non-uniform RELU streams must not fuse
# --------------------------------------------------------------------------

@settings(max_examples=12, deadline=None)
@given(
    h=st.sampled_from([6, 8, 10, 12]), c=st.integers(1, 4),
    k=st.integers(2, 10),
    g_h=st.integers(1, 4), g_k=st.integers(1, 4),
    mode=st.sampled_from(["spat", "wino"]),
    dataflow=st.sampled_from(["is", "ws"]),
    flip=st.booleans(), seed=st.integers(0, 2 ** 16),
)
def test_opt_levels_agree_on_random_block_structures(
        h, c, k, g_h, g_k, mode, dataflow, flip, seed):
    """For randomized geometry/grouping (and randomly non-uniform RELU
    streams via one flipped COMP bit), the fused/stacked lowering equals
    the literal per-block reference and the strict interpreter; a stream
    with mixed RELU bits never reports 'fused' for the touched layer."""
    from conftest import flip_first_comp
    from repro.core.executor import (
        analyze_program,
        lower_program,
        to_dram_params,
        validate_schedule,
    )
    from repro.core.runtime import run_program

    spec = ConvSpec("c1", h, h, c, k, relu=True)
    prog = compile_network([spec], [LayerPlan(mode, dataflow, 2, g_k, g_h)])
    if flip:
        prog = flip_first_comp(prog)
    key = jax.random.PRNGKey(seed)
    kw, kb, kx = jax.random.split(key, 3)
    params = [(jax.random.normal(kw, (3, 3, c, k)) * 0.2,
               jax.random.normal(kb, (k,)) * 0.1)]
    x = jax.random.normal(kx, (1, h, h, c))
    verdict = analyze_program(prog)[0]
    n_blocks = (len(prog.layers[0].row_groups)
                * len(prog.layers[0].k_groups))
    if flip and n_blocks > 1:
        assert verdict.kind != "fused"
    else:
        assert verdict.kind == "fused"
    dram = to_dram_params(prog, params)
    validate_schedule(prog)
    y1 = lower_program(prog, opt_level=1)(dram, x)
    y0 = lower_program(prog, opt_level=0)(dram, x)
    ys = run_program(prog, params, x, strict=True)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(ys),
                               rtol=1e-4, atol=1e-4)
