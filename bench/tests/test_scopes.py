"""The per-layer reduction (``scopes.py``): by hand, and on a recorded v5e
trace whose ops carry their layer scopes and whose host spans include the
session's own.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
import contextlib
import json
import types
from pathlib import Path

import pytest

from bench import scopes
from bench import trace as tracing
from bench.models import vgg16
from repro.serving.telemetry import SpanRecorder

DATA = Path(__file__).resolve().parent / "data"
VGG16 = {"img": 224, "scale": 1, "n_classes": 1000}
TINY = {"img": 32, "scale": 16, "n_classes": 1000}


def _program_layers(sizes):
    """``(layer_id, kind)`` of a program that lowers the reference one
    layer for one, as the program lowers VGG16."""
    return [(i, layer[0]) for i, layer in enumerate(vgg16.layers(sizes))]


def _events():
    """One device; window [100, 200) ns. A ``while`` (L0) encloses a body op
    of its layer and one with no scope of its own (as XLA's loop bodies
    have); a layout copy between layers carries no scope."""
    return {
        "devices": {"0": {
            "ops": [["while.1", 100, 40], ["fusion.2", 105, 10],
                    ["fusion.3", 120, 10], ["dot.4", 150, 32],
                    ["copy.5", 185, 2], ["dot.4", 190, 20]],
            "op_scopes": ["L0:conv.spat", "L0:conv.spat", None,
                          "L2:fc", None, "L2:fc"],
            "modules": [["jit_traced", 100, 100]]}},
        "host": [["bench.window", 100, 100], ["bench.wait", 100, 100],
                 ["session.sync", 140, 60]],
    }


def test_reduce_scopes_by_hand():
    r = scopes.reduce_scopes(_events(), [0])
    # L0: the while's [100,140) holds its body: 40, not 60; L2: 32 + 10
    assert r["scope_s"] == {"L0:conv.spat": pytest.approx(40e-9),
                            "L2:fc": pytest.approx(42e-9)}
    # 82 of the 84 ns of op time carry a scope
    assert r["scoped_share"] == pytest.approx(82 / 84)
    assert r["module_s"] == pytest.approx(100e-9)
    # the same sums as trace.reduce, each name labelled with its scope
    base = tracing.reduce(_events(), [0])
    assert [t for _, t in r["device_ops"]] == [t for _, t in
                                               base["device_ops"]]
    labels = [name for name, _ in r["device_ops"]]
    assert labels[:2] == ["dot.4 [L2:fc]", "while.1 [L0:conv.spat]"]
    # the while's body op takes the while's scope; the copy stays bare
    assert "fusion.3 [L0:conv.spat]" in labels and "copy.5" in labels
    # the gap [140,150) falls in the session's span, the innermost open
    assert ["session.sync", pytest.approx(10e-9)] in base["idle_gaps"]


def test_reduce_scopes_reads_none_when_scopes_are_missing():
    ev = _events()
    ev["devices"]["0"]["op_scopes"] = [None] * 6
    assert scopes.reduce_scopes(ev, [0]) is None
    del ev["devices"]["0"]["op_scopes"]
    assert scopes.reduce_scopes(ev, [0]) is None
    ev = _events()
    ev["devices"]["0"]["op_scopes"][3] = None     # 50 of 84 ns scoped
    assert scopes.reduce_scopes(ev, [0]) is None
    ev = _events()
    ev["devices"]["0"]["op_scopes"][0] = None     # the while loses its
    assert scopes.reduce_scopes(ev, [0]) is None


def test_scopes_from_the_compiled_text():
    text = ('  %fusion.7 = f32[8]{0} fusion(%p), kind=kLoop, '
            'metadata={op_name="jit(traced)/L3:pool/reduce_window" '
            'source_file="x.py" source_line=1}\n'
            '  %copy = bf16[8]{0} copy(%x.1), metadata={op_name="x"}\n'
            '  %fusion.8 = f32[8]{0} fusion(%copy), kind=kLoop, '
            'metadata={op_type="add" op_name="jit(traced)/L1:fc/add"}\n'
            '  ROOT %copy.2 = f32[8]{0} copy(%fusion.7)\n')
    # the input's layout copy is the first layer's (here L1) load
    assert scopes.hlo_scopes(text) == {"fusion.7": "L3:pool",
                                       "fusion.8": "L1:fc", "copy": "L1:fc"}


# as a TPU v5e compile of the VGG16 executor prints them: fc7's weights
# prefetched across programs, conv1_1's within the program, one copy read
# through a tuple by a while
PREFETCH_HLO = '''
  %copy-start = (f32[64,64]{1,0:S(1)}, f32[64,64]{1,0}, u32[]{:S(2)}) copy-start(%params_14__0_.1), cross_program_prefetch_index=0
  %copy-start.5 = (f32[3,3,3,64]{3,2,1,0:S(1)}, f32[3,3,3,64]{3,2,1,0}, u32[]{:S(2)}) copy-start(%params_0__0_.1)
  %copy-done.5 = f32[3,3,3,64]{3,2,1,0:S(1)} copy-done(%copy-start.5)
  %fusion.12 = bf16[8,8,8,64]{3,0,2,1} fusion(%copy.25, %copy-done.5), kind=kOutput, calls=%fused_computation.18, metadata={op_name="jit(traced)/L0:conv.spat/conv_general_dilated" stack_frame_id=10}
  %copy-start.7 = (bf16[6,6]{1,0:S(1)}, bf16[6,6]{1,0}, u32[]{:S(2)}) copy-start(%constant.114)
  %copy-done.7 = bf16[6,6]{1,0:S(1)} copy-done(%copy-start.7)
  %tuple.3 = (s32[], bf16[6,6]{1,0}) tuple(%constant.1, %copy-done.7)
  %while.1 = (s32[], bf16[6,6]{1,0}) while(%tuple.3), condition=%cond, body=%body, metadata={op_name="jit(traced)/L1:conv.wino/while"}
  %copy-done = f32[64,64]{1,0:S(1)} copy-done(%copy-start)
  ROOT %fusion.49 = bf16[8,64]{1,0} fusion(%fusion.50, %copy-done), kind=kOutput, calls=%fused_computation.67, metadata={op_name="jit(traced)/L19:fc/dot_general" stack_frame_id=45}
'''


def test_async_copies_take_the_scope_of_their_reader():
    names = scopes.hlo_scopes(PREFETCH_HLO)
    assert names["copy-start"] == names["copy-done"] == "L19:fc"
    assert names["copy-start.5"] == names["copy-done.5"] == "L0:conv.spat"
    # read through a tuple by the while that holds its layer's scope
    assert names["copy-start.7"] == names["copy-done.7"] == "L1:conv.wino"
    assert "tuple.3" not in names
    # only the weight prefetched across programs moves outside its layer
    assert scopes.prefetched_scopes(PREFETCH_HLO, names) == {"L19:fc"}


def test_capture_joins_the_sessions_spans_and_scopes():
    rec = SpanRecorder()

    @contextlib.contextmanager
    def record_spans():
        yield rec
    session = types.SimpleNamespace(record_spans=record_spans)
    with scopes.capture({"fusion.1": "L0:conv.spat"}, session) as events:
        rec.add("session.stage", 1000, 7)
        rec.batches[3] = (7, 8)
    names = [h[0] for h in events["host"]]
    if names:       # the CPU profile carries its start time
        assert names == [tracing.WINDOW_SPAN, "session.stage"]
        assert events["host"][1][2] == 1000
    assert events["batches"] == {"3": [7, 8]}
    for dev in events["devices"].values():
        assert len(dev["op_scopes"]) == len(dev["ops"])
    # spans off: nothing recorded, the benchmark's own spans alone
    with scopes.capture({}) as events:
        events["host"].append(["bench.wait", 0, 1])
    assert "batches" not in events
    assert all(not h[0].startswith("session.") for h in events["host"])


def test_layer_costs_sum_to_the_hand_count():
    layers = _program_layers(VGG16)
    costs = scopes.layer_costs(vgg16, VGG16, 1, 4, layers)
    assert len(costs) == 21
    assert sum(f for _, f, _ in costs) == vgg16.flops_per_image(VGG16)
    assert round(sum(f for _, f, _ in costs) / 1e9, 2) == 30.94
    # fc6: 25088 x 4096 weights dominate its bytes; 8 images share them
    kind, flops, nbytes = scopes.layer_costs(vgg16, VGG16, 8, 4, layers)[18]
    assert kind == "fc" and flops == 8 * 2 * 25088 * 4096
    assert nbytes == 4 * (25088 * 4096 + 4096 + 8 * (25088 + 4096))
    # conv1_1 at int8: weights once, maps per image
    kind, _, nbytes = scopes.layer_costs(vgg16, VGG16, 8, 1, layers)[0]
    assert kind == "conv"
    assert nbytes == 27 * 64 + 64 + 8 * 224 * 224 * (3 + 64)


def _model(layers, init_params=vgg16.init_params):
    return types.SimpleNamespace(layers=lambda sizes: layers,
                                 init_params=init_params)


def test_layer_costs_refuse_what_they_do_not_describe():
    layers = vgg16.layers(TINY)
    program = _program_layers(TINY)
    with pytest.raises(ValueError, match="program's layers"):
        scopes.layer_costs(vgg16, TINY, 8, 4, program[:-1])
    with pytest.raises(ValueError, match="program's layers"):
        scopes.layer_costs(vgg16, TINY, 8, 4,
                           [(i + 1, k) for i, k in program])
    # a layer kind, or a form of a known kind, that has no cost here
    for odd in (("eltwise", 8, 32), ("conv", 32, 3, 4, 7), ("pool", 7, 4)):
        model = _model(layers[:1] + [odd])
        with pytest.raises(ValueError, match="no cost"):
            scopes.layer_costs(model, TINY, 8, 4, [(0, "conv"), (1, odd[0])])

    # a 7x7 stem, told as a 3x3 conv tuple: its weights give it away
    def seven(key, sizes):
        params = vgg16.init_params(key, sizes)
        w, b = params[0]
        return [(w.repeat(3, 0)[:7].repeat(3, 1)[:, :7], b)] + params[1:]
    with pytest.raises(ValueError, match="not 3x3"):
        scopes.layer_costs(_model(layers, seven), TINY, 8, 4, program)
    # a stride-2 conv: the next layer reads a map it does not write
    strided = list(layers)
    _, h, c, k = strided[1]
    strided[1:] = [("conv", h, c, k)] + [
        (l[0], l[1] // 2, *l[2:]) if l[0] != "fc" else l
        for l in strided[2:]]
    with pytest.raises(ValueError, match="does not read"):
        scopes.layer_costs(_model(strided), TINY, 8, 4, program)


def test_layer_rows_roofline():
    costs = [("conv", 2e9, 1e6), ("fc", 1e6, 8e8)]
    rows = scopes.layer_rows({"L0:conv.spat": 0.04, "L1:fc": 0.02}, 2,
                             costs, 1e12, 1e12)
    # L0: 20 ms per call, 2 ms of compute at the peak -> 10%, compute bound
    assert rows[0]["ms_per_call"] == pytest.approx(20.0)
    assert rows[0]["roofline"] == pytest.approx(10.0)
    assert rows[0]["bound"] == "compute"
    # L1: 10 ms per call, 0.8 ms of bytes -> 8%, memory bound
    assert rows[1]["roofline"] == pytest.approx(8.0)
    assert rows[1]["bound"] == "memory"
    assert rows[0]["unmeasured"] is rows[1]["unmeasured"] is None
    # prefetched weights, or a share over 100%: not measured, and why
    rows = scopes.layer_rows({"L0:conv.spat": 0.001, "L1:fc": 0.02}, 2,
                             costs, 1e12, 1e12, prefetched={"L1:fc"})
    assert [r["roofline"] for r in rows] == [None, None]
    assert rows[0]["unmeasured"] == "work done in another scope's ops"
    assert rows[1]["unmeasured"] == "weights prefetched across programs"


@pytest.fixture(scope="module")
def recorded():
    ev = json.loads((DATA / "trace_v5e_scoped.json").read_text())
    scopes.attach_scopes(ev, scopes.hlo_scopes(ev["hlo"]))
    dev = max(ev["devices"], key=lambda k: len(ev["devices"][k]["ops"]))
    return ev, [int(dev)]


def test_recorded_scopes_partition_the_module_time(recorded):
    ev, ids = recorded
    r = scopes.reduce_scopes(ev, ids)
    assert r is not None and r["scoped_share"] >= scopes.MIN_SCOPED
    # each scope names a layer of the tiny VGG16 by its kind; a fusion
    # carries its root's scope, so a small conv fused into the next layer's
    # fusion owns no op of its own
    kinds = [k for k, *_ in vgg16.layers(
        {"img": 32, "scale": 16, "n_classes": 1000})]
    for scope in r["scope_s"]:
        i, kind = int(scope[1:scope.index(":")]), scope.split(":")[1]
        assert kind.split(".")[0] == kinds[i], scope
    assert "L0:conv.spat" in r["scope_s"] and len(r["scope_s"]) >= 15
    # layers run one after another: their unions tile the scoped op time,
    # which lies inside the module time
    total = sum(r["scope_s"].values())
    base = tracing.reduce(ev, ids)
    assert total <= base["busy_s"] * 1.0001
    assert total >= scopes.MIN_SCOPED * base["busy_s"]
    assert base["busy_s"] <= r["module_s"] * 1.0001


def test_recorded_device_ops_sums_are_the_old_reduction(recorded):
    ev, ids = recorded
    old = tracing.reduce(ev, ids)["device_ops"]
    new = scopes.reduce_scopes(ev, ids)["device_ops"]
    assert [t for _, t in new] == [t for _, t in old]
    for (label, _), (name, _) in zip(new, old):
        assert label.startswith(name + " [L") and label.endswith("]")


def test_recorded_gaps_take_the_session_span_names(recorded):
    ev, ids = recorded
    names = {n for n, *_ in ev["host"]}
    assert {"session.stage", "session.assemble", "session.launch",
            "session.sync", "session.deliver"} <= names
    gaps = tracing.reduce(ev, ids)["idle_gaps"]
    # a gap whose middle falls inside a session span takes the session's
    # name, the innermost open span
    spans = [e for e in ev["host"] if e[0] != tracing.WINDOW_SPAN]
    assert any(name.startswith("session.") for name, _ in gaps)
    for name, _ in gaps:
        assert name.startswith(("bench.", "session.")) \
            or name == "no bench span"
    assert spans and ev["calls"] > 0


def test_recorded_copies_are_scoped_and_rooflines_bounded(recorded):
    ev, ids = recorded
    dev = ev["devices"][str(ids[0])]
    copies = [(n, s) for (n, _, _), s in zip(dev["ops"], dev["op_scopes"])
              if n.startswith(("copy-start", "copy-done"))]
    assert copies and all(s is not None for _, s in copies)
    r = scopes.reduce_scopes(ev, ids)
    costs = scopes.layer_costs(vgg16, TINY, round(ev["rows"] / ev["calls"]),
                               4, _program_layers(TINY))
    names = scopes.hlo_scopes(ev["hlo"])
    rows = scopes.layer_rows(r["scope_s"], ev["calls"], costs, 197e12,
                             819e9, scopes.prefetched_scopes(ev["hlo"],
                                                             names))
    for row in rows:
        assert (row["roofline"] is None) == (row["unmeasured"] is not None)
        assert row["roofline"] is None or 0 < row["roofline"] <= 100.0
    # the tiny model's fc8 weights come across programs; fc6 streams its
    # own, inside its scope and under its roofline
    fc = {row["scope"]: row for row in rows if row["scope"].endswith(":fc")}
    assert fc["L20:fc"]["unmeasured"] == "weights prefetched across programs"
    assert 0 < fc["L18:fc"]["roofline"] <= 100.0
