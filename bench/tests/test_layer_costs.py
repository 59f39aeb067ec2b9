"""Per-layer costs of a model reference (``models/<name>.py``:
``layer_costs``), and the harness's check that they are the program's
layers (``scopes.check_costs``).

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
import pytest

from bench import scopes
from bench.models import vgg16

VGG16 = {"img": 224, "scale": 1, "n_classes": 1000}
TINY = {"img": 32, "scale": 16, "n_classes": 1000}


def _program_layers(sizes):
    """``(layer_id, kind)`` of a program that lowers the reference one
    layer for one, as the program lowers VGG16."""
    return [(i, layer[0]) for i, layer in enumerate(vgg16.layers(sizes))]


def test_layer_costs_sum_to_the_hand_count():
    costs = vgg16.layer_costs(VGG16, 1, 4)
    assert scopes.check_costs(costs, _program_layers(VGG16)) is costs
    assert len(costs) == 21
    assert sum(f for _, f, _ in costs) == vgg16.flops_per_image(VGG16)
    assert round(sum(f for _, f, _ in costs) / 1e9, 2) == 30.94
    # fc6: 25088 x 4096 weights dominate its bytes; 8 images share them
    kind, flops, nbytes = vgg16.layer_costs(VGG16, 8, 4)[18]
    assert kind == "fc" and flops == 8 * 2 * 25088 * 4096
    assert nbytes == 4 * (25088 * 4096 + 4096 + 8 * (25088 + 4096))
    # conv1_1 at int8: weights once, maps per image
    kind, _, nbytes = vgg16.layer_costs(VGG16, 8, 1)[0]
    assert kind == "conv"
    assert nbytes == 27 * 64 + 64 + 8 * 224 * 224 * (3 + 64)


def test_layer_costs_refuse_what_they_do_not_describe(monkeypatch):
    layers = vgg16.layers(TINY)
    program = _program_layers(TINY)
    costs = vgg16.layer_costs(TINY, 8, 4)
    with pytest.raises(ValueError, match="program's layers"):
        scopes.check_costs(costs, program[:-1])
    with pytest.raises(ValueError, match="program's layers"):
        scopes.check_costs(costs, [(i + 1, k) for i, k in program])
    # a layer kind, or a form of a known kind, that has no cost here
    for odd in (("eltwise", 8, 32), ("conv", 32, 3, 4, 7), ("pool", 7, 4)):
        monkeypatch.setattr(vgg16, "layers",
                            lambda sizes, odd=odd: layers[:1] + [odd])
        with pytest.raises(ValueError, match="no cost"):
            vgg16.layer_costs(TINY, 8, 4)
    monkeypatch.setattr(vgg16, "layers", lambda sizes: layers)

    # a 7x7 stem, told as a 3x3 conv tuple: its weights give it away
    init_params = vgg16.init_params

    def seven(key, sizes):
        params = init_params(key, sizes)
        w, b = params[0]
        return [(w.repeat(3, 0)[:7].repeat(3, 1)[:, :7], b)] + params[1:]
    monkeypatch.setattr(vgg16, "init_params", seven)
    with pytest.raises(ValueError, match="not 3x3"):
        vgg16.layer_costs(TINY, 8, 4)
    monkeypatch.setattr(vgg16, "init_params", init_params)
    # a stride-2 conv: the next layer reads a map it does not write
    strided = list(layers)
    _, h, c, k = strided[1]
    strided[1:] = [("conv", h, c, k)] + [
        (l[0], l[1] // 2, *l[2:]) if l[0] != "fc" else l
        for l in strided[2:]]
    monkeypatch.setattr(vgg16, "layers", lambda sizes: strided)
    with pytest.raises(ValueError, match="does not read"):
        vgg16.layer_costs(TINY, 8, 4)
