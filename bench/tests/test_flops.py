"""Model FLOPs from the configurations' shapes."""

import pytest

from bench import flops, harness


def _model(name):
    cfg = harness.load_json(harness.BENCH / "configs" / f"{name}.json")
    return cfg, harness.load_module(harness.BENCH / "configs" / f"{name}.py",
                                    name)


def test_vgg16_forward_macs():
    # published ~15.5 G multiply-adds at 224x224 include the three FC
    # layers (0.12 G); the thirteen convolutions alone are 15.35 G
    cfg, m = _model("vgg16")
    assert flops.forward_conv_macs(m.conv_layers(cfg)) == pytest.approx(
        15.35e9, rel=2e-3)


def test_resnet50_forward_macs():
    cfg, m = _model("resnet50")
    assert flops.forward_conv_macs(m.conv_layers(cfg)) == pytest.approx(
        4.1e9, rel=0.02)
    assert len(m.conv_layers(cfg)) == 1 + 16 * 3 + 4


def test_train_flops_count_three_passes_but_no_first_dx():
    layers = [dict(h_out=2, w_out=2, k=3, cin=1, cout=2),
              dict(h_out=2, w_out=2, k=1, cin=2, cout=2)]
    fwd = [2 * 4 * 9 * 1 * 2, 2 * 4 * 1 * 2 * 2]
    assert flops.train_flops_per_image(layers, (2, 3)) == \
        2 * fwd[0] + 3 * fwd[1] + 3 * 2 * 2 * 3


def test_vgg16_train_flops_per_image():
    cfg, m = _model("vgg16")
    got = flops.train_flops_per_image(m.conv_layers(cfg), m.linear(cfg))
    assert got == pytest.approx(91.9e9, rel=2e-3)
