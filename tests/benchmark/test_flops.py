"""The FLOP and byte functions against hand counts."""

import pytest


@pytest.fixture(scope="module")
def resnet(real_spec):
    config = real_spec.config("resnet56_cifar10")
    return real_spec.module("flops", config["flops"]), config


def test_one_conv_by_hand(resnet):
    f, _ = resnet
    # 3x3 conv, 16 -> 16 channels on 32x32: 32*32*9*16*16 multiply-adds
    assert f.conv_fwd_flops(32, 32, 3, 16, 16) == 2 * 2_359_296


def test_resnet56_layers_and_forward_count(resnet):
    f, config = resnet
    layers = f.conv_layers(config)
    assert len(layers) == 1 + 3 * 9 * 2 + 2        # stem, blocks, projections
    fwd = sum(f.conv_fwd_flops(*l[:5]) for l in layers)
    # the well-known 125.7 M multiply-adds of ResNet-56's convolutions
    assert fwd == 2 * 125_747_200
    flops, nbytes = f.conv_train_cost_per_sample(config)
    stem = f.conv_fwd_flops(32, 32, 3, 3, 16)
    assert flops == 3 * fwd - stem                  # no input grad for the stem
    assert f.train_flops_per_sample(config) == flops + 3 * 2 * 64 * 10
    assert nbytes > 0


def test_conv_bytes_of_one_layer_by_hand(resnet):
    f, config = resnet
    tiny = {**config, "model": {**config["model"], "blocks_per_stage": 0,
                                "widths": [16]}}
    # only the stem remains: 2 passes, bf16, batch 64
    _flops, nbytes = f.conv_train_cost_per_sample(tiny)
    assert nbytes == 2 * 2 * (32 * 32 * 3 + 32 * 32 * 16 + 3 * 3 * 3 * 16 / 64)


def test_lr_layer_by_hand(tiny_spec):
    config = {"data": {"input_dim": 10_000, "classes": 500}}
    f = tiny_spec.module("flops", "tiny_lr")
    # forward and weight gradient of a 10,000 x 500 dense layer
    assert f.train_flops_per_sample(config) == 2 * 2 * 10_000 * 500
