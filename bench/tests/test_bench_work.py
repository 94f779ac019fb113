"""Operation and byte counts against hand counts, one shape per config."""

import json
import os

import pytest

from bench.work.glow import flow_kernel_work, model_flops_per_example

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs")


def _config(name):
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        return json.load(f)


def test_glow_fig1_at_256():
    model = _config("glow-fig1")
    # per flow step and position: conv1 9*6*64, conv2 64*64, conv3 9*64*12,
    # 1x1 12*12 at 128^2; then C=24 at 64^2 and C=48 at 32^2; 8 steps each
    by_hand = 2 * 8 * (128**2 * (9 * 6 * 64 + 64 * 64 + 9 * 64 * 12 + 144)
                       + 64**2 * (9 * 12 * 64 + 64 * 64 + 9 * 64 * 24 + 576)
                       + 32**2 * (9 * 24 * 64 + 64 * 64 + 9 * 64 * 48 + 2304))
    assert model_flops_per_example(model, 256) == by_hand
    assert by_hand == pytest.approx(6.279e9, rel=1e-3)
    # 11 float32 (B, M, C) tensors per flow step in training, 3 in sampling;
    # M*C is 196608, 98304 and 49152 at the three scales
    _, train_bytes = flow_kernel_work(model, 256, 8, "train")
    assert train_bytes == 4 * 11 * 8 * 8 * (196608 + 98304 + 49152)
    assert train_bytes == pytest.approx(968.9e6, rel=1e-4)
    _, sample_bytes = flow_kernel_work(model, 256, 32, "sample")
    assert sample_bytes == 4 * 3 * 32 * 8 * (196608 + 98304 + 49152)


def test_glow_cifar_at_32():
    model = _config("glow-cifar")
    by_hand = 2 * 32 * (16**2 * (9 * 6 * 512 + 512 * 512 + 9 * 512 * 12 + 144)
                        + 8**2 * (9 * 12 * 512 + 512 * 512 + 9 * 512 * 24 + 576)
                        + 4**2 * (9 * 24 * 512 + 512 * 512 + 9 * 512 * 48 + 2304))
    assert model_flops_per_example(model, 32) == by_hand
    assert by_hand == pytest.approx(8.0e9, rel=0.01)
    ops, byts = flow_kernel_work(model, 32, 64, "train")
    assert byts == 4 * 11 * 64 * 32 * (256 * 12 + 64 * 24 + 16 * 48)
    assert ops == 8 * 64 * 32 * (256 * 144 + 64 * 576 + 16 * 2304)
    # memory-bound on a v5e: bytes / 819 GB/s exceed ops / 197 TFLOP/s
    assert byts / 819e9 > ops / 197e12
