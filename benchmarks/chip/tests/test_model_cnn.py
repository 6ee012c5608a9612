"""The CNN's own work counts (``models/cnn.py``) against the hand count at
the paper's widths."""
import json

import pytest

import run
import work
from cell import HERE

cnn = run.load_module(HERE / "models" / "cnn.py")


@pytest.fixture(scope="module")
def sec6():
    return json.loads((HERE / "configs" / "paper_sec6.json").read_text())[
        "setting"]


def test_train_flops_per_sample_is_the_hand_count(sec6):
    # forward 14.80 M MACs; weight gradients 14.80 M; input gradients of
    # conv2 and the dense layer 14.58 M (conv1 needs none): 88.36 MFLOP
    fwd = 784 * 9 * 32 + 784 * 288 * 64 + 12544 * 10
    dx = 784 * 288 * 64 + 12544 * 10
    assert cnn.train_flops_per_sample(sec6) == 2 * (2 * fwd + dx)
    assert cnn.train_flops_per_sample(sec6) == pytest.approx(88.36e6,
                                                            rel=1e-4)


def test_params_and_conv_work(sec6):
    assert work.n_params(cnn.param_shapes(sec6)) == 320 + 18496 + 125450
    flops, bytes_ = cnn.conv_work(sec6, 1, 0)
    assert flops == 2 * (784 * 9 * 32 * 2 + 784 * 288 * 64 * 3)
    # bytes-bound on a v5e: under its ridge of 197e12 / 819e9 FLOP/B
    assert flops / bytes_ < 197e12 / 819e9


def test_eval_targets_are_the_test_labels():
    assert cnn.n_eval({"test_y": [3, 1, 4, 1, 5]}) == 5
