"""The work counts against the hand count at the paper's widths."""
import json

import pytest

import work
from cell import HERE


@pytest.fixture(scope="module")
def sec6():
    return json.loads((HERE / "configs" / "paper_sec6.json").read_text())[
        "setting"]


def test_train_flops_per_sample_is_the_hand_count(sec6):
    # forward 14.80 M MACs; weight gradients 14.80 M; input gradients of
    # conv2 and the dense layer 14.58 M (conv1 needs none): 88.36 MFLOP
    fwd = 784 * 9 * 32 + 784 * 288 * 64 + 12544 * 10
    dx = 784 * 288 * 64 + 12544 * 10
    assert work.train_flops_per_sample(sec6) == 2 * (2 * fwd + dx)
    assert work.train_flops_per_sample(sec6) == pytest.approx(88.36e6,
                                                             rel=1e-4)


def test_params_and_conv_work(sec6):
    assert work.n_params(sec6) == 320 + 18496 + 125450
    flops, bytes_ = work.conv_work(sec6, 1, 0)
    assert flops == 2 * (784 * 9 * 32 * 2 + 784 * 288 * 64 * 3)
    # bytes-bound on a v5e: under its ridge of 197e12 / 819e9 FLOP/B
    assert flops / bytes_ < 197e12 / 819e9


def test_least_time_names_its_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.least_time(1000, 10, peak) == (10.0, "flops")
    assert work.least_time(10, 1000, peak) == (100.0, "bytes")
