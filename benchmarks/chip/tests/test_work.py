"""The model-free work counts against the hand count."""
import pytest

import work


def test_least_time_names_its_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.least_time(1000, 10, peak) == (10.0, "flops")
    assert work.least_time(10, 1000, peak) == (100.0, "bytes")


def test_n_params_sums_the_leaf_sizes():
    assert work.n_params({"w": (3, 4, 5), "b": (5,), "s": ()}) == 66


def test_aggregate_work_of_a_sec6_round():
    # 25 slots x K=2 edge rounds + 5 edges mixed, 2 x 5 edge models + 1
    # global written; 144,266 float32 parameters; bytes-bound on a v5e
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    flops, bytes_ = work.aggregate_work(144266, 55, 11)
    assert flops == 15 * 144266 * 55
    assert bytes_ == 4 * 144266 * (5 * 55 + 11)
    least, bound = work.least_time(flops, bytes_, peak)
    assert bound == "bytes"
    assert least == pytest.approx(0.2015e-3, rel=1e-3)
