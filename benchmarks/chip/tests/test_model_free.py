"""The harness takes a client model other than the CNN without an edit:
a toy MLP (``toy_mlp.py``, kept here) goes through the one metric context,
the plain reference of the FL rounds, and the comparison that decides
``correct``, with no change to the harness's own files."""
import json

import jax.numpy as jnp
import pytest

import compare
import run as harness
from cell import HERE
from tiny import DATA, RECORDED_SEED, read_trace, reader, tiny_cell

toy = harness.load_module(HERE / "tests" / "toy_mlp.py")


@pytest.fixture(scope="module")
def cell():
    return tiny_cell()


@pytest.fixture(scope="module")
def prepared(cell):
    """The tiny deployment as the recorded run built it.  The program
    trains the CNN, so its planes come from the CNN's set-up."""
    _, cnn = harness.load_models(cell)
    return harness.prepare(cell, RECORDED_SEED, cnn)


@pytest.fixture(scope="module")
def context(cell, prepared, tmp_path_factory):
    trace = read_trace("small_trace", tmp_path_factory.mktemp("trace"))
    rounds = json.loads((DATA / "small_trace" / "result.json").read_text()
                        )["attempted"]
    peak = json.loads((HERE / "peaks.json").read_text())["TPU v5 lite"]
    return harness.metric_context(cell, toy, prepared, rounds, trace, peak,
                                  {})


def test_toy_flops_are_the_hand_count(cell):
    # 784 pixels -> 32 hidden -> 10 classes: forward 25,408 MACs, weight
    # gradients the same, input gradient of the second layer 320 MACs
    assert toy.train_flops_per_sample(cell.config["setting"]) \
        == 2 * (25408 + 25408 + 320)


def test_context_and_mfu_read_the_models_own_flops(context):
    flops = context.train_samples * 102272
    assert reader("train_mfu").read(context) == pytest.approx(
        100 * flops / (context.trace.window_s * 197e12))
    assert reader("conv_roofline").read(context) is None


def test_aggregate_roofline_counts_the_models_parameters(context):
    # 784 x 32 + 32 + 32 x 10 + 10 parameters
    least = 4 * 25450 * (5 * context.agg_participants
                         + context.agg_outputs) / 819e9
    share = reader("aggregate_roofline").read(context)
    phase = reader("round.aggregate_s").read(context)
    assert share / 100 * phase * context.rounds == pytest.approx(
        least, rel=1e-9)


def test_reference_trains_it_and_correct_separates_the_control(cell,
                                                                prepared):
    ref, _ = harness.load_models(cell)
    w0 = toy.init_params(cell.config, 41)
    p = prepared
    base = ref.run(toy, cell.config, p.planes, w0, p.checked)
    again = ref.run(toy, cell.config, p.planes, w0, p.checked)
    ctl = ref.run(toy, cell.config, p.planes, w0, p.checked,
                  dtype=jnp.bfloat16)
    assert base["loss"][-1] < base["loss"][0]
    assert all(v == 0 for v in compare.numbers(again, base, w0).values())
    ok, checks = compare.judge(compare.numbers(again, base, w0), cell.limits)
    assert ok, checks
    ok, checks = compare.judge(compare.numbers(ctl, base, w0), cell.limits)
    assert not ok, checks


@pytest.mark.parametrize("path", [
    "run.py", "work.py", "compare.py", "cell.py", "references/bhfl.py",
    "calibrate.py", "rehearse.py",
    *sorted(p.relative_to(HERE).as_posix()
            for p in (HERE / "metrics").glob("*.py"))])
def test_harness_names_no_model_size(path):
    """Only a model module reads a model's sizes, so a new model needs
    no edit of the harness."""
    text = (HERE / path).read_text()
    for key in ("image_hw", "cnn_c1", "cnn_c2", "n_classes", "HIDDEN",
                "toy_mlp", "toy_lm", "VOCAB", "WIDTH", "RANK", "SEQ",
                "frozen_seed"):
        assert key not in text, (path, key)
