"""The harness takes a client model with frozen weights and integer inputs,
and its plain reference trains device slots in blocks, with no edit to a
harness file: a toy token language model (``toy_lm.py``, kept here) goes
through the metric context, the reference and ``compare.judge``; the
CNN's reference is the same program as before blocks existed."""
import copy
import dataclasses
import hashlib
import json

import jax.numpy as jnp
import numpy as np
import pytest

import cell as cells
import compare
import run as harness
from cell import HERE
from tiny import DATA, RECORDED_SEED, read_trace, reader, tiny_cell

TOY_LM = HERE / "tests" / "toy_lm.py"
toy = harness.load_module(TOY_LM)
#: Token ids above bfloat16's exact range (256), 16 of them, so that a
#: few SGD steps can learn which ones occur.
IDS = 256 + 47 * np.arange(16)


def token_planes(planes: dict, seed: int) -> dict:
    """``planes`` with the data replaced by seeded int32 token sequences
    of the same row counts: each row walks the cycle ``IDS`` five ids at
    a time from a random start; ``y`` is ``x`` shifted by one token."""
    rng = np.random.default_rng(seed)

    def seqs(n):
        k = (rng.integers(0, len(IDS), (n, 1))
             + 5 * np.arange(toy.SEQ + 1)) % len(IDS)
        s = IDS[k].astype(np.int32)
        return s[:, :-1], s[:, 1:]

    out = dict(planes)
    out["train_x"], out["train_y"] = seqs(len(planes["train_y"]))
    out["test_x"], out["test_y"] = seqs(len(planes["test_y"]))
    return out


def with_block(path, block):
    """A fresh copy of the model module at ``path`` whose reference trains
    ``block`` device slots at once."""
    mod = harness.load_module(path)
    mod.reference_block = lambda setting: block
    return mod


@pytest.fixture(scope="module")
def cell():
    return tiny_cell()


@pytest.fixture(scope="module")
def prepared(cell):
    """The tiny deployment as the recorded run built it (the program
    trains the CNN, so its planes come from the CNN's set-up)."""
    _, cnn = harness.load_models(cell)
    return harness.prepare(cell, RECORDED_SEED, cnn)


@pytest.fixture(scope="module")
def lm(cell, prepared):
    """The toy's configuration (the tiny cell's, with a seed for its
    frozen weights and a learning rate at which twelve SGD steps move a
    1,000-way head), its token planes, frozen and initial weights."""
    cfg = copy.deepcopy(cell.config)
    cfg.update(model="toy_lm", frozen_seed=11)
    cfg["setting"]["lr0"] = 1.0
    w0 = {k: np.asarray(v) for k, v in toy.init_params(cfg, 41).items()}
    return dataclasses.replace(cell, config=cfg), dict(
        planes=token_planes(prepared.planes, 3),
        frozen=toy.frozen_params(cfg), w0=w0, checked=prepared.checked)


@pytest.fixture(scope="module")
def runs(cell, lm):
    """The reference over the toy in float32, again, and as the bfloat16
    control, through a copy of the module that records the dtypes of the
    tokens and targets its ``loss`` and ``test_count`` receive."""
    ref, _ = harness.load_models(cell)
    lm_cell, d = lm
    rec = harness.load_module(TOY_LM)
    seen = {"float32": set(), "bfloat16": set()}
    now = []
    loss, count = rec.loss, rec.test_count

    def rec_loss(p, x, y, frozen):
        now[0].add(("loss", x.dtype.name, y.dtype.name))
        return loss(p, x, y, frozen)

    def rec_count(p, x, y, frozen):
        now[0].add(("test_count", x.dtype.name, y.dtype.name))
        return count(p, x, y, frozen)

    rec.loss, rec.test_count = rec_loss, rec_count
    before = {k: np.array(v) for k, v in d["frozen"].items()}
    out = {}
    for name, dtype in (("base", jnp.float32), ("again", jnp.float32),
                        ("control", jnp.bfloat16)):
        now[:] = [seen[jnp.dtype(dtype).name]]
        out[name] = ref.run(rec, lm_cell.config, d["planes"], d["w0"],
                            d["checked"], dtype=dtype, frozen=d["frozen"])
    out["seen"], out["frozen_before"] = seen, before
    return out


def test_reference_trains_the_adapter_and_head(lm, runs):
    base = runs["base"]
    assert base["loss"][-1] < base["loss"][0]
    for m in base["models"]:
        assert set(m) == set(toy.param_shapes(lm[0].config["setting"]))


def test_a_repeat_reads_nought_and_the_control_fails(cell, lm, runs):
    w0 = lm[1]["w0"]
    again = compare.numbers(runs["again"], runs["base"], w0)
    assert all(v == 0 for v in again.values()), again
    ok, checks = compare.judge(again, cell.limits)
    assert ok, checks
    ok, checks = compare.judge(
        compare.numbers(runs["control"], runs["base"], w0), cell.limits)
    assert not ok, checks


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_token_ids_reach_the_model_as_int32(runs, dtype):
    assert runs["seen"][dtype] == {("loss", "int32", "int32"),
                                   ("test_count", "int32", "int32")}


def test_frozen_weights_are_unchanged_and_never_aggregated(lm, runs):
    frozen = lm[1]["frozen"]
    for k, v in runs["frozen_before"].items():
        assert np.asarray(frozen[k]).tobytes() == v.tobytes(), k
    for name in ("base", "control"):
        for m in runs[name]["models"]:
            assert not set(m) & set(frozen), (name, sorted(m))


def test_the_frozen_weights_are_read(cell, lm, runs):
    """Other frozen weights give another loss: the reference passes them
    to the model, not a copy of its own."""
    ref, _ = harness.load_models(cell)
    lm_cell, d = lm
    other = toy.frozen_params({"frozen_seed": 12})
    got = ref.run(toy, lm_cell.config, d["planes"], d["w0"], 1,
                  frozen=other)
    assert got["loss"][0] != runs["base"]["loss"][0]


@pytest.fixture(scope="module")
def context(lm, prepared, tmp_path_factory):
    trace = read_trace("small_trace", tmp_path_factory.mktemp("trace"))
    rounds = json.loads((DATA / "small_trace" / "result.json").read_text()
                        )["attempted"]
    peak = json.loads((HERE / "peaks.json").read_text())["TPU v5 lite"]
    return harness.metric_context(lm[0], toy, prepared, rounds, trace, peak,
                                  {})


def test_aggregate_roofline_counts_only_the_federated_parameters(context):
    # adapter 32 x 4 + 4 x 32, head 32 x 1000 + 1000; the frozen
    # embedding (1000 x 32) and hidden layer (32 x 32) are not mixed
    least = 4 * 33256 * (5 * context.agg_participants
                         + context.agg_outputs) / 819e9
    share = reader("aggregate_roofline").read(context)
    phase = reader("round.aggregate_s").read(context)
    assert share / 100 * phase * context.rounds == pytest.approx(
        least, rel=1e-9)


def test_train_flops_count_no_gradient_of_a_frozen_weight(context):
    # per token: forward 32x32 + 2 x 32x4 + 32x1000 MACs; weight
    # gradients of the adapter and head 2 x 32x4 + 32x1000; input
    # gradients through the head and the adapter's second factor
    # 32x1000 + 4x32; eight tokens a sequence
    per_token = (1024 + 256 + 32000) + (256 + 32000) + (32000 + 128)
    assert toy.train_flops_per_sample(context.setting) == 2 * 8 * per_token
    flops = context.train_samples * 2 * 8 * per_token
    assert reader("train_mfu").read(context) == pytest.approx(
        100 * flops / (context.trace.window_s * 197e12))


# ------------------------------------------------- frozen weights, program
@dataclasses.dataclass
class Inputs:
    init_w: dict


@dataclasses.dataclass
class FrozenInputs:
    init_w: dict
    frozen_w: dict


def test_with_frozen_weights_needs_the_programs_field():
    frozen = {"embed": jnp.zeros((4, 2))}
    with pytest.raises(ValueError, match="frozen_w"):
        cells.with_frozen_weights(Inputs(init_w={}), frozen)


def test_with_frozen_weights_checks_the_layout():
    frozen = {"embed": jnp.ones((4, 2))}
    inp = FrozenInputs(init_w={}, frozen_w={"embed": jnp.zeros((4, 2))})
    assert cells.with_frozen_weights(inp, frozen).frozen_w["embed"] \
        is frozen["embed"]
    for bad in ({"embed": jnp.zeros((4, 3))},
                {"embed": jnp.zeros((4, 2), jnp.bfloat16)},
                {"embed": jnp.zeros((4, 2)), "hidden": jnp.zeros((2, 2))}):
        with pytest.raises(ValueError, match="frozen_w layout"):
            cells.with_frozen_weights(FrozenInputs({}, bad), frozen)


def test_a_run_fails_at_set_up_where_the_program_takes_no_frozen_weights(
        cell, lm, monkeypatch):
    ref, _ = harness.load_models(cell)
    monkeypatch.setattr(harness, "load_models", lambda c: (ref, toy))
    with pytest.raises(ValueError, match="no frozen_w field"):
        harness.run_cell(lm[0], 24, seconds=0.1, trace=False)


def test_rehearse_hands_the_program_the_frozen_shapes(lm, monkeypatch):
    """The described compile goes through the same check, with the
    frozen weights' shapes, and stops there while the program has no
    ``frozen_w`` input."""
    import rehearse

    monkeypatch.setattr(cells.Cell, "named", lambda workload: lm[0])
    monkeypatch.setattr(harness, "load_models", lambda c: (None, toy))
    with pytest.raises(ValueError, match="no frozen_w field"):
        rehearse.rehearse("sec6.hieavg", None)


# ------------------------------------------------------------- blocks
def programs(ref, model, config, planes, w0, checked, **kw):
    """SHA-256 of the StableHLO text of each jitted call that ``ref.run``
    makes, in order.  The same text is the same program, so it computes
    the same bits on every machine with this JAX.  ``ref`` is a module
    of its own (``run.load_models`` loads a fresh one), so its functions
    are wrapped for good."""
    out = []
    for name in ("edge_round", "global_round"):
        def call(*a, _fn=getattr(ref, name), _name=name, **k):
            text = _fn.lower(*a, **k).as_text()
            out.append([_name, hashlib.sha256(text.encode()).hexdigest()])
            return _fn(*a, **k)

        setattr(ref, name, call)
    ref.run(model, config, planes, w0, checked, **kw)
    return out


VARIANTS = {"reference": {}, "control": {"dtype": jnp.bfloat16},
            **{f: {"fault": f} for f in ("half_batch", "no_exchange",
                                          "altered_update")}}


@pytest.mark.parametrize("variant", list(VARIANTS))
def test_default_block_is_the_reference_that_came_before(cell, prepared,
                                                         variant):
    """``data/reference_programs.json`` holds the programs that the CNN's
    reference ran on the tiny cell before it trained slots in blocks."""
    ref, cnn = harness.load_models(cell)
    p = prepared
    want = json.loads((DATA / "reference_programs.json").read_text())
    assert programs(ref, cnn, cell.config, p.planes, p.w0, p.checked,
                    **VARIANTS[variant]) == want[variant]


@pytest.mark.parametrize("block", [1, 4])
@pytest.mark.parametrize("model", ["cnn", "toy_lm"])
def test_blocks_stay_within_the_lower_readings(cell, prepared, lm, model,
                                               block):
    """1 slot at a time, and 4 (the tiny cell has 2 x 3 slots, so the
    last block is short), against all at once."""
    ref, cnn = harness.load_models(cell)
    if model == "cnn":
        path, c, planes, w0, frozen = (HERE / "models" / "cnn.py",
                                       cell.config, prepared.planes,
                                       prepared.w0, None)
        mod = cnn
    else:
        d = lm[1]
        path, c, planes, w0, frozen = (TOY_LM, lm[0].config, d["planes"],
                                       d["w0"], d["frozen"])
        mod = toy
    base = ref.run(mod, c, planes, w0, prepared.checked, frozen=frozen)
    got = ref.run(with_block(path, block), c, planes, w0, prepared.checked,
                  frozen=frozen)
    values = compare.numbers(got, base, w0)
    for name, v in values.items():
        assert v <= cell.limits["numbers"][name]["lower"], (name, v)
