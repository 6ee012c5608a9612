"""The trace reduction on a small trace recorded on a TPU v5e.

``data/small_trace`` holds the window of the tiny cell (``tiny.py``), as
``record_trace.py`` recorded it: the profiler's ``.xplane.pb`` and the
window program's text, both gzipped, and the run's result line.  The
readers get the context that ``run.metric_context`` builds for that run.
"""
import json

import pytest

import run as harness
from cell import HERE
from devtrace import CONTROL, SPAN, WINDOW
from tiny import DATA, RECORDED_SEED, read_trace, reader, tiny_cell


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    return read_trace("small_trace", tmp_path_factory.mktemp("trace"))


@pytest.fixture(scope="module")
def recorded():
    return json.loads((DATA / "small_trace" / "result.json").read_text())


@pytest.fixture(scope="module")
def context(trace, recorded):
    """The metric context of the recorded run, rebuilt from its seed."""
    c = tiny_cell()
    _, model = harness.load_models(c)
    p = harness.prepare(c, RECORDED_SEED, model)
    peak = json.loads((HERE / "peaks.json").read_text())["TPU v5 lite"]
    return harness.metric_context(c, model, p, recorded["attempted"], trace,
                                  peak, {})


def test_window_and_busy_time(trace, recorded):
    assert trace.chips == 1
    assert 0 < trace.busy_s() <= trace.window_s
    assert trace.busy_s() == pytest.approx(recorded["device"]["busy_s"])
    assert trace.window_s == pytest.approx(recorded["device"]["window_s"])


def test_ops_are_named_by_instruction_and_attributed(trace):
    names = {n for _, n, _, _ in trace.ops}
    assert names and not any(" " in n or n.startswith("%") for n in names)
    # every op the trace holds is an instruction of the window program
    assert names <= set(trace.hlo.opcode)
    top = trace.top_ops(10)
    assert top and all(s > 0 for _, s in top)
    # loops enclose other ops and are not counted as work of their own
    assert not any(trace.hlo.opcode.get(k) in CONTROL for k, _ in top)
    assert any(k.startswith("kernels/") for k, _ in top)


def test_attributed_work_is_inside_the_busy_time(trace):
    from repro import telemetry as tel
    t_conv = trace.attributed_s(reader("conv_roofline").FRAMES)
    t_agg = trace.attributed_s(op_names=(tel.EDGE_AGG, tel.GLOBAL_AGG))
    assert t_conv > 0 and t_agg > 0
    assert t_conv + t_agg <= trace.busy_s()


def test_idle_gaps_are_named_by_host_spans(trace):
    gaps = trace.idle_gaps()
    assert gaps
    assert all(k.startswith(SPAN) for k, _ in gaps)
    idle = trace.window_s - trace.busy_s()
    assert sum(s for _, s in gaps) == pytest.approx(idle, rel=1e-6)
    assert any(k != WINDOW for k, _ in gaps)


def test_context_counts_the_windows_work(context, recorded):
    # 2 edges x 3 devices, 2 SGD steps of batch 8, K=2; 100 test images
    rounds = recorded["attempted"]
    assert (context.rounds, context.chips) == (rounds, 1)
    assert context.train_samples == rounds * 6 * 2 * 8 * 2
    assert context.eval_samples == rounds * 100
    assert context.agg_participants == rounds * (2 * 6 + 2)
    assert context.agg_outputs == rounds * (2 * 2 + 1)


def test_rooflines_and_mfu_are_shares(context, recorded):
    for name in ("train_mfu", "conv_roofline", "aggregate_roofline",
                 "device.idle_share"):
        v = reader(name).read(context)
        assert v is not None and 0 < v < 100, name
        assert v == pytest.approx(recorded["metrics"][name]["value"]), name


def test_aggregate_roofline_is_the_least_time_over_the_phase(context):
    # HieAvg's least traffic over the 144,266 parameters of the paper's
    # CNN, bytes-bound, against the whole aggregation phase's device time
    least = 4 * 144266 * (5 * context.agg_participants
                          + context.agg_outputs) / 819e9
    share = reader("aggregate_roofline").read(context)
    phase = reader("round.aggregate_s").read(context)
    assert share / 100 * phase * context.rounds == pytest.approx(
        least, rel=1e-9)
