"""The trace reduction on a small trace recorded on a TPU v5e.

``data/small_trace`` holds the window of the tiny cell (``tiny.py``), as
``record_trace.py`` recorded it: the profiler's ``.xplane.pb`` and the
window program's text, both gzipped.
"""
import gzip
import importlib.util
import json
import shutil

import pytest

import work
from cell import HERE
from devtrace import CONTROL, SPAN, WINDOW, Trace
from tiny import tiny_cell

DATA = HERE / "tests" / "data" / "small_trace"


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    d = tmp_path_factory.mktemp("trace")
    prof = d / "plugins" / "profile" / "recorded"
    prof.mkdir(parents=True)
    with gzip.open(DATA / "window.xplane.pb.gz") as src, \
            open(prof / "window.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    hlo = gzip.open(DATA / "window.hlo.txt.gz", "rt").read()
    return Trace.read(str(d), hlo)


@pytest.fixture(scope="module")
def recorded():
    return json.loads((DATA / "result.json").read_text())


def reader(name):
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"), HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


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
    conv = reader("conv_roofline")
    agg = reader("aggregate_roofline")
    t_conv = trace.attributed_s(conv.FRAMES, conv.OP_NAMES)
    t_agg = trace.attributed_s(agg.FRAMES, agg.OP_NAMES)
    assert t_conv > 0 and t_agg > 0
    assert t_conv + t_agg <= trace.busy_s()


def test_idle_gaps_are_named_by_host_spans(trace):
    gaps = trace.idle_gaps()
    assert gaps
    assert all(k.startswith(SPAN) for k, _ in gaps)
    idle = trace.window_s - trace.busy_s()
    assert sum(s for _, s in gaps) == pytest.approx(idle, rel=1e-6)
    assert any(k != WINDOW for k, _ in gaps)


def test_rooflines_and_mfu_are_shares(trace, recorded):
    c = tiny_cell()
    s = c.config["setting"]
    rounds = recorded["attempted"]
    samples = s["n_edges"] * s["j_per_edge"] * 2 * s["batch_size"] \
        * s["k_edge_rounds"]
    peak = json.loads((HERE / "peaks.json").read_text())["TPU v5 lite"]
    conv_f, conv_b = work.conv_work(s, rounds * samples, rounds * 100)
    agg_f, agg_b = work.aggregate_work(
        s, rounds * (s["k_edge_rounds"] * 6 + 2),
        rounds * (s["k_edge_rounds"] * 2 + 1))

    class Run:
        pass

    run = Run()
    run.trace, run.chips, run.peak, run.setup = trace, 1, peak, {}
    run.least_time = lambda f, b: work.least_time(f, b, peak)
    run.work = {"train_flops": rounds * samples
                * work.train_flops_per_sample(s),
                "conv_flops": conv_f, "conv_bytes": conv_b,
                "agg_flops": agg_f, "agg_bytes": agg_b}
    for name in ("train_mfu", "conv_roofline", "aggregate_roofline",
                 "device.idle_share"):
        v = reader(name).read(run)
        assert v is not None and 0 < v < 100, name
        assert v == pytest.approx(recorded["metrics"][name]["value"]), name
