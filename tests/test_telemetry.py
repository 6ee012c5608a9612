"""The program's own tracing (``repro.telemetry``): host spans, compile
counters, and the phase scopes of the compiled round program."""
import dataclasses
import glob
import re
import time

import jax
import jax.numpy as jnp
import pytest

from repro import telemetry
from repro.configs.bhfl_cnn import REDUCED
from repro.fl import BHFLSimulator, engine

TINY = dataclasses.replace(REDUCED, t_global_rounds=3, n_edges=2,
                           j_per_edge=3, image_hw=8)
KW = dict(n_train=120, n_test=40, steps_per_epoch=2)


def _named(name):
    return [s for s in telemetry.spans() if s.name == name]


def _inside(child, parent):
    return (child.parent == parent.name
            and parent.start_ns <= child.start_ns <= child.end_ns
            <= parent.end_ns)


def test_spans_record_name_parent_and_times_and_nest():
    telemetry.reset()
    with telemetry.span("outer"):
        with telemetry.span("inner"):
            time.sleep(0.002)
        with telemetry.span("inner2"):
            pass
    inner, inner2, outer = telemetry.spans()
    assert [s.name for s in (inner, inner2, outer)] == \
        ["inner", "inner2", "outer"]
    assert outer.parent is None
    assert _inside(inner, outer) and _inside(inner2, outer)
    assert inner.end_ns <= inner2.start_ns
    assert inner.seconds >= 0.002


def test_span_closes_when_its_body_raises():
    telemetry.reset()
    with pytest.raises(RuntimeError):
        with telemetry.span("outer"):
            with telemetry.span("failing"):
                raise RuntimeError("boom")
    with telemetry.span("after"):
        pass
    failing, outer, after = telemetry.spans()
    assert (failing.name, failing.parent) == ("failing", "outer")
    assert failing.end_ns >= failing.start_ns
    assert (outer.name, outer.parent) == ("outer", None)
    # nothing is left open: the next span has no parent
    assert (after.name, after.parent) == ("after", None)


def test_span_buffer_is_bounded():
    telemetry.reset()
    for i in range(telemetry.MAX_SPANS + 10):
        with telemetry.span(f"s{i}"):
            pass
    got = telemetry.spans()
    assert len(got) == telemetry.MAX_SPANS
    assert got[0].name == "s10"
    assert got[-1].name == f"s{telemetry.MAX_SPANS + 9}"


def test_span_lands_on_the_profiler_host_plane(tmp_path):
    from jax.profiler import ProfileData

    telemetry.reset()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with telemetry.span("first"):
            time.sleep(0.01)
        time.sleep(0.005)
        with telemetry.span("second"):
            time.sleep(0.02)
    finally:
        jax.profiler.stop_trace()
    mem = {s.name: s for s in telemetry.spans()}
    pb = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert pb
    events = {}
    for plane in ProfileData.from_file(pb[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(telemetry.SPAN_PREFIX):
                    events[e.name] = (e.start_ns, e.end_ns)
    first, second = events["bhfl/first"], events["bhfl/second"]
    ms = 1e6
    for name, (s, e) in (("first", first), ("second", second)):
        assert abs((e - s) - (mem[name].end_ns - mem[name].start_ns)) < ms
    # the trace's times are relative to its start: compare offsets
    assert abs((second[0] - first[0])
               - (mem["second"].start_ns - mem["first"].start_ns)) < ms


def test_compile_counters_count_a_fresh_jit_once():
    x = jnp.arange(5.0)
    f = jax.jit(lambda v: jnp.sin(v) * 3.0 + 1.0)
    before = telemetry.counters()
    f(x).block_until_ready()
    after_first = telemetry.counters()
    f(x).block_until_ready()
    after_second = telemetry.counters()

    def total(c, key):
        return sum(c.get(key, {}).values())

    assert total(after_first, "compiles") == total(before, "compiles") + 1
    assert any("lambda" in k for k in after_first["compiles"])
    for key in ("trace_s", "lower_s", "backend_compile_s"):
        assert total(after_first, key) > total(before, key), key
    assert after_second == after_first


def test_simulator_and_inputs_record_their_spans():
    telemetry.reset()
    sim = BHFLSimulator(TINY, **KW)
    engine.build_inputs(sim)
    (build,) = _named("sim.build")
    (inputs,) = _named("inputs.build")
    assert build.parent is None and inputs.parent is None
    assert build.end_ns <= inputs.start_ns
    for child in ("inputs.replay_chain", "inputs.batches", "inputs.latency",
                  "inputs.to_device"):
        (c,) = _named(child)
        assert _inside(c, inputs), child


@pytest.mark.parametrize("entry", ["run", "run_checkpointed"])
def test_runs_record_their_spans(entry, tmp_path):
    sim = BHFLSimulator(TINY, **KW)
    telemetry.reset()
    if entry == "run":
        sim.run()
        children = ("inputs.build", "run.execute", "run.readback")
    else:
        sim.run_checkpointed(str(tmp_path), every=2)
        children = ("inputs.build", "run.segment", "run.checkpoint")
    (top,) = _named(f"sim.{entry}")
    assert top.parent is None
    for child in children:
        found = _named(child)
        assert found and all(_inside(c, top) for c in found), child
    if entry == "run_checkpointed":
        # T=3 rounds in segments of 2: two segments, two checkpoints
        assert len(_named("run.segment")) == len(_named("run.checkpoint")) \
            == 2


def test_sweep_records_plan_probe_and_bucket_spans():
    from repro.fl.sweep import run_sweep

    telemetry.reset()
    run_sweep(TINY, overrides=[{"j_per_edge": 2}, {"j_per_edge": 3}],
              placement="vmap", **KW)
    (plan,) = _named("sweep.plan")
    probes = _named("sweep.step_probe")
    assert all(_inside(p, plan) for p in probes)
    assert [s for s in _named("inputs.build") if _inside(s, plan)]
    assert _named("sweep.bucket")


# ------------------------------------------------------------ phase scopes
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*\S+\s+([\w\-]+)\(")
#: Opcodes that do a round's heavy work.
HEAVY = ("convolution", "dot", "custom-call", "gather")


def phase_ops(hlo_text: str) -> list[tuple[str, list[str]]]:
    """``(opcode, phase scopes in its op_name)`` of every instruction of
    ``hlo_text`` with a non-empty ``op_name``."""
    out = []
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        op = re.search(r'op_name="([^"]*)"', line)
        if m and op and op.group(1):
            out.append((m.group(1),
                        [p for p in telemetry.PHASES if p in op.group(1)]))
    return out


def one_round_program(sim, kernel_mode):
    """The lowered one-round ``run_engine_chunk`` of ``sim``."""
    inp = engine.build_inputs(sim)
    carry = engine.init_engine_carry(inp, None)
    return engine.run_engine_chunk.lower(
        engine.slice_rounds(inp, 0, 1), carry, jnp.int32(0),
        aggregator=sim.aggregator, kernel_mode=kernel_mode)


@pytest.mark.parametrize("kernel_mode", ["xla", "interpret"])
def test_each_op_lies_under_at_most_one_phase(kernel_mode):
    sim = BHFLSimulator(TINY, **KW)
    ops = phase_ops(one_round_program(sim, kernel_mode).compile().as_text())
    assert all(len(scopes) <= 1 for _, scopes in ops), \
        [o for o in ops if len(o[1]) > 1][:5]
    heavy = [scopes for code, scopes in ops if code in HEAVY]
    assert heavy and all(len(scopes) == 1 for scopes in heavy)
    assert {p for _, scopes in ops for p in scopes} == set(telemetry.PHASES)
