"""The readers of the program's own tracing: round phases from the device
trace, set-up from the program's host spans and compile counters.

The phase readers are checked on a window program written out here, and
on ``data/unscoped_trace``, recorded on a TPU v5e from an earlier version
of the program that named no round phases, where they read nothing.  The
set-up readers read this process's own spans and counters.
"""
import types

import jax
import jax.numpy as jnp
import pytest

import cell as cells
from devtrace import Trace
from tiny import read_trace, reader, tiny_cell

ROUND = ("round.train_s", "round.aggregate_s", "round.eval_s",
         "round.unscoped_s")
SETUP = ("setup.sim_s", "setup.replay_s", "setup.planes_s",
         "compile.trace_s")


@pytest.fixture(scope="module")
def unscoped(tmp_path_factory):
    """The recorded trace, as ``run.run_cell`` hands it to a reader."""
    return types.SimpleNamespace(trace=read_trace(
        "unscoped_trace", tmp_path_factory.mktemp("unscoped_trace")))


#: A window program whose ops name the round phases, as the engine's do.
SCOPED_HLO = """HloModule jit_run_engine_chunk

ENTRY %main {
  %conv = f32[8] fusion(%p), metadata={op_name="jit(run_engine_chunk)/while/body/bhfl.train/while/body/bhfl.train/vmap(jvp())/mul"}
  %agg = f32[8] custom-call(%p), metadata={op_name="jit(run_engine_chunk)/while/body/bhfl.edge_agg/cond/branch_0_fun/jit(hieavg_agg)/pallas_call"}
  %gagg = f32[8] add(%p, %p), metadata={op_name="jit(run_engine_chunk)/while/body/bhfl.global_agg/add"}
  %eval = f32[8] dot(%p, %p), metadata={op_name="jit(run_engine_chunk)/bhfl.eval/dot_general"}
  %clock = f32[] add(%q, %q), metadata={op_name="jit(run_engine_chunk)/while/body/add"}
  %copy = f32[8] copy(%p)
  %loop = (f32[8]) while(%t), metadata={op_name="jit(run_engine_chunk)/while"}
}
"""


def test_round_phases_sum_to_the_leaf_op_seconds():
    from devtrace import HloIndex, SPAN

    chip = "/device:TPU:0"
    ops = [(chip, "loop", 0, 900), (chip, "conv", 0, 400),
           (chip, "agg", 400, 430), (chip, "gagg", 430, 440),
           (chip, "eval", 440, 500), (chip, "clock", 500, 505),
           (chip, "copy", 505, 525), (chip, "conv", 600, 1300)]
    spans = [(SPAN + "window", 0, 1000), (SPAN + "round.dispatch", 1, 2),
             (SPAN + "round.dispatch", 590, 591),
             (SPAN + "round.dispatch", 1200, 1201)]     # after the window
    run = types.SimpleNamespace(trace=Trace(
        chips=1, window=(0, 1000), ops=ops, spans=spans,
        hlo=HloIndex(SCOPED_HLO)))
    got = {name: reader(name).read(run) for name in ROUND}
    ns = 1e-9 / 2                                       # two window rounds
    assert got == pytest.approx({
        "round.train_s": (400 + 400) * ns, "round.aggregate_s": 40 * ns,
        "round.eval_s": 60 * ns, "round.unscoped_s": 25 * ns})
    leaves = sum(s for _, s in run.trace.top_ops(10 ** 6))
    assert sum(got.values()) * 2 == pytest.approx(leaves, rel=1e-9)


def test_round_readers_read_nothing_without_phase_scopes(unscoped):
    assert all(reader(name).read(unscoped) is None for name in ROUND)


def test_setup_readers_read_the_programs_spans_and_counters():
    from repro import telemetry
    from repro.fl import engine

    c = tiny_cell()
    jax.clear_caches()
    telemetry.reset()
    sim = cells.build_simulator(c.config, c.traffic, seed=7)
    inp = engine.build_inputs(sim)
    engine.run_engine_chunk.lower(
        engine.slice_rounds(inp, 0, 1), engine.init_engine_carry(inp),
        jnp.int32(0), **cells.chunk_kwargs(sim))
    got = {name: reader(name).read(None) for name in SETUP}
    assert all(v is not None and v > 0 for v in got.values()), got
    spans = {s.name: s.seconds for s in telemetry.spans()}
    host = got["setup.sim_s"] + got["setup.replay_s"] + got["setup.planes_s"]
    assert host == pytest.approx(spans["sim.build"] + spans["inputs.build"])
