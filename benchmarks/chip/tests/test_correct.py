"""``correct`` at a size a test run holds, on the CPU: true for a sound
run, false for the control (the reference in bfloat16 in the program's
place) and for each fault planted in the timed path underneath a run.

The limits are the cells' own (``limits/<workload>.json``); the run skips
only the look for a chip (``run.run_cell`` is called directly).
"""
from functools import partial

import jax
import jax.numpy as jnp
import pytest

import compare
import run
from tiny import tiny_cell


@pytest.fixture(scope="module")
def cell():
    return tiny_cell()


@pytest.fixture(autouse=True)
def fresh_traces():
    jax.clear_caches()
    yield
    jax.clear_caches()


def run_once(c, seed):
    return run.run_cell(c, seed, seconds=0.1, trace=False)


def test_sound_run_is_correct(cell):
    r = run_once(cell, 21)
    assert r["correct"], r["checks"]
    assert list(r)[-1] == "checks"


def test_control_is_not_correct(cell):
    ref, model = run.load_models(cell)
    p = run.prepare(cell, 22, model)
    base = ref.run(model, cell.config, p.planes, p.w0, p.checked)
    ctl = ref.run(model, cell.config, p.planes, p.w0, p.checked,
                  dtype=jnp.bfloat16)
    ok, checks = compare.judge(compare.numbers(ctl, base, p.w0),
                               cell.limits)
    assert not ok, checks


def _unchanged(monkeypatch):
    """The step returns the state it was given."""
    from repro.fl import engine
    orig = engine.run_engine_chunk

    @partial(jax.jit, static_argnames=("aggregator", "normalize",
                                       "history_dtype", "kernel_mode"))
    def frozen(inp, carry, t_start, **kw):
        outs, _ = orig(inp, carry, t_start, **kw)
        return outs, carry

    monkeypatch.setattr(engine, "run_engine_chunk", frozen)


def _half_batch(monkeypatch):
    """Each step's loss is the mean over half of its batch."""
    from repro.fl import engine
    orig = engine.cnn_loss_fast

    def half(p, im, lb, kernel_mode="xla"):
        n = im.shape[0] // 2
        return orig(p, im[:n], lb[:n], kernel_mode=kernel_mode)

    monkeypatch.setattr(engine, "cnn_loss_fast", half)


def _no_exchange(monkeypatch):
    """Each edge takes its first device's model instead of aggregating."""
    from repro.kernels import dispatch
    warm, cold = dispatch.edge_aggregate_batched, \
        dispatch.edge_aggregate_cold_batched

    def first(ws):
        return jax.tree.map(lambda w: w[:, 0], ws)

    monkeypatch.setattr(dispatch, "edge_aggregate_batched",
                        lambda ws, *a, **k: (first(ws), warm(ws, *a, **k)[1]))
    monkeypatch.setattr(dispatch, "edge_aggregate_cold_batched",
                        lambda ws, *a, **k: first(ws))


def _altered_update(monkeypatch):
    """Device slot 0's local update counts double where it is produced."""
    from repro.fl import engine
    orig = engine.train_epoch_body

    def altered(params, *a, **k):
        new, loss = orig(params, *a, **k)
        return jax.tree.map(lambda n, o: n.at[0].add(n[0] - o[0]), new,
                            params), loss

    monkeypatch.setattr(engine, "train_epoch_body", altered)


@pytest.mark.parametrize("plant", [_unchanged, _half_batch, _no_exchange,
                                   _altered_update],
                         ids=lambda f: f.__name__.strip("_"))
def test_planted_fault_is_not_correct(cell, monkeypatch, plant):
    plant(monkeypatch)
    r = run_once(cell, 23)
    assert not r["correct"], r["checks"]
