"""The sweep fabric: shape-bucketed planner + mesh placement.

Every point of a padded grid — including grids over topology (N edges,
J devices per edge) and round counts (K, T), which change engine array
shapes per point — must reproduce a standalone ``BHFLSimulator.run`` of
the same setting, and padded extents must never contribute to any
aggregate.  Bucketing (grouping points into a few shape buckets instead
of padding everything to the single grid max) and the seed-deduped data
plane (one ``[n_seeds]`` dataset stack gathered by ``seed_idx`` inside
the engine) must both be invisible to numerics: the bucketed/deduped
grid is pinned per point against the single-bucket reference AND against
standalone runs that materialize their own data.  The multi-device
``shard_map`` path is pinned against ``vmap`` in
``test_multidevice_sweep.py`` (forced-host-device subprocess).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.configs.bhfl_cnn import REDUCED
from repro.core import straggler
from repro.fl import (BHFLSimulator, build_inputs, plan_sweep, run_plan,
                      run_sweep)
from repro.fl.engine import run_engine

TINY = dataclasses.replace(REDUCED, t_global_rounds=3, n_edges=3,
                           j_per_edge=3, image_hw=8)
KW = dict(n_train=300, n_test=100, steps_per_epoch=2)


def _standalone(ov, seed=0, setting=TINY, kw=KW, **sim_kw):
    s = dataclasses.replace(setting, **ov)
    return BHFLSimulator(s, "hieavg", "temporary", "temporary", seed=seed,
                         **kw, **sim_kw).run()


def _check_point(sw, p, r):
    tv = int(sw.t_valid[p])
    np.testing.assert_allclose(sw.accuracy[p, :tv], r.accuracy, atol=1e-6)
    np.testing.assert_allclose(sw.loss[p, :tv], r.loss, rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(sw.grad_norm[p, :tv], r.grad_norm, rtol=1e-4,
                               atol=1e-6)
    # latency fabric: per-point simulated clock parity rides every grid
    np.testing.assert_allclose(sw.sim_clock[p, :tv], r.sim_clock, rtol=1e-5)


# ----------------------------------------------------------- grid parity
def test_topology_grid_matches_standalone_runs():
    """N x J x K grid — shape-changing, ONE compiled call — per-point
    parity with individual engine runs (the acceptance criterion)."""
    overrides = [{"n_edges": n, "j_per_edge": j, "k_edge_rounds": k}
                 for n in (2, 3) for j in (2, 3) for k in (1, 2)]
    sw = run_sweep(TINY, overrides=overrides, **KW)
    assert sw.accuracy.shape == (8, TINY.t_global_rounds)
    for p, (ov, seed) in enumerate(sw.points):
        _check_point(sw, p, _standalone(ov, seed))


def test_ragged_round_counts():
    """t_global_rounds may vary per point; trailing rounds repeat the
    final valid accuracy and zero the loss/delta."""
    sw = run_sweep(TINY, overrides=[{"t_global_rounds": 2},
                                    {"t_global_rounds": 4}], **KW)
    assert sw.accuracy.shape == (2, 4)
    np.testing.assert_array_equal(sw.t_valid, [2, 4])
    for p, (ov, seed) in enumerate(sw.points):
        _check_point(sw, p, _standalone(ov, seed))
    # padded tail: accuracy/clock frozen at the final valid value, metrics
    # zeroed
    np.testing.assert_array_equal(sw.accuracy[0, 2:],
                                  np.repeat(sw.accuracy[0, 1], 2))
    np.testing.assert_array_equal(sw.loss[0, 2:], 0.0)
    np.testing.assert_array_equal(sw.grad_norm[0, 2:], 0.0)
    np.testing.assert_array_equal(sw.sim_clock[0, 2:],
                                  np.repeat(sw.sim_clock[0, 1], 2))
    acc, loss, gn = sw.trajectory(0)
    assert acc.shape == loss.shape == gn.shape == (2,)
    clock, acc_t = sw.latency_trajectory(0)
    assert clock.shape == acc_t.shape == (2,)


def test_varying_steps_per_epoch():
    """steps_per_epoch=None makes the step count depend on the device
    count (paper Sec. 6.1.5) — the planner pads the step axis too."""
    kw = dict(KW, steps_per_epoch=None)
    overrides = [{"j_per_edge": 2}, {"j_per_edge": 3}]
    sw = run_sweep(TINY, overrides=overrides, **kw)
    for p, (ov, seed) in enumerate(sw.points):
        _check_point(sw, p, _standalone(ov, seed, kw=kw))


def test_ragged_j_per_edge_list_override():
    """Fig. 4b inconsistent-J deployments ride through the planner."""
    sw = run_sweep(TINY, overrides=[{"j_per_edge": [1, 2, 3]}], **KW)
    r = BHFLSimulator(TINY, "hieavg", "temporary", "temporary",
                      j_per_edge=[1, 2, 3], **KW).run()
    _check_point(sw, 0, r)


@pytest.mark.parametrize("agg", ["t_fedavg", "d_fedavg"])
def test_topology_grid_other_aggregators(agg):
    ovs = [{"n_edges": 2}, {"k_edge_rounds": 1}]
    sw = run_sweep(TINY, overrides=ovs, aggregator=agg, **KW)
    for p, (ov, seed) in enumerate(sw.points):
        s = dataclasses.replace(TINY, **ov)
        r = BHFLSimulator(s, agg, "temporary", "temporary", seed=seed,
                          **KW).run()
        _check_point(sw, p, r)


# ------------------------------------------------------ padding invariants
def test_padding_is_a_numeric_noop():
    """A single deployment run through grid-max padding must match its
    unpadded self — padded slots never contribute to any aggregate."""
    sim_a = BHFLSimulator(TINY, "hieavg", "temporary", "temporary", **KW)
    sim_b = BHFLSimulator(TINY, "hieavg", "temporary", "temporary", **KW)
    inp = build_inputs(sim_a)
    pad = build_inputs(sim_b, t_max=5, k_max=4, n_max=5, j_max=6,
                       steps_max=4)
    a = run_engine(inp)
    b = run_engine(pad)
    T = TINY.t_global_rounds
    for x, y in zip(a, b):
        np.testing.assert_allclose(np.asarray(y)[:T], np.asarray(x),
                                   rtol=1e-5, atol=1e-6)


def test_padded_inputs_are_inert():
    """Structural invariants: padded extents carry zero weight/lr/masks."""
    sim = BHFLSimulator(TINY, "hieavg", "temporary", "temporary", **KW)
    pad = build_inputs(sim, t_max=5, k_max=4, n_max=5, j_max=6, steps_max=4)
    N, K, T, S = TINY.n_edges, TINY.k_edge_rounds, TINY.t_global_rounds, 2
    assert (int(pad.n_valid), int(pad.k_valid), int(pad.t_valid),
            int(pad.s_valid)) == (N, K, T, S)
    np.testing.assert_array_equal(np.asarray(pad.j_arr[N:]), 0.0)
    assert not np.asarray(pad.valid)[N:].any()
    assert not np.asarray(pad.valid)[:, 3:].any()      # j_per_edge=3
    assert not np.asarray(pad.dev_masks)[T:].any()
    assert not np.asarray(pad.dev_masks)[:, K:].any()
    assert not np.asarray(pad.edge_masks)[:, N:].any()
    np.testing.assert_array_equal(np.asarray(pad.lr)[T:], 0.0)
    np.testing.assert_array_equal(np.asarray(pad.lr)[:, K:], 0.0)
    assert not np.asarray(pad.has_data)[N:].any()
    assert not np.asarray(pad.batch_idx)[:, :, :, :, S:].any()


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 50), n_max=st.integers(4, 7))
def test_stack_ragged_n_max_pads_inert_edges(seed, n_max):
    rng = np.random.default_rng(seed)
    js = [int(rng.integers(1, 5)) for _ in range(3)]
    scheds = [straggler.temporary(8, j, max(j // 2, 1), seed=seed + i)
              for i, j in enumerate(js)]
    dense, valid = straggler.stack_ragged(scheds, n_max=n_max)
    assert dense.shape == (8, n_max, max(js))
    assert not dense[:, 3:].any() and not valid[3:].any()
    for e, j in enumerate(js):
        np.testing.assert_array_equal(dense[:, e, :j], scheds[e])


def test_stack_ragged_rejects_too_small_n_max():
    scheds = [straggler.no_stragglers(4, 2)] * 3
    with pytest.raises(ValueError, match="n_max"):
        straggler.stack_ragged(scheds, n_max=2)


def test_build_inputs_rejects_undersized_pad_targets():
    sim = BHFLSimulator(TINY, "hieavg", "temporary", "temporary", **KW)
    with pytest.raises(ValueError, match="pad targets"):
        build_inputs(sim, j_max=2)       # j_per_edge=3
    sim = BHFLSimulator(TINY, "hieavg", "temporary", "temporary", **KW)
    with pytest.raises(ValueError, match="pad targets"):
        build_inputs(sim, t_max=1)       # t_global_rounds=3


# ------------------------------------------------------------ error paths
def test_unsupported_field_raises_naming_it():
    with pytest.raises(ValueError, match="image_hw"):
        run_sweep(TINY, overrides=[{"image_hw": 10}], **KW)
    with pytest.raises(ValueError, match="batch_size"):
        run_sweep(TINY, overrides=[{"batch_size": 8}], **KW)


def test_unknown_field_raises_naming_it():
    with pytest.raises(ValueError, match="not_a_field"):
        run_sweep(TINY, overrides=[{"not_a_field": 1}], **KW)


def test_mismatched_ragged_j_per_edge_raises():
    """A ragged device list must name every edge exactly once — silently
    inflating D (steps, latency) would corrupt results, not crash."""
    with pytest.raises(ValueError, match="n_edges"):
        run_sweep(TINY, overrides=[{"n_edges": 2,
                                    "j_per_edge": [3, 4, 5]}], **KW)


def test_forced_shard_raises_clearly_on_one_device():
    with pytest.raises(ValueError, match="placement='shard'"):
        run_sweep(TINY, overrides=[{}, {"straggler_frac": 0.4}],
                  placement="shard", **KW)


@pytest.mark.parametrize("point_batch", [1, 2])
def test_point_batch_walks_points_with_the_same_results(point_batch):
    """``point_batch`` walks a bucket's points n at a time inside its one
    compiled call (bounding device memory); per point the numbers match
    the all-at-once vmap to float rounding, including a tail batch."""
    ovs = [{"aggregation": "hieavg"}, {"aggregation": "delayed_grad"},
           {"aggregation": "fedavg"}]
    ref = run_sweep(TINY, overrides=ovs, **KW)
    got = run_sweep(TINY, overrides=ovs, point_batch=point_batch, **KW)
    np.testing.assert_allclose(got.accuracy, ref.accuracy, atol=1e-6)
    np.testing.assert_allclose(got.loss, ref.loss, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got.grad_norm, ref.grad_norm, rtol=1e-4,
                               atol=1e-6)
    np.testing.assert_array_equal(got.sim_clock, ref.sim_clock)


def test_point_batch_must_be_positive():
    with pytest.raises(ValueError, match="point_batch"):
        run_sweep(TINY, overrides=[{}], point_batch=0, **KW)


# ---------------------------------------------------------- history dtype
def test_history_dtype_f8_runs_and_stays_close():
    """EXPERIMENTS.md X1: f8 history storage is a memory/accuracy knob,
    not a correctness switch — trajectories stay finite and close to f32
    at tiny scale."""
    f32 = BHFLSimulator(TINY, "hieavg", "temporary", "temporary", **KW).run()
    f8 = BHFLSimulator(TINY, "hieavg", "temporary", "temporary",
                       history_dtype=jnp.float8_e4m3fn, **KW).run()
    assert np.all(np.isfinite(f8.accuracy)) and np.all(np.isfinite(f8.loss))
    np.testing.assert_allclose(f8.loss, f32.loss, rtol=0.2, atol=0.05)


def test_seed_override_is_honored():
    """A {"seed": ...} override pins that point's seed — it is neither
    silently ignored (the simulator's explicit seed argument would
    otherwise win) nor crossed with the ``seeds`` tuple (which would emit
    duplicate identical points)."""
    sw = run_sweep(TINY, seeds=(0, 1),
                   overrides=[{"seed": 2}, {"straggler_frac": 0.2}], **KW)
    assert [s for _, s in sw.points] == [2, 0, 1]   # pinned, then crossed
    assert not np.array_equal(sw.accuracy[0], sw.accuracy[1])
    _check_point(sw, 0, _standalone({}, 2))


def test_history_dtype_threads_through_sweep():
    sw = run_sweep(TINY, overrides=[{"n_edges": 2}, {}],
                   history_dtype=jnp.float8_e4m3fn, **KW)
    assert np.all(np.isfinite(sw.accuracy))


# ----------------------------------------------------------------- planner
def test_plan_exposes_grid_maxima_and_stacked_inputs():
    plan = plan_sweep(TINY, overrides=[{"n_edges": 2, "k_edge_rounds": 2},
                                       {"n_edges": 4, "j_per_edge": 2}],
                      max_buckets=1, **KW)
    assert plan.grid_max["n"] == 4 and plan.grid_max["j"] == 3
    assert plan.grid_max["k"] == TINY.k_edge_rounds
    # max_buckets=1: the PR 2 single global-max stack; plan.inputs is the
    # single-bucket convenience accessor
    assert len(plan.buckets) == 1
    assert plan.inputs.dev_masks.shape == (
        2, plan.grid_max["t"], plan.grid_max["k"], plan.grid_max["n"],
        plan.grid_max["j"])


def test_plan_dedups_dataset_by_distinct_seed():
    """The data plane is seed-major: one ``[n_seeds]`` stack of the
    train/test/init arrays (they are a pure function of seed +
    grid-constant geometry) shared by every bucket, with per-point
    ``seed_idx`` gather indices — NEVER one dataset copy per point."""
    one = plan_sweep(TINY, overrides=[{"straggler_frac": 0.2},
                                      {"straggler_frac": 0.4}], **KW)
    assert one.n_seeds == 1
    assert one.inputs.train_x.shape == (1, KW["n_train"],
                                        TINY.image_hw, TINY.image_hw, 1)
    assert one.inputs.batch_idx.shape[0] == 2        # point plane stacked
    # single-seed plan: seed_idx stays a shared scalar (unmapped under
    # vmap, so the engine's test/init gathers stay unbatched)
    assert np.asarray(one.inputs.seed_idx).shape == ()
    assert int(one.inputs.seed_idx) == 0

    multi = plan_sweep(TINY, seeds=(0, 1), overrides=[{}, {"gamma0": 0.5}],
                       **KW)
    assert multi.n_seeds == 2
    # 4 points, but only 2 dataset rows — memory scales with seeds
    assert len(multi.points) == 4
    assert multi.inputs.train_x.shape[0] == 2
    np.testing.assert_array_equal(np.asarray(multi.inputs.seed_idx),
                                  [0, 1, 0, 1])


def test_bucketing_bounds_programs_and_cuts_padding():
    """The padding-waste heuristic: at most ``max_buckets`` buckets, every
    point in exactly one bucket, and strictly less padded compute than the
    single global-max bucket on a mixed-shape grid."""
    ovs = [{"n_edges": 2}, {"n_edges": 4}, {"j_per_edge": 2},
           {"k_edge_rounds": 1}, {"t_global_rounds": 2}, {}]
    auto = plan_sweep(TINY, overrides=ovs, max_buckets=3, bucket_waste=1.0,
                      **KW)
    single = plan_sweep(TINY, overrides=ovs, max_buckets=1, **KW)
    assert len(single.buckets) == 1
    assert 1 < len(auto.buckets) <= 3
    assert sorted(i for b in auto.buckets for i in b.point_ids) \
        == list(range(len(ovs)))
    sa, ss = auto.padding_stats(), single.padding_stats()
    assert sa["ideal_volume"] == ss["ideal_volume"]
    assert sa["padded_volume"] < ss["padded_volume"]
    assert 0.0 <= sa["padded_flop_frac"] < sa["single_bucket_flop_frac"]
    # per-bucket inputs are padded to the bucket max, not the global max
    assert any(b.inputs.dev_masks.shape[1:] != (
        single.grid_max["t"], single.grid_max["k"], single.grid_max["n"],
        single.grid_max["j"]) for b in auto.buckets)
    assert "bucket" in auto.describe()
    with pytest.raises(ValueError, match="buckets"):
        auto.inputs          # multi-bucket plan: no single stacked inputs
    with pytest.raises(ValueError, match="max_buckets"):
        plan_sweep(TINY, overrides=ovs, max_buckets=0, **KW)


def test_identical_shapes_always_share_a_bucket():
    """Shape-preserving grids (fig7-style data-only sweeps) stay ONE
    compiled call no matter the bucketing knobs."""
    plan = plan_sweep(TINY, overrides=[{"straggler_frac": f}
                                       for f in (0.0, 0.2, 0.4)],
                      max_buckets=4, bucket_waste=1.0, **KW)
    assert len(plan.buckets) == 1
    assert plan.padding_stats()["padded_flop_frac"] == 0.0


# ------------------------------------------------- bucketed execution parity
def test_bucketed_grid_matches_single_bucket_and_standalone():
    """The acceptance criterion: a fig3-style mixed J/N/K grid run through
    ≤3 bucketed programs matches the single-bucket reference per point
    (trajectories and sim_clock) AND standalone runs."""
    ovs = [{"n_edges": 2}, {"n_edges": 4}, {"j_per_edge": 2},
           {"k_edge_rounds": 1}, {"t_global_rounds": 2}, {}]
    bucketed = run_sweep(TINY, overrides=ovs, max_buckets=3,
                         bucket_waste=1.0, **KW)
    single = run_sweep(TINY, overrides=ovs, max_buckets=1, **KW)
    assert bucketed.accuracy.shape == single.accuracy.shape
    np.testing.assert_allclose(bucketed.accuracy, single.accuracy,
                               atol=1e-6)
    np.testing.assert_allclose(bucketed.loss, single.loss, rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(bucketed.grad_norm, single.grad_norm,
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(bucketed.sim_clock, single.sim_clock,
                               rtol=1e-5)
    for p, (ov, seed) in enumerate(bucketed.points):
        _check_point(bucketed, p, _standalone(ov, seed))


def test_seed_dedup_gather_matches_per_point_materialized_data():
    """A ≥3-seed grid pulls every point's dataset through the in-engine
    ``seed_idx`` gather of the shared ``[n_seeds]`` plane; standalone runs
    materialize their own data — the two must agree exactly."""
    sw = run_sweep(TINY, seeds=(0, 1, 2),
                   overrides=[{}, {"straggler_frac": 0.4}], **KW)
    assert len(sw.points) == 6
    for p, (ov, seed) in enumerate(sw.points):
        _check_point(sw, p, _standalone(ov, seed))
    # distinct seeds genuinely produce distinct data/trajectories
    assert not np.array_equal(sw.accuracy[0], sw.accuracy[1])


def test_seed_dedup_composes_with_bucketing():
    """Multi-seed x mixed-shape: buckets may split seed groups arbitrarily;
    every bucket still gathers from the one shared data plane."""
    plan = plan_sweep(TINY, seeds=(0, 1),
                      overrides=[{}, {"n_edges": 2, "k_edge_rounds": 1}],
                      max_buckets=2, bucket_waste=1.0, **KW)
    assert plan.n_seeds == 2 and len(plan.buckets) == 2
    for b in plan.buckets:
        assert b.inputs.train_x.shape[0] == 2        # full plane everywhere
        # same device buffers in every bucket, not copies
        assert b.inputs.train_x is plan.buckets[0].inputs.train_x
    sw = run_plan(plan)
    for p, (ov, seed) in enumerate(sw.points):
        _check_point(sw, p, _standalone(ov, seed))
