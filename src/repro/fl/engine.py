"""Fully-jitted batched BHFL simulation engine.

The legacy ``BHFLSimulator.run`` loop dispatches one jitted aggregation call
per edge per edge-round plus host-side numpy batching — every sweep point
pays per-edge dispatch and host→device transfer overhead.  This engine
compiles an ENTIRE run into one program:

  * ragged ``j_per_edge`` is padded into a dense ``[N, J_max]`` device layout
    with a boolean ``valid`` mask (padded slots carry zero aggregation
    weight and are overwritten by the edge sync every round),
  * HieAvg edge aggregation is one vmapped ``_mix_and_update`` across all N
    edges instead of N sequential calls,
  * straggler masks, batch indices, and the learning-rate schedule are
    precomputed host-side into dense arrays (``core.straggler.stack_ragged``),
  * the K edge rounds and the global aggregation are driven by nested
    ``jax.lax.scan`` — one global round is one fused XLA computation, and the
    T rounds run without returning to Python,
  * the program is *shape-polymorphic via padding*: ``build_inputs`` can pad
    every array dim (T/K/N/J/steps) past a deployment's own extents, and
    ``run_engine`` treats everything padded as a numeric no-op — this is
    what lets the sweep planner (``repro.fl.sweep``) batch grid points that
    disagree on topology or round counts into a handful of compiled,
    mesh-sharded calls (shape buckets),
  * the data plane is *seed-major*: train/test/init arrays carry a leading
    ``[n_seeds]`` axis and every run gathers its own dataset by the scalar
    ``seed_idx`` — under the sweep fabric the data plane is shared across
    all grid points (vmap ``in_axes=None`` / ``shard_map`` replicated), so
    a multi-seed confidence grid holds the *distinct-seed* count in device
    memory, not one dataset copy per point,
  * the aggregation phases (HieAvg at both hierarchy layers, the
    cold-boot means, the baselines) and the CNN conv blocks route through
    the *kernel plane* (``repro.kernels.dispatch``): a static
    ``kernel_mode`` knob selects the Pallas kernels and XLA's own
    convolution on TPU, the pure-XLA reference and the im2col conv on
    CPU ("auto"), or the Pallas interpreter for validation — and the
    donating entries (``run_engine_donated``; ``split_inputs`` /
    ``SHARED_DATA_FIELDS``) hand the per-run input planes to the
    compiled call so callers stop holding a second copy.

The padding/validity-mask contract and the seed-dedup invariants are
documented in docs/ARCHITECTURE.md (§Engine); tests/test_sweep_fabric.py
enforces both.

The Raft chain (control plane, no model numerics) is replayed host-side
*before* the jitted run: it consumes the same RNG stream in the same order as
the legacy loop, so leader failover produces identical edge masks.

Parity with ``BHFLSimulator.run_legacy`` is tested in
``tests/test_engine_parity.py``; throughput is tracked in
``BENCH_engine.json`` (see EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import dataclasses
import warnings
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.core import baselines, hieavg
from repro.core import latency as lat
from repro.core import rng as rng_streams
from repro.core import straggler as strag
from repro.data import partition
from repro.fl import faults as _faults
from repro.kernels import dispatch as kernel_dispatch
from repro.models import (cnn_correct_fast, cnn_loss, cnn_loss_fast,
                          init_from_specs)
from repro.optim import paper_lr

PyTree = Any


# --------------------------------------------------------------- local step
def train_epoch_body(params: PyTree, images: jnp.ndarray,
                     labels: jnp.ndarray, lr: jnp.ndarray,
                     loss_fn=None,
                     step_ok: Optional[jnp.ndarray] = None,
                     kernel_mode: str = "xla") -> tuple[PyTree, jnp.ndarray]:
    """One local epoch for all devices.  params: stacked [D, ...];
    images: [D, steps, B, H, W, 1]; labels: [D, steps, B]. Returns
    (new stacked params, mean loss per device [D]).

    scan(vmap(step)) rather than vmap(scan): one fused all-device matmul per
    step instead of D separate small ones.  The engine trains with
    ``cnn_loss_fast``: the im2col conv on the CPU, XLA's own convolution
    on a TPU; the legacy reference loop keeps the shifted-sum conv (same
    math, different summation order).

    ``step_ok`` (optional, [steps] f32 of 0/1): per-step validity for the
    sweep fabric, whose grid points may disagree on steps-per-epoch.  A
    padded step (0) applies no update and is excluded from the mean loss;
    a real step multiplies lr by 1.0, which is exact in f32, so a fully
    valid mask is bitwise identical to passing ``None``.

    ``kernel_mode`` (resolved — ``"pallas"``/``"interpret"``/``"xla"``):
    when ``loss_fn`` is None (the default), routes the conv blocks inside
    the loss through ``kernels.dispatch`` (XLA's own convolution under a
    fused mode; ``cnn_loss_fast(kernel_mode=...)``).  An explicit
    ``loss_fn`` (``run_legacy``'s shifted-sum ``cnn_loss``) is used
    as-is.  The update is XLA's ``w - scale * g`` per leaf on every
    path; the padded-step mask folds into ``scale`` (0 → exact identity)
    so padding stays a numeric no-op.
    """
    if loss_fn is None:
        loss_fn = partial(cnn_loss_fast, kernel_mode=kernel_mode)

    def step(ps, xs):
        if step_ok is None:
            im, lb = xs                                 # [D, B, ...]
            scale = lr
        else:
            im, lb, ok = xs
            scale = lr * ok
        # the scope again inside the scanned body: XLA names what it
        # expands from a scatter (the im2col backward's col2im) after the
        # reducer, whose op_name is relative to this body
        with jax.named_scope(telemetry.TRAIN):
            loss, g = jax.vmap(jax.value_and_grad(loss_fn))(ps, im, lb)
            ps = jax.tree.map(lambda w, gw: w - scale * gw, ps, g)
        return ps, loss

    images = jnp.swapaxes(images, 0, 1)                 # [steps, D, ...]
    labels = jnp.swapaxes(labels, 0, 1)
    if step_ok is None:
        params, losses = jax.lax.scan(step, params, (images, labels))
        return params, jnp.mean(losses, axis=0)
    params, losses = jax.lax.scan(step, params, (images, labels, step_ok))
    n_ok = jnp.maximum(jnp.sum(step_ok), 1.0)
    return params, jnp.sum(losses * step_ok[:, None], axis=0) / n_ok


#: Test images per eval chunk.  The im2col eval forward holds a 9x
#: activation buffer, so the engine walks the test set in chunks of this
#: many rows, not all at once: at the paper's widths a whole 1000-image
#: test set, times a sweep's points, outgrows a 16 GB chip.
EVAL_CHUNK = 250


def eval_accuracy(params: PyTree, test_x: jnp.ndarray, test_y: jnp.ndarray,
                  kernel_mode: str = "xla") -> jnp.ndarray:
    """Test-set accuracy of one model, ``EVAL_CHUNK`` rows at a time.

    The tail chunk is padded with label -1, which never counts, and the
    exact integer count over the whole set is divided once, so the value
    equals ``cnn_accuracy_fast`` on the whole set.
    """
    n = test_y.shape[0]
    c = min(n, EVAL_CHUNK)
    pad = (-n) % c
    xs = jnp.pad(test_x, ((0, pad),) + ((0, 0),) * (test_x.ndim - 1))
    ys = jnp.pad(test_y, (0, pad), constant_values=-1)
    counts = jax.lax.map(
        lambda xy: cnn_correct_fast(params, *xy, kernel_mode=kernel_mode),
        (xs.reshape((-1, c) + xs.shape[1:]), ys.reshape(-1, c)))
    return jnp.sum(counts).astype(jnp.float32) / n


# jitted legacy-exact epoch (shifted-sum conv), used by run_legacy
train_epoch = jax.jit(partial(train_epoch_body, loss_fn=cnn_loss))


# ------------------------------------------------------------ dense inputs
@jax.tree_util.register_dataclass
@dataclasses.dataclass
class EngineInputs:
    """Everything a jitted run consumes, as dense device arrays.

    Leaves are stackable across grid points (the sweep fabric vmaps or
    shard_maps over a leading point axis); gamma0/lam/t_cold_boot ride along
    as scalars so decay-factor sweeps are data, not recompiles.

    The array dims T/K/N/J/steps are *bucket maxima* when the inputs were
    built with pad targets (``build_inputs(..., t_max=...)``): the
    ``t_valid``/``k_valid``/``n_valid``/``s_valid`` scalars carry each
    point's real extents, and ``run_engine`` turns everything padded into a
    numeric no-op — padded device/edge slots get zero aggregation weight
    (``valid``/``j_arr``), padded edge rounds and global rounds carry the
    scan state through unchanged, padded SGD steps apply no update.

    Data-plane fields (train/test/init, ``engine.SHARED_DATA_FIELDS``) are
    *seed-major*: a leading ``[S]`` axis of distinct seeds, gathered per
    run by the scalar ``seed_idx``.  The sweep fabric never stacks them
    along the point axis — they are shared (replicated) across the whole
    grid, so device-resident data scales with the distinct-seed count.
    A standalone ``build_inputs`` emits ``S=1`` with ``seed_idx=0``.
    """

    train_x: jnp.ndarray      # [S, n_train, H, W, 1] f32 (seed-major)
    train_y: jnp.ndarray      # [S, n_train] i32
    test_x: jnp.ndarray       # [S, n_test, H, W, 1] f32
    test_y: jnp.ndarray       # [S, n_test] i32
    init_w: PyTree            # [S, ...] global model at t=0, per seed
    seed_idx: jnp.ndarray     # scalar i32 — this run's row of the [S] axis
    batch_idx: jnp.ndarray    # [T, K, N, J, steps, B] i32 into train_x
    has_data: jnp.ndarray     # [N, J] f32 — 0 for empty-shard/padded slots
    valid: jnp.ndarray        # [N, J] bool — real device slots
    dev_masks: jnp.ndarray    # [T, K, N, J] bool submission masks
    edge_masks: jnp.ndarray   # [T, N] bool (failover already applied)
    lr: jnp.ndarray           # [T, K] f32 paper schedule (0 when padded)
    j_arr: jnp.ndarray        # [N] f32 devices per edge (0 = padded edge)
    gamma0: jnp.ndarray       # scalar f32
    lam: jnp.ndarray          # scalar f32
    t_cold_boot: jnp.ndarray  # scalar i32
    t_valid: jnp.ndarray      # scalar i32 — real global rounds (<= T)
    k_valid: jnp.ndarray      # scalar i32 — real edge rounds (<= K)
    n_valid: jnp.ndarray      # scalar i32 — real edges (<= N).  Metadata
    #   for callers/tests: run_engine itself never reads it — padded edges
    #   are inert purely through their all-False ``valid`` rows and zero
    #   ``j_arr`` weights.
    s_valid: jnp.ndarray      # scalar i32 — real SGD steps/epoch (<= steps)
    # --- latency plane (PR 3): precomputed per-round time draws feeding
    # the engine's simulated clock.  Padded slots/rounds are zero.
    dev_time: jnp.ndarray     # [T, K, N, J] f32 — per-device round time
    #   (2*LM + LP draws, straggler submissions delayed + deadline-capped;
    #   population mode folds the occupant's speed profile in)
    cons_time: jnp.ndarray    # [T] f32 — per-round consensus latency L_bc
    #   (replayed consensus-chain election + commit — the zoo protocol the
    #   setting names — scaled by consensus_mult)
    cons_energy: jnp.ndarray  # [T] f32 — per-round consensus energy (J),
    #   the chain's ``.energy`` differenced per round.  Zero on padded
    #   rounds (the energy axis's padding inertness is bitwise); never
    #   scaled by consensus_mult.
    edge_hop: jnp.ndarray     # scalar f32 — 2 * E[LM'] edge<->leader hop
    # --- population/cohort plane (PR 6): the engine's per-round arrays are
    # already COHORT-sized ([N, J] = the gathered cohort, not the
    # population) — the only trace the population leaves here is churn:
    cohort_change: jnp.ndarray  # [T, N, J] bool — slot occupant changed at
    #   the start of global round t (all-False for fixed membership).
    #   Resets the delayed-gradient pending/age state of the slot; HieAvg
    #   histories are slot-stream-keyed under churn (documented in
    #   docs/ARCHITECTURE.md).
    # --- aggregation-mode plane (PR 6): traced per-point scalars so an
    # aggregation-strategy axis is sweep DATA, not a recompile.  Only the
    # static aggregator="switched" engine reads agg_sel; stale_beta/
    # delay_delta feed delayed_grad (direct or switched).
    agg_sel: jnp.ndarray      # scalar i32 — 0 hieavg, 1 delayed_grad,
    #   2 fedavg (see AGG_SEL)
    stale_beta: jnp.ndarray   # scalar f32 — delayed-grad staleness
    #   discount beta (setting.staleness_discount)
    delay_delta: jnp.ndarray  # scalar f32 — max tolerated consecutive-miss
    #   staleness delta (setting.delay_delta)


#: ``EngineInputs`` fields that form the seed-major data plane: a pure
#: function of (seed, grid-constant geometry), carried with a leading
#: ``[S]`` distinct-seed axis and shared — never stacked per point — by
#: the sweep fabric (vmap ``in_axes=None`` / shard_map replicated), and
#: never *donated*: every bucket of a plan (and every same-seed point via
#: ``share_data_from``) aliases the same device buffers, so handing them
#: to XLA for reuse would invalidate the other aliases.
SHARED_DATA_FIELDS = frozenset({"train_x", "train_y", "test_x", "test_y",
                                "init_w"})

#: ``agg_sel`` encoding for the ``"switched"`` engine — the aggregation
#: strategies that can share one compiled program as a traced axis.
AGG_SEL = {"hieavg": 0, "delayed_grad": 1, "fedavg": 2}


def split_inputs(inp: EngineInputs, *, shared_seed_idx: bool = False
                 ) -> tuple[dict, dict]:
    """Split an ``EngineInputs`` into ``(hot, shared)`` field dicts.

    ``hot`` holds the per-run (sweep: per-point stacked) planes — safe to
    donate to the compiled run, so a big bucketed grid does not hold two
    copies of the stacked state (caller buffers + device working set)
    while it executes.  ``shared`` holds the seed-major data plane, which
    is aliased across buckets/points and therefore never donated (and
    never mapped/sharded — see ``launch.sharding.sweep_data_spec``).

    ``shared_seed_idx``: on single-seed sweep plans ``seed_idx`` is a
    plan-wide scalar 0 and rides the shared side (keeping the engine's
    test/init gathers unbatched under vmap); multi-seed plans stack it
    per point, so it belongs to the hot side like every stacked field.
    """
    hot, shared = {}, {}
    for f in dataclasses.fields(EngineInputs):
        side = shared if (f.name in SHARED_DATA_FIELDS
                          or (f.name == "seed_idx" and shared_seed_idx)) \
            else hot
        side[f.name] = getattr(inp, f.name)
    return hot, shared


def merge_inputs(hot: dict, shared: dict) -> EngineInputs:
    """Inverse of ``split_inputs`` (used inside the jitted runners)."""
    return EngineInputs(**hot, **shared)


def replay_chain(sim) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Replay the control plane exactly as the legacy loop interleaves it:
    elect → (maybe crash the leader) → commit, once per global round —
    now under the deployment's fault schedule (``repro.fl.faults``).

    Per round, the schedule's churn planes are diff-applied onto the
    chain's alive set (``fail_node``/``recover_node``) before the protocol
    round runs, so alive counts — and with them latency and energy — vary
    over rounds; a below-quorum round runs the schedule's bounded
    stall-and-retry policy (``faults.stalled_round``), with the backoff
    landing in that round's consensus-latency draw (the engine's C2 stall
    accounting picks it up).  Mutates only ``sim.chain`` (plus the
    ``sim._failed_leader`` crash memo); ``sim.edge_masks`` is never
    touched — the failover/outage mask is *derived* per replay, so a
    repeated ``run()`` is bitwise repeatable under a leader crash.  The
    chain RNG stream is consumed in the same order as the legacy loop
    (an inert schedule adds zero draws), so the same leaders win.  The
    ``fail_leader_at`` crash is applied at most once per simulator: a
    repeated ``run()`` replays the same failed edge instead of killing
    another leader (which would eventually lose quorum).

    Returns ``(cons [T], energy [T], edge_avail [T, N])``: per-round
    consensus latency (election + commit + any stall backoff, simulated
    seconds) and consensus energy (the chain's cumulative ``.energy``
    differenced per round, Joules) — the discrete-event draws the engine's
    clock and energy accounting consume — plus the derived per-round edge
    availability (crashed leader from its crash round on, scheduled edge
    outages, lost global submissions) that ``build_inputs`` ANDs into the
    ``edge_masks`` plane.
    """
    sched = sim.fault_schedule
    crash_at = sched.spec.leader_crash_round
    failed_edge: Optional[int] = getattr(sim, "_failed_leader", None)
    T = sim.s.t_global_rounds
    cons = np.zeros(T, np.float64)
    energy = np.zeros(T, np.float64)
    pinned = set() if failed_edge is None else {failed_edge}
    for t in range(1, T + 1):
        crash = crash_at is not None and t == crash_at and failed_edge is None
        elapsed, de, _, crashed = _faults.stalled_round(
            sim.chain, t, sched, pinned_down=pinned, crash_leader=crash)
        if crashed is not None:
            failed_edge = crashed
            sim._failed_leader = crashed
            pinned.add(crashed)
        cons[t - 1] = elapsed
        energy[t - 1] = de
    edge_avail = ~sched.edge_down & ~sched.edge_msg_drop    # [T, N]
    if failed_edge is not None:
        # from the crash round on — same extent the old in-place mutation
        # produced, but derived fresh per replay
        edge_avail[crash_at - 1:, failed_edge] = False
    return cons, energy, edge_avail


@telemetry.span("inputs.build")
def build_inputs(sim, *, t_max: Optional[int] = None,
                 k_max: Optional[int] = None, n_max: Optional[int] = None,
                 j_max: Optional[int] = None,
                 steps_max: Optional[int] = None,
                 share_data_from: Optional[EngineInputs] = None
                 ) -> EngineInputs:
    """Precompute a ``BHFLSimulator``'s whole run into dense device arrays.

    Batch indices are sampled from a fresh ``default_rng(seed)`` in the same
    (round, device) order as the legacy loop's per-round ``_epoch_batches``,
    so a fresh legacy instance and a fresh engine instance see identical
    batches.  Also replays the Raft chain (see ``replay_chain``).

    The ``*_max`` targets pad the emitted arrays past this deployment's own
    extents — how the sweep planner (``repro.fl.sweep``) stacks grid points
    that disagree on topology or round counts.  Padding is all-inert:
    padded rounds get zero lr and all-False masks, padded edges get
    ``j_arr`` 0 and all-False ``valid`` rows, padded steps index sample 0
    but are masked out of the SGD update.  The real extents ride along in
    ``t_valid``/``k_valid``/``n_valid``/``s_valid``.

    ``share_data_from``: reuse another point's train/test/init device
    buffers instead of converting this sim's own — the sweep planner's
    same-seed dedup (the caller guarantees the seed and data geometry
    match, which makes those arrays byte-identical; see
    ``engine.SHARED_DATA_FIELDS``).  The emitted data plane always carries
    the seed-major ``[S=1]`` leading axis with ``seed_idx=0``; the planner
    concatenates distinct-seed planes and rewrites ``seed_idx`` per point
    when it stacks a grid.
    """
    s = sim.s
    T, K, N = s.t_global_rounds, s.k_edge_rounds, sim.N
    steps, bs = sim.steps, s.batch_size
    Tm, Km, Nm = t_max or T, k_max or K, n_max or N
    Sm = steps_max or steps
    if (Tm < T or Km < K or Nm < N or Sm < steps
            or (j_max is not None and j_max < max(sim.j_per_edge))):
        raise ValueError("pad targets must be >= the deployment's extents")

    with telemetry.span("inputs.replay_chain"):
        cons_draws, energy_draws, edge_avail = replay_chain(sim)

    dense_dev, valid = strag.stack_ragged(sim.dev_masks, j_max=j_max,
                                          n_max=Nm)
    J = valid.shape[1]
    # ---- fault plane (repro.fl.faults): a down edge trains nothing (all
    # its device submissions cleared for the round's K edge rounds — the
    # edge-layer HieAvg miss_counts span the outage exactly like the
    # global layer's), and a burst/lost-message device misses its edge
    # round.  Both fold into the submission masks BEFORE the latency
    # computation, so a dropped submission is deadline-capped exactly
    # like a straggler miss.  The inert schedule skips the folding (and
    # the copy) entirely — bitwise parity with the pre-chaos path.
    sched = sim.fault_schedule
    if sched.edge_down.any() or sched.dev_drop.any():
        dense_dev = dense_dev.copy()
        if sched.edge_down.any():
            ed = np.repeat(sched.edge_down, K, axis=0)       # [T*K, N]
            dense_dev[:T * K, :N] &= ~ed[:, :, None]
        if sched.dev_drop.any():
            dd = sched.dev_drop                              # [T*K, N, Js]
            dense_dev[:T * K, :N, :dd.shape[2]] &= ~dd
    dev_masks = np.zeros((Tm, Km, Nm, J), dtype=bool)
    dev_masks[:T, :K] = dense_dev[:T * K].reshape(T, K, Nm, J)
    edge_masks = np.zeros((Tm, Nm), dtype=bool)
    edge_masks[:T, :N] = np.asarray(sim.edge_masks[:T], dtype=bool) \
        & edge_avail

    R = T * K
    # device d's slot (edge, index) in the dense [N, J] layout
    slots = [(e, j) for e in range(N) for j in range(sim.j_per_edge[e])]
    # batch indices in legacy order: per edge-round, per device.  The
    # fresh generator rides the deployment's "batches" SeedSequence stream
    # (core.rng) — the same stream run_legacy opens per run, so a legacy
    # and an engine run of one instance see identical batches.
    with telemetry.span("inputs.batches"):
        rng = rng_streams.stream_rng(sim.seed, "batches")
        if getattr(sim, "pop", None) is not None:
            # population mode: one vectorized draw for all (round, slot)
            # pairs — the occupant's classes select the sample pools, the
            # draws are slot-keyed.  O(R x cohort), never O(population).
            ids_r = np.repeat(sim.cohort_ids, K, axis=0).reshape(R, sim.D)
            cls_rd = sim.pop.classes[ids_r.reshape(-1)]      # [R*D, M]
            flat_idx = partition.sample_class_batches(
                sim._pool, sim._pool_off, sim._pool_cnt, cls_rd, steps, bs,
                rng).reshape(R, sim.D, steps, bs)
            flat_has = np.ones((sim.D,), np.float32)
        else:
            flat_idx = np.zeros((R, sim.D, steps, bs), np.int32)
            flat_has = np.zeros((sim.D,), np.float32)
            for r in range(R):
                for d, idx in enumerate(sim.device_idx):
                    if len(idx) == 0:
                        continue
                    flat_idx[r, d] = rng.choice(idx, size=(steps, bs),
                                                replace=True)
                    flat_has[d] = 1.0
        batch_idx = np.zeros((Tm, Km, Nm, J, Sm, bs), np.int32)
        has_data = np.zeros((Nm, J), np.float32)
        rect = flat_idx.reshape(T, K, sim.D, steps, bs)
        for d, (e, j) in enumerate(slots):
            batch_idx[:T, :K, e, j, :steps] = rect[:, :, d]
            has_data[e, j] = flat_has[d]
    # per-device round-time draws (latency fabric).  A separate RNG stream
    # from the batch sampler above: adding latency accounting must not
    # perturb batch draws (legacy parity).  Draws cover only the REAL
    # (T, K, D) extents so a point padded to larger grid maxima sees
    # byte-identical times (padding stays a numeric no-op).  Population
    # mode scales each slot's draw by the round occupant's speed profile.
    with telemetry.span("inputs.latency"):
        lp = sim.lat
        lrng = rng_streams.stream_rng(sim.seed, "latency")
        jm = lrng.uniform(1.0 - lp.lm_jitter, 1.0 + lp.lm_jitter, (R, sim.D))
        jp = lrng.uniform(1.0 - lp.lp_jitter, 1.0 + lp.lp_jitter, (R, sim.D))
        draw = 2.0 * lp.lm_device * jm + lp.lp_device * jp
        spd = sim.cohort_time_scale() \
            if getattr(sim, "pop", None) is not None else None
        if spd is not None:
            draw = draw * spd
        elif lp.rate_mult is not None:
            # heterogeneous fleet: device d's clock rate scales every one of
            # its round draws (before straggler slowdown / deadline capping,
            # exactly like a population occupant's time_scale would)
            rm = np.asarray(lp.rate_mult, np.float64).reshape(-1)
            if rm.shape != (sim.D,):
                raise ValueError(
                    f"LatencyParams.rate_mult must have one entry per device "
                    f"({sim.D}), got shape {rm.shape}")
            draw = draw * rm[None, :]
        draw = draw.reshape(T, K, sim.D)
        deadline = lat.device_deadline(lp)
        sub = dense_dev[:R].reshape(T, K, Nm, J)    # real submission masks
        dev_time = np.zeros((Tm, Km, Nm, J), np.float32)
        for d, (e, j) in enumerate(slots):
            # a straggler's submission is delayed (slowdown x draw); the
            # edge proceeds at the deadline without it — deadline-based
            # aggregation, so its round time is capped there
            dly = np.where(sub[:, :, e, j], draw[:, :, d],
                           draw[:, :, d] * lp.straggler_slowdown)
            dev_time[:T, :K, e, j] = np.minimum(dly, deadline)
    cons_time = np.zeros((Tm,), np.float32)
    cons_time[:T] = cons_draws * float(s.consensus_mult)
    # energy is a protocol cost, not a latency knob: consensus_mult never
    # scales it.  Padded rounds stay exactly 0.0 (bitwise-inert additions).
    cons_energy = np.zeros((Tm,), np.float32)
    cons_energy[:T] = energy_draws

    lr = np.zeros((Tm, Km), np.float32)
    lr[:T, :K] = np.asarray(
        paper_lr(jnp.arange(R), s.lr0, s.lr_decay)).reshape(T, K)
    j_arr = np.zeros((Nm,), np.float32)
    j_arr[:N] = sim.j_per_edge

    # cohort churn (population mode: occupant changed at round start;
    # all-False for fixed membership) — padded rounds/edges stay False
    cohort_change = np.zeros((Tm, Nm, J), dtype=bool)
    if hasattr(sim, "cohort_change"):
        chg = sim.cohort_change()
        cohort_change[:T, :N, :chg.shape[2]] = chg

    with telemetry.span("inputs.to_device"):
        if share_data_from is not None:
            src = share_data_from
            train_x, train_y = src.train_x, src.train_y
            test_x, test_y, init_w = src.test_x, src.test_y, src.init_w
        else:
            # [None]: the seed-major [S=1] axis (a reshape of the device
            # buffer, not a copy)
            train_x = jnp.asarray(sim.train_x)[None]
            train_y = jnp.asarray(sim.train_y)[None]
            test_x = jnp.asarray(sim.test_x)[None]
            test_y = jnp.asarray(sim.test_y)[None]
            init_w = jax.tree.map(
                lambda x: x[None],
                init_from_specs(sim.specs, jax.random.key(sim.seed)))

        return EngineInputs(
            train_x=train_x, train_y=train_y,
            test_x=test_x, test_y=test_y, init_w=init_w,
            seed_idx=jnp.int32(0),
            batch_idx=jnp.asarray(batch_idx),
            has_data=jnp.asarray(has_data), valid=jnp.asarray(valid),
            dev_masks=jnp.asarray(dev_masks),
            edge_masks=jnp.asarray(edge_masks),
            lr=jnp.asarray(lr), j_arr=jnp.asarray(j_arr),
            gamma0=jnp.float32(s.gamma0), lam=jnp.float32(s.lam),
            t_cold_boot=jnp.int32(s.t_cold_boot),
            t_valid=jnp.int32(T), k_valid=jnp.int32(K),
            n_valid=jnp.int32(N), s_valid=jnp.int32(steps),
            dev_time=jnp.asarray(dev_time), cons_time=jnp.asarray(cons_time),
            cons_energy=jnp.asarray(cons_energy),
            edge_hop=jnp.float32(2.0 * lp.lm_edge),
            cohort_change=jnp.asarray(cohort_change),
            agg_sel=jnp.int32(AGG_SEL.get(sim.aggregator, 0)),
            stale_beta=jnp.float32(s.staleness_discount),
            delay_delta=jnp.float32(s.delay_delta))


# ------------------------------------------------------------- jitted run
def _bcast_edges_tree(tree: PyTree, n: int) -> PyTree:
    """Broadcast a global model to per-edge copies: [...] -> [N, ...]."""
    return jax.tree.map(lambda x: jnp.broadcast_to(x, (n,) + x.shape), tree)


def _bcast_devices_tree(tree: PyTree, n: int, j: int) -> PyTree:
    """Broadcast edge models to device slots: [N, ...] -> [N, J, ...]."""
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[:, None], (n, j) + x.shape[1:]), tree)


def init_engine_carry(inp: EngineInputs, history_dtype=None) -> tuple:
    """The engine scan's round-zero carry (the full cross-round state:
    device/edge/global models, both HieAvg histories, the d_fedavg /
    delayed-grad stores and ages, the simulated clock, and the cumulative
    consensus energy).

    Extracted from ``_engine_body`` so chunked execution
    (``run_engine_chunk`` / ``BHFLSimulator.run_checkpointed``) can build
    the same round-zero state outside the jit, checkpoint a mid-run carry,
    and feed it back — the carry IS the whole resume state.  Values are
    identical to the inline construction (broadcasts and zeros are exact).
    """
    N, J = inp.dev_masks.shape[2:]
    init_w = jax.tree.map(lambda v: v[inp.seed_idx], inp.init_w)
    edge0 = _bcast_edges_tree(init_w, N)
    dev0 = _bcast_devices_tree(edge0, N, J)
    return (dev0,
            hieavg.init_history_batched(dev0, history_dtype),  # @r==0
            jax.tree.map(jnp.zeros_like, dev0),      # d_fedavg last /
            #   delayed_grad pending stores (mutually exclusive users)
            hieavg.init_history(edge0, history_dtype),         # @t==1
            jax.tree.map(jnp.zeros_like, edge0),
            init_w,
            jnp.float32(0.0),                        # simulated clock
            jnp.zeros((N, J), jnp.float32),   # delayed-grad edge ages
            jnp.zeros((N,), jnp.float32),     # delayed-grad global ages
            jnp.float32(0.0))                 # cumulative consensus J


def _vary_like(tree: PyTree, like: PyTree) -> PyTree:
    """Mark ``tree``'s leaves varying over every manual mesh axis that any
    leaf of ``like`` varies over.

    Under ``jax.shard_map`` a scan carry must enter with the varying axes
    it leaves with.  The round-zero carry is built from the replicated
    data plane and constants, while the first round already mixes in the
    sharded per-point planes, so the carry is cast up front.  Outside
    ``shard_map`` no axis varies and this is the identity.
    """
    axes = kernel_dispatch.varying_axes(like)

    def cast(x):
        missing = tuple(sorted(axes - jax.typeof(x).vma))
        return jax.lax.pcast(x, missing, to="varying") if missing else x

    return jax.tree.map(cast, tree)


#: ``EngineInputs`` fields with a leading global-round (T) axis — what
#: ``slice_rounds`` cuts per chunk for resumable execution.
ROUND_FIELDS = ("batch_idx", "dev_masks", "edge_masks", "lr", "dev_time",
                "cons_time", "cons_energy", "cohort_change")


def slice_rounds(inp: EngineInputs, t0: int, t1: int) -> EngineInputs:
    """A view of ``inp`` restricted to global rounds ``t0..t1-1`` (0-based
    rows of the T-leading planes).  Scalars — including the GLOBAL
    ``t_valid`` — ride along unchanged: the engine's round conditions
    (cold boot, history init, validity) compare against absolute round
    numbers, which is what makes chunked execution bitwise-composable."""
    return dataclasses.replace(
        inp, **{f: getattr(inp, f)[t0:t1] for f in ROUND_FIELDS})


def _engine_body(inp: EngineInputs, *, aggregator: str = "hieavg",
                 normalize: bool = False, history_dtype=None,
                 kernel_mode: str = "auto",
                 carry0: Optional[tuple] = None,
                 t_start: Optional[jnp.ndarray] = None,
                 with_carry: bool = False
                 ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray,
                            jnp.ndarray, jnp.ndarray]:
    """One whole BHFL run as a single compiled program.

    Returns per-global-round (accuracy [T], mean local loss [T],
    global-model round-to-round delta norm [T], simulated clock [T],
    cumulative consensus energy [T] in Joules).

    The energy row is the second traced cost axis beside the clock: the
    per-round ``cons_energy`` draws (the replayed chain's ``.energy``
    differenced per round — see ``replay_chain``) accumulate through the
    scan carry exactly like the clock.  Padded rounds contribute a
    bitwise-exact zero (the draw is 0.0 AND the carry passes through);
    rounds past ``t_valid`` repeat the final cumulative value.

    The clock is the latency fabric's cumulative simulated seconds after
    each global round: per edge round the slowest valid device's time draw
    (stragglers delayed, deadline-capped — see ``build_inputs``), summed
    over the K valid edge rounds per edge, maxed over the edges the global
    aggregation waits for (submitting edges; all valid edges when none
    submitted), plus the edge<->leader hop and any consensus stall
    ``max(0, L_bc - edge window)`` — constraint C2 made empirical: when
    consensus hides inside the K-round window it costs nothing, otherwise
    the round waits out the difference.  Rounds past ``t_valid`` repeat
    the final valid clock (like accuracy).

    Dims past the point's ``t_valid``/``k_valid``/``s_valid`` extents are
    sweep-fabric padding: a padded edge round or global round computes and
    then *discards* its result (the scan carry passes through unchanged,
    which under vmap costs the same as a branch anyway), a padded SGD step
    applies no update, and padded edge/device slots carry zero aggregation
    weight via ``valid``/``j_arr``.  Output rounds past ``t_valid`` repeat
    the final valid global model (accuracy) and report 0 loss/delta.

    Training data, the test split, and the init weights are gathered from
    the seed-major ``[S]`` data plane by ``inp.seed_idx``.  The seed index
    is folded straight into the batch gather (``train_x[seed_idx, bidx]``)
    so no per-point copy of the *training set* — the dominant input — is
    ever materialized; the test/init gathers are whole-row, so the sweep
    fabric keeps ``seed_idx`` unmapped on single-seed plans (the gathers
    then stay unbatched: one shared test split under vmap) and only
    multi-seed plans pay a per-point ``[P, n_test, ...]`` eval gather.

    ``history_dtype`` overrides HieAvg's history storage dtype end-to-end
    (EXPERIMENTS.md X1): bf16 cuts the two-model-copies-per-layer memory
    cost 2× for free, f8 4× at an accuracy cost; estimation math stays f32.

    ``aggregator`` is static: ``"hieavg"``/``"t_fedavg"``/``"d_fedavg"``/
    ``"delayed_grad"``/``"fedavg"`` trace only their own branch;
    ``"switched"`` traces hieavg, delayed_grad, AND fedavg and picks per
    run by the *traced* ``inp.agg_sel`` scalar — the sweep fabric's
    mixed-aggregation grids batch into one compiled program that way
    (the unselected strategies are the batching cost).  Delayed-gradient
    state (pending stores + consecutive-miss ages, both layers) rides the
    scan carry; ``inp.cohort_change`` resets a slot's pending/age when
    population-mode churn hands the slot to a new occupant.

    ``kernel_mode`` routes the aggregation phases through the kernel
    plane (``repro.kernels.dispatch``): the warm HieAvg edge/global
    aggregations, the cold-boot means, the FedAvg and delayed-gradient
    aggregates (the "switched" set), and the conv blocks of the train
    step and the eval.  ``"auto"`` resolves to the Pallas kernels and
    XLA's own convolution on TPU and to the pure-XLA reference with the
    im2col conv on CPU (zero overhead); ``"interpret"`` forces the
    Pallas interpreter (the CPU validation path the parity tests pin);
    ``"xla"`` forces the reference.  The SGD update, the eval head, the
    legacy ``t_fedavg``/``d_fedavg`` baselines and the tiny history
    bookkeeping are XLA on every path.
    """
    kernel_mode = kernel_dispatch.resolve_kernel_mode(kernel_mode)
    T, K, N, J = inp.dev_masks.shape
    steps, bs = inp.batch_idx.shape[-2:]
    D = N * J
    v32 = inp.valid.astype(jnp.float32)
    hd = inp.has_data
    step_ok = (jnp.arange(steps) < inp.s_valid).astype(jnp.float32)

    def passthru(ok, new, old):
        """Gate a carry update on a traced bool (padding = carry-through)."""
        return jax.tree.map(lambda a, b: jnp.where(ok, a, b), new, old)

    def sel3(sel, a, b, c):
        """Tri-select pytrees by the traced ``agg_sel`` scalar (the
        "switched" engine: 0 = hieavg, 1 = delayed_grad, 2 = fedavg)."""
        return jax.tree.map(
            lambda x, y, z: jnp.where(sel == 0, x, jnp.where(sel == 1, y, z)),
            a, b, c)

    def bleaf(m, x):
        """Broadcast a ``[N, J]`` slot mask against a ``[N, J, ...]`` leaf."""
        return m.reshape(m.shape + (1,) * (x.ndim - 2))

    def bcast_edges(tree):   # [...] global -> [N, ...]
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x, (N,) + x.shape), tree)

    def bcast_devices(tree):  # [N, ...] edge models -> [N, J, ...]
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x[:, None], (N, J) + x.shape[1:]),
            tree)

    def flat(tree):           # [N, J, ...] -> [N*J, ...]
        return jax.tree.map(lambda x: x.reshape((D,) + x.shape[2:]), tree)

    def unflat(tree):
        return jax.tree.map(lambda x: x.reshape((N, J) + x.shape[1:]), tree)

    def global_round(carry, xs):
        prev_carry = carry
        (device_w, ehist, elast, ghist, glast, prev_global, clock,
         eage, gage, energy) = carry
        (t, bidx_t, dmask_t, emask, lr_t, dtime_t, cons_t, cons_en_t,
         chg_t) = xs

        # ---- K edge rounds: local epoch + per-edge aggregation + sync
        def edge_aggregate(ws, dmask, r, k, ehist, elast, eage):
            """Every edge's aggregation of its slots' models ``ws``,
            broadcast back to the slots: the new edge-round carry."""
            if aggregator in ("hieavg", "switched"):
                ehist = jax.lax.cond(
                    r == 0,
                    lambda h: _vary_like(
                        hieavg.init_history_batched(ws, history_dtype), h),
                    lambda h: h, ehist)

                def cold(w, m, h):
                    return (kernel_dispatch.edge_aggregate_cold_batched(
                        w, inp.valid, mode=kernel_mode),
                            hieavg.update_history_batched(h, w, m))

                def warm(w, m, h):
                    return kernel_dispatch.edge_aggregate_batched(
                        w, m, h, inp.valid, inp.gamma0, inp.lam, normalize,
                        mode=kernel_mode)

                agg_h, ehist = jax.lax.cond(
                    t <= inp.t_cold_boot, cold, warm, ws, dmask, ehist)
            if aggregator in ("delayed_grad", "switched"):
                # first edge round: everyone counts present (nothing in
                # flight); cohort churn resets the slot's pending/age at
                # the round's first edge round
                m_eff = jnp.logical_or(dmask, r == 0)
                chg = jnp.logical_and(chg_t, k == 0)
                pend = jax.tree.map(
                    lambda p, w: jnp.where(bleaf(chg, w), w, p), elast, ws)
                age = eage * (1.0 - chg.astype(jnp.float32))
                agg_d, elast, eage = jax.vmap(
                    partial(kernel_dispatch.delayed_grad, mode=kernel_mode),
                    in_axes=(0, 0, 0, 0, None, None, 0))(
                    ws, m_eff, pend, age, inp.stale_beta, inp.delay_delta,
                    v32)

            if aggregator == "hieavg":
                edge_models = agg_h
            elif aggregator == "delayed_grad":
                edge_models = agg_d
            elif aggregator == "t_fedavg":
                edge_models = jax.vmap(baselines.t_fedavg)(ws, dmask, v32)
            elif aggregator == "d_fedavg":
                m_eff = jnp.logical_or(dmask, r == 0)  # first round: all in
                edge_models, elast = jax.vmap(baselines.d_fedavg)(
                    ws, m_eff, elast, v32)
            elif aggregator == "fedavg":
                edge_models = jax.vmap(
                    partial(kernel_dispatch.fedavg, mode=kernel_mode))(
                    ws, v32)
            elif aggregator == "switched":
                # all three strategies are computed; the traced per-point
                # agg_sel picks one — an aggregation-mode grid batches
                # into one padded shard_map call like any data field
                edge_models = sel3(
                    inp.agg_sel, agg_h, agg_d,
                    jax.vmap(partial(kernel_dispatch.fedavg,
                                     mode=kernel_mode))(ws, v32))
            else:
                raise ValueError(f"unknown aggregator {aggregator!r}")

            return (bcast_devices(edge_models), ehist, elast, eage)

        def edge_round(c, xs_k):
            prev_c = c
            device_w, ehist, elast, eage = c
            # [N,J,steps,B], [N,J], scalar lr, round counter r, k index,
            # per-device time draws [N,J]
            bidx, dmask, lr, r, k, dtime = xs_k

            with jax.named_scope(telemetry.TRAIN):
                x = inp.train_x[inp.seed_idx, bidx] \
                    * hd[:, :, None, None, None, None, None]
                y = jnp.where(hd[:, :, None, None] > 0,
                              inp.train_y[inp.seed_idx, bidx], 0)
                pflat, loss = train_epoch_body(
                    flat(device_w), x.reshape((D, steps, bs) + x.shape[4:]),
                    y.reshape(D, steps, bs), lr, step_ok=step_ok,
                    kernel_mode=kernel_mode)
            # outside the scope: XLA merges this reshape with the edge
            # aggregation's, and the merged op_name would name both phases
            ws = unflat(pflat)
            dev_loss = loss.reshape(N, J)

            with jax.named_scope(telemetry.EDGE_AGG):
                edge_c = edge_aggregate(ws, dmask, r, k, ehist, elast, eage)
            # per-edge elapsed: the slowest valid device closes the round
            # (padded slots carry dev_time 0; padded edge rounds count 0)
            el = jnp.max(jnp.where(inp.valid, dtime, 0.0), axis=1)
            el = el * (k < inp.k_valid)
            # padded edge round (k >= k_valid): carry passes through
            return passthru(k < inp.k_valid, edge_c, prev_c), (dev_loss, el)

        ks = jnp.arange(K)
        rs = (t - 1) * K + ks
        (device_w, ehist, elast, eage), (dev_losses, edge_els) = jax.lax.scan(
            edge_round, (device_w, ehist, elast, eage),
            (bidx_t, dmask_t, lr_t, rs, ks, dtime_t))

        # ---- global aggregation on the (replayed) leader
        def global_aggregate(device_w, ghist, glast, gage):
            """The leader's aggregation of the edge models, broadcast back
            to the device slots."""
            # after the sync every device slot holds its edge model
            edge_models = jax.tree.map(lambda x: x[:, 0], device_w)
            if aggregator in ("hieavg", "switched"):
                ghist = jax.lax.cond(
                    t == 1,
                    lambda h: _vary_like(
                        hieavg.init_history(edge_models, history_dtype), h),
                    lambda h: h, ghist)
                pw = inp.j_arr / jnp.sum(inp.j_arr)

                def coldg(w, m, h):
                    return (kernel_dispatch.global_aggregate_cold(
                        w, inp.j_arr, mode=kernel_mode),
                            hieavg.update_history(h, w, m))

                def warmg(w, m, h):
                    return kernel_dispatch.global_aggregate(
                        w, m, h, pw, inp.gamma0, inp.lam, normalize,
                        mode=kernel_mode)

                gagg_h, ghist = jax.lax.cond(
                    t <= inp.t_cold_boot, coldg, warmg, edge_models, emask,
                    ghist)
            if aggregator in ("delayed_grad", "switched"):
                # edges are fixed infrastructure — no churn reset here
                m_eff = jnp.logical_or(emask, t == 1)
                gagg_d, glast, gage = kernel_dispatch.delayed_grad(
                    edge_models, m_eff, glast, gage, inp.stale_beta,
                    inp.delay_delta, inp.j_arr, mode=kernel_mode)

            if aggregator == "hieavg":
                global_w = gagg_h
            elif aggregator == "delayed_grad":
                global_w = gagg_d
            elif aggregator == "t_fedavg":
                global_w = baselines.t_fedavg(edge_models, emask, inp.j_arr)
            elif aggregator == "d_fedavg":
                m_eff = jnp.logical_or(emask, t == 1)
                global_w, glast = baselines.d_fedavg(
                    edge_models, m_eff, glast, inp.j_arr)
            elif aggregator == "switched":
                global_w = sel3(inp.agg_sel, gagg_h, gagg_d,
                                kernel_dispatch.fedavg(edge_models, inp.j_arr,
                                                       mode=kernel_mode))
            else:
                global_w = kernel_dispatch.fedavg(edge_models, inp.j_arr,
                                                  mode=kernel_mode)
            return (bcast_devices(bcast_edges(global_w)), global_w, ghist,
                    glast, gage)

        with jax.named_scope(telemetry.GLOBAL_AGG):
            device_w, global_w, ghist, glast, gage = global_aggregate(
                device_w, ghist, glast, gage)

        # ---- per-round metrics (same definitions as the legacy loop);
        # test accuracy is evaluated OUTSIDE the scan, batched over rounds.
        # The last *valid* edge round's losses, not dev_losses[-1]: trailing
        # K entries may be sweep padding.
        last_loss = jnp.take(dev_losses, inp.k_valid - 1, axis=0)
        loss = jnp.sum(last_loss * v32) / jnp.maximum(jnp.sum(v32), 1.0)
        delta = jnp.sqrt(sum(
            jnp.sum(jnp.square(a - b)) for a, b in
            zip(jax.tree.leaves(global_w), jax.tree.leaves(prev_global))))

        # ---- simulated clock: the global aggregation waits for the
        # slowest SUBMITTING edge's K-round window (all valid edges when
        # every edge straggled), plus the edge<->leader hop, plus the
        # consensus stall when L_bc does not hide inside the window (C2)
        window = jnp.sum(edge_els, axis=0)             # [N]
        valid_edge = inp.j_arr > 0
        sub = emask & valid_edge
        w_sub = jnp.max(jnp.where(sub, window, 0.0))
        w_all = jnp.max(jnp.where(valid_edge, window, 0.0))
        w = jnp.where(jnp.any(sub), w_sub, w_all)
        round_time = w + inp.edge_hop + jnp.maximum(0.0, cons_t - w)

        # padded global round (t > t_valid): carry passes through, outputs
        # repeat the final valid global model/clock with zeroed loss/delta
        t_ok = t <= inp.t_valid
        out_carry = passthru(t_ok, (device_w, ehist, elast, ghist, glast,
                                    global_w, clock + round_time,
                                    eage, gage, energy + cons_en_t),
                             prev_carry)
        return out_carry, (out_carry[5], jnp.where(t_ok, loss, 0.0),
                           jnp.where(t_ok, delta, 0.0), out_carry[6],
                           out_carry[9])

    # round-zero carry unless resuming a chunked run (the carry IS the
    # whole cross-round state — see init_engine_carry); the scanned round
    # numbers are GLOBAL (t_start-offset), so cold boot / history-init /
    # validity conditions are chunk-invariant
    if carry0 is None:
        carry0 = init_engine_carry(inp, history_dtype)
    carry0 = _vary_like(carry0, inp)
    t0 = jnp.int32(0) if t_start is None else t_start
    xs = (t0 + jnp.arange(1, T + 1), inp.batch_idx, inp.dev_masks,
          inp.edge_masks, inp.lr, inp.dev_time, inp.cons_time,
          inp.cons_energy, inp.cohort_change)
    final_carry, (globals_per_round, losses, deltas, clocks, energies) = \
        jax.lax.scan(global_round, carry0, xs)
    with jax.named_scope(telemetry.EVAL):
        accs = jax.lax.map(
            lambda w: eval_accuracy(w, inp.test_x[inp.seed_idx],
                                    inp.test_y[inp.seed_idx], kernel_mode),
            globals_per_round)
    if with_carry:
        return (accs, losses, deltas, clocks, energies), final_carry
    return accs, losses, deltas, clocks, energies


@partial(jax.jit, static_argnames=("aggregator", "normalize",
                                   "history_dtype", "kernel_mode"))
def run_engine(inp: EngineInputs, *, aggregator: str = "hieavg",
               normalize: bool = False, history_dtype=None,
               kernel_mode: str = "auto"
               ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray,
                          jnp.ndarray, jnp.ndarray]:
    """The standard jitted entry — see ``_engine_body`` for the contract
    (returns accuracy, loss, delta norm, simulated clock, cumulative
    consensus energy — each ``[T]``).

    Input buffers are left intact (callers may reuse ``inp``); the
    donating twin is ``run_engine_donated``.
    """
    return _engine_body(inp, aggregator=aggregator, normalize=normalize,
                        history_dtype=history_dtype, kernel_mode=kernel_mode)


@partial(jax.jit, static_argnames=("aggregator", "normalize",
                                   "history_dtype", "kernel_mode"))
def run_engine_chunk(inp: EngineInputs, carry: tuple, t_start: jnp.ndarray,
                     *, aggregator: str = "hieavg", normalize: bool = False,
                     history_dtype=None, kernel_mode: str = "auto"
                     ) -> tuple[tuple, tuple]:
    """Run a contiguous segment of global rounds and return the carry.

    ``inp`` is a ``slice_rounds`` view covering rounds ``t_start..t_start+C``
    (0-based), ``carry`` the scan state after round ``t_start`` (round zero:
    ``init_engine_carry``).  Returns ``((acc, loss, delta, clock, energy)
    each [C], new_carry)``.  ``t_start`` is TRACED, so every equal-length
    chunk of a run shares one compiled program; running the chunks back to
    back is the same per-round op sequence as one full-length scan, and
    feeding a checkpointed carry back in resumes bitwise (the carry is the
    entire cross-round state — ``BHFLSimulator.run_checkpointed`` builds
    the round-level checkpoint/resume loop on top of this).
    """
    return _engine_body(inp, aggregator=aggregator, normalize=normalize,
                        history_dtype=history_dtype, kernel_mode=kernel_mode,
                        carry0=carry, t_start=t_start, with_carry=True)


@partial(jax.jit, static_argnames=("aggregator", "normalize",
                                   "history_dtype", "kernel_mode"),
         donate_argnums=(0,))
def _run_engine_donated(hot: dict, shared: dict, *,
                        aggregator: str, normalize: bool, history_dtype,
                        kernel_mode: str):
    return _engine_body(merge_inputs(hot, shared), aggregator=aggregator,
                        normalize=normalize, history_dtype=history_dtype,
                        kernel_mode=kernel_mode)


def run_engine_donated(inp: EngineInputs, *, aggregator: str = "hieavg",
                       normalize: bool = False, history_dtype=None,
                       kernel_mode: str = "auto"
                       ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray,
                                  jnp.ndarray, jnp.ndarray]:
    """``run_engine`` with the hot input planes DONATED to the program.

    Every ``EngineInputs`` field except the seed-major data plane
    (``SHARED_DATA_FIELDS`` — aliased across callers, never donated) is
    handed to XLA for buffer reuse, so the run does not hold the caller's
    copy of the batch-index/mask/latency planes alive next to its own
    working set.  ``inp``'s hot leaves are DELETED afterwards — callers
    must treat the inputs as consumed (``BHFLSimulator.run`` rebuilds
    them per call; the sweep runners donate per bucket the same way).
    Numerics are identical to ``run_engine`` (same traced body).
    """
    hot, shared = split_inputs(inp)
    with warnings.catch_warnings():
        # expected: the engine's outputs are tiny [T] rows, so XLA rarely
        # finds an input-output alias for the big donated planes — the
        # donation is still correct (and pays off where aliasing applies);
        # the caller-side release of the consumed inputs is the real win
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        return _run_engine_donated(hot, shared, aggregator=aggregator,
                                   normalize=normalize,
                                   history_dtype=history_dtype,
                                   kernel_mode=kernel_mode)


# ----------------------------------------------------------------- sweeps
# The sweep subsystem lives in ``repro.fl.sweep``: a shape-polymorphic
# planner (grids may change topology/rounds; points are grouped into shape
# buckets and padded to each bucket's maxima) plus mesh placement
# (shard_map over the data axis per bucket, vmap fallback).
# ``run_sweep``/``SweepResult`` are re-exported there and via ``repro.fl``.
