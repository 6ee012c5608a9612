"""BHFL simulator — runs the paper's experiments (Sec. 6) end to end.

Simulates N edge servers × J_i local devices training the paper's CNN on a
non-IID class-partitioned dataset, with the full BHFL workflow:

  1. Updates Submission — every device trains locally (vmapped SGD epoch),
  2. Edge Aggregation   — HieAvg (or a benchmark aggregator) per edge,
     repeated K times per global round,
  3. Blockchain Consensus — Raft leader election overlapped with the K edge
     rounds (latency-accounted, Sec. 5.1.3),
  4. Global Aggregation — the leader aggregates edge models, commits a block.

Straggler schedules (permanent / temporary, per layer) drive boolean masks;
the aggregator sees only the masks, exactly like a real deadline-based
system.  Aggregators: ``hieavg`` (the paper), ``t_fedavg`` (drop),
``d_fedavg`` (reuse last), ``delayed_grad`` (stale updates arrive one round
late with staleness-discounted weights, arXiv:2102.06329), ``fedavg``
(oracle; meaningful with no-straggler schedules).

All devices are simulated in one jitted vmap over the stacked device
dimension, so a full Fig. 2 run takes seconds on CPU.

``run()`` delegates to the fully-jitted batched engine (``repro.fl.engine``):
one compiled program per run instead of a Python loop per edge per round.
The original per-edge loop is kept as ``run_legacy()`` — it is the numerics
reference for ``tests/test_engine_parity.py`` and the baseline for
``BENCH_engine.json``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.bhfl_cnn import BHFLSetting
from repro.core import (baselines, consensus as _consensus, hieavg,
                        latency as lat, rng as rng_streams,
                        straggler as strag)
from repro.kernels import dispatch as _kdispatch
from repro.data import by_class, class_images, class_pools
from repro.models import cnn_accuracy, cnn_specs, init_from_specs
from repro.optim import paper_lr

from repro.checkpoint import ckpt as _ckpt

from . import engine as _engine
from . import faults as _faults
from . import population as _population

PyTree = Any

# the shared local-training epoch lives in the engine module now
_train_epoch = _engine.train_epoch


def _stack(trees: list[PyTree]) -> PyTree:
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def _index(tree: PyTree, i) -> PyTree:
    return jax.tree.map(lambda x: x[i], tree)


def _bcast_like(tree: PyTree, n: int) -> PyTree:
    return jax.tree.map(lambda x: jnp.broadcast_to(x, (n,) + x.shape), tree)


# ----------------------------------------------------------------- results
@dataclasses.dataclass
class RunResult:
    accuracy: np.ndarray          # [T] test accuracy after each global round
    loss: np.ndarray              # [T] mean local training loss
    grad_norm: np.ndarray         # [T] proxy: global-model round-to-round delta
    wall_time: float
    sim_latency: float            # paper's latency model total (Sec. 5.1.4)
    blocks: int                   # committed blockchain blocks
    chain_valid: bool
    sim_clock: Optional[np.ndarray] = None  # [T] cumulative simulated
    #   seconds after each global round (latency fabric; engine path —
    #   pairs with ``accuracy`` into a time-to-accuracy curve).
    #   ``run_legacy`` leaves it None.
    sim_energy: Optional[np.ndarray] = None  # [T] cumulative consensus
    #   energy (J) after each global round — the second traced cost axis
    #   (consensus zoo; engine path only, ``run_legacy`` leaves it None).


# --------------------------------------------------------------- simulator
class BHFLSimulator:
    """One BHFL deployment over the synthetic MNIST surrogate."""

    def __init__(self, setting: BHFLSetting = BHFLSetting(),
                 aggregator: str = "hieavg",
                 device_stragglers: str = "temporary",
                 edge_stragglers: str = "temporary",
                 j_per_edge: Optional[list[int]] = None,
                 n_train: int = 4000, n_test: int = 1000,
                 steps_per_epoch: Optional[int] = None,
                 normalize: bool = False,
                 fail_leader_at: Optional[int] = None,
                 seed: Optional[int] = None,
                 history_dtype=None,
                 kernel_mode: str = "auto",
                 population=None,
                 j_cohort: Optional[int] = None,
                 device_rates: Optional[list] = None,
                 faults: Optional[_faults.FaultSpec] = None):
        """``fail_leader_at``: global round at which the current Raft
        leader crashes — the paper's single-point-of-failure scenario.
        The consortium re-elects and training continues (the failed edge
        also becomes a permanent straggler at the global layer).  Since
        the chaos plane landed this is sugar for a one-event
        ``FaultSpec(leader_crash_round=...)`` — it rides the fault
        schedule, parity-pinned bitwise against the scripted path.

        ``faults``: an explicit ``repro.fl.faults.FaultSpec`` overriding
        the setting's fault fields (``edge_fail_rate`` …
        ``stall_backoff``), from which the per-round fault schedule is
        compiled by default.  The schedule draws from the deployment's
        dedicated ``"faults"`` RNG stream (an all-zero spec is
        draw-free) and is pure data: it feeds the chain replay (validator
        churn, quorum stall-and-retry) and the engine's submission/edge
        masks (outages, bursts, message loss).  Engine path only —
        ``run_legacy`` refuses stochastic fault processes.

        ``history_dtype``: HieAvg history storage dtype override (engine
        path only) — straggler estimation keeps two extra model copies
        per participant per layer; ``jnp.bfloat16`` cuts that 2× at no
        measured accuracy cost, ``jnp.float8_e4m3fn`` 4× with an accuracy
        penalty.  The estimation math stays f32.  See EXPERIMENTS.md X1.

        ``kernel_mode``: the kernel-plane backend knob (engine path only,
        like ``history_dtype``) — ``"auto"`` runs the fused Pallas
        aggregation/SGD kernels on TPU and the pure-XLA reference on
        CPU; ``"interpret"``/``"pallas"``/``"xla"`` force a path.  See
        ``repro.kernels.dispatch``.

        ``population`` (+ ``j_cohort``): population mode — an int device
        -population size (with ``j_cohort`` devices gathered per edge per
        round), a ``fl.population.PopulationSpec``, or a prebuilt
        ``DevicePopulation`` store (shared across sweep points).  Each
        global round samples a cohort ``[N, j_cohort]`` from the
        population by index; straggler propensity, data shard, and speed
        come from the occupant's profile while all per-round randomness
        is keyed by slot, so memory and per-round work scale with the
        cohort, not the population.  Engine path only (``run_legacy``
        refuses).  See ``repro.fl.population``.

        ``device_rates``: per-device clock-rate multipliers (length =
        total devices, positive) for a heterogeneous fleet — device d's
        per-round latency draw is scaled by ``device_rates[d]`` (before
        straggler slowdown / deadline capping) instead of iid draws
        around one shared ``LatencyParams``.  Refused in population
        mode, where the occupant's ``time_scale`` profile already plays
        this role per cohort."""
        self.s = setting
        self.aggregator = aggregator
        self.normalize = normalize
        self.history_dtype = history_dtype
        # resolve once: validates the knob early and keys the engine's jit
        # cache on the concrete mode instead of "auto"
        self.kernel_mode = _kdispatch.resolve_kernel_mode(kernel_mode)
        self.fail_leader_at = fail_leader_at
        self.seed = setting.seed if seed is None else seed
        self.N = setting.n_edges
        # ---- population mode: the cohort shape is fixed by the store
        if population is not None:
            if j_per_edge is not None:
                raise ValueError(
                    "population mode fixes the per-edge device count to "
                    "j_cohort; pass j_cohort instead of j_per_edge")
            self.pop = _population.as_population(
                population, j_cohort, n_classes=setting.n_classes,
                max_classes=setting.classes_per_device,
                seed=rng_streams.stream_seed(self.seed, "population"))
            self.j_per_edge = [self.pop.spec.j_cohort] * self.N
        else:
            self.pop = None
            self.j_per_edge = j_per_edge or [setting.j_per_edge] * self.N
        if len(self.j_per_edge) != self.N:
            raise ValueError(
                f"j_per_edge has {len(self.j_per_edge)} entries for "
                f"n_edges={self.N}; a ragged device list must name every "
                "edge exactly once")
        self.D = sum(self.j_per_edge)  # total devices (cohort size in
        #                                population mode)
        # paper semantics: one local iteration = one epoch over the
        # device's own shard — so per-round steps scale inversely with the
        # device count when the total data pool is fixed (Sec. 6.1.5)
        self.steps = steps_per_epoch if steps_per_epoch is not None \
            else max(1, n_train // (self.D * setting.batch_size))

        # ---- data: synthetic class-clustered images, non-IID partition.
        # All host-side randomness is drawn from named SeedSequence streams
        # (core.rng): independent per (seed, stream), collision-free across
        # adjacent seeds — see tests/test_rng_streams.py.
        imgs, labels = class_images(
            n_train + n_test, seed=rng_streams.stream_seed(self.seed, "data"),
            hw=setting.image_hw, n_classes=setting.n_classes)
        # kept as (read-only) numpy views: the device put happens once in
        # build_inputs / the jitted eval — a sweep planner constructs one
        # simulator per grid point, and P per-instance device copies of
        # the test set would pin memory for nothing
        self.test_x = imgs[n_train:]
        self.test_y = labels[n_train:]
        self.train_x, self.train_y = imgs[:n_train], labels[:n_train]
        part_seed = rng_streams.stream_seed(self.seed, "partition")
        if self.pop is None:
            parts = by_class(labels[:n_train], self.N, self.j_per_edge,
                             max_classes=setting.classes_per_device,
                             seed=part_seed)
            self.device_idx = [idx for edge in parts for idx in edge]
        else:
            # population shards are the per-class pools themselves: the
            # occupant's classes select pools, batches sample from them
            # (overlapping shards — see data.partition)
            self.device_idx = None
            self._pool, self._pool_off, self._pool_cnt = class_pools(
                labels[:n_train])
            used = np.unique(self.pop.classes)
            if (self._pool_cnt[used] == 0).any():
                raise ValueError(
                    "population mode needs every assigned class present in "
                    "the train split; increase n_train or n_classes")

        # ---- straggler schedules (submission masks per round)
        rounds = setting.t_global_rounds * setting.k_edge_rounds + 1
        if self.pop is not None:
            self.cohort_ids, self.dev_masks = self._population_schedules(
                rounds, device_stragglers)
        else:
            self.cohort_ids = None
            n_dev_strag = int(round(
                setting.straggler_frac * setting.j_per_edge))
            dev_masks = []
            for e in range(self.N):
                kw = dict(stop_round=setting.permanent_stop_round
                          * setting.k_edge_rounds) \
                    if device_stragglers == "permanent" else {}
                dev_masks.append(strag.from_fraction(
                    rounds, self.j_per_edge[e],
                    n_dev_strag / max(setting.j_per_edge, 1),
                    kind=device_stragglers,
                    seed=rng_streams.stream_seed(self.seed, "dev_masks", e),
                    **kw))
            self.dev_masks = dev_masks                  # list of [rounds, J_e]
        kw = dict(stop_round=setting.permanent_stop_round) \
            if edge_stragglers == "permanent" else {}
        self.edge_masks = strag.from_fraction(
            setting.t_global_rounds + 1, self.N, setting.straggler_frac,
            kind=edge_stragglers,
            seed=rng_streams.stream_seed(self.seed, "edge_masks"),
            **kw)  # [T+1, N]

        # ---- models
        self.specs = cnn_specs(setting.image_hw, 1, setting.n_classes,
                               c1=setting.cnn_c1, c2=setting.cnn_c2)
        # ---- latency fabric: the Sec. 5 model for this deployment plus
        # the consensus chain (protocol, link latency, and shard count all
        # come from the setting, so consensus is a data-batched sweep
        # field — see repro.core.consensus)
        rate_mult = None
        if device_rates is not None:
            if self.pop is not None:
                raise ValueError(
                    "population mode draws per-device rates from the "
                    "store's time_scale profiles; device_rates only "
                    "applies to fixed fleets")
            rate_mult = np.asarray(device_rates, np.float64).reshape(-1)
            if rate_mult.shape != (self.D,):
                raise ValueError(
                    f"device_rates must name every device once "
                    f"(D={self.D}), got shape {rate_mult.shape}")
            if not (rate_mult > 0).all():
                raise ValueError("device_rates must be positive "
                                 "multipliers")
        self.lat = lat.LatencyParams(
            T=setting.t_global_rounds, N=self.N,
            J=int(round(float(np.mean(self.j_per_edge)))),
            lm_device=setting.lm_device, lp_device=setting.lp_device,
            lm_edge=setting.lm_edge, rate_mult=rate_mult)
        self.chain = _consensus.make_chain(
            setting.consensus, self.N,
            link_latency=setting.link_latency, n_shards=setting.n_shards,
            seed=rng_streams.stream_seed(self.seed, "chain"))
        # ---- fault plane (repro.fl.faults): the declarative spec comes
        # from the setting's fault fields unless passed explicitly;
        # fail_leader_at rides the spec as its one-event leader-crash
        # schedule.  Compiled once into per-round event planes on the
        # dedicated "faults" stream — the engine and the chain replay
        # consume the planes as data.
        if faults is None:
            faults = _faults.FaultSpec.from_setting(
                setting, leader_crash_round=fail_leader_at)
        elif faults.leader_crash_round is None and fail_leader_at is not None:
            faults = dataclasses.replace(faults,
                                         leader_crash_round=fail_leader_at)
        self.fault_spec = faults
        self.fail_leader_at = faults.leader_crash_round
        self.fault_schedule = _faults.compile_schedule(
            faults, t_rounds=setting.t_global_rounds,
            k_rounds=setting.k_edge_rounds, n_edges=self.N,
            j_per_edge=list(self.j_per_edge), seed=self.seed)

    # ----------------------------------------------------- population plane
    def _population_schedules(self, rounds: int, device_stragglers: str
                              ) -> tuple[np.ndarray, list[np.ndarray]]:
        """Sample the cohort plan and its slot-keyed straggler masks.

        Returns ``(cohort_ids [T, N, J], dev_masks list of [rounds, J])``.
        All draws are SLOT-keyed uniforms compared against the occupant's
        gathered ``miss_prob`` — so a gathered cohort and a materialized
        ``store.subset`` of the same rows see identical masks (the
        cohort-gather parity invariant, tests/test_population.py).

        Unlike the fixed-membership ``temporary`` schedule (forced return
        the round after a miss), population straggling is i.i.d. Bernoulli
        per round from the occupant's propensity — the fleet-realistic
        model; cold-boot edge rounds (``t <= t_cold_boot``) are never
        missed, matching Alg. 1's assumption.
        """
        s, N, J = self.s, self.N, self.pop.spec.j_cohort
        T, K = s.t_global_rounds, s.k_edge_rounds
        cohort_ids = self.pop.cohort_ids(
            T, N, rng_streams.stream_seed(self.seed, "cohort"))
        if device_stragglers not in ("temporary", "none"):
            raise ValueError(
                "population mode draws straggling from per-device "
                "propensity profiles; device_stragglers must be "
                f"'temporary' or 'none', got {device_stragglers!r}")
        if device_stragglers == "none":
            masks = np.ones((rounds, N, J), dtype=bool)
        else:
            # occupant of global round t holds its slot for all K edge
            # rounds; the trailing schedule row reuses the last cohort
            ids_r = np.repeat(cohort_ids, K, axis=0)
            ids_r = np.concatenate([ids_r, ids_r[-1:]])[:rounds]
            u = rng_streams.stream_rng(self.seed, "dev_masks").random(
                (rounds, N, J))
            masks = u >= self.pop.miss_prob[ids_r]
            masks[:s.t_cold_boot * K] = True
        return cohort_ids, [masks[:, e, :] for e in range(N)]

    def cohort_change(self) -> np.ndarray:
        """``[T, N, J]`` bool — slot occupant changed at the start of global
        round t (always False at t=0 and outside population mode).  Feeds
        the engine's delayed-gradient pending/age reset."""
        T = self.s.t_global_rounds
        J = max(self.j_per_edge)
        if self.cohort_ids is None:
            return np.zeros((T, self.N, J), dtype=bool)
        chg = np.zeros((T, self.N, J), dtype=bool)
        chg[1:] = self.cohort_ids[1:] != self.cohort_ids[:-1]
        return chg

    def cohort_time_scale(self) -> Optional[np.ndarray]:
        """``[T*K, D]`` per-round occupant round-time multipliers for the
        latency fabric (None outside population mode)."""
        if self.cohort_ids is None:
            return None
        K = self.s.k_edge_rounds
        ids_r = np.repeat(self.cohort_ids, K, axis=0)    # [T*K, N, J]
        return self.pop.time_scale[ids_r].reshape(ids_r.shape[0], self.D)

    # ------------------------------------------------------------- batching
    def _epoch_batches(self, rng) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Sample [D, steps, B] batches from each device's own shard."""
        bs = self.s.batch_size
        xs = np.zeros((self.D, self.steps, bs, self.s.image_hw,
                       self.s.image_hw, 1), np.float32)
        ys = np.zeros((self.D, self.steps, bs), np.int32)
        for d, idx in enumerate(self.device_idx):
            if len(idx) == 0:
                continue
            take = rng.choice(idx, size=(self.steps, bs), replace=True)
            xs[d] = self.train_x[take]
            ys[d] = self.train_y[take]
        return jnp.asarray(xs), jnp.asarray(ys)

    def paper_latency(self) -> float:
        """The paper's latency model total (Sec. 5.1.4) for this deployment."""
        return lat.total_latency(self.s.k_edge_rounds, self.lat)

    # ----------------------------------------------------------------- run
    def run(self, progress: bool = False) -> RunResult:
        """Run the deployment on the fully-jitted batched engine.

        Numerically equivalent to ``run_legacy`` (see
        tests/test_engine_parity.py) but executes the whole run as one
        compiled program.  Uses a fresh batch-RNG on the deployment's
        ``"batches"`` stream (``core.rng``), so every ``run()`` call on the
        same instance is identical; the Raft chain, however, advances per
        call exactly like the legacy loop.
        """
        t0 = time.time()
        inp = _engine.build_inputs(self)
        # donated entry: the freshly built hot input planes are handed to
        # the compiled run for buffer reuse (they are rebuilt per call, so
        # nothing else holds them)
        accs, losses, deltas, clock, energy = _engine.run_engine_donated(
            inp, aggregator=self.aggregator, normalize=self.normalize,
            history_dtype=self.history_dtype, kernel_mode=self.kernel_mode)
        accs, losses, deltas, clock, energy = (
            np.asarray(accs), np.asarray(losses), np.asarray(deltas),
            np.asarray(clock), np.asarray(energy))
        if progress:
            for t in range(1, self.s.t_global_rounds + 1):
                if t % 10 == 0 or t == 1:
                    print(f"  t={t:3d} acc={accs[t - 1]:.4f} "
                          f"loss={losses[t - 1]:.4f} "
                          f"clock={clock[t - 1]:.1f}s")
        return RunResult(
            accuracy=accs, loss=losses, grad_norm=deltas,
            wall_time=time.time() - t0, sim_latency=self.paper_latency(),
            blocks=len(self.chain.blocks) - 1,
            chain_valid=self.chain.validate(), sim_clock=clock,
            sim_energy=energy)

    # ------------------------------------------------- checkpointed run
    def run_checkpointed(self, ckpt_dir: str, *, every: int = 10,
                         resume: bool = True,
                         progress: bool = False) -> RunResult:
        """``run()`` in resumable segments of ``every`` global rounds,
        checkpointing after each one (``repro.checkpoint.ckpt`` — atomic
        npz of the engine scan carry plus the per-round outputs so far).

        A killed run restarts from the latest surviving checkpoint and
        finishes **bitwise-identically** to the uninterrupted call: the
        carry is the engine's entire cross-round state, every segment runs
        the same compiled chunk program (``engine.run_engine_chunk``,
        global round numbers threaded through), and the checkpoint
        round-trips every dtype exactly (bf16 histories via raw bits).
        Resume from a **fresh** simulator instance (same constructor
        arguments): the chain replay, fault schedule, and batch/latency
        draws are all rebuilt from their named RNG streams, so the
        rebuilt input planes are byte-identical — whereas reusing a
        half-run instance would replay the chain from an advanced RNG
        state.  Pass ``resume=False`` to ignore (and overwrite) existing
        checkpoints.

        Numerics match ``run()`` (same per-round op sequence; XLA may
        fuse chunk boundaries differently, so cross-entry comparisons are
        allclose, not bitwise — the bitwise contract is between
        checkpointed runs).
        """
        if every < 1:
            raise ValueError(f"every must be >= 1, got {every}")
        t0 = time.time()
        T = self.s.t_global_rounds
        inp = _engine.build_inputs(self)
        carry = _engine.init_engine_carry(inp, self.history_dtype)
        keys = ("accuracy", "loss", "delta", "clock", "energy")
        outs = {k: np.zeros((0,), np.float32) for k in keys}
        t_done = 0
        if resume:
            step = _ckpt.latest_step(ckpt_dir)
            if step is not None:
                like = {"carry": carry,
                        "outs": {k: np.zeros((step,), np.float32)
                                 for k in keys}}
                state, _ = _ckpt.restore_checkpoint(ckpt_dir, like, step)
                carry, outs, t_done = state["carry"], state["outs"], step
                if progress:
                    print(f"  resumed from checkpoint @ t={t_done}")
        while t_done < T:
            t1 = min(t_done + every, T)
            seg = _engine.run_engine_chunk(
                _engine.slice_rounds(inp, t_done, t1), carry,
                jnp.int32(t_done), aggregator=self.aggregator,
                normalize=self.normalize, history_dtype=self.history_dtype,
                kernel_mode=self.kernel_mode)
            (acc, loss, delta, clock, energy), carry = seg
            for k, v in zip(keys, (acc, loss, delta, clock, energy)):
                outs[k] = np.concatenate([outs[k],
                                          np.asarray(v, np.float32)])
            t_done = t1
            _ckpt.save_checkpoint(ckpt_dir, t_done,
                                  {"carry": carry, "outs": outs},
                                  metadata={"t": t_done})
            if progress:
                print(f"  t={t_done:3d} acc={outs['accuracy'][-1]:.4f} "
                      f"clock={outs['clock'][-1]:.1f}s  [checkpointed]")
        return RunResult(
            accuracy=outs["accuracy"], loss=outs["loss"],
            grad_norm=outs["delta"], wall_time=time.time() - t0,
            sim_latency=self.paper_latency(),
            blocks=len(self.chain.blocks) - 1,
            chain_valid=self.chain.validate(), sim_clock=outs["clock"],
            sim_energy=outs["energy"])

    # ---------------------------------------------------------- legacy run
    def run_legacy(self, progress: bool = False) -> RunResult:
        """The original per-edge Python loop (numerics reference).

        Uses a fresh per-run batch generator on the same ``"batches"``
        stream as the engine path — repeated or interleaved ``run()`` /
        ``run_legacy()`` calls on one instance are all batch-identical.
        (Previously this consumed a shared mutable ``self.rng``, so a
        second legacy run silently diverged from the first.)
        """
        if self.pop is not None:
            raise ValueError(
                "population mode runs on the engine path only; use run()")
        if self.fault_spec.any_faults:
            raise ValueError(
                "stochastic fault injection (repro.fl.faults) runs on the "
                "engine path only; use run()")
        s = self.s
        t0 = time.time()
        batch_rng = rng_streams.stream_rng(self.seed, "batches")
        # device-resident test set for the per-round eval (self.test_x is
        # a numpy view; re-committing it every round would tax the loop)
        test_x, test_y = jnp.asarray(self.test_x), jnp.asarray(self.test_y)
        key = jax.random.key(self.seed)
        global_w = init_from_specs(self.specs, key)
        device_w = _bcast_like(global_w, self.D)        # stacked [D, ...]

        # per-edge device histories + the global edge-model history
        edge_slices = np.cumsum([0] + self.j_per_edge)
        dev_hist = None      # stacked [N? ragged] -> list per edge
        glob_hist = None
        dev_last = None      # d_fedavg last-submission stores
        glob_last = None

        accs, losses, deltas = [], [], []
        prev_global = global_w
        round_ctr = 0        # edge-round counter (t*K + k) for masks/lr

        failed_edge: Optional[int] = None
        # failover availability is DERIVED per run, never written back to
        # self.edge_masks — a repeated run sees pristine simulator state
        # (matches the engine path's replay-derived edge_avail plane)
        edge_avail = np.ones(self.N, dtype=bool)
        for t in range(1, s.t_global_rounds + 1):
            # ---- Raft: overlap leader election with the K edge rounds
            _, elect_t = self.chain.elect_leader()
            if self.fail_leader_at is not None and t == self.fail_leader_at:
                # single-point-of-failure drill: crash the elected leader;
                # Raft re-elects among the surviving edges (commit_block
                # below triggers the election) and BHFL keeps training
                failed_edge = self.chain.leader
                self.chain.fail_node(failed_edge)
            if failed_edge is not None:
                edge_avail[failed_edge] = False
            edge_models = None
            for k in range(1, s.k_edge_rounds + 1):
                lr = paper_lr(jnp.asarray(round_ctr), s.lr0, s.lr_decay)
                bx, by = self._epoch_batches(batch_rng)
                device_w, dev_loss = _train_epoch(device_w, bx, by, lr)

                # per-edge aggregation with this edge round's masks
                new_edge_models, new_hists, new_lasts = [], [], []
                for e in range(self.N):
                    sl = slice(edge_slices[e], edge_slices[e + 1])
                    ws = _index(device_w, sl)
                    mask = jnp.asarray(self.dev_masks[e][round_ctr])
                    agg, hist_e, last_e = self._edge_agg(
                        ws, mask, t,
                        None if dev_hist is None else dev_hist[e],
                        None if dev_last is None else dev_last[e])
                    new_edge_models.append(agg)
                    new_hists.append(hist_e)
                    new_lasts.append(last_e)
                dev_hist, dev_last = new_hists, new_lasts
                edge_models = _stack(new_edge_models)   # [N, ...]
                # devices sync to their edge model for the next epoch
                device_w = _stack([
                    _index(edge_models, e)
                    for e in range(self.N) for _ in range(self.j_per_edge[e])])
                round_ctr += 1

            # ---- global aggregation on the leader + block commit
            emask = jnp.asarray(self.edge_masks[t - 1] & edge_avail)
            j_arr = jnp.asarray(self.j_per_edge, jnp.float32)
            global_w, glob_hist, glob_last = self._global_agg(
                edge_models, emask, t, glob_hist, glob_last, j_arr)
            device_w = _bcast_like(global_w, self.D)
            self.chain.commit_block(f"edges@t={t}", f"global@t={t}")

            # ---- metrics
            acc = float(cnn_accuracy(global_w, test_x, test_y))
            accs.append(acc)
            losses.append(float(jnp.mean(dev_loss)))
            dn = float(sum(float(jnp.sum(jnp.square(a - b)))
                           for a, b in zip(jax.tree.leaves(global_w),
                                           jax.tree.leaves(prev_global))) ** 0.5)
            deltas.append(dn)
            prev_global = global_w
            if progress and (t % 10 == 0 or t == 1):
                print(f"  t={t:3d} acc={acc:.4f} loss={losses[-1]:.4f}")

        return RunResult(
            accuracy=np.asarray(accs), loss=np.asarray(losses),
            grad_norm=np.asarray(deltas), wall_time=time.time() - t0,
            sim_latency=self.paper_latency(),
            blocks=len(self.chain.blocks) - 1,
            chain_valid=self.chain.validate())

    # ------------------------------------------------------- agg dispatch
    def _edge_agg(self, ws, mask, t, hist, last):
        return self._agg(ws, mask, t, hist, last, part_weights=None)

    def _global_agg(self, ws, mask, t, hist, last, j_arr):
        return self._agg(ws, mask, t, hist, last, part_weights=j_arr)

    def _agg(self, ws, mask, t, hist, last, part_weights):
        """Returns (aggregate, new history, new last-store)."""
        s = self.s
        n = int(mask.shape[0])
        if self.aggregator == "hieavg":
            if hist is None:                       # first-ever submission
                hist = hieavg.init_history(ws)
            if t <= s.t_cold_boot:                 # Alg. 1: cold boot
                if part_weights is None:
                    agg = hieavg.edge_aggregate_cold(ws)
                else:
                    agg = hieavg.global_aggregate_cold(ws, part_weights)
                hist = hieavg.update_history(hist, ws, mask)
                return agg, hist, last
            if part_weights is None:
                agg, hist = hieavg.edge_aggregate(
                    ws, mask, hist, gamma0=s.gamma0, lam=s.lam,
                    normalize=self.normalize)
            else:
                agg, hist = hieavg.global_aggregate(
                    ws, mask, hist, part_weights, gamma0=s.gamma0,
                    lam=s.lam, normalize=self.normalize)
            return agg, hist, last
        if self.aggregator == "t_fedavg":
            return baselines.t_fedavg(ws, mask, part_weights), hist, last
        if self.aggregator == "d_fedavg":
            if last is None:
                last = jax.tree.map(jnp.zeros_like, ws)
                # first round: treat everyone as present for the store
                agg, last = baselines.d_fedavg(
                    ws, jnp.ones_like(mask), last, part_weights)
                return agg, hist, last
            agg, last = baselines.d_fedavg(ws, mask, last, part_weights)
            return agg, hist, last
        if self.aggregator == "delayed_grad":
            if last is None:
                # first round: everyone counts present (nothing in flight)
                last = (jax.tree.map(jnp.zeros_like, ws),
                        jnp.zeros((n,), jnp.float32))
                mask = jnp.ones_like(mask)
            pending, age = last
            agg, pending, age = baselines.delayed_grad(
                ws, mask, pending, age, s.staleness_discount,
                float(s.delay_delta), part_weights)
            return agg, hist, (pending, age)
        if self.aggregator == "fedavg":
            return baselines.fedavg(ws, part_weights), hist, last
        raise ValueError(f"unknown aggregator {self.aggregator!r}")


# --------------------------------------------------------------- shortcuts
def run_comparison(setting: BHFLSetting = BHFLSetting(),
                   kinds: tuple[str, ...] = ("hieavg", "t_fedavg", "d_fedavg"),
                   straggler_kind: str = "temporary",
                   include_oracle: bool = True, **kw) -> dict[str, RunResult]:
    """Fig. 2-style comparison: same data/seed, different aggregators."""
    out = {}
    if include_oracle:
        out["wo_stragglers"] = BHFLSimulator(
            setting, "fedavg", "none", "none", **kw).run()
    for kind in kinds:
        out[kind] = BHFLSimulator(
            setting, kind, straggler_kind, straggler_kind, **kw).run()
    return out
