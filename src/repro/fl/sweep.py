"""Sweep fabric — shape-bucketed sweep planner, sharded over the mesh.

The paper's headline claims are *grids*: convergence vs. straggler fraction
(Fig. 3), non-IID skew (Fig. 4), topology (N edges x J devices x K edge
rounds), consensus latency.  PR 1's ``run_sweep`` could only vmap grids
whose points agreed on every array shape; PR 2 padded every point to the
single grid maximum (one compiled call, but fig3's mixed J/N/K grid paid
several-fold padding compute); this PR buckets.

The module is a three-layer subsystem:

  Planner   ``plan_sweep`` classifies override fields (batchable / paddable
            / unsupported-with-a-clear-error), groups grid points into a
            small number of *shape buckets* — compatible ``t/k/n/j/steps``
            maxima chosen by a greedy padding-waste heuristic
            (``_bucket_points``) — and builds every point's
            ``EngineInputs`` padded to its *bucket's* maxima, stacked along
            a leading point axis per bucket.  Padded extents are numeric
            no-ops inside ``run_engine``; each point's real extents ride
            along as ``t_valid``/``k_valid``/``n_valid``/``s_valid``.
            The data plane (train/test/init, ``SHARED_DATA_FIELDS``) is
            *seed-deduped*: distinct-seed datasets are stacked once along
            a ``[n_seeds]`` axis shared by every bucket, and each point
            gathers its own row by ``seed_idx`` inside the engine — a
            10-seed confidence grid holds the distinct-seed count in
            device memory, never one dataset copy per point.

  Placement ``execute_plan`` runs each bucket as one compiled call: the
            stacked point axis shards across the mesh ``data`` axis with
            ``shard_map`` (``launch.sharding.SWEEP_RULES`` via
            ``sweep_spec``), vmapping within each shard; the data plane is
            replicated (``sweep_data_spec`` / vmap ``in_axes=None``).  The
            same autoscaling contract as the weight shardings applies per
            bucket: if a bucket's point count does not divide a >1 mesh
            axis, that bucket runs as a single-device ``vmap`` instead of
            failing to lower.  Per-bucket outputs are merged back into one
            ``[P, T_max]`` stack in original point order (rows from a
            narrower bucket extend by the engine's own tail convention:
            accuracy/clock repeat the final value, loss/grad are 0).

  Callers   ``run_sweep`` (= ``plan_sweep`` + ``run_plan``) is the
            ``BHFLSimulator``-facing wrapper returning a ``SweepResult``.
            benchmarks/fig3_sweeps.py, fig4_heterogeneity.py, and the
            examples drive it; ``SweepPlan.describe()`` renders the chosen
            bucket plan.  tests/test_sweep_fabric.py pins every padded,
            bucketed, sharded point to a standalone ``run_engine`` run.

Invariants (see docs/ARCHITECTURE.md §Sweep):
  * every grid point lands in exactly one bucket; merged outputs are in
    original point order regardless of bucketing,
  * bucketing never changes numerics — only padding extents differ, and
    padding is inert by the engine contract,
  * at most ``max_buckets`` compiled programs per plan (default 4), and
    voluntary merges keep total padded compute within ``bucket_waste``
    of the no-padding ideal,
  * the data plane rows are distinct seeds in first-appearance order; all
    buckets alias the SAME device buffers.
"""
from __future__ import annotations

import dataclasses
import functools
import time
import warnings
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from repro.configs.bhfl_cnn import BHFLSetting
from repro.fl.engine import (AGG_SEL, SHARED_DATA_FIELDS, EngineInputs,
                             build_inputs, merge_inputs, run_engine,
                             split_inputs, train_epoch_body)
from repro.kernels.dispatch import resolve_kernel_mode
from repro.models import cnn_specs
from repro.launch.mesh import make_sweep_mesh
from repro.launch.sharding import sweep_data_spec, sweep_spec

# ------------------------------------------------------- field classification
#: Fields a grid may vary freely: they only change *data* (schedules, decay
#: scalars, batch indices, per-round latency draws), never array shapes.
#: The latency-fabric fields (lm_device/lp_device/lm_edge/link_latency/
#: consensus_mult) batch because ``build_inputs`` bakes them into the
#: ``dev_time``/``cons_time``/``edge_hop`` planes of ``EngineInputs`` —
#: a consensus-latency x topology x K grid is ONE compiled call.  The
#: consensus-zoo fields (``consensus``/``n_shards``) batch the same way:
#: the protocol only changes the host-side chain replay feeding the
#: ``cons_time``/``cons_energy`` planes (unlike ``aggregation``, which
#: needs the traced "switched" program), so a mixed raft/pofel/sharded
#: grid is pure data.
#: The fault-plane fields (``edge_fail_rate`` … ``stall_backoff``) batch
#: for the same reason as the consensus zoo: faults only change host-side
#: planes — the submission/edge masks and the replayed chain's
#: ``cons_time``/``cons_energy`` draws — never array shapes, so an
#: "accuracy vs fault rate x consensus protocol" degradation grid is ONE
#: padded call (see ``repro.fl.faults`` and benchmarks/bench_faults.py).
BATCHED_FIELDS = frozenset({
    "straggler_frac", "gamma0", "lam", "t_cold_boot", "classes_per_device",
    "lr0", "lr_decay", "permanent_stop_round", "seed",
    "lm_device", "lp_device", "lm_edge", "link_latency", "consensus_mult",
    "consensus", "n_shards",
    "staleness_discount", "delay_delta",
    "edge_fail_rate", "edge_recover_rate", "val_fail_rate",
    "val_recover_rate", "burst_prob", "burst_frac", "msg_loss_prob",
    "max_stall_rounds", "stall_backoff",
})

#: Pseudo-field accepted in override dicts (NOT a ``BHFLSetting`` field):
#: the per-point aggregation strategy.  A single-valued grid plans as that
#: aggregator; a mixed grid plans as the engine's traced ``"switched"``
#: program — HieAvg-vs-delayed-gradient(-vs-FedAvg) is then ONE padded
#: shard_map call, selected per point by the batched ``agg_sel`` scalar.
AGGREGATION_FIELD = "aggregation"

#: Aggregators the traced "switched" engine can mix in one program (the
#: ``engine.AGG_SEL`` encoding); other aggregators are single-valued-only.
SWITCHABLE_AGGREGATORS = tuple(sorted(AGG_SEL))

_ALL_AGGREGATORS = ("hieavg", "t_fedavg", "d_fedavg", "delayed_grad",
                    "fedavg")

#: Fields that change array shapes but that the planner absorbs by padding
#: every point to its shape bucket's maximum.
PADDED_FIELDS = frozenset({
    "n_edges", "j_per_edge", "k_edge_rounds", "t_global_rounds",
})

#: Shape-defining fields padding cannot absorb (they change the model or
#: data geometry itself) — swept values get a clear error naming the field.
UNSUPPORTED_FIELDS = frozenset({
    "image_hw", "cnn_c1", "cnn_c2", "n_classes", "batch_size",
})


def _validate_overrides(overrides: list[dict]) -> None:
    setting_fields = {f.name for f in dataclasses.fields(BHFLSetting)}
    for ov in overrides:
        for name in ov:
            if name == AGGREGATION_FIELD:
                if ov[name] not in _ALL_AGGREGATORS:
                    raise ValueError(
                        f"run_sweep: unknown aggregation {ov[name]!r}; "
                        f"known aggregators: {_ALL_AGGREGATORS}")
                continue
            if name not in setting_fields:
                raise ValueError(
                    f"run_sweep: {name!r} is not a BHFLSetting field "
                    f"(known fields: {sorted(setting_fields)})")
            if name in UNSUPPORTED_FIELDS:
                raise ValueError(
                    f"run_sweep cannot sweep {name!r}: it changes the "
                    "model/data geometry, which padding cannot absorb. "
                    "Fix it across the grid (pass it via the base setting) "
                    "or run separate sweeps per value. Sweepable shape "
                    f"fields: {sorted(PADDED_FIELDS)}; data fields: "
                    f"{sorted(BATCHED_FIELDS)}.")
            # remaining fields are BATCHED or PADDED — both fine.


# ------------------------------------------------------------ shape buckets
# The seed-major data plane (``SHARED_DATA_FIELDS``, defined next to
# ``EngineInputs`` in ``repro.fl.engine`` and re-exported here): ONE
# ``[n_seeds, ...]`` stack shared by every bucket (vmap ``in_axes=None`` /
# shard_map replicated, never donated), gathered per point by ``seed_idx``
# inside the engine — never stacked along the point axis.

_SHAPE_KEYS = ("t", "k", "n", "j", "steps")


def _vol(ext: dict) -> int:
    """Padded-compute proxy for one point at extents ``ext``: training
    work scales with rounds x devices x steps = t*k*(n*j)*steps.

    Still the unit of ``padding_stats()``/``point_volume`` (a pure FLOP
    account, comparable across plans); the bucketing decisions themselves
    use measured step times by default (``_measured_cost_fn``).
    """
    return ext["t"] * ext["k"] * ext["n"] * ext["j"] * ext["steps"]


#: Measured wall seconds of one vmapped train step, keyed
#: (geometry, kernel_mode) -> {stacked device count D -> seconds}.
#: Module-level so repeated plans (figures re-planning the same grids)
#: pay each (geometry, D) compile-and-time exactly once per process.
_STEP_TIME_CACHE: dict[tuple, dict[int, float]] = {}


def _measured_step_time(d: int, geom: tuple) -> float:
    """Measured seconds for ONE train step over ``d`` stacked devices.

    ``geom`` = (image_hw, batch_size, c1, c2, n_classes, kernel_mode) —
    the grid-constant geometry (``plan_sweep`` rejects grids that vary
    it).  First query per (geom, d) runs one warm-up call of the
    engine's actual inner step (``train_epoch_body``: fwd + bwd + SGD
    update on zero data, through the plan's kernel path) to compile,
    then times two more and keeps the best; later queries hit the cache.

    The returned cost is forced strictly increasing in ``d`` (running
    max over cached smaller counts, plus a tiny ``1 + 1e-6·d`` tilt) so
    a merge envelope never *measures* cheaper than its members — timing
    noise would otherwise make bucketing non-deterministic.
    """
    times = _STEP_TIME_CACHE.setdefault(geom, {})
    if d not in times:
        hw, bs, c1, c2, n_classes, kernel_mode = geom
        specs = cnn_specs(hw, 1, n_classes, c1, c2)
        params = {k: jnp.zeros((d,) + sp.shape, jnp.float32)
                  for k, sp in specs.items()}
        images = jnp.zeros((d, 1, bs, hw, hw, 1), jnp.float32)
        labels = jnp.zeros((d, 1, bs), jnp.int32)
        lr = jnp.float32(0.01)
        fn = jax.jit(functools.partial(train_epoch_body,
                                       kernel_mode=kernel_mode))
        jax.block_until_ready(fn(params, images, labels, lr))  # compile
        best = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(params, images, labels, lr))
            best = min(best, time.perf_counter() - t0)
        times[d] = best
    mono = max(t for dd, t in times.items() if dd <= d)
    return mono * (1.0 + 1e-6 * d)


def _measured_cost_fn(geom: tuple):
    """Bucketing cost: rounds x measured per-step seconds at D = n·j."""

    def cost(ext: dict) -> float:
        return (ext["t"] * ext["k"] * ext["steps"]
                * _measured_step_time(ext["n"] * ext["j"], geom))

    return cost


def _bucket_points(extents: list[dict], max_buckets: int,
                   bucket_waste: float, cost_fn=_vol) -> list[dict]:
    """Group points into shape buckets under a padding-waste heuristic.

    Greedy agglomerative merge: start with one bucket per distinct extent
    tuple (identical shapes are free to share), then repeatedly merge the
    pair whose elementwise-max envelope adds the least padded compute.  A
    merge is *forced* while the bucket count exceeds ``max_buckets`` (the
    compiled-program budget) and *voluntary* while total padded compute
    stays within ``bucket_waste`` x the no-padding ideal — fewer compiles
    for bounded waste.  ``cost_fn(ext)`` prices one point padded to
    ``ext`` — the ``_vol`` proxy, or measured step times
    (``_measured_cost_fn``, ``plan_sweep``'s default), which only runs
    its timings when the grid actually has shapes to merge.  Returns
    ``[{"ids": [point indices], "ext": {...}}]`` ordered by first point
    id, ids ascending within each bucket.
    """
    if max_buckets < 1:
        raise ValueError(f"max_buckets must be >= 1, got {max_buckets}")
    by_key: dict[tuple, list[int]] = {}
    for i, e in enumerate(extents):
        by_key.setdefault(tuple(e[k] for k in _SHAPE_KEYS), []).append(i)
    buckets = [{"ids": ids, "ext": dict(zip(_SHAPE_KEYS, key))}
               for key, ids in by_key.items()]
    if len(buckets) > 1:                   # uniform grids never pay cost_fn
        ideal = sum(cost_fn(e) for e in extents)

        def cost(b):
            return len(b["ids"]) * cost_fn(b["ext"])

        total = sum(cost(b) for b in buckets)
        while len(buckets) > 1:
            best = None
            for x in range(len(buckets)):
                for y in range(x + 1, len(buckets)):
                    ext = {k: max(buckets[x]["ext"][k], buckets[y]["ext"][k])
                           for k in _SHAPE_KEYS}
                    delta = ((len(buckets[x]["ids"])
                              + len(buckets[y]["ids"])) * cost_fn(ext)
                             - cost(buckets[x]) - cost(buckets[y]))
                    if best is None or delta < best[0]:
                        best = (delta, x, y, ext)
            delta, x, y, ext = best
            if (len(buckets) > max_buckets
                    or total + delta <= bucket_waste * ideal):
                merged = {"ids": buckets[x]["ids"] + buckets[y]["ids"],
                          "ext": ext}
                buckets = [b for i, b in enumerate(buckets)
                           if i not in (x, y)] + [merged]
                total += delta
            else:
                break
    for b in buckets:
        b["ids"].sort()
    buckets.sort(key=lambda b: b["ids"][0])
    return buckets


def _stack_points(inputs: list[EngineInputs], data_plane: dict,
                  seed_ids: list[int], seed_shared: bool) -> EngineInputs:
    """Stack one bucket's per-point inputs along a leading point axis.

    Data-plane fields take the plan-wide seed-major stack (same device
    buffers in every bucket); ``seed_idx`` becomes the per-point ``[Pb]``
    gather index (or stays the scalar 0 on single-seed plans, matching
    ``split_inputs``' ``shared_seed_idx`` side — keeping it unmapped keeps
    the engine's test/init gathers unbatched, so vmap never materializes
    P identical test-set copies); everything else stacks point-major.
    """
    def one(name):
        if name == "seed_idx":
            return jnp.int32(0) if seed_shared \
                else jnp.asarray(seed_ids, jnp.int32)
        if name in SHARED_DATA_FIELDS:
            return data_plane[name]
        vals = [getattr(i, name) for i in inputs]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *vals)

    return EngineInputs(**{f.name: one(f.name)
                           for f in dataclasses.fields(EngineInputs)})


@dataclasses.dataclass
class SweepBucket:
    """One shape bucket: a compiled-call-ready stack of compatible points."""
    point_ids: list            # indices into the plan's point order
    inputs: Optional[EngineInputs]  # stacked [Pb, ...], padded to bucket
    #   maxima.  None after a donated execute consumed this bucket (the
    #   donation contract: the stacked planes are handed to the compiled
    #   call and the plan stops pinning them).
    grid_max: dict             # this bucket's {"t","k","n","j","steps"}


@dataclasses.dataclass
class SweepPlan:
    """A bucketed, compiled-call-ready sweep: stacked inputs + metadata.

    Holds only host scalars per point besides the bucket inputs — the
    planning simulators (and their schedules/chains) are released once
    their latency/block summaries are extracted, so plan lifetime does not
    pin P sets of host state.  All buckets alias ONE seed-major data plane
    (``n_seeds`` rows), so plan memory scales with distinct seeds.
    """
    points: list                    # (overrides dict, seed) per grid point
    buckets: list                   # [SweepBucket], first-point order
    grid_max: dict                  # global {"t","k","n","j","steps"} maxima
    aggregator: str
    normalize: bool
    history_dtype: Any
    kernel_mode: str                # resolved kernel-plane backend (never
    #   "auto": plan_sweep resolves so runner caches key on the concrete
    #   mode — see repro.kernels.dispatch)
    n_seeds: int                    # distinct seeds in the data plane
    sim_latency: np.ndarray         # [P] paper latency model totals
    blocks: np.ndarray              # [P] committed blocks per point
    t_valid: np.ndarray             # [P] real rounds per point
    point_volume: np.ndarray        # [P] no-padding compute proxy per point

    @property
    def inputs(self) -> EngineInputs:
        """The single bucket's stacked inputs (single-bucket plans only —
        the PR 2 shape; multi-bucket plans use ``plan.buckets[i].inputs``)."""
        if len(self.buckets) != 1:
            raise ValueError(
                f"plan has {len(self.buckets)} shape buckets; per-bucket "
                "inputs live at plan.buckets[i].inputs")
        if self.buckets[0].inputs is None:
            raise ValueError(
                "this SweepPlan's bucket inputs were consumed by a donated "
                "execute_plan/run_plan; build a fresh plan, or run with "
                "donate=False to keep a plan re-runnable")
        return self.buckets[0].inputs

    def padding_stats(self) -> dict:
        """Padded-compute accounting for the chosen bucket plan.

        ``padded_flop_frac`` is the fraction of the plan's compute volume
        that is padding (0 = no waste); ``single_bucket_flop_frac`` is the
        same quantity had every point been padded to the global maxima
        (the PR 2 baseline this planner retires).
        """
        ideal = int(self.point_volume.sum())
        padded = sum(len(b.point_ids) * _vol(b.grid_max)
                     for b in self.buckets)
        single = len(self.points) * _vol(self.grid_max)
        return {
            "ideal_volume": ideal,
            "padded_volume": padded,
            "single_bucket_volume": single,
            "padded_flop_frac": 1.0 - ideal / padded,
            "single_bucket_flop_frac": 1.0 - ideal / single,
            "buckets": [dict(points=len(b.point_ids), **b.grid_max)
                        for b in self.buckets],
        }

    def describe(self) -> str:
        """Human-readable bucket plan (what the planner chose and why it's
        cheap) — logged by examples/sweep_topology.py and fig3_sweeps."""
        st = self.padding_stats()
        lines = [
            f"sweep plan: {len(self.points)} points -> "
            f"{len(self.buckets)} shape bucket(s), {self.n_seeds} distinct "
            f"seed(s) in the data plane; padded-compute waste "
            f"{st['padded_flop_frac']:.1%} (single-bucket baseline "
            f"{st['single_bucket_flop_frac']:.1%})"]
        for i, b in enumerate(self.buckets):
            g = b.grid_max
            lines.append(
                f"  bucket {i}: {len(b.point_ids)} point(s) padded to "
                f"T={g['t']} K={g['k']} N={g['n']} J={g['j']} "
                f"steps={g['steps']}")
        return "\n".join(lines)


@dataclasses.dataclass
class SweepResult:
    """Batched trajectories for a grid of runs (leading axis = grid point).

    Rows are padded to the grid's max round count: row ``p`` is valid up to
    ``t_valid[p]`` rounds; past that, ``accuracy`` repeats the final valid
    value, ``loss``/``grad_norm`` are 0, and ``sim_clock``/``sim_energy``
    repeat the final valid value.  ``trajectory(p)`` /
    ``latency_trajectory(p)`` / ``energy_trajectory(p)`` slice one point's
    valid prefix.  Rows are in original point order no matter how the
    planner bucketed them.
    """
    points: list              # (overrides dict, seed) per grid point
    accuracy: np.ndarray      # [P, T_max]
    loss: np.ndarray          # [P, T_max]
    grad_norm: np.ndarray     # [P, T_max]
    sim_clock: np.ndarray     # [P, T_max] cumulative simulated seconds
    sim_energy: np.ndarray    # [P, T_max] cumulative consensus energy (J)
    sim_latency: np.ndarray   # [P] paper's Sec. 5.1.4 expectation totals
    blocks: np.ndarray        # [P]
    t_valid: np.ndarray       # [P] real rounds per point

    def trajectory(self, p: int) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        tv = int(self.t_valid[p])
        return (self.accuracy[p, :tv], self.loss[p, :tv],
                self.grad_norm[p, :tv])

    def latency_trajectory(self, p: int) -> tuple[np.ndarray, np.ndarray]:
        """(simulated clock [tv], accuracy [tv]) — one point's
        time-to-accuracy curve (the latency fabric's x-axis)."""
        tv = int(self.t_valid[p])
        return self.sim_clock[p, :tv], self.accuracy[p, :tv]

    def energy_trajectory(self, p: int) -> tuple[np.ndarray, np.ndarray]:
        """(simulated clock [tv], cumulative consensus energy [tv] J) —
        one point's energy-over-time curve (the zoo's second cost axis)."""
        tv = int(self.t_valid[p])
        return self.sim_clock[p, :tv], self.sim_energy[p, :tv]

    def time_to_accuracy(self, p: int, target: float) -> float:
        """Simulated seconds until point ``p`` first reaches ``target``
        test accuracy; +inf when it never does."""
        clock, acc = self.latency_trajectory(p)
        hit = np.flatnonzero(acc >= target)
        return float(clock[hit[0]]) if hit.size else float("inf")

    def k_star_empirical(self, target: float
                         ) -> tuple[Optional[int], np.ndarray]:
        """The *measured* K* selector: the grid point reaching ``target``
        accuracy in the least simulated time.

        Returns ``(best_point_index, times[P])``; the index is None when
        no point reaches the target.  Reported next to the theoretical
        ``omega_bound`` K* (``repro.core.optimize_k``) by
        ``examples/latency_optimization.py`` / ``benchmarks/fig7_latency``
        — the empirical selector sees what the bound cannot: actual
        convergence speed and the actual consensus stalls of small-K
        windows.
        """
        times = np.array([self.time_to_accuracy(p, target)
                          for p in range(len(self.points))])
        if not np.isfinite(times).any():
            return None, times
        return int(np.argmin(times)), times


def plan_sweep(setting: BHFLSetting, seeds=(0,), *,
               overrides: Optional[list] = None,
               aggregator: str = "hieavg",
               device_stragglers: str = "temporary",
               edge_stragglers: str = "temporary",
               normalize: bool = False, history_dtype=None,
               kernel_mode: str = "auto",
               max_buckets: int = 4, bucket_waste: float = 1.25,
               bucket_cost: str = "measured",
               **sim_kw) -> SweepPlan:
    """Precompute a grid (overrides x seeds) into bucketed ``EngineInputs``.

    ``overrides`` entries may change topology and round counts
    (``PADDED_FIELDS``) — points are grouped into at most ``max_buckets``
    shape buckets by the padding-waste heuristic (``bucket_waste`` caps the
    total padded-compute ratio voluntary merges may reach; see
    ``_bucket_points``), and every point is padded to its bucket's maxima.
    ``bucket_cost`` prices a padded point for those decisions:
    ``"measured"`` (default) times one real train step per candidate
    device count through the plan's kernel path (compiled once, cached
    process-wide, strictly monotone in device count so noise can't flip
    the plan); ``"proxy"`` keeps the analytic ``t·k·n·j·steps`` volume.
    ``max_buckets=1`` forces the single global-max bucket (the PR 2
    behavior).  ``j_per_edge`` additionally accepts a per-edge list
    (Fig. 4b inconsistent-J deployments).  Geometry fields
    (``UNSUPPORTED_FIELDS``) raise immediately with the field named.

    Datasets/init weights are seed-deduped: one ``[n_seeds]`` stack shared
    by every bucket, with per-point ``seed_idx`` gathers inside the engine.

    ``kernel_mode`` is the kernel-plane backend knob (like
    ``history_dtype``): resolved here (``"auto"`` → fused Pallas kernels
    on TPU, pure-XLA reference on CPU) and baked into the plan so the
    cached runners key on the concrete mode.

    Each override may name its own ``"aggregation"``; see ``run_sweep``.
    The plan's aggregator is the grid's single value, or ``"switched"``
    when mixed (mixing a non-``SWITCHABLE_AGGREGATORS`` strategy raises).
    """
    from repro.fl.simulator import BHFLSimulator  # lazy: avoid import cycle

    kernel_mode = resolve_kernel_mode(kernel_mode)   # validate up front
    overrides = [dict(ov) for ov in (overrides or [{}])]
    _validate_overrides(overrides)
    # an override's explicit "seed" wins over the ``seeds`` cross product
    # and is NOT crossed with it (the simulator's seed argument governs
    # data/schedules/chain, so crossing would emit duplicate points)
    points = []
    for ov in overrides:
        if "seed" in ov:
            points.append((ov, int(ov["seed"])))
        else:
            points.extend((ov, seed) for seed in seeds)

    sims = []
    point_aggs = []
    for ov, seed in points:
        ov = dict(ov)
        ov.pop("seed", None)
        agg = ov.pop(AGGREGATION_FIELD, aggregator)
        point_aggs.append(agg)
        kw = dict(sim_kw)
        jpe = ov.pop("j_per_edge", None)
        if isinstance(jpe, (list, tuple, np.ndarray)):
            kw["j_per_edge"] = [int(j) for j in jpe]
        elif jpe is not None:
            ov["j_per_edge"] = int(jpe)
        sims.append(BHFLSimulator(
            dataclasses.replace(setting, **ov), agg,
            device_stragglers, edge_stragglers, normalize=normalize,
            seed=seed, **kw))

    # A mixed-aggregation grid compiles as the engine's traced "switched"
    # aggregator: every point's program computes hieavg/delayed_grad/fedavg
    # and tri-selects by its batched ``agg_sel`` scalar, so the whole grid
    # stays one padded shard_map call.  Single-aggregator grids keep the
    # cheaper static dispatch.
    distinct = sorted(set(point_aggs))
    if len(distinct) == 1:
        plan_aggregator = distinct[0]
    else:
        bad = [a for a in distinct if a not in SWITCHABLE_AGGREGATORS]
        if bad:
            raise ValueError(
                f"mixed-aggregation sweep includes {bad}, which cannot be "
                f"traced-switched; switchable: {SWITCHABLE_AGGREGATORS}. "
                "Run those aggregators as separate sweeps.")
        plan_aggregator = "switched"

    extents = [{"t": s.s.t_global_rounds, "k": s.s.k_edge_rounds,
                "n": s.N, "j": max(s.j_per_edge), "steps": s.steps}
               for s in sims]
    grid_max = {k: max(e[k] for e in extents) for k in _SHAPE_KEYS}
    if bucket_cost not in ("measured", "proxy"):
        raise ValueError(f"unknown bucket_cost {bucket_cost!r}; "
                         "expected 'measured' or 'proxy'")
    if bucket_cost == "measured":
        s0 = sims[0].s
        cost_fn = _measured_cost_fn((s0.image_hw, s0.batch_size, s0.cnn_c1,
                                     s0.cnn_c2, s0.n_classes, kernel_mode))
    else:
        cost_fn = _vol
    groups = _bucket_points(extents, max_buckets, bucket_waste, cost_fn)

    # seed-dedup: data/init arrays are a pure function of (seed, geometry),
    # and geometry is grid-constant — the first point of each distinct seed
    # becomes that seed's data-plane row (its device buffers are reused by
    # every same-seed point via share_data_from, so H2D puts scale with
    # distinct seeds), and the rows concatenate into ONE [n_seeds] stack
    # every bucket aliases.
    seed_to_idx: dict = {}
    for s in sims:
        seed_to_idx.setdefault(s.seed, len(seed_to_idx))
    first_by_seed: dict = {}
    built: list = []          # (group, [EngineInputs per point])
    for g in groups:
        ext = g["ext"]
        binputs = []
        for i in g["ids"]:
            s = sims[i]
            inp = build_inputs(
                s, t_max=ext["t"], k_max=ext["k"], n_max=ext["n"],
                j_max=ext["j"], steps_max=ext["steps"],
                share_data_from=first_by_seed.get(s.seed))
            first_by_seed.setdefault(s.seed, inp)
            binputs.append(inp)
        shapes = [jax.tree.map(jnp.shape, i) for i in binputs]
        if any(sh != shapes[0] for sh in shapes[1:]):
            raise ValueError(
                "sweep grid points disagree on array shapes even after "
                "padding — the base setting/sim kwargs (image size, batch "
                "size, data sizes) must be identical across the grid")
        built.append((g, binputs))

    reps = [first_by_seed[seed] for seed in seed_to_idx]
    data_plane = {}
    for name in SHARED_DATA_FIELDS:
        vals = [getattr(r, name) for r in reps]
        data_plane[name] = vals[0] if len(vals) == 1 else jax.tree.map(
            lambda *xs: jnp.concatenate(xs, axis=0), *vals)

    seed_shared = len(seed_to_idx) == 1
    buckets = [SweepBucket(
        point_ids=list(g["ids"]),
        inputs=_stack_points(binputs, data_plane,
                             [seed_to_idx[sims[i].seed] for i in g["ids"]],
                             seed_shared),
        grid_max=dict(g["ext"]))
        for g, binputs in built]
    return SweepPlan(points=points, buckets=buckets, grid_max=grid_max,
                     aggregator=plan_aggregator, normalize=normalize,
                     history_dtype=history_dtype,
                     kernel_mode=kernel_mode,
                     n_seeds=len(seed_to_idx),
                     sim_latency=np.asarray([s.paper_latency()
                                             for s in sims]),
                     blocks=np.asarray([len(s.chain.blocks) - 1
                                        for s in sims]),
                     t_valid=np.asarray([s.s.t_global_rounds
                                         for s in sims]),
                     point_volume=np.asarray([_vol(e) for e in extents]))


# ---------------------------------------------------------------- placement
def _engine_runner(aggregator: str, normalize: bool, history_dtype,
                   kernel_mode: str):
    """The per-point engine call over split ``(hot, shared)`` input dicts
    (``engine.split_inputs``): the hot dict rides the stacked point axis
    (vmap ``in_axes=0`` / shard_map point spec) and is the donation
    target; the shared dict is the seed-major data plane (unmapped /
    replicated, never donated)."""
    def runner(hot, shared):
        return run_engine(merge_inputs(hot, shared), aggregator=aggregator,
                          normalize=normalize, history_dtype=history_dtype,
                          kernel_mode=kernel_mode)

    return runner


def _map_points(runner, point_batch: Optional[int]):
    """``runner(hot, shared)`` over the stacked point axis of ``hot``: all
    points in one vmap, or ``point_batch`` points at a time (``lax.map``),
    so the call's working set is that many points' rather than the
    bucket's.  At the paper's widths one point needs about 6.4 GB of a
    TPU v5e's 16 GB, so a bucket of several points must walk them."""
    if point_batch is None:
        return jax.vmap(runner, in_axes=(0, None))

    def mapped(hot, shared):
        return jax.lax.map(lambda h: runner(h, shared), hot,
                           batch_size=point_batch)

    return mapped


@functools.lru_cache(maxsize=None)
def _vmap_runner(aggregator: str, normalize: bool, history_dtype,
                 kernel_mode: str, donate: bool,
                 point_batch: Optional[int] = None):
    """jit(vmap(run_engine)) over the stacked point axis — cached like
    ``_sharded_runner``.  ``donate=True`` hands the hot (stacked) input
    dict to XLA for buffer reuse: a big bucketed grid does not hold the
    caller's copy of the stacked planes alive next to the running
    program's working set.  The shared data plane is never donated.
    ``point_batch`` as in ``_map_points``."""
    fn = _map_points(_engine_runner(aggregator, normalize, history_dtype,
                                    kernel_mode), point_batch)
    return jax.jit(fn, donate_argnums=(0,) if donate else ())


@functools.lru_cache(maxsize=None)
def _sharded_runner(aggregator: str, normalize: bool, history_dtype,
                    mesh, spec, kernel_mode: str, donate: bool,
                    point_batch: Optional[int] = None):
    """jit(shard_map(vmap(run_engine))) — cached so repeated sweeps with
    the same static config reuse the compiled executable instead of paying
    a fresh trace + compile per call (jit caches by callable identity; a
    multi-bucket plan compiles one program per bucket *shape* under the
    same cached callable).  ``spec`` shards every hot (stacked) leaf over
    the mesh point axis; the shared data plane is replicated
    (``sweep_data_spec``).  ``donate`` and ``point_batch`` (per device)
    as in ``_vmap_runner``."""
    inner = _map_points(_engine_runner(aggregator, normalize, history_dtype,
                                       kernel_mode), point_batch)
    # the Pallas interpreter evaluates kernel bodies without varying-axis
    # types, so the checker can stay on for every mode but that one
    sharded = jax.shard_map(inner, mesh=mesh,
                            in_specs=(spec, sweep_data_spec()),
                            out_specs=spec,
                            check_vma=(kernel_mode != "interpret"))
    return jax.jit(sharded, donate_argnums=(0,) if donate else ())


def execute_plan(plan: SweepPlan, *, mesh=None, placement: str = "auto",
                 donate: bool = True, point_batch: Optional[int] = None
                 ) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray,
                            np.ndarray]:
    """Run a plan's buckets — one compiled call each — and merge outputs.

    Returns per-point ``(accuracy, loss, grad_norm, sim_clock,
    sim_energy)``, each ``[P, T_max]`` with ``T_max = plan.grid_max["t"]``,
    in original point order.  Rows from a bucket padded to fewer rounds
    are extended by the engine's own tail convention (accuracy/clock/
    energy repeat the final value, loss/grad are 0), so bucketing is
    invisible to every accessor.

    ``placement``: ``"auto"`` shards each bucket's point axis over the mesh
    ``data`` axis when ``sweep_spec`` says it divides (falling back to
    single-device ``vmap`` per bucket — the same autoscaling contract as
    the weight shardings); ``"vmap"`` forces the single-device path;
    ``"shard"`` requires the sharded path for every bucket and raises if
    the mesh cannot take one.

    ``donate`` (default True): each bucket's stacked hot input planes are
    donated to its compiled call, so a big grid never holds the plan's
    copy of the stacked state next to the run's working set.  The shared
    seed-major data plane is never donated (all buckets alias it).  After
    a donated execute the plan's bucket inputs are CONSUMED — re-running
    the same ``SweepPlan`` object requires ``donate=False`` (or a fresh
    plan; ``run_sweep`` re-plans per call either way).

    ``point_batch`` (default None: all of a bucket's points, or of a
    device's share of them, in one vmap): run them that many at a time
    inside the bucket's one compiled call, bounding its device memory to
    that many points' working set.  Results agree with the all-at-once
    vmap to float rounding.
    """
    if placement not in ("auto", "vmap", "shard"):
        raise ValueError(f"unknown placement {placement!r}")
    if point_batch is not None and point_batch < 1:
        raise ValueError(f"point_batch must be >= 1, got {point_batch}")
    if placement != "vmap" and mesh is None:
        mesh = make_sweep_mesh()

    # resolve every bucket's spec up front so placement='shard' fails fast
    # (before any bucket compiles/runs) rather than mid-plan
    specs = [sweep_spec(len(b.point_ids), mesh) if placement != "vmap"
             else PartitionSpec() for b in plan.buckets]
    if placement == "shard":
        for b, spec in zip(plan.buckets, specs):
            if spec == PartitionSpec():
                raise ValueError(
                    f"placement='shard' but a bucket of {len(b.point_ids)} "
                    f"grid points (of {len(plan.points)} total) does not "
                    f"divide a >1 mesh axis (mesh="
                    f"{dict(mesh.shape) if mesh is not None else None}); "
                    "force max_buckets=1 or use placement='auto'")

    P_, Tg = len(plan.points), plan.grid_max["t"]
    acc = np.zeros((P_, Tg), np.float32)
    loss = np.zeros((P_, Tg), np.float32)
    gn = np.zeros((P_, Tg), np.float32)
    clock = np.zeros((P_, Tg), np.float32)
    energy = np.zeros((P_, Tg), np.float32)
    seed_shared = plan.n_seeds == 1
    for b, spec in zip(plan.buckets, specs):
        if b.inputs is None:
            raise ValueError(
                "this SweepPlan's bucket inputs were consumed by a "
                "previous donated execute_plan/run_plan; build a fresh "
                "plan, or run with donate=False to keep a plan re-runnable")
        hot, shared = split_inputs(b.inputs, shared_seed_idx=seed_shared)
        with warnings.catch_warnings():
            # expected under donation: the engine's [P, T] outputs are far
            # smaller than the stacked input planes, so XLA rarely finds
            # an input-output alias — the reference release below is the
            # real win
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            if spec == PartitionSpec():
                outs = _vmap_runner(plan.aggregator, plan.normalize,
                                    plan.history_dtype, plan.kernel_mode,
                                    donate, point_batch)(hot, shared)
            else:
                outs = _sharded_runner(plan.aggregator, plan.normalize,
                                       plan.history_dtype, mesh, spec,
                                       plan.kernel_mode, donate,
                                       point_batch)(hot, shared)
        if donate:
            # the compiled call has consumed the stacked planes: drop the
            # plan's reference so it stops pinning the caller-side copy
            # (the shared data plane stays — every bucket and same-seed
            # point aliases it).  Only after a SUCCESSFUL dispatch: a
            # bucket that failed to compile/run stays intact, so the plan
            # remains retryable
            b.inputs = None
        del hot
        a, l, g, c, en = (np.asarray(o) for o in outs)
        ids = np.asarray(b.point_ids)
        Tb = a.shape[1]
        acc[ids, :Tb] = a
        acc[ids, Tb:] = a[:, -1:]
        loss[ids, :Tb] = l
        gn[ids, :Tb] = g
        clock[ids, :Tb] = c
        clock[ids, Tb:] = c[:, -1:]
        energy[ids, :Tb] = en
        energy[ids, Tb:] = en[:, -1:]
    return acc, loss, gn, clock, energy


def run_plan(plan: SweepPlan, *, mesh=None, placement: str = "auto",
             donate: bool = True, point_batch: Optional[int] = None
             ) -> SweepResult:
    """Execute a prepared plan and package a ``SweepResult`` — lets callers
    inspect/log the bucket plan (``plan.describe()``) before running it.
    ``donate`` and ``point_batch`` as in ``execute_plan`` (donated bucket
    inputs are consumed — pass False to keep the plan re-runnable)."""
    accs, losses, deltas, clocks, energies = execute_plan(
        plan, mesh=mesh, placement=placement, donate=donate,
        point_batch=point_batch)
    return SweepResult(
        points=plan.points,
        accuracy=accs, loss=losses, grad_norm=deltas, sim_clock=clocks,
        sim_energy=energies,
        sim_latency=plan.sim_latency, blocks=plan.blocks,
        t_valid=plan.t_valid)


# ------------------------------------------------------------------ wrapper
def run_sweep(setting: BHFLSetting, seeds=(0,), *,
              overrides: Optional[list] = None,
              aggregator: str = "hieavg",
              device_stragglers: str = "temporary",
              edge_stragglers: str = "temporary",
              normalize: bool = False, history_dtype=None,
              kernel_mode: str = "auto",
              mesh=None, placement: str = "auto",
              max_buckets: int = 4, bucket_waste: float = 1.25,
              bucket_cost: str = "measured",
              point_batch: Optional[int] = None,
              **sim_kw) -> SweepResult:
    """Grids (including topology/round grids) as a few compiled sharded
    calls — one per shape bucket.

    ``overrides`` is a list of ``BHFLSetting`` field-override dicts crossed
    with ``seeds``.  Straggler fractions/kinds, gamma/lambda, cold-boot
    length, lr schedule, and seeds vary as pure data; ``n_edges``,
    ``j_per_edge`` (int or per-edge list), ``k_edge_rounds``, and
    ``t_global_rounds`` vary via padding to the bucket max (``max_buckets``
    / ``bucket_waste`` steer the padding-waste heuristic, priced by
    measured step times unless ``bucket_cost="proxy"``; ``max_buckets=1``
    restores the single global-max call); model/data geometry fields raise
    a ``ValueError`` naming the field.  Multi-seed grids keep one dataset
    copy per *distinct seed* in device memory, not per point.

    An override may also carry the ``"aggregation"`` pseudo-field (not a
    ``BHFLSetting`` field): the per-point aggregation strategy.  A grid
    mixing ``SWITCHABLE_AGGREGATORS`` compiles ONE traced-``"switched"``
    program selected per point by a batched scalar — e.g. HieAvg vs
    delayed-gradient in a single padded shard_map call.

    ``point_batch`` bounds how many points one compiled call holds at once
    (see ``execute_plan``); at the paper's widths a TPU v5e needs 1.
    """
    plan = plan_sweep(setting, seeds, overrides=overrides,
                      aggregator=aggregator,
                      device_stragglers=device_stragglers,
                      edge_stragglers=edge_stragglers, normalize=normalize,
                      history_dtype=history_dtype, kernel_mode=kernel_mode,
                      max_buckets=max_buckets,
                      bucket_waste=bucket_waste, bucket_cost=bucket_cost,
                      **sim_kw)
    return run_plan(plan, mesh=mesh, placement=placement,
                    point_batch=point_batch)
