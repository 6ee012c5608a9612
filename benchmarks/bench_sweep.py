"""Sweep-fabric throughput — writes ``BENCH_sweep.json``.

Measures points/sec for the same REDUCED 4-point *shape-changing* grid
(topology varies per point — impossible to batch before the sweep fabric)
driven five ways:

  * ``legacy_loop``     — one ``BHFLSimulator.run_legacy`` per point
                          (the original per-edge Python loop),
  * ``engine_per_point``— one compiled ``BHFLSimulator.run`` per point
                          (each point its own shapes, own compile),
  * ``vmap``            — the fabric's single-device path with
                          ``max_buckets=1``: all points padded to the
                          single grid max, one ``vmap(run_engine)`` call,
  * ``bucketed``        — the shape-bucketed planner (default knobs): the
                          grid splits into a few shape buckets, one
                          compiled call each, trading extra compiles for
                          less padded compute (the ``padded_flop_frac``
                          column shows the fraction of each plan's compute
                          volume that is padding),
  * ``sharded``         — the fabric's ``shard_map`` path over the mesh
                          ``data`` axis of this process's own devices,
                          measured only where there are several and the
                          grid divides them (single-bucket, since 1-2-point
                          buckets cannot divide 4 devices).

Timings are best-of-``REPS`` after a warm-up run (jit caches hot), like
``bench_engine``.  The grid is intentionally small (T=10, 1 local step) so
the numbers track orchestration + padding overhead, not training FLOPs —
which also means the bucketed row undersells bucketing (per-bucket compile
overhead is amortized, but padded-FLOP savings only matter when real
training FLOPs dominate; the ``padded_flop_frac`` column is the
scale-independent signal).

  PYTHONPATH=src python -m benchmarks.run --only sweep --emit-json
"""
from __future__ import annotations

import dataclasses
import json

import jax

from repro.configs.bhfl_cnn import REDUCED

from .common import Csv, best_of

T_ROUNDS = 10
KW = dict(n_train=1500, n_test=300, steps_per_epoch=1, normalize=True)
REPS = 2

# a shape-changing grid: every point has a different topology/round count
OVERRIDES = [
    {"n_edges": 3},
    {"n_edges": 5},
    {"j_per_edge": 3},
    {"k_edge_rounds": 1},
]


def _setting():
    return dataclasses.replace(REDUCED, t_global_rounds=T_ROUNDS)


def _measure(placement: str, **sweep_kw) -> float:
    from repro.fl import run_sweep
    return best_of(lambda: run_sweep(_setting(), overrides=OVERRIDES,
                                     placement=placement, **sweep_kw,
                                     **KW), REPS)


def _padding_stats(**sweep_kw) -> dict:
    """Padding accounting for the plan a ``_measure`` call with the SAME
    ``sweep_kw`` executes — pass identical kwargs to both so the reported
    fractions always describe the plan that was actually timed."""
    from repro.fl import plan_sweep
    return plan_sweep(_setting(), overrides=OVERRIDES, **sweep_kw,
                      **KW).padding_stats()


def main(emit_json: bool = True) -> dict:
    from repro.fl import BHFLSimulator

    csv = Csv("bench_sweep")
    csv.row("path", "devices", "seconds", "points_per_sec",
            "padded_flop_frac")
    n_pts = len(OVERRIDES)

    def per_point(method):
        for ov in OVERRIDES:
            sim = BHFLSimulator(dataclasses.replace(_setting(), **ov),
                                "hieavg", "temporary", "temporary", **KW)
            getattr(sim, method)()

    t_legacy = best_of(lambda: per_point("run_legacy"), REPS)
    csv.row("legacy_loop", 1, f"{t_legacy:.2f}", f"{n_pts / t_legacy:.2f}",
            "0.000")
    t_point = best_of(lambda: per_point("run"), REPS)
    csv.row("engine_per_point", 1, f"{t_point:.2f}",
            f"{n_pts / t_point:.2f}", "0.000")
    stats_single = _padding_stats(max_buckets=1)
    frac_single = stats_single["padded_flop_frac"]
    t_vmap = _measure("vmap", max_buckets=1)
    csv.row("vmap", 1, f"{t_vmap:.2f}", f"{n_pts / t_vmap:.2f}",
            f"{frac_single:.3f}")
    stats_bucketed = _padding_stats()     # default bucketing knobs...
    frac_bucketed = stats_bucketed["padded_flop_frac"]
    t_bucketed = _measure("vmap")         # ...same knobs as the timed run
    csv.row("bucketed", 1, f"{t_bucketed:.2f}",
            f"{n_pts / t_bucketed:.2f}", f"{frac_bucketed:.3f}")

    n_dev = len(jax.devices())
    t_shard = None
    if n_dev > 1 and n_pts % n_dev == 0:
        t_shard = _measure("shard", max_buckets=1)
        csv.row("sharded", n_dev, f"{t_shard:.2f}",
                f"{n_pts / t_shard:.2f}", f"{frac_single:.3f}")

    out = {
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "devices": n_dev,
        "setting": "REDUCED",
        "grid": OVERRIDES,
        "t_global_rounds": T_ROUNDS,
        "steps_per_epoch": KW["steps_per_epoch"],
        "reps": REPS,
        "points": n_pts,
        "legacy_points_per_sec": round(n_pts / t_legacy, 3),
        "engine_per_point_points_per_sec": round(n_pts / t_point, 3),
        "vmap_points_per_sec": round(n_pts / t_vmap, 3),
        "vmap_speedup_vs_legacy": round(t_legacy / t_vmap, 2),
        "bucketed_points_per_sec": round(n_pts / t_bucketed, 3),
        "bucketed_speedup_vs_single_bucket": round(t_vmap / t_bucketed, 2),
        "bucket_count": len(stats_bucketed["buckets"]),
        "single_bucket_padded_flop_frac": round(frac_single, 4),
        "bucketed_padded_flop_frac": round(frac_bucketed, 4),
    }
    if t_shard is not None:
        out.update({
            "sharded_points_per_sec": round(n_pts / t_shard, 3),
            "sharded_speedup_vs_legacy": round(t_legacy / t_shard, 2),
            "sharded_speedup_vs_vmap": round(t_vmap / t_shard, 2),
        })
    if emit_json:
        with open("BENCH_sweep.json", "w") as f:
            json.dump(out, f, indent=2)
        print(f"# wrote BENCH_sweep.json (vmap "
              f"{out['vmap_speedup_vs_legacy']}x vs legacy, bucketed "
              f"{out['bucket_count']} programs cut padding "
              f"{frac_single:.0%} -> {frac_bucketed:.0%}"
              + (f", sharded {out['sharded_speedup_vs_legacy']}x"
                 if t_shard is not None else "") + ")")
    csv.done()
    return out


if __name__ == "__main__":
    main()
