"""Benchmark entry point: one harness per paper table/figure + roofline.

  PYTHONPATH=src python -m benchmarks.run            # reduced budget
  BENCH_FULL=1 PYTHONPATH=src python -m benchmarks.run
  PYTHONPATH=src python -m benchmarks.run --only fig2,roofline
  PYTHONPATH=src python -m benchmarks.run --only engine --emit-json
"""
from __future__ import annotations

import argparse
import functools
import time

from repro.launch.compile_cache import use_compile_cache

from . import (ablations, bench_engine, bench_faults, bench_latency,
               bench_population, bench_sweep, fig2_convergence, fig3_sweeps,
               fig4_heterogeneity, fig56_single_layer, fig7_latency,
               roofline)

SUITES = {
    "fig2": fig2_convergence.main,
    "fig3": fig3_sweeps.main,
    "fig4": fig4_heterogeneity.main,
    "fig56": fig56_single_layer.main,
    "fig7": fig7_latency.main,
    "ablations": ablations.main,
    "roofline": lambda: roofline.main([]),
    "engine": bench_engine.main,
    "sweep": bench_sweep.main,
    "latency": bench_latency.main,
    "population": bench_population.main,
    "faults": bench_faults.main,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of " + ",".join(SUITES))
    ap.add_argument("--emit-json", action="store_true",
                    help="write BENCH_*.json (engine/sweep/latency/population/"
                         "faults)")
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale budget (latency/faults suites; used "
                         "by the bench-emission smoke test)")
    args = ap.parse_args()
    use_compile_cache()
    names = args.only.split(",") if args.only else list(SUITES)
    suites = dict(SUITES)
    suites["engine"] = functools.partial(bench_engine.main,
                                         emit_json=args.emit_json)
    suites["sweep"] = functools.partial(bench_sweep.main,
                                        emit_json=args.emit_json)
    suites["latency"] = functools.partial(bench_latency.main,
                                          emit_json=args.emit_json,
                                          smoke=args.smoke)
    suites["population"] = functools.partial(bench_population.main,
                                             emit_json=args.emit_json)
    suites["faults"] = functools.partial(bench_faults.main,
                                         emit_json=args.emit_json,
                                         smoke=args.smoke)
    t0 = time.time()
    for name in names:
        suites[name]()
    print(f"# all benchmarks done in {time.time() - t0:.0f}s")


if __name__ == "__main__":
    main()
