"""Drive the BHFL engine's main path on a TPU and check what comes out.

    python chip_smoke.py                # one chip: phases 1 and 2
    python chip_smoke.py --four-chips   # four chips: the sharded sweep only

Every run is at the paper's widths (``configs.bhfl_cnn.DEFAULT``: N = 5
edges x J = 5 devices, K = 2, batch 32, 28x28 inputs, 32/64 conv channels,
60000 training and 1000 test images), cut to T = 3 global rounds, with
weights and data made from seed 0.

Phase 1 trains one deployment through ``BHFLSimulator.run`` with
``kernel_mode="auto"``, which must resolve to the compiled Pallas kernels,
and again with the pure-XLA path, and compares the trajectories.  Phase 2
runs a three-point mixed-aggregation grid (HieAvg, delayed gradient,
FedAvg) through ``plan_sweep``/``run_plan``: the traced "switched"
program, which reaches the cold-boot, FedAvg and delayed-gradient kernels
too.  ``--four-chips`` runs only a 4-point shape-changing grid sharded
over four chips (``placement="shard"``) and the same grid on one chip
(``placement="vmap"``), in this one process.  That grid trains on 12000
of the images, an epoch of 15 to 37 steps per device instead of 75 to
187, so that its runs on four chips stay short; its widths are the same.

Sweeps walk their points one at a time (``point_batch=1``): one point at
these widths needs 6.4 to 8.9 GB of a v5e's 16 GB.

Tolerances.  On a TPU v5e both paths feed f32 matmuls to the MXU as one
bf16 pass with f32 accumulation (XLA's default precision, and what
Mosaic does for the kernels' f32 dots), so they differ only in summation
order and fusion: f32 rounding, which 450 SGD steps amplify.  On the chip
the two deployments' losses differed by 2.9e-6 relative and their
accuracies not at all, while running the XLA path at float32 matmul
precision instead moved the loss by 5.3e-2 and the accuracy by 0.008: one
bf16 rounding more or less per matmul shows at the percent level.
Losses must agree to ``LOSS_RTOL`` = 1e-4, thirty times the noise and
far below a precision or kernel error; accuracies to ``ACC_ATOL`` =
0.005, 5 of the 1000 test images, where a prediction whose top two logits
tie to rounding may flip.  The simulated clock comes from host-drawn
latency planes and must agree to ``CLOCK_RTOL`` = 1e-6.  The same
tolerances hold between the grids' points and their references, which
share kernels but batch them differently.

Earlier lines of standard output are JSON records of each phase (run
seconds, peak device memory, Pallas call count, largest differences); a
comparison is printed before it is checked.  The last line is one JSON
object naming the device.  The script exits nonzero, printing no such
line, when JAX finds no TPU or a check fails.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ACC_ATOL = 0.005
LOSS_RTOL = 1e-4
CLOCK_RTOL = 1e-6
T_ROUNDS = 3
SIM_KW = dict(n_train=60000, n_test=1000)
POINT_BATCH = 1
SWEEP_MIX = [{"aggregation": "hieavg"}, {"aggregation": "delayed_grad"},
             {"aggregation": "fedavg"}]
FOUR_CHIP_GRID = [{"n_edges": 2}, {"j_per_edge": 2}, {"k_edge_rounds": 1},
                  {"straggler_frac": 0.4}]
FOUR_CHIP_SIM_KW = dict(SIM_KW, n_train=12000)


def record(**kw) -> None:
    print(json.dumps(kw), flush=True)


def peak_bytes(devs) -> list:
    """Each device's peak bytes in use so far in this process."""
    return [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def trajectory(res, p=None) -> tuple:
    """(accuracy, loss, sim_clock) of a run, or of sweep point ``p``."""
    import numpy as np
    arrays = (res.accuracy, res.loss, res.sim_clock)
    return tuple(np.asarray(a if p is None else a[p]) for a in arrays)


def check_finite(name: str, res) -> None:
    import numpy as np
    for field in ("accuracy", "loss", "grad_norm", "sim_clock", "sim_energy"):
        a = np.asarray(getattr(res, field))
        if not np.all(np.isfinite(a)):
            raise AssertionError(f"{name}: non-finite {field}: {a}")


def compare(phase: str, what, got, ref) -> None:
    """Print the largest differences of two (accuracy, loss, clock)
    trajectories, then check them against the tolerances."""
    import numpy as np
    (ga, gl, gc), (ra, rl, rc) = got, ref
    record(phase=phase, check=what,
           max_acc_abs_diff=float(np.max(np.abs(ga - ra))),
           max_loss_rel_diff=float(np.max(np.abs(gl - rl) / np.abs(rl))),
           max_clock_rel_diff=float(np.max(np.abs(gc - rc) / np.abs(rc))),
           acc_atol=ACC_ATOL, loss_rtol=LOSS_RTOL, clock_rtol=CLOCK_RTOL)
    name = f"{phase} {what}"
    np.testing.assert_allclose(ga, ra, atol=ACC_ATOL, rtol=0,
                               err_msg=f"{name}: accuracy")
    np.testing.assert_allclose(gl, rl, rtol=LOSS_RTOL,
                               err_msg=f"{name}: loss")
    np.testing.assert_allclose(gc, rc, rtol=CLOCK_RTOL,
                               err_msg=f"{name}: sim_clock")


def pallas_calls(sim) -> int:
    """Pallas custom calls in the engine lowered for ``sim``'s inputs."""
    from repro.fl.engine import build_inputs, run_engine
    text = run_engine.lower(build_inputs(sim),
                            kernel_mode=sim.kernel_mode).as_text()
    return text.count("tpu_custom_call")


def deployment(setting, devs) -> tuple:
    """Phase 1: one deployment, compiled kernels against pure XLA."""
    from repro.fl import BHFLSimulator

    def sim(mode):
        return BHFLSimulator(setting, "hieavg", "temporary", "temporary",
                             kernel_mode=mode, **SIM_KW)

    s = sim("auto")
    if s.kernel_mode != "pallas":
        raise AssertionError(f"kernel_mode='auto' resolved to "
                             f"{s.kernel_mode!r} on a TPU, not 'pallas'")
    # lowered for a twin deployment, so this simulator's chain is untouched
    n_calls = pallas_calls(sim("auto"))
    record(phase="deployment", kernel_mode_auto=s.kernel_mode,
           pallas_custom_calls_lowered=n_calls)
    if n_calls == 0:
        raise AssertionError("no tpu_custom_call in the lowered engine")

    trajs = {}
    for mode in ("auto", "xla"):
        s = s if mode == "auto" else sim(mode)
        res, first_s = timed(s.run)
        _, warm_s = timed(s.run)
        if res.blocks != setting.t_global_rounds or not res.chain_valid:
            raise AssertionError(f"{s.kernel_mode}: blocks={res.blocks} "
                                 f"chain_valid={res.chain_valid}")
        check_finite(f"deployment {s.kernel_mode}", res)
        trajs[s.kernel_mode] = trajectory(res)
        record(phase="deployment", kernel_mode=s.kernel_mode,
               first_run_s=first_s, warm_run_s=warm_s,
               first_minus_warm_s=first_s - warm_s,
               peak_bytes_in_use=peak_bytes(devs),
               accuracy=trajs[s.kernel_mode][0].tolist(),
               loss=trajs[s.kernel_mode][1].tolist())
    compare("deployment", "pallas_vs_xla", trajs["pallas"], trajs["xla"])
    return trajs["pallas"]


def mixed_grid(setting, devs, standalone) -> None:
    """Phase 2: the switched mixed-aggregation grid on one chip."""
    from repro.fl import plan_sweep, run_plan

    trajs = {}
    for mode in ("auto", "xla"):
        plan = plan_sweep(setting, overrides=SWEEP_MIX, kernel_mode=mode,
                          **SIM_KW)
        if plan.aggregator != "switched" or len(plan.buckets) != 1:
            raise AssertionError(f"unexpected plan: {plan.describe()}")
        res, secs = timed(lambda: run_plan(plan, placement="vmap",
                                           point_batch=POINT_BATCH))
        check_finite(f"grid {plan.kernel_mode}", res)
        trajs[plan.kernel_mode] = [trajectory(res, p)
                                   for p in range(len(SWEEP_MIX))]
        record(phase="mixed_grid", kernel_mode=plan.kernel_mode,
               points=len(SWEEP_MIX), point_batch=POINT_BATCH,
               run_plan_s=secs, peak_bytes_in_use=peak_bytes(devs),
               final_accuracy=[float(a) for a in res.accuracy[:, -1]])
    for p, ov in enumerate(SWEEP_MIX):
        compare("mixed_grid", f"pallas_vs_xla {ov['aggregation']}",
                trajs["pallas"][p], trajs["xla"][p])
    # the grid's HieAvg point is the phase-1 deployment
    compare("mixed_grid", "hieavg_point_vs_deployment", trajs["pallas"][0],
            standalone)


def sharded_grid(setting, devs) -> None:
    """--four-chips: the sweep sharded over four chips vs one chip."""
    from repro.fl import run_sweep

    trajs = {}
    for placement in ("shard", "vmap"):
        res, secs = timed(lambda: run_sweep(
            setting, overrides=FOUR_CHIP_GRID, placement=placement,
            max_buckets=1, bucket_cost="proxy", point_batch=POINT_BATCH,
            **FOUR_CHIP_SIM_KW))
        check_finite(f"grid {placement}", res)
        trajs[placement] = [trajectory(res, p)
                            for p in range(len(FOUR_CHIP_GRID))]
        record(phase="four_chip_grid", placement=placement,
               points=len(FOUR_CHIP_GRID), point_batch=POINT_BATCH,
               run_sweep_s=secs, peak_bytes_in_use=peak_bytes(devs),
               final_accuracy=[float(a) for a in res.accuracy[:, -1]])
    for p, ov in enumerate(FOUR_CHIP_GRID):
        compare("four_chip_grid", f"shard_vs_vmap {ov}", trajs["shard"][p],
                trajs["vmap"][p])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sweep sharded over four chips and "
                         "its one-chip reference")
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devs[0].platform!r}",
              file=sys.stderr)
        return 1
    if args.four_chips and len(devs) != 4:
        print(f"chip_smoke: --four-chips needs 4 chips, found {len(devs)}",
              file=sys.stderr)
        return 1

    from repro.configs.bhfl_cnn import DEFAULT
    from repro.launch.compile_cache import use_compile_cache

    record(phase="setup", compile_cache=use_compile_cache(),
           device_kind=devs[0].device_kind, device_count=len(devs))
    setting = dataclasses.replace(DEFAULT, t_global_rounds=T_ROUNDS)
    if args.four_chips:
        sharded_grid(setting, devs)
    else:
        standalone = deployment(setting, devs[:1])
        mixed_grid(setting, devs[:1], standalone)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
