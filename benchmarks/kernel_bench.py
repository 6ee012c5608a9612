"""Kernel micro-benchmarks + kernel-plane engine rows — ``BENCH_kernels.json``.

Two sections:

  * **micro** — every fused kernel vs its XLA reference path on realistic
    shapes: analytic HBM traffic per path (the quantity the fused kernels
    actually optimize), measured wall time of both (reps interleaved via
    ``interleaved_best_of`` so box-load drift never reads as a path
    difference), and an allclose check.  Rows:

      - ``hieavg_agg``     — warm edge aggregation (estimate+mix+history),
      - ``eval_head``      — logits → argmax → correct-count, one pass,
      - ``coef_agg_pair``  — the generalized coefficient aggregate (pair
        form: the delayed-gradient fill + weighted mean in one pass).

    The kernels take the default interpret policy
    (``dispatch.default_interpret``): compiled on a TPU, the Pallas
    *interpreter* on a CPU (``fused_backend`` records which), where their
    wall time is not a device figure of merit.
  * **engine** — rounds/sec of the same REDUCED deployment as
    ``bench_engine`` with the kernel plane on (``kernel_mode="auto"``) vs
    forced off (``"xla"``), reps interleaved.  On CPU "auto" resolves to
    the XLA reference dispatch, so the acceptance bar is parity: auto
    within a few percent of xla (the dispatch layer adds no overhead).
    On accelerators the same row measures the fused-kernel speedup.

  The JSON carries the ``padded_flop_frac``-style kernel-plane coverage
  block (``fused_phase_coverage``): which engine round phases run fused
  under the measured mode, and under a fused mode — conv fwd/bwd (XLA's
  own convolution, no Pallas kernel), SGD, warm+cold aggregation, fedavg,
  delayed-grad, and the eval head, i.e. the whole round.

  PYTHONPATH=src python -m benchmarks.run --only kernels --emit-json
"""
from __future__ import annotations

import dataclasses
import json

import jax
import jax.numpy as jnp

from repro.configs.bhfl_cnn import REDUCED
from repro.core import hieavg
from repro.kernels import (default_interpret, fused_phase_coverage,
                           resolve_kernel_mode)
from repro.kernels import ops, ref

from .bench_engine import kernel_plane_record
from .common import Csv, interleaved_best_of

# same budget as bench_engine so the engine rows are comparable to
# BENCH_engine.json
T_ROUNDS = 20
ENGINE_KW = dict(n_train=2000, n_test=400, steps_per_epoch=1,
                 normalize=True)
REPS = 3


def hbm_traffic_gb(n: int, l: int, bytes_per: int = 4) -> tuple[float, float]:
    """(XLA-path, fused-path) HBM bytes for one edge aggregation.

    XLA path (observed from the jaxpr of hieavg.edge_aggregate): reads w,
    prev, dmean for the estimate, again for the mix, again for both history
    updates, and writes agg + 2 history trees ≈ 7 full passes.
    Fused: read w/prev/dmean once, write agg + 2 histories once ≈ 2 passes.
    """
    leaf = n * l * bytes_per
    xla = 7 * leaf
    fused = (3 * leaf) + (2 * leaf + l * bytes_per)
    return xla / 1e9, fused / 1e9


def eval_traffic_gb(m: int, f: int, c: int,
                    bytes_per: int = 4) -> tuple[float, float]:
    """(XLA, fused) HBM bytes for the eval head.

    XLA materializes the ``[M, C]`` logits (write) then re-reads them for
    the argmax; the fused kernel folds argmax+compare+count into the
    matmul tiles and never writes logits to HBM (output: one count/tile).
    """
    feats, logits = m * f * bytes_per, m * c * bytes_per
    return (feats + 2 * logits) / 1e9, feats / 1e9


def pair_traffic_gb(n: int, l: int, bytes_per: int = 4) -> tuple[float, float]:
    """(XLA, fused) HBM bytes for the pair-form coefficient aggregate.

    XLA (the ``delayed_grad`` reference): fill ``where(mask, w, pending)``
    reads both ``[n, L]`` operands and writes the filled intermediate,
    then the weighted mean re-reads it ≈ 4 full passes; the fused kernel
    reads each operand once and writes the ``[L]`` aggregate.
    """
    leaf, out = n * l * bytes_per, l * bytes_per
    return (4 * leaf + out) / 1e9, (2 * leaf + out) / 1e9


def _pair_ms(xla_fn, fused_fn) -> tuple[float, float]:
    """Interleaved best-of wall ms for one (xla, fused) micro pair."""
    best = interleaved_best_of({
        "xla": lambda: jax.block_until_ready(xla_fn()),
        "fused": lambda: jax.block_until_ready(fused_fn()),
    }, REPS)
    return best["xla"] * 1e3, best["fused"] * 1e3


def _row(csv: Csv, name, n, l, xla_gb, fused_gb, xla_ms, fused_ms,
         ok) -> dict:
    csv.row(name, n, l, f"{xla_gb:.3f}", f"{fused_gb:.3f}",
            f"{xla_gb / fused_gb:.1f}x", f"{xla_ms:.1f}",
            f"{fused_ms:.1f}", ok)
    return {"kernel": name, "n": n, "L": l,
            "xla_hbm_gb": round(xla_gb, 3),
            "fused_hbm_gb": round(fused_gb, 3),
            "hbm_reduction": round(xla_gb / fused_gb, 2),
            "xla_ms": round(xla_ms, 2), "fused_ms": round(fused_ms, 2),
            "allclose": ok}


def _micro_rows(csv: Csv) -> list[dict]:
    rows = []
    # warm edge aggregation (the original row set)
    for n, l in ((5, 100_000), (25, 100_000), (16, 400_000)):
        ks = jax.random.split(jax.random.key(0), 3)
        w = jax.random.normal(ks[0], (n, l))
        stacked = {"p": w}
        hist = hieavg.init_history(stacked)
        mask = jnp.arange(n) % 5 != 0
        xla_ms, fused_ms = _pair_ms(
            lambda: hieavg.edge_aggregate(stacked, mask, hist)[0]["p"],
            lambda: ops.fused_edge_aggregate(stacked, mask, hist)[0]["p"])
        agg, _ = hieavg.edge_aggregate(stacked, mask, hist)
        agg_f, _ = ops.fused_edge_aggregate(stacked, mask, hist)
        ok = bool(jnp.allclose(agg["p"], agg_f["p"], atol=1e-4))
        xla_gb, fused_gb = hbm_traffic_gb(n, l)
        rows.append(_row(csv, "hieavg_agg", n, l, xla_gb, fused_gb,
                         xla_ms, fused_ms, ok))

    # fused eval head (logits -> argmax -> count, one pass)
    ks = jax.random.split(jax.random.key(2), 4)
    m, f, c = 400, 784, 10
    feats = jax.random.normal(ks[0], (m, f))
    wmat = jax.random.normal(ks[1], (f, c)) * 0.05
    bias = jax.random.normal(ks[2], (c,)) * 0.05
    labels = jax.random.randint(ks[3], (m,), 0, c)
    xla_eval = jax.jit(ref.eval_head_ref)
    fused_eval = jax.jit(ops.eval_head)
    xla_ms, fused_ms = _pair_ms(
        lambda: xla_eval(feats, wmat, bias, labels),
        lambda: fused_eval(feats, wmat, bias, labels))
    ok = bool(xla_eval(feats, wmat, bias, labels)
              == fused_eval(feats, wmat, bias, labels))
    xla_gb, fused_gb = eval_traffic_gb(m, f, c)
    rows.append(_row(csv, "eval_head", m, f, xla_gb, fused_gb,
                     xla_ms, fused_ms, ok))

    # generalized coefficient aggregate, pair form (delayed-grad fill+mean)
    ks = jax.random.split(jax.random.key(3), 4)
    n, l = 25, 100_000
    w = jax.random.normal(ks[0], (n, l))
    aux = jax.random.normal(ks[1], (n, l))
    coef = jax.nn.softmax(jax.random.normal(ks[2], (n,)))
    msk = (jax.random.uniform(ks[3], (n,)) > 0.3).astype(jnp.float32)
    ca, cb = coef * msk, coef * (1.0 - msk)
    xla_pair = jax.jit(ref.coef_agg_pair_ref)
    fused_pair = jax.jit(ops.coef_agg_pair)
    xla_ms, fused_ms = _pair_ms(lambda: xla_pair(w, aux, ca, cb),
                                lambda: fused_pair(w, aux, ca, cb))
    ok = bool(jnp.allclose(xla_pair(w, aux, ca, cb),
                           fused_pair(w, aux, ca, cb), atol=1e-5))
    xla_gb, fused_gb = pair_traffic_gb(n, l)
    rows.append(_row(csv, "coef_agg_pair", n, l, xla_gb, fused_gb,
                     xla_ms, fused_ms, ok))
    return rows


def _engine_rounds_per_sec() -> dict[str, float]:
    """rounds/sec for kernel_mode auto vs forced xla, reps interleaved
    (``interleaved_best_of``): on CPU the two are the same compiled
    program and should measure equal up to noise."""
    from repro.fl import BHFLSimulator
    setting = dataclasses.replace(REDUCED, t_global_rounds=T_ROUNDS)

    def once(mode):
        BHFLSimulator(setting, "hieavg", "temporary", "temporary",
                      kernel_mode=mode, **ENGINE_KW).run()

    best = interleaved_best_of({
        "auto": lambda: once("auto"),
        "xla": lambda: once("xla"),
    }, REPS)
    return {mode: T_ROUNDS / t for mode, t in best.items()}


def main(emit_json: bool = False) -> dict:
    csv = Csv("kernel_bench")
    # engine rows first: the interpret-mode micro bench below loads the
    # box for seconds at a time, which would skew an engine timing that
    # followed it
    auto_mode = resolve_kernel_mode("auto")
    rps = _engine_rounds_per_sec()
    rps_auto, rps_xla = rps["auto"], rps["xla"]

    csv.row("kernel", "n", "L", "xla_hbm_GB", "fused_hbm_GB", "reduction",
            "xla_ms", "fused_ms", "allclose")
    micro = _micro_rows(csv)
    # engine throughput is a different table — own header, own columns
    kp = kernel_plane_record("auto")
    csv.row("engine_path", "kernel_mode", "rounds_per_sec",
            "fused_phase_frac")
    csv.row("engine_kernel_plane_auto", auto_mode, f"{rps_auto:.2f}",
            f"{kp['fused_phase_frac']:.3f}")
    csv.row("engine_kernel_plane_off", "xla", f"{rps_xla:.2f}", "0.000")

    out = {
        "backend": jax.default_backend(),
        "fused_backend": "interpret" if default_interpret() else "pallas",
        "auto_resolves_to": auto_mode,
        "micro": micro,
        "kernel_plane": kp,
        # which phases the plane covers when a fused mode is forced on —
        # the full round (coverage is mode-independent once fused)
        "fused_phases_when_on": fused_phase_coverage("interpret"),
        "engine_t_global_rounds": T_ROUNDS,
        "engine_auto_rounds_per_sec": round(rps_auto, 3),
        "engine_xla_rounds_per_sec": round(rps_xla, 3),
        "engine_auto_vs_xla": round(rps_auto / rps_xla, 3),
    }
    if emit_json:
        with open("BENCH_kernels.json", "w") as f:
            json.dump(out, f, indent=2)
        print(f"# wrote BENCH_kernels.json (engine auto {rps_auto:.2f} r/s"
              f" vs xla {rps_xla:.2f} r/s; auto -> {auto_mode})")
    csv.done()
    return out


if __name__ == "__main__":
    main()
