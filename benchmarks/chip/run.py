"""The chip benchmark of the BHFL engine: one cell, one seed, one window.

    python3 benchmarks/chip/run.py --workload sec6.hieavg --seed 7 \
        --seconds 10 --trace 0

Runs on the machine it is started on and needs the cell's TPU chips; with
none it exits 1 and prints no result.  One run:

1. builds the cell's deployment from ``--seed`` (``BHFLSetting.seed``: data,
   partition, batches, latency, chain, faults and cohorts all come from its
   named streams) and the initial weights, on the device, from the seed,
   by the client model's module (``models/<model>.py``, which the
   configuration names: the one file that knows the model), and the
   model's frozen weights where it has them, from the configuration,
   handed to the program and to the reference alike;
2. compiles the one-round ``engine.run_engine_chunk`` program ahead of
   time (``kernel_mode="auto"``: the compiled Pallas kernels on a TPU),
   from JAX's persistent cache after a checkout's first run;
3. drives that program through the cold-boot rounds and the first HieAvg
   round, keeping their outputs for the check;
4. measures: whole global rounds, one call each, each ended by
   ``block_until_ready``, until ``--seconds`` have passed (at least one
   round); after round T it restarts from the carry saved after cold
   boot, so every round in the window is a warm HieAvg round;
5. with ``--trace 1`` traces that window instead and reduces the trace
   to the per-layer metrics (``metrics/<name>.py``, each reading the one
   context ``metric_context`` builds) and a breakdown;
6. frees the program's state and runs the plain reference over the same
   first rounds, and compares (``compare.py``, ``limits/<workload>.json``).

``setup_s`` is process start to the window's first round.
``samples_per_s`` is the real device-training samples of the window's
rounds (devices with data x SGD steps x batch x edge rounds) over the
wall time from the window's start to the return of its last
``block_until_ready``: eval, aggregation and every host gap are inside.

The last line of standard output is one JSON object; the compared numbers
with their limits are the last lines of standard error and the result's
last key.  Host phases are ``TraceAnnotation`` spans named ``bench/...``,
so a traced run can put each idle gap of the device down to one of them.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import cell as cells  # noqa: E402
import compare  # noqa: E402
import work  # noqa: E402

#: Where the global model sits in the engine's scan carry.
GLOBAL_MODEL = 5


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(path.stem.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def span(name: str):
    import jax
    from devtrace import SPAN
    return jax.profiler.TraceAnnotation(SPAN + name)


def peak_for(kind: str) -> dict:
    peaks = json.loads((HERE / "peaks.json").read_text())
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return peaks[kind]


def load_models(c: cells.Cell):
    """The plain reference and the client model module that the cell's
    configuration names."""
    return (load_module(HERE / "references" / f"{c.config['reference']}.py"),
            load_module(HERE / "models" / f"{c.config['model']}.py"))


def prepare(c: cells.Cell, seed: int, model) -> types.SimpleNamespace:
    """Set-up up to the compile: the deployment, the initial weights that
    ``model`` makes from the seed, its frozen weights where it has them
    (from the configuration, the same in every run), the input planes cut
    per round, the round-zero carry, and the planes the reference reads."""
    import jax
    import numpy as np
    from repro.fl import engine

    sim = cells.build_simulator(c.config, c.traffic, seed)
    w0 = model.init_params(c.config, seed)
    inp = engine.build_inputs(sim)
    frozen = None
    if hasattr(model, "frozen_params"):
        frozen = model.frozen_params(c.config)
        inp = cells.with_frozen_weights(inp, frozen)
    inp = cells.with_init_weights(inp, w0)
    T, t_c = int(inp.t_valid), int(inp.t_cold_boot)
    checked = t_c + 1                   # cold-boot rounds + first HieAvg one
    if T <= checked:
        raise ValueError(f"T={T} leaves no round after the {checked} "
                         "checked ones")
    host = {f: np.asarray(getattr(inp, f)) for f in engine.ROUND_FIELDS}
    rounds = [dataclasses.replace(inp, **r) for r in jax.device_put(
        [{f: host[f][i:i + 1] for f in host} for i in range(T)])]
    starts = jax.device_put([np.int32(i) for i in range(T)])
    carry = engine.init_engine_carry(inp, sim.history_dtype)
    planes = {f: host[f][:checked] for f in host}
    planes.update(
        train_x=sim.train_x, train_y=sim.train_y, test_x=sim.test_x,
        test_y=sim.test_y, has_data=np.asarray(inp.has_data),
        valid=np.asarray(inp.valid), j_arr=np.asarray(inp.j_arr),
        edge_hop=float(inp.edge_hop))
    jax.block_until_ready((rounds, starts, carry))
    return types.SimpleNamespace(
        sim=sim, T=T, t_c=t_c, checked=checked, rounds=rounds,
        starts=starts, carry=carry, planes=planes, frozen=frozen,
        w0={k: np.asarray(v) for k, v in w0.items()},
        samples=cells.samples_per_round(inp),
        slots=int(np.sum(planes["valid"])))


def compile_round(p: types.SimpleNamespace):
    """The one-round ``run_engine_chunk`` program, compiled ahead of time."""
    from repro.fl import engine
    return engine.run_engine_chunk.lower(
        p.rounds[0], p.carry, p.starts[0], **cells.chunk_kwargs(p.sim)
    ).compile()


def first_rounds(compiled, p: types.SimpleNamespace, model
                 ) -> tuple[dict, tuple]:
    """Drive ``compiled`` from round zero through the checked rounds.
    Returns the outputs the check compares, and the carry after cold boot
    (the window's restart point) and after the checked rounds."""
    import numpy as np

    prog = {"loss": [], "correct": [], "clock": [], "energy": [],
            "models": []}
    n_eval = model.n_eval(p.planes)
    carry = restart = p.carry
    for i in range(p.checked):
        outs, carry = compiled(p.rounds[i], carry, p.starts[i])
        acc, loss, _, clock, energy = (float(np.asarray(o)[0]) for o in outs)
        prog["loss"].append(loss)
        prog["correct"].append(round(acc * n_eval))
        prog["clock"].append(clock)
        prog["energy"].append(energy)
        prog["models"].append({k: np.asarray(v) for k, v in
                               carry[GLOBAL_MODEL].items()})
        if i == p.t_c - 1:
            restart = carry
    return prog, (restart, carry)


def metric_context(c: cells.Cell, model, p: types.SimpleNamespace,
                   rounds: int, trace, peak: dict, setup: dict
                   ) -> types.SimpleNamespace:
    """What every per-layer metric reads (``metrics/<name>.py``'s
    ``read``): the cell's ``config``, its ``setting``, the client
    ``model`` module, ``chips``, the device's ``peak``, the window's
    ``trace``, the harness's ``setup`` seconds, ``least_time(flops,
    bytes)``, and the counts of the window's ``rounds``: ``train_samples``
    (real ones, as ``samples_per_s`` counts them), ``eval_samples``, and
    the models that its aggregations mix (``agg_participants``: each edge
    round's device slots and each global round's edges) and write
    (``agg_outputs``: each edge's model and the global one).  A metric
    counts its own work from these; nothing here is a model's size."""
    s = c.config["setting"]
    k, n_edges = s["k_edge_rounds"], len(p.planes["j_arr"])
    return types.SimpleNamespace(
        config=c.config, setting=s, model=model, chips=c.chips, peak=peak,
        trace=trace, setup=setup,
        least_time=lambda f, b: work.least_time(f, b, peak),
        rounds=rounds, train_samples=rounds * p.samples,
        eval_samples=rounds * model.n_eval(p.planes),
        agg_participants=rounds * (k * p.slots + n_edges),
        agg_outputs=rounds * (k * n_edges + 1))


def run_cell(c: cells.Cell, seed: int, seconds: float, trace: bool, *,
             t0: float = T0, trace_dir: str | None = None) -> dict:
    """One run of cell ``c``; returns the result line as a dict."""
    import jax
    import numpy as np
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    ref, model = load_models(c)
    devs = jax.devices()[:c.chips]
    now = time.perf_counter
    setup = {}

    with span("setup.inputs"):
        t = now()
        p = prepare(c, seed, model)
        setup["inputs_s"] = now() - t
    with span("setup.compile"):
        t = now()
        compiled = compile_round(p)
        setup["compile_s"] = now() - t
    with span("setup.first_rounds"):
        prog, (restart, carry) = first_rounds(compiled, p, model)
    planes, w0, frozen = p.planes, p.w0, p.frozen

    # ---- the window
    tdir = None
    if trace:
        hlo_text = compiled.as_text()
        tdir = trace_dir or tempfile.mkdtemp(prefix="bhfl-trace-")
        jax.profiler.start_trace(tdir)
    setup_s = now() - t0
    t_next, n_rounds, failed, ends = p.checked, 0, 0, []
    with span("window"):
        tw = now()
        while True:
            with span("round.dispatch"):
                outs, carry = compiled(p.rounds[t_next], carry,
                                       p.starts[t_next])
            with span("round.wait"):
                jax.block_until_ready((outs, carry))
            with span("round.readback"):
                vals = [np.asarray(o) for o in outs]
            failed += not all(np.all(np.isfinite(v)) for v in vals)
            n_rounds += 1
            ends.append(now() - tw)
            t_next += 1
            if t_next == p.T:
                with span("round.restart"):
                    carry, t_next = restart, p.t_c
            if now() - tw >= seconds:
                break
        wall = now() - tw
    if trace:
        jax.profiler.stop_trace()
    # the runtime reserves a program's temporaries apart from its buffers
    stats = [d.memory_stats() or {} for d in devs]
    mem_peak = max(m.get("peak_bytes_in_use", 0)
                   + m.get("peak_bytes_reserved", m.get("bytes_reserved", 0))
                   for m in stats)
    mem = compiled.memory_analysis()
    print(f"memory_stats {stats}", file=sys.stderr)
    print(f"window_round_ends_s {ends}", file=sys.stderr)

    dev = devs[0]
    result = {"correct": False, "attempted": n_rounds, "failed": failed,
              "metrics": {},
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(jax.devices()),
                         "memory_peak_bytes": int(mem_peak),
                         "program_bytes": int(
                             mem.argument_size_in_bytes
                             + mem.output_size_in_bytes
                             + mem.temp_size_in_bytes)}}
    if trace:
        from devtrace import Trace
        tr = Trace.read(tdir, hlo_text)
        ctx = metric_context(c, model, p, n_rounds, tr,
                             peak_for(dev.device_kind), setup)
        for m in c.per_layer:
            v = load_module(HERE / "metrics" / f"{m['name']}.py").read(ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        result["device"].update(busy_s=tr.busy_s(), window_s=tr.window_s)
        result["breakdown"] = {"device_ops": tr.top_ops(10),
                               "idle_gaps": tr.idle_gaps(10)}
        if trace_dir:
            (Path(trace_dir) / "window.hlo.txt").write_text(hlo_text)
        else:
            shutil.rmtree(tdir, ignore_errors=True)
    else:
        e2e = {"samples_per_s": n_rounds * p.samples / wall,
               "setup_s": setup_s}
        for m in c.end_to_end:
            result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                            "unit": m["unit"]}

    # ---- the check, once the program's state is freed
    checked = p.checked
    del compiled, carry, restart, outs, p
    gc.collect()
    got = ref.run(model, c.config, planes, w0, checked, frozen=frozen)
    ok, checks = compare.judge(compare.numbers(prog, got, w0), c.limits)
    result["correct"] = ok and failed == 0
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir",
                    help="keep the raw trace and the window program's text "
                         "here (default: a temporary directory, removed)")
    args = ap.parse_args(argv)

    import jax
    c = cells.Cell.named(args.workload)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < c.chips:
        print(f"run.py: {args.workload} needs {c.chips} TPU chip(s); JAX "
              f"found {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return 1
    result = run_cell(c, args.seed, args.seconds, bool(args.trace),
                      trace_dir=args.trace_dir)
    for name, chk in result["checks"].items():
        print(f"check {name} {chk['value']!r} limit {chk['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
