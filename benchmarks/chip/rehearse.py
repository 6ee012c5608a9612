"""Compile each cell's window program for a described TPU v5e, no chip needed.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse.py [WORKLOAD ...]
        [--hlo-dir DIR]

For every workload of ``BENCHMARK.json`` (or those named) this builds the
deployment from seed 0 on the host, lowers the one-round
``run_engine_chunk`` program with the compiled Pallas kernels for one chip
of a described ``v5e:2x2``, compiles it, and prints one JSON line: the
compiler's ``memory_analysis()`` (argument, output and temporary bytes),
the number of Pallas custom calls, the compile seconds, and the
parameters of the client model that the configuration names, whose
module's ``param_shapes`` must match the program's layout leaf for leaf.
A model's frozen weights, where it has them, go in as the program's
``frozen_w`` input (their shapes only, checked as a run checks them), so
the compile counts them among the arguments.
The TPU compiler refuses here what it would refuse on the chip: a kernel
that does not tile, a program that does not fit.  ``--hlo-dir`` also writes
each compiled program's text, whose instruction metadata the trace
reader attributes device time by.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(Path(__file__).resolve().parent))

import cell as cells  # noqa: E402
import run  # noqa: E402
import work  # noqa: E402


def rehearse(workload: str, hlo_dir: str | None) -> dict:
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from repro.fl import engine

    c = cells.Cell.named(workload)
    sim = cells.build_simulator(c.config, c.traffic, seed=0,
                                kernel_mode="pallas")
    inp = engine.build_inputs(sim)
    _, model = run.load_models(c)
    if hasattr(model, "frozen_params"):
        inp = cells.with_frozen_weights(
            inp, jax.eval_shape(lambda: model.frozen_params(c.config)))
    leaves = model.param_shapes(c.config["setting"])
    layout = {k: tuple(v.shape[1:]) for k, v in inp.init_w.items()}
    if layout != leaves:
        raise ValueError(f"{c.config['model']}.param_shapes {leaves} does "
                         f"not match the program's layout {layout}")
    carry = engine.init_engine_carry(inp, sim.history_dtype)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        (engine.slice_rounds(inp, 0, 1), carry, jax.numpy.int32(0)))
    t0 = time.perf_counter()
    compiled = engine.run_engine_chunk.lower(
        *shapes, **cells.chunk_kwargs(sim)).compile()
    secs = time.perf_counter() - t0
    text = compiled.as_text()
    if hlo_dir:
        Path(hlo_dir).mkdir(parents=True, exist_ok=True)
        (Path(hlo_dir) / f"{workload}.hlo.txt").write_text(text)
    mem = compiled.memory_analysis()
    return {"workload": workload, "compile_s": secs,
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "generated_code_bytes": mem.generated_code_size_in_bytes,
            "pallas_custom_calls": text.count("tpu_custom_call"),
            "model_params": work.n_params(leaves)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("workloads", nargs="*")
    ap.add_argument("--hlo-dir")
    args = ap.parse_args(argv)
    import jax
    # a compile for a described chip cannot be read back from the cache
    jax.config.update("jax_enable_compilation_cache", False)
    names = args.workloads or [w["name"]
                               for w in cells.benchmark()["workloads"]]
    for name in names:
        print(json.dumps(rehearse(name, args.hlo_dir)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
