"""The readings that each limit of ``correct`` is set from, on the chip.

    python3 benchmarks/chip/calibrate.py --workload sec6.hieavg \
        --seeds 101 102 103 [--program] [--variants control half_batch ...]

For each seed this builds the cell's deployment as a run's set-up does,
runs the plain reference (float32, the configuration's precision) of the
client model the configuration names over the checked rounds, and puts
in the program's place, at the cell's own size:

* ``program``: the window's compiled one-round program, as a run's set-up
  drives it (the lower readings come from sound runs of this);
* ``control``: the reference computed in bfloat16, the nearest precision
  below the configuration's float32 (frozen weights too; integer planes,
  such as token ids, keep their dtype);
* ``half_batch``, ``no_exchange``, ``altered_update``: the reference with
  one of the faults the check must catch planted (see the reference's
  docstring).  A step that returns its state unchanged reads 1 on both
  change numbers by their definition and needs no run.

Each reading is one JSON line on standard output: the seed, what stood in
the program's place, and the numbers ``compare.numbers`` gives against
the reference.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import cell as cells  # noqa: E402
import compare  # noqa: E402
import run  # noqa: E402

VARIANTS = ("control", "half_batch", "no_exchange", "altered_update")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--program", action="store_true")
    ap.add_argument("--variants", nargs="*", default=list(VARIANTS),
                    choices=VARIANTS)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from repro.launch.compile_cache import use_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("calibrate.py: needs a TPU", file=sys.stderr)
        return 1
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    c = cells.Cell.named(args.workload)
    ref, model = run.load_models(c)
    compiled = None
    for seed in args.seeds:
        p = run.prepare(c, seed, model)
        planes, w0, checked, frozen = p.planes, p.w0, p.checked, p.frozen
        prog = None
        if args.program:
            compiled = compiled or run.compile_round(p)
            prog, _ = run.first_rounds(compiled, p, model)
        del p
        gc.collect()
        t = time.perf_counter()
        base = ref.run(model, c.config, planes, w0, checked, frozen=frozen)
        secs = {"reference": time.perf_counter() - t}
        stand_ins = {"program": prog} if prog else {}
        for v in args.variants:
            t = time.perf_counter()
            stand_ins[v] = ref.run(
                model, c.config, planes, w0, checked,
                dtype=jnp.bfloat16 if v == "control" else jnp.float32,
                fault=None if v == "control" else v, frozen=frozen)
            secs[v] = time.perf_counter() - t
        for name, got in stand_ins.items():
            print(json.dumps({"workload": c.name, "seed": seed,
                              "stand_in": name,
                              **compare.numbers(got, base, w0),
                              "seconds": secs.get(name),
                              "reference_seconds": secs["reference"]}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
