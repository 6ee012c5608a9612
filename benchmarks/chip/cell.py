"""A benchmark cell: its entry in ``BENCHMARK.json``, its files, and the
deployment they describe.

Everything that belongs to one configuration, traffic mix or per-layer
metric sits in a file of its own, found by the name ``BENCHMARK.json``
gives it:

    configs/<config>.json     sizes, simulator arguments, and the names of
                              its client model and its reference
    models/<model>.py         the client model, written plainly: the one
                              file that reads the model's sizes
    traffic/<traffic>.json    aggregator, straggler modes and rates, faults
    limits/<workload>.json    the limit of each number ``correct`` compares
    metrics/<metric>.py       one reader per per-layer metric
    references/<name>.py      the plain reference of the FL rounds, which
                              takes the model module as an argument

so a new cell is a new data file or two plus one ``workloads`` entry, and
a new client model one more module.  Every model module keeps one
contract (written out in ``models/cnn.py``):

    param_shapes(setting), init_params(config, seed), loss(p, x, y),
    test_count(p, x, y), n_eval(planes), train_flops_per_sample(setting)

and may add counts of its kernels' work, such as ``conv_work``.  A model
that trains over weights it never changes adds ``frozen_params(config)``
and takes them as a fourth argument, ``loss(p, x, y, frozen)`` and
``test_count(p, x, y, frozen)``; its ``param_shapes`` are then the
federated parameters alone, and ``train_flops_per_sample`` counts the
forward pass, the input gradients through the frozen layers and the
weight gradients of the federated parameters only.  A model may also say
how many device slots its plain reference trains at once,
``reference_block(setting)`` (all of them where it does not).
"""
from __future__ import annotations

import dataclasses
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[1]
if str(REPO / "src") not in sys.path:
    sys.path.insert(0, str(REPO / "src"))


def benchmark() -> dict:
    return json.loads((REPO / "BENCHMARK.json").read_text())


def load(kind: str, name: str) -> dict:
    return json.loads((HERE / kind / f"{name}.json").read_text())


@dataclasses.dataclass
class Cell:
    """One ``workloads`` entry with its configuration, traffic and limits
    read from their files."""
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list

    @classmethod
    def named(cls, workload: str) -> "Cell":
        bench = benchmark()
        by_name = {w["name"]: w for w in bench["workloads"]}
        if workload not in by_name:
            raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                           f"known: {sorted(by_name)}")
        w = by_name[workload]

        def applies(metric):
            return workload in metric.get("workloads", [workload])

        cfg = next(c for c in bench["configs"] if c["name"] == w["config"])
        return cls(name=workload, chips=int(w["chips"]),
                   config=json.loads((REPO / cfg["file"]).read_text()),
                   traffic=load("traffic", w["traffic"]),
                   limits=load("limits", workload),
                   end_to_end=[m for m in bench["end_to_end"] if applies(m)],
                   per_layer=[m for m in bench["per_layer"] if applies(m)])


def build_simulator(config: dict, traffic: dict, seed: int,
                    kernel_mode: str = "auto"):
    """The deployment of ``config`` under ``traffic``, every random draw
    (data, partition, batches, latency, chain, faults, cohorts) taken
    from the named streams of ``seed``."""
    from repro.configs.bhfl_cnn import BHFLSetting
    from repro.fl import BHFLSimulator
    from repro.fl.population import PopulationSpec

    setting = BHFLSetting(**{**config["setting"],
                             **traffic.get("setting", {}), "seed": seed})
    kw = dict(config.get("simulator", {}))
    pop = {**config.get("population", {}), **traffic.get("population", {})}
    if pop:
        kw["population"] = PopulationSpec(**pop)
    return BHFLSimulator(setting, traffic["aggregator"],
                         traffic["device_stragglers"],
                         traffic["edge_stragglers"],
                         kernel_mode=kernel_mode, **kw)


def with_init_weights(inp, weights: dict):
    """``inp`` with the global model at round zero replaced by ``weights``
    (made by the benchmark from the seed), checked leaf for leaf against
    the program's own layout."""
    import jax

    want = jax.tree.map(lambda v: (v.shape[1:], v.dtype), inp.init_w)
    got = jax.tree.map(lambda v: (v.shape, v.dtype), weights)
    if want != got:
        raise ValueError(f"reference weights {got} do not match the "
                         f"program's layout {want}")
    return dataclasses.replace(
        inp, init_w=jax.tree.map(lambda v: v[None], weights))


def with_frozen_weights(inp, frozen: dict):
    """``inp`` with the model's frozen weights (made by the benchmark from
    the configuration) as its ``frozen_w`` field, the one copy that every
    device slot reads, checked leaf for leaf against the program's own
    layout of that field."""
    import jax

    if "frozen_w" not in {f.name for f in dataclasses.fields(inp)}:
        raise ValueError(
            f"the model has frozen weights, and the program's inputs "
            f"({type(inp).__name__}) have no frozen_w field to take them")
    want = jax.tree.map(lambda v: (v.shape, v.dtype), inp.frozen_w)
    got = jax.tree.map(lambda v: (v.shape, v.dtype), frozen)
    if want != got:
        raise ValueError(f"frozen weights {got} do not match the program's "
                         f"frozen_w layout {want}")
    return dataclasses.replace(inp, frozen_w=frozen)


def chunk_kwargs(sim) -> dict:
    """The static arguments of ``run_engine_chunk`` for ``sim``."""
    return dict(aggregator=sim.aggregator, normalize=sim.normalize,
                history_dtype=sim.history_dtype, kernel_mode=sim.kernel_mode)


def samples_per_round(inp) -> int:
    """Training samples in one global round: devices with data x real
    SGD steps x batch x real edge rounds.  Padded and empty slots, which
    the program also steps, are not counted."""
    import numpy as np

    bs = int(inp.batch_idx.shape[-1])
    return int(round(float(np.sum(np.asarray(inp.has_data))))) \
        * int(inp.s_valid) * bs * int(inp.k_valid)
