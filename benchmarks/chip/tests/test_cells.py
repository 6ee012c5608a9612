"""Every cell, configuration, traffic mix and per-layer metric of
BENCHMARK.json is found by name in a file of its own."""
import importlib.util

import pytest

import cell as cells

BENCH = cells.benchmark()


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_cell_files_load(workload):
    c = cells.Cell.named(workload)
    assert (cells.HERE / "references"
            / f"{c.config['reference']}.py").is_file()
    assert set(c.limits["numbers"]) == {
        "loss_gap", "update1_gap", "update3_gap", "test_images_gap",
        "clock_gap", "energy_gap"}
    assert c.end_to_end and c.per_layer


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_metric_reader_exists(metric):
    path = cells.HERE / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location("m", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert callable(mod.read)
