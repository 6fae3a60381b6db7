"""Every cell in ``BENCHMARK.json`` finds its files by name: the
configuration, the traffic and its runner, the limits and a reader for
each metric it reports."""
import json

import pytest

import harness

with open(harness.ROOT / "BENCHMARK.json") as f:
    BENCH = json.load(f)


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_cell_files_are_found_by_name(workload):
    cell = harness.load_cell(workload, BENCH)
    drv = harness.runner(cell.traffic["runner"])
    assert callable(drv.run) and callable(drv.check)
    assert cell.limits
    for names in cell.metrics.values():
        assert names
        for name, _ in names:
            assert callable(harness.metric_reader(name))
