"""BENCHMARK.json: names, units, files found by name, metric coverage."""
import json

import pytest

from conftest import CHIP
from chipbench import spec

BENCH = json.loads((CHIP.parents[1] / "BENCHMARK.json").read_text())


def test_benchmark_json_is_valid():
    spec.validate(BENCH)
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= 1


@pytest.mark.parametrize("bad", ["has space", "a/b", "", ".dot", "x" * 65,
                                 "café"])
def test_bad_names_are_refused(bad):
    with pytest.raises(ValueError):
        spec.check_name(bad, "metric")


@pytest.mark.parametrize("unit", ["ms", "records/s", "%", "tokens/s", "s"])
def test_units_allowed(unit):
    assert spec.check_unit(unit) == unit


def test_bad_unit_refused():
    with pytest.raises(ValueError):
        spec.check_unit("tokens per second")


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_has_its_files_and_metrics(workload):
    cell = spec.load_cell(workload, BENCH)
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in names
        assert callable(spec.metric_reader(m["name"]))
    assert cell.limits, f"no limits/{workload}.json"
    assert (CHIP / "chipbench" / f"{cell.traffic['kind']}.py").exists()
