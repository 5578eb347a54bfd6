"""What is found by name is there: every cell of BENCHMARK.json has its
files, and every metric a workload file names has a reader and an entry."""
import json
from pathlib import Path

BENCH = Path(__file__).parents[1]
MANIFEST = json.loads((BENCH.parent / "BENCHMARK.json").read_text())


def test_cells_configs_and_metrics_are_found_by_name():
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    layer = {m["name"]: m for m in MANIFEST["per_layer"]}
    configs = {c["name"]: c for c in MANIFEST["configs"]}
    assert MANIFEST["command"][1] == "chipbench/run.py"
    for w in MANIFEST["workloads"]:
        cell = json.loads((BENCH / "workloads" / f"{w['name']}.json").read_text())
        assert cell["config"] == w["config"] and cell["chips"] == w["chips"]
        assert cell["traffic"] == w["traffic"] and cell["why"] == w["why"]
        assert (BENCH / "traffic" / f"{cell['traffic']}.json").is_file()
        assert (BENCH / "drivers" / f"{cell['driver']}.py").is_file()
        cfg = configs[cell["config"]]
        held = json.loads((BENCH.parent / cfg["file"]).read_text())
        assert held["source"] == cfg["source"]
        assert held["reduced"] == cfg["reduced"]
        assert "setup_s" in cell["end_to_end"] and len(cell["end_to_end"]) > 1
        assert cell["per_layer"]
        for name in cell["end_to_end"] + cell["per_layer"]:
            assert (BENCH / "metrics" / f"{name}.py").is_file(), name
            entry = e2e.get(name) or layer[name]
            assert w["name"] in entry.get("workloads", [w["name"]]), name
        for name in cell["per_layer"]:
            assert layer[name]["moves"] in cell["end_to_end"], name
    for name, m in {**e2e, **layer}.items():
        for cellname in m.get("workloads", []):
            cell = json.loads((BENCH / "workloads" / f"{cellname}.json").read_text())
            assert name in cell["end_to_end"] + cell["per_layer"], (name, cellname)
