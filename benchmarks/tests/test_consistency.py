"""Every file the harness finds by name is there and well-formed, and
BENCHMARK.json agrees with the data files."""

import json
import re
from pathlib import Path

import pytest

from benchmarks.reduce import REDUCERS

ROOT = Path(__file__).resolve().parents[1]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCH = json.loads((ROOT.parent / "BENCHMARK.json").read_text())

WORKLOADS = {p.stem: json.loads(p.read_text()) for p in (ROOT / "workloads").glob("*.json")}
LAYER_METRICS = {p.stem: json.loads(p.read_text()) for p in (ROOT / "layer_metrics").glob("*.json")}
END_TO_END = {m["name"]: m for m in BENCH["end_to_end"]}
#: the end-to-end metrics each runner measures (runners/<name>.py,
#: "end_to_end"); a workload file lists the ones its cell reports
MEASURES = {
    "train": {"train_tokens_per_s", "setup_s"},
    "serve": {"ttft_p50_ms", "ttft_p95_ms", "tpot_p95_ms", "serve_tokens_per_s", "setup_s"},
}


def _cells_of(metric):
    """The cells that report a per-layer metric, as layers.load_for finds them."""
    return {c for c, w in WORKLOADS.items()
            if metric in w["layer_metrics"] or c in LAYER_METRICS[metric].get("cells", ())}


def test_a_cell_finds_the_metrics_it_lists_and_the_metrics_that_name_it(tmp_path, monkeypatch):
    from benchmarks import layers

    (tmp_path / "layer_metrics").mkdir()
    spec = {"layer": "l", "unit": "ms", "moves": "setup_s", "source": "span",
            "select": "x", "reduce": "mean_ms"}
    for name, extra in (("old", {}), ("new", {"cells": ["cell.a"]}), ("other", {})):
        (tmp_path / "layer_metrics" / f"{name}.json").write_text(json.dumps({**spec, **extra}))
    monkeypatch.setattr(layers, "HERE", tmp_path)
    assert sorted(layers.load_for("cell.a", {"layer_metrics": ["old"]})) == ["new", "old"]
    assert sorted(layers.load_for("cell.b", {"layer_metrics": ["old"]})) == ["old"]
    with pytest.raises(SystemExit):
        layers.load_for("cell.b", {"layer_metrics": ["gone"]})


@pytest.mark.parametrize("name", sorted(WORKLOADS))
def test_workload_names_a_config_and_a_runner_that_exist(name):
    w = WORKLOADS[name]
    assert NAME.match(name)
    assert (ROOT / "configs" / f"{w['config']}.json").is_file()
    assert (ROOT / "runners" / f"{w['runner']}.py").is_file()
    assert w["chips"] in (1, 4)
    assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"] and "\t" not in w["why"]
    assert "traffic" in w
    assert "setup_s" in w["end_to_end"] and len(w["end_to_end"]) >= 2
    assert set(w["end_to_end"]) <= MEASURES[w["runner"]]
    assert w["layer_metrics"] and len(set(w["layer_metrics"])) == len(w["layer_metrics"])
    for metric in w["layer_metrics"]:
        assert metric in LAYER_METRICS, f"{name}: no layer metric {metric}"
        # what a per-layer metric should move, the cell reports
        assert LAYER_METRICS[metric]["moves"] in w["end_to_end"]


@pytest.mark.parametrize("name", sorted(LAYER_METRICS))
def test_layer_metric_is_well_formed_and_some_cell_reads_it(name):
    m = LAYER_METRICS[name]
    assert NAME.match(name) and UNIT.match(m["unit"])
    assert m["source"] in ("span", "trace")
    assert m["reduce"] in REDUCERS
    re.compile(m["select"])
    assert m["moves"] in END_TO_END
    assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    assert _cells_of(name), f"{name}: no cell reads it"
    for cell in m.get("cells", ()):
        assert cell in WORKLOADS and m["moves"] in WORKLOADS[cell]["end_to_end"]
    if name.endswith("_roofline"):
        assert m["unit"] == "%"


def test_benchmark_json_agrees_with_the_data_files():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks"]
    configs = {c["name"]: c for c in BENCH["configs"]}
    for c in configs.values():
        assert NAME.match(c["name"]) and (ROOT.parent / c["file"]).is_file()
        file_cfg = json.loads((ROOT.parent / c["file"]).read_text())
        assert file_cfg["source"] == c["source"] and file_cfg["reduced"] == c["reduced"]
    cells = {}
    for w in BENCH["workloads"]:
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        data = WORKLOADS[w["name"]]
        assert not data.get("rehearsal")
        assert (data["config"], data["chips"], data["why"]) == (w["config"], w["chips"], w["why"])
        assert w["config"] in configs
        cells[w["name"]] = data
    assert {c for c in configs} == {w["config"] for w in BENCH["workloads"]}
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(1, len(cells) // 4)
    for m in BENCH["end_to_end"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
        # the metric's cells are exactly the cells whose file lists it
        assert set(m.get("workloads", cells)) == {
            c for c, w in cells.items() if m["name"] in w["end_to_end"]}
    assert "setup_s" in END_TO_END and "workloads" not in END_TO_END["setup_s"]
    layer_names = set()
    for m in BENCH["per_layer"]:
        data = LAYER_METRICS[m["name"]]
        assert (data["layer"], data["unit"], data["moves"]) == (m["layer"], m["unit"], m["moves"])
        assert m["source"] == {"span": "program_span", "trace": "device_trace"}[data["source"]]
        listed = set(m.get("workloads", cells))
        assert listed == _cells_of(m["name"]) & set(cells)
        moved = END_TO_END[m["moves"]]
        assert listed <= set(moved.get("workloads", cells))
        layer_names.add(m["layer"])
    # every listed cell has at least one per-layer metric
    for cell in cells:
        assert any(cell in m.get("workloads", cells) for m in BENCH["per_layer"])
