"""Every entry of ``BENCHMARK.json`` resolves to its files by name, and
the file keeps the shape the harness relies on."""
import importlib
import importlib.util
import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    assert BENCH["paths"] == ["chipbench"]


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_config_resolves(entry):
    path = ROOT / entry["file"]
    assert path.is_file() and entry["file"].startswith("chipbench/")
    cfg = json.loads(path.read_text())
    assert cfg["name"] == entry["name"]
    assert set(entry["reduced"]) <= set(cfg["reduced"])
    assert (ROOT / "chipbench" / "runners" / f"{cfg['runner']}.py").is_file()
    importlib.import_module(f"chipbench.reference.{cfg['model']}")
    assert any(w["config"] == entry["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_resolves(cell):
    from chipbench.run import resolve, selected_metrics
    wl, cfg, traffic = resolve(BENCH, cell["name"])
    assert wl is cell and traffic["mode"] in ("gba", "sync")
    e2e = {m["name"] for m in selected_metrics(BENCH, cell["name"], False)}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert selected_metrics(BENCH, cell["name"], True)
    assert len(cell["why"]) <= 200 and cell["chips"] in (1, 4)


@pytest.mark.parametrize(
    "metric", BENCH["end_to_end"] + BENCH["per_layer"],
    ids=lambda m: m["name"])
def test_metric_reader_resolves(metric):
    path = ROOT / "chipbench" / "metrics" / f"{metric['name']}.py"
    spec = importlib.util.spec_from_file_location("m", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert callable(mod.read)
    assert metric["better"] in ("lower", "higher")
    assert set(metric.get("workloads", CELLS)) <= set(CELLS)


def test_names_and_bounds():
    names = [c["name"] for c in BENCH["configs"]] + CELLS + [
        m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    for n in names + [w["traffic"] for w in BENCH["workloads"]]:
        assert NAME.match(n), n
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
    assert len({(w["config"], w["traffic"]) for w in BENCH["workloads"]}) \
        == len(CELLS)
