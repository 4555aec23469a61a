"""``BENCHMARK.json`` keeps to the benchmark's contract, and every file
it names is there: configurations, traffic mixes, metric readers."""
import json
import pathlib
import re

import pytest

_BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = _BENCH.parent
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_check_time_fits_with_24_cells():
    runs = 2 + 14 * 24
    total = runs * (SPEC["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200


def test_names_are_unique_and_well_formed():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in SPEC[group]]
        assert len(names) == len(set(names)), group
        assert all(NAME.match(n) for n in names), names
    metrics = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(metrics) == len(set(metrics))


@pytest.mark.parametrize("c", SPEC["configs"], ids=lambda c: c["name"])
def test_config_entry(c):
    assert set(c) == {"name", "source", "file", "reduced", "why"}
    f = ROOT / c["file"]
    assert f.is_file() and c["file"].startswith("bench/")
    body = json.loads(f.read_text())
    assert body["name"] == c["name"]
    assert body["reduced"] == c["reduced"]
    assert any(w["config"] == c["name"] for w in SPEC["workloads"])
    assert 1 <= len(c["why"]) <= 200 and 1 <= len(c["source"]) <= 200


@pytest.mark.parametrize("w", SPEC["workloads"], ids=lambda w: w["name"])
def test_workload_entry(w):
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert w["chips"] == 1
    assert (_BENCH / "traffic" / f"{w['traffic']}.json").is_file()
    assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    e2e = [m for m in SPEC["end_to_end"]
           if w["name"] in m.get("workloads", [w["name"]])]
    names = {m["name"] for m in e2e}
    assert "setup_s" in names and len(names) >= 2
    layer = [m for m in SPEC["per_layer"]
             if w["name"] in m.get("workloads", [w["name"]])]
    assert layer, "every cell reports a per-layer metric"
    for m in layer:
        assert m["moves"] in names, (m["name"], w["name"])


def test_pairs_of_config_and_traffic_are_unique():
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("m", SPEC["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_metric(m):
    assert set(m) <= {"name", "unit", "better", "bound", "source",
                      "workloads"}
    assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert 0.01 <= m["bound"] <= 0.25
    assert m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("m", SPEC["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_has_a_reader(m):
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}
    assert UNIT.match(m["unit"]) and m["source"] in SOURCES
    assert (_BENCH / "metrics" / f"{m['name']}.py").is_file()
    cells = {w["name"] for w in SPEC["workloads"]}
    assert set(m["workloads"]) <= cells
    if m["name"].endswith("_roofline") or "mfu" in m["name"]:
        assert m["unit"] == "%"


def test_setup_metric_is_there_with_its_bound():
    s = next(m for m in SPEC["end_to_end"] if m["name"] == "setup_s")
    assert s["bound"] <= 0.25 and "workloads" not in s
