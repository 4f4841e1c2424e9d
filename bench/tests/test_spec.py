"""BENCHMARK.json against the benchmark's contract: names, units, bounds,
files found by name, and the check's budget."""

import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]


def test_top_level_keys_and_command():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert SPEC["command"][1].startswith("bench/")
    assert 1 <= SPEC["run_seconds"] <= 51


def test_names_units_and_lines():
    names = ([c["name"] for c in SPEC["configs"]]
             + [w["name"] for w in SPEC["workloads"]]
             + [m["name"] for m in METRICS])
    assert all(NAME.match(n) for n in names)
    for group in (SPEC["configs"], SPEC["workloads"], METRICS):
        assert len({e["name"] for e in group}) == len(group)
    assert all(UNIT.match(m["unit"]) for m in METRICS)
    lines = ([c["why"] for c in SPEC["configs"]]
             + [c["source"] for c in SPEC["configs"]]
             + [w["why"] for w in SPEC["workloads"]]
             + [m["layer"] for m in SPEC["per_layer"]])
    assert all(0 < len(s) <= 200 and "\n" not in s and "\t" not in s
               for s in lines)


def test_every_configuration_traffic_and_metric_has_its_file():
    for c in SPEC["configs"]:
        assert c["file"].startswith("bench/") and (ROOT / c["file"]).is_file()
        assert c["reduced"] == json.loads(
            (ROOT / c["file"]).read_text())["reduced"]
        assert any(w["config"] == c["name"] for w in SPEC["workloads"])
    for w in SPEC["workloads"]:
        traffic = ROOT / "bench" / "traffic" / f"{w['traffic']}.json"
        assert traffic.is_file()
        config = next(c for c in SPEC["configs"] if c["name"] == w["config"])
        assert (json.loads(traffic.read_text())["app"]
                == json.loads((ROOT / config["file"]).read_text())["app"])
    for kind, folder in (("end_to_end", "end_to_end"),
                         ("per_layer", "layer_metrics")):
        for m in SPEC[kind]:
            assert (ROOT / "bench" / folder / f"{m['name']}.py").is_file()


def test_bounds_sources_and_what_each_cell_reports():
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" for m in SPEC["end_to_end"])
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    cells = [w["name"] for w in SPEC["workloads"]]

    def reports(metric, cell):
        return cell in metric.get("workloads", cells)

    for m in SPEC["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in e2e
        assert all(reports(e2e[m["moves"]], c) for c in m["workloads"])
    for cell in cells:
        assert sum(reports(m, cell) for m in SPEC["end_to_end"]) >= 2
        assert any(reports(m, cell) for m in SPEC["per_layer"])


@pytest.mark.parametrize("cells", [len(SPEC["workloads"]), 24])
def test_a_full_check_fits_its_budget(cells):
    runs = 2 + 14 * cells
    seconds = runs * (SPEC["run_seconds"] + 60) + cells * 2 * 90 + 1200
    assert seconds <= 43200


def test_at_most_half_the_cells_take_four_chips():
    chips = [w["chips"] for w in SPEC["workloads"]]
    assert set(chips) <= {1, 4}
    assert chips.count(4) <= max(1, len(chips) // 2)
