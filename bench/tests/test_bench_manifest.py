"""BENCHMARK.json against the rules it is read by, and discovery by name:
every cell, configuration, traffic mix, system and metric reader is found
from the names in the manifest alone."""

import json
import re
from pathlib import Path

import pytest

from bench import run

REPO = Path(__file__).resolve().parents[2]
MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
CELLS = [w["name"] for w in MANIFEST["workloads"]]
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_command():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(MANIFEST["paths"]) <= 16
    for p in MANIFEST["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
    cmd = MANIFEST["command"]
    assert 1 <= len(cmd) <= 32 and all(_line(w) for w in cmd)
    for word in cmd[1:]:
        assert not word.startswith("/") and ".." not in word
        if "/" in word:
            assert any(word.startswith(p + "/") for p in MANIFEST["paths"])
    assert len(json.dumps(MANIFEST)) <= 64 * 1024


def test_a_full_check_of_24_cells_fits_its_time():
    rs = MANIFEST["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_and_units_use_only_the_allowed_characters():
    names = [m["name"] for m in METRICS] + CELLS
    for c in MANIFEST["configs"]:
        names += [c["name"]] + list(c["reduced"])
    for w in MANIFEST["workloads"]:
        names += [w["config"], w["traffic"]]
    assert all(NAME.match(n) for n in names), names
    for m in METRICS:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for group in (METRICS, MANIFEST["workloads"], MANIFEST["configs"]):
        assert len({e["name"] for e in group}) == len(group)


def test_entries_have_just_their_keys():
    for c in MANIFEST["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert _line(c["source"]) and _line(c["why"]) and len(c["reduced"]) <= 16
    for w in MANIFEST["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and _line(w["why"])
    for m in MANIFEST["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in MANIFEST["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert _line(m["layer"])


def _reports(metric, cell):
    return "workloads" not in metric or cell in metric["workloads"]


def test_every_cell_reports_setup_another_end_to_end_and_a_layer():
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    assert "setup_s" in e2e
    for cell in CELLS:
        mine = [n for n, m in e2e.items() if _reports(m, cell)]
        assert "setup_s" in mine and len(mine) >= 2
        assert any(_reports(m, cell) for m in MANIFEST["per_layer"])


def test_every_layer_metric_moves_an_end_to_end_metric_its_cells_report():
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    for m in MANIFEST["per_layer"]:
        assert m["moves"] in e2e
        cells = m.get("workloads", CELLS)
        assert set(cells) <= set(CELLS)
        assert all(_reports(e2e[m["moves"]], c) for c in cells)


def test_every_configuration_has_a_cell_and_a_file_of_its_own():
    used = {w["config"] for w in MANIFEST["workloads"]}
    files = [c["file"] for c in MANIFEST["configs"]]
    assert len(set(files)) == len(files)
    for c in MANIFEST["configs"]:
        assert c["name"] in used
        assert any(c["file"].startswith(p + "/") for p in MANIFEST["paths"])
        assert (REPO / c["file"]).is_file()
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) <= max(
        1, len(CELLS) // 2)


@pytest.mark.parametrize("cell", CELLS)
def test_a_cell_resolves_by_name(cell):
    found = run.resolve_cell(REPO, cell)
    for fn in ("build", "check_steps", "reference", "compare", "round_work"):
        assert callable(getattr(found["system"], fn))
    limits = found["config"]["limits"]
    assert limits and all(v > 0 for v in limits.values())


@pytest.mark.parametrize("metric", [m["name"] for m in MANIFEST["per_layer"]])
def test_a_layer_metric_has_a_reader_of_its_own(metric):
    reader = run.load_module(REPO / "bench" / "metrics" / f"{metric}.py",
                             f"test_reader_{metric}")
    assert callable(reader.read)


def test_an_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        run.resolve_cell(REPO, "no-such-cell")
