"""BENCHMARK.json against the benchmark's contract and its own files."""
import re

import pytest

from bench import harness as H

M = H.load_manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in M["workloads"]]


def reported(cell: str, group: str) -> set:
    return {m["name"] for m in H.cell_metrics(M, cell, group)}


def test_top_level_keys():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert M["paths"] == ["bench"]
    assert M["command"][1] == "bench/run_cell.py"
    assert 1 <= M["run_seconds"] <= 51


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_resolve(cell):
    w = H.cell_entry(M, cell)
    cfg = H.config_entry(M, w["config"])
    assert (H.ROOT / cfg["file"]).is_file()
    traffic = H.load_json(H.traffic_path(w["traffic"]))
    assert (H.BENCH / "gens" / f"{traffic['kind']}.py").is_file()
    assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200


@pytest.mark.parametrize("cell", CELLS)
def test_cell_reports_enough(cell):
    e2e = reported(cell, "end_to_end")
    assert "setup_s" in e2e and len(e2e) >= 2
    assert reported(cell, "per_layer")


def test_every_metric_has_a_reader():
    for m in M["end_to_end"] + M["per_layer"]:
        assert (H.BENCH / "metrics" / f"{m['name']}.py").is_file(), m["name"]


def test_per_layer_moves_a_metric_its_cells_report():
    for m in M["per_layer"]:
        for cell in m["workloads"]:
            assert m["moves"] in reported(cell, "end_to_end"), (m, cell)


def test_names_units_and_sources():
    names = [x["name"] for g in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in M[g]]
    for n in names + [w["traffic"] for w in M["workloads"]]:
        assert NAME.match(n), n
    for g in ("end_to_end", "per_layer"):
        for m in M[g]:
            assert UNIT.match(m["unit"]), m
            assert m["better"] in ("lower", "higher")
    for m in M["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in M["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    assert len(set(names)) == len(names)


def test_four_chip_cells_at_most_half():
    four = sum(w["chips"] == 4 for w in M["workloads"])
    assert four <= max(1, len(M["workloads"]) // 2)


def test_config_cuts_listed():
    for c in M["configs"]:
        data = H.load_json(H.ROOT / c["file"])
        changed = set(data.get("published", {}))
        assert changed == set(c["reduced"]), c["name"]
        for k in c["reduced"]:
            assert NAME.match(k)
            assert not k.endswith(("_dim", "_rank", "_size")) or \
                k == "vocab_size", k
