"""BENCHMARK.json against the rules the benchmark is built to, and the
files it names: every configuration, mix and per-layer metric is found
by its name."""
import json
import re

import pytest

import load
import run

M = json.loads((run.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
ENTRY_KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_keys_and_command():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert M["command"] == ["python3", "bench/run.py"]
    assert M["paths"] == ["bench"]
    assert 1 <= M["run_seconds"] <= 51
    assert len(json.dumps(M)) < 64 * 1024


@pytest.mark.parametrize("section", sorted(ENTRY_KEYS))
def test_entries_have_just_their_keys_and_valid_names(section):
    names = [e["name"] for e in M[section]]
    assert len(names) == len(set(names))
    for e in M[section]:
        extra = {"workloads"} if section in ("end_to_end", "per_layer") \
            else set()
        assert ENTRY_KEYS[section] <= set(e) <= ENTRY_KEYS[section] | extra
        assert NAME.match(e["name"])
        if "unit" in e:
            assert UNIT.match(e["unit"]) and e["better"] in ("lower",
                                                             "higher")
        for k in ("why", "layer", "source"):
            if k in e and section != "end_to_end" and section != "per_layer":
                assert _line(e[k])
        if "layer" in e:
            assert _line(e["layer"])


def test_configs_are_files_of_their_own_and_used():
    used = {w["config"] for w in M["workloads"]}
    files = set()
    for c in M["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("bench/configs/")
        files.add(c["file"])
        cfg = json.loads((run.ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert set(c["reduced"]) == set(cfg["reduced"])
        assert all(NAME.match(k) for k in c["reduced"])
        assert (run.BENCH / "references" / f"{cfg['reference']}.py").is_file()
        assert cfg["share_kind"] in ("xor", "additive")
    assert len(files) == len(M["configs"])
    assert len({c["source"] for c in M["configs"]}) == len(M["configs"])


def test_cells_name_a_mix_file_and_one_or_four_chips():
    pairs = {(w["config"], w["traffic"]) for w in M["workloads"]}
    assert len(pairs) == len(M["workloads"])
    assert sum(w["chips"] == 4 for w in M["workloads"]) <= max(
        1, len(M["workloads"]) // 2)
    for w in M["workloads"]:
        assert w["chips"] in (1, 4) and _line(w["why"]) and NAME.match(
            w["traffic"])
        load.validate(json.loads(
            (run.BENCH / "traffic" / f"{w['traffic']}.json").read_text()))


def test_bounds():
    for m in M["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert next(m for m in M["end_to_end"]
                if m["name"] == "setup_s")["bound"] == 0.25


def test_every_cell_reports_enough_and_each_metric_is_read():
    cells = {w["name"] for w in M["workloads"]}
    e2e = {m["name"]: set(m.get("workloads", cells)) for m in M["end_to_end"]}
    for cell in cells:
        assert cell in e2e["setup_s"]
        assert sum(cell in ws for n, ws in e2e.items() if n != "setup_s")
        assert any(cell in m.get("workloads", cells) for m in M["per_layer"])
    for m in M["per_layer"]:
        assert (run.BENCH / "metrics" / f"{m['name']}.py").is_file()
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        for cell in m.get("workloads", cells):
            assert cell in cells and cell in e2e[m["moves"]]
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_load_cell_finds_every_cell():
    for w in M["workloads"]:
        b = run.load_cell(w["name"])
        assert b["cell"] == w and b["traffic"]["kind"] in load.KINDS
        assert {m["name"] for m in b["end_to_end"]} >= {"setup_s"}
