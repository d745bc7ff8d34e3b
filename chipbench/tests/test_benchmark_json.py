"""BENCHMARK.json against the contract's mechanical rules, and every name it holds
against the files it has to resolve to."""
import os
import re

import pytest

from chipbench import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
BENCH = harness.load_benchmark()
ROOT = harness.ROOT


def _line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_size():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert BENCH["paths"] == ["chipbench"] and 1 <= BENCH["run_seconds"] <= 51
    assert all(_line(w) and not w.startswith("/") and ".." not in w for w in BENCH["command"])
    # a full check of 24 cells has to fit: 2 + 14 * cells runs
    cells, s = 24, BENCH["run_seconds"]
    assert (2 + 14 * cells) * (s + 60) + cells * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("kind", ["configs", "workloads", "end_to_end", "per_layer"])
def test_names_are_allowed_and_unique(kind):
    names = [e["name"] for e in BENCH[kind]]
    assert all(NAME.match(n) for n in names) and len(set(names)) == len(names)


def test_metric_entries():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and len(e2e & {m["name"] for m in BENCH["per_layer"]}) == 0
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert _line(m["layer"]) and m["moves"] in e2e


def test_cells_resolve_to_files_that_exist():
    configs = {c["name"]: c for c in BENCH["configs"]}
    assert sum(c["chips"] == 4 for c in BENCH["workloads"]) == 1
    assert len({(c["config"], c["traffic"]) for c in BENCH["workloads"]}) == len(BENCH["workloads"])
    for cell in BENCH["workloads"]:
        assert set(cell) == {"name", "config", "traffic", "chips", "why"} and _line(cell["why"])
        for rel in harness.cell_files(BENCH, cell).values():
            assert rel.startswith("chipbench/") and os.path.isfile(os.path.join(ROOT, rel)), rel
        config = harness.load_json(ROOT, configs[cell["config"]]["file"])
        mix = harness.load_json(ROOT, harness.cell_files(BENCH, cell)["traffic"])
        pair = f"subjects/{config['family']}.{mix['driver']}"
        for rel in (f"drivers/{mix['driver']}.py", f"references/{config['family']}.py", pair + ".py", pair + ".json"):
            assert os.path.isfile(os.path.join(ROOT, "chipbench", rel)), rel
        limits = harness.load_json(ROOT, f"chipbench/{pair}.json")["limits"]
        assert limits and all(isinstance(v, (int, float)) for v in limits.values())
    pairs = {n[:-3] for n in os.listdir(os.path.join(ROOT, "chipbench", "subjects")) if n.endswith(".py")}
    for n in os.listdir(os.path.join(ROOT, "chipbench", "subjects")):
        if n.endswith(".json") and n[:-5] not in pairs:      # limits of one cell: <family>.<driver>.<cell>.json
            pair, _, cell = n[:-5].rpartition(".")
            assert pair in pairs and any(w["name"] == cell for w in BENCH["workloads"]), n
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"} and _line(c["source"]) and _line(c["why"])
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])
        assert all(NAME.match(k) for k in c["reduced"]) and len(c["reduced"]) <= 16


def test_each_cell_reports_what_the_contract_asks():
    for cell in BENCH["workloads"]:
        e2e = [m["name"] for m in harness.metrics_for(BENCH, cell, "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert harness.metrics_for(BENCH, cell, "per_layer")


def test_layer_metrics_move_a_metric_of_every_cell_they_list_and_have_a_reader():
    cells = {c["name"]: c for c in BENCH["workloads"]}
    quantity = {}      # the names configurations report a reader's quantity under
    for c in BENCH["configs"]:
        quantity.update({v: k for k, v in harness.load_json(ROOT, c["file"]).get("report_as", {}).items()})
    for m in BENCH["per_layer"]:
        assert callable(harness.load_reader(quantity.get(m["name"], m["name"])).read)
        for name in m.get("workloads", cells):
            reported = {e["name"] for e in harness.metrics_for(BENCH, cells[name], "end_to_end")}
            assert m["moves"] in reported, (m["name"], name)


def test_every_file_under_paths_is_named_from_a_names_characters():
    for base, dirs, files in os.walk(os.path.join(ROOT, "chipbench")):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            assert re.match(r"^[A-Za-z0-9_.\-]+$", f), os.path.join(base, f)


def test_peaks_know_the_v5e_and_refuse_the_unknown():
    from chipbench.peaks import peaks_for

    assert peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks_for("TPU v9")
    with pytest.raises(KeyError):
        peaks_for("_source")
