"""``BENCHMARK.json`` and every file under ``chipbench/`` agree, and the
manifest keeps to the limits of its contract."""

import json
import re

import pytest

from chipbench_tiny import ROOT

from chipbench import run

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def _reader(name):
    return run.load_reader(name, ROOT / "chipbench" / "metrics")


def test_manifest_has_exactly_the_contract_keys(manifest):
    assert set(manifest) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer",
    }
    assert manifest["command"] == ["python3", "chipbench/run.py"]
    assert manifest["paths"] == ["chipbench", "tests/chipbench"]
    assert 1 <= manifest["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_names_units_and_lines_are_within_the_allowed_characters(manifest):
    entries = manifest["configs"] + manifest["workloads"] + manifest["end_to_end"] + manifest["per_layer"]
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
        for key in ("why", "layer", "source"):
            if key in e:
                assert 1 <= len(e[key]) <= 200 and "\n" not in e[key] and "\t" not in e[key], e
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    names = [e["name"] for e in manifest["end_to_end"] + manifest["per_layer"]]
    assert len(names) == len(set(names))
    for w in manifest["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert set(w) == {"name", "config", "traffic", "chips", "why"}


def test_end_to_end_metrics_have_bounds_and_setup_is_there(manifest):
    by = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in by and by["setup_s"]["bound"] <= 0.1
    for m in by.values():
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}


def test_every_cell_names_a_configuration_a_mix_and_a_runner_that_exist(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    pairs = set()
    for w in manifest["workloads"]:
        assert w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        mix = json.loads((ROOT / "chipbench" / "traffic" / f"{w['traffic']}.json").read_text())
        assert (ROOT / "chipbench" / "runners" / f"{mix['runner']}.py").is_file()
        assert (ROOT / configs[w["config"]]["file"]).is_file()
    used = {w["config"] for w in manifest["workloads"]}
    assert used == set(configs), "every configuration is used by some cell"
    four = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(manifest["workloads"]) // 4)


def test_configuration_files_state_what_the_manifest_says(manifest):
    widths = ("d_model", "n_head")
    for c in manifest["configs"]:
        assert c["file"].startswith("chipbench/configs/")
        body = json.loads((ROOT / c["file"]).read_text())
        assert body["name"] == c["name"] and body["source"] == c["source"]
        assert body["reduced"] == c["reduced"]
        assert not set(c["reduced"]) & set(widths)
        assert set(body["limits"]) == {"loss_gap", "grad_norm_gap", "update_norm_gap"}
        opt = body["assumed"]["optimizer"]
        assert set(opt) == {"clip_norm", "learning_rate", "weight_decay", "b1", "b2", "eps"}
    small = json.loads((ROOT / "chipbench/configs/gpt2-small.json").read_text())
    medium = json.loads((ROOT / "chipbench/configs/gpt2-medium.json").read_text())
    assert (small["n_layer"], small["n_head"], small["d_model"]) == (12, 12, 768)
    assert (medium["n_layer"], medium["n_head"], medium["d_model"]) == (24, 16, 1024)
    for body in (small, medium):
        assert (body["max_seq"], body["vocab_size"], body["attention"]) == (1024, 50257, "flash")


def test_per_layer_metrics_agree_with_their_readers(manifest):
    end = {m["name"]: m for m in manifest["end_to_end"]}
    cells = {w["name"] for w in manifest["workloads"]}
    layers = {}
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["moves"] in end
        assert set(m.get("workloads", [])) <= cells
        reader = _reader(m["name"])
        assert (reader.UNIT, reader.LAYER, reader.MOVES, reader.SOURCE) == (
            m["unit"], m["layer"], m["moves"], m["source"]
        ), m["name"]
        assert callable(reader.read)
        layers.setdefault(m["layer"], []).append(m["name"])
    readers = {p.stem for p in (ROOT / "chipbench" / "metrics").glob("*.py")}
    assert readers == {m["name"] for m in manifest["per_layer"]}
    perf = (ROOT / "PERF.md").read_text()
    for layer in layers:
        assert layer in perf, f"PERF.md's list of layers lacks {layer!r}"
    assert any(m["name"].endswith("_roofline") and m["unit"] == "%" for m in manifest["per_layer"])


def test_every_cell_reports_setup_one_more_end_to_end_and_a_per_layer_metric(manifest):
    for w in manifest["workloads"]:
        end = [m["name"] for m in manifest["end_to_end"] if "workloads" not in m or w["name"] in m["workloads"]]
        assert "setup_s" in end and len(end) >= 2
        per = [m for m in manifest["per_layer"] if "workloads" not in m or w["name"] in m["workloads"]]
        assert per and all(m["moves"] in end for m in per)


def test_files_under_paths_are_named_from_the_allowed_characters(manifest):
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base in manifest["paths"]:
        for p in (ROOT / base).rglob("*"):
            if "__pycache__" in p.parts or p.suffix == ".pyc":
                continue
            rel = str(p.relative_to(ROOT))
            assert ok.match(rel) and len(rel) <= 200, rel
