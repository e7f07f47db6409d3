"""The benchmark is driven by data: every configuration, traffic mix, cell
and per-layer metric is a file found by its name, and BENCHMARK.json agrees
with those files."""
import json
import re
import shutil
from pathlib import Path

import pytest

from bench import harness as H

ROOT = Path(H.__file__).resolve().parent.parent
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _names(kind):
    return sorted(p.stem for p in (H.BENCH / kind).glob("*.json"))


def test_top_level_keys_and_paths():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51


def test_names_and_units_use_the_allowed_characters():
    for entry in SPEC["configs"] + SPEC["workloads"] + SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(entry["name"]), entry["name"]
    for entry in SPEC["configs"]:
        assert all(NAME.match(k) for k in entry["reduced"])
    for entry in SPEC["workloads"]:
        assert NAME.match(entry["config"]) and NAME.match(entry["traffic"])
        assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]
    for entry in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(entry["unit"]), entry["unit"]
        assert entry["better"] in ("lower", "higher")
        assert entry["source"] in SOURCES
    for entry in SPEC["end_to_end"]:
        assert entry["source"] in ("host_clock", "device_trace")
        assert 0.01 <= entry["bound"] <= 0.25
    for kind in ("configs", "workloads", "traffic"):
        assert all(NAME.match(n) for n in _names(kind)), kind


def test_every_cell_file_is_a_benchmark_cell():
    cells = {w["name"]: w for w in SPEC["workloads"]}
    assert sorted(cells) == _names("workloads")
    for name, entry in cells.items():
        cell = H.load_cell(name)
        assert cell.workload["config"] == entry["config"]
        assert cell.workload["traffic"] == entry["traffic"]
        assert cell.chips == entry["chips"] in (1, 4)
        assert cell.workload["why"] == entry["why"]
        assert cell.mesh[0] * cell.mesh[1] == cell.chips
        assert set(cell.workload["limits"]) <= set(H.CHECKS)
    assert len({(w["config"], w["traffic"]) for w in SPEC["workloads"]}) == len(cells)
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(1, len(cells) // 2)


def test_every_configuration_file_is_used_and_states_its_cut():
    used = {w["config"] for w in SPEC["workloads"]}
    assert {c["name"] for c in SPEC["configs"]} == used == set(_names("configs"))
    for entry in SPEC["configs"]:
        cfg = json.loads((ROOT / entry["file"]).read_text())
        assert entry["file"] == f"bench/configs/{entry['name']}.json"
        assert cfg["name"] == entry["name"] and cfg["source"] == entry["source"]
        assert cfg["reduced"] == entry["reduced"]
        for key in entry["reduced"]:
            # a key of the source's own config sits at the top level; one
            # the program names sits under "model"
            got = cfg[key] if key in cfg else cfg["model"][key]
            assert cfg["published"][key] != got
        H.reference_module(cfg)                      # its reference exists


def test_per_layer_metrics_match_their_readers():
    mods = H.metric_modules()
    assert {m["name"] for m in SPEC["per_layer"]} == set(mods)
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    cells = {w["name"] for w in SPEC["workloads"]}
    for entry in SPEC["per_layer"]:
        mod = mods[entry["name"]]
        assert (mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER, mod.MOVES) == (
            entry["unit"], entry["better"], entry["source"], entry["layer"], entry["moves"])
        assert entry["moves"] in e2e
        assert set(entry.get("workloads", cells)) <= cells


def test_new_cell_mix_and_metric_are_found_by_name(tmp_path):
    base = tmp_path / "bench"
    shutil.copytree(H.BENCH, base, ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in base.rglob("*") if p.is_file()}
    traffic = dict(H.load_json("traffic", "mlm16x512"), seq=128, batch=64)
    (base / "traffic" / "mlm128.json").write_text(json.dumps(traffic))
    cell = dict(H.load_json("workloads", "smile-3.7b.mlm512"), traffic="mlm128")
    (base / "workloads" / "smile-3.7b.mlm128.json").write_text(json.dumps(cell))
    (base / "metrics" / "steps_traced.py").write_text(
        'NAME = "steps_traced"\nUNIT = "steps"\nBETTER = "higher"\n'
        'SOURCE = "device_trace"\nLAYER = "train step"\nMOVES = "tokens_per_s"\n\n\n'
        "def read(run):\n    return run.trace.devices[0].steps\n")
    got = H.load_cell("smile-3.7b.mlm128", base)
    assert (got.seq, got.batch, got.tokens_per_step) == (128, 64, 8192)
    assert "steps_traced" in H.metric_modules(base)
    assert all(p.read_bytes() == b for p, b in before.items())


def test_peak_table_refuses_an_unknown_device():
    assert H.peak("TPU v5 lite")["bf16_flops"] == 197e12
    assert H.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        H.peak("TPU v9 imaginary")
