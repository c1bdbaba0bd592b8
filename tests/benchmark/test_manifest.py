"""BENCHMARK.json against the files it names: every cell, configuration,
generator and metric exists, every name and unit keeps to the permitted
characters, and adding one of them needs only new files and entries."""

import json
import os
import re

import pytest

from benchmark import run

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
BENCH = os.path.join(REPO, "benchmark")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


def _json(*parts):
    with open(os.path.join(REPO, *parts)) as fh:
        return json.load(fh)


_module = run.load_module


@pytest.fixture(scope="module")
def manifest():
    return _json("BENCHMARK.json")


def _cells_of(metric, manifest):
    return metric.get("workloads") or [
        w["name"] for w in manifest["workloads"]
    ]


def test_exactly_the_contracts_keys_and_limits(manifest):
    assert set(manifest) == KEYS
    assert 1 <= manifest["run_seconds"] <= 51
    assert isinstance(manifest["run_seconds"], int)
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    cmd = manifest["command"]
    assert len(cmd) <= 32 and all(1 <= len(w) <= 200 for w in cmd)
    for path in manifest["paths"]:
        assert os.path.isdir(os.path.join(REPO, path))
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", path)
    # the command names a file under paths
    assert any(cmd[1].startswith(p + "/") for p in manifest["paths"])
    assert os.path.exists(os.path.join(REPO, cmd[1]))
    # full check: 2 + 14 runs a cell, at the full 24 cells
    runs = 2 + 14 * 24
    assert runs * (manifest["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_every_file_under_paths_is_named_from_permitted_characters(manifest):
    for path in manifest["paths"]:
        for root, dirs, files in os.walk(os.path.join(REPO, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                assert re.fullmatch(r"[A-Za-z0-9_.\-]+", f), (root, f)


def test_every_name_and_unit_uses_the_permitted_characters(manifest):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in manifest[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"), entry["name"]))
    assert len(names) == len(set(names))
    metric_names = [e["name"] for g in ("end_to_end", "per_layer")
                    for e in manifest[g]]
    assert len(metric_names) == len(set(metric_names))
    for w in manifest["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["source"]) <= 200
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {
            "name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {
            "name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in SOURCES
        assert 1 <= len(m["layer"]) <= 200
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")


def test_every_cell_names_files_that_exist(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    assert 2 <= len(manifest["workloads"]) <= 24
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))
    files = [c["file"] for c in manifest["configs"]]
    assert len(files) == len(set(files))
    used = set()
    for w in manifest["workloads"]:
        cfg = configs[w["config"]]
        used.add(w["config"])
        assert any(cfg["file"].startswith(p + "/") for p in manifest["paths"])
        conf = _json(*cfg["file"].split("/"))
        assert conf["source"] == cfg["source"]
        for key in cfg["reduced"]:
            assert key in conf, f"{cfg['name']}: reduced key {key} not in file"
            assert any(r.startswith(key + ":") for r in conf["reduced"])
        for key in ("assumed", "guarantees", "crypto", "validators"):
            assert key in conf
        traffic = _json("benchmark", "traffic", w["traffic"] + ".json")
        gen = _module("traffic", traffic["generator"])
        for fn in ("build", "warm", "drive"):
            assert callable(getattr(gen, fn))
        cell = _json("benchmark", "workloads", w["name"] + ".json")
        assert cell["config"] == w["config"]
        assert cell["traffic"] == w["traffic"]
        assert cell["chips"] == w["chips"] and w["chips"] in (1, 4)
    assert used == set(configs), "a configuration no cell uses"


def test_at_most_half_the_cells_take_four_chips(manifest):
    four = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(manifest["workloads"]) // 2)


def test_cells_and_manifest_agree_on_who_reports_what(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    layers = {m["name"]: m for m in manifest["per_layer"]}
    for w in manifest["workloads"]:
        cell = _json("benchmark", "workloads", w["name"] + ".json")
        want_e2e = {n for n, m in e2e.items()
                    if w["name"] in _cells_of(m, manifest)}
        want_layers = {n for n, m in layers.items()
                       if w["name"] in _cells_of(m, manifest)}
        assert set(cell["end_to_end"]) == want_e2e, w["name"]
        assert set(cell["layers"]) == want_layers, w["name"]
        assert "setup_s" in want_e2e and len(want_e2e) >= 2
        assert want_layers
        for name in want_layers:
            assert layers[name]["moves"] in want_e2e, (
                f"{w['name']}: {name} moves {layers[name]['moves']}, "
                "which the cell does not report"
            )


@pytest.mark.parametrize("kind,group", [("end_to_end", "end_to_end"),
                                        ("layers", "per_layer")])
def test_every_metric_file_has_its_entry_and_agrees_with_it(
        manifest, kind, group):
    entries = {m["name"]: m for m in manifest[group]}
    on_disk = {
        f[:-3] for f in os.listdir(os.path.join(BENCH, kind))
        if f.endswith(".py")
    }
    assert on_disk == set(entries)
    for name in on_disk:
        mod = _module(kind, name)
        entry = entries[name]
        assert mod.NAME == name
        assert mod.UNIT == entry["unit"]
        assert mod.BETTER == entry["better"]
        assert mod.SOURCE == entry["source"]
        assert callable(mod.read)
        if kind == "layers":
            assert mod.LAYER == entry["layer"]
            assert mod.MOVES == entry["moves"]


def test_layers_are_named_letter_for_letter_as_perf_md_lists_them(manifest):
    with open(os.path.join(REPO, "PERF.md")) as fh:
        perf = fh.read()
    for m in manifest["per_layer"]:
        assert re.fullmatch(r"[A-Za-z0-9_.\-]+", m["layer"])
        assert m["layer"] in perf, f"PERF.md does not list {m['layer']}"
        assert m["name"] in perf


def test_run_py_knows_no_cell_configuration_generator_or_metric(manifest):
    with open(os.path.join(BENCH, "run.py")) as fh:
        src = fh.read()
    names = {e["name"] for g in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in manifest[g]}
    names |= {w["traffic"] for w in manifest["workloads"]}
    names |= {
        f[:-3] for f in os.listdir(os.path.join(BENCH, "traffic"))
        if f.endswith(".py")
    }
    # setup_s is the one metric the harness itself has to take
    for name in names - {"setup_s"}:
        assert name not in src, f"run.py names {name!r}"


def test_peaks_are_keyed_by_device_kind_with_a_source():
    peaks = _json("benchmark", "peaks.json")
    assert peaks["source"]
    v5e = peaks["TPU v5 lite"]
    assert v5e["int8_ops_per_s"] == 393e12
    assert v5e["hbm_bytes_per_s"] == 819e9


def test_test_basenames_stay_unique_across_tests():
    seen = {}
    tests = os.path.join(REPO, "tests")
    for root, dirs, files in os.walk(tests):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            if f.startswith("test_") and f.endswith(".py"):
                assert f not in seen, (root, seen[f])
                seen[f] = root
