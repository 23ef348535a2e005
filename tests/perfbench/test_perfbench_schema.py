"""`BENCHMARK.json` against the contract it has to meet before a single run,
and against the files the harness finds by name."""

import importlib
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(_dim|_rank)$|hidden|intermediate|latent|state_size|"
                   r"proj|head_size|n_embd|n_inner|expansion|"
                   r"experts_per_tok|stem_width")


def load(rel):
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


BENCH = load("BENCHMARK.json")
CELLS = [w["name"] for w in BENCH["workloads"]]
CONFIGS = [c["name"] for c in BENCH["configs"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def line(text, limit=200):
    return isinstance(text, str) and 1 <= len(text) <= limit \
        and "\n" not in text and "\t" not in text


def test_top_level_keys_and_sizes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10
    assert 1 <= len(BENCH["paths"]) <= 16
    assert all(PATH.match(p) and not p.startswith("/") and ".." not in p
               for p in BENCH["paths"])
    assert 1 <= len(BENCH["command"]) <= 32
    assert all(line(w) for w in BENCH["command"])
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    assert 1 <= len(BENCH["configs"]) <= 24
    assert 2 <= len(BENCH["workloads"]) <= 24
    assert 1 <= len(BENCH["end_to_end"]) <= 16
    assert 1 <= len(BENCH["per_layer"]) <= 128


def test_a_full_check_fits_with_24_cells():
    s = BENCH["run_seconds"]
    assert (2 + 14 * 24) * (s + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_command_names_only_files_under_paths():
    for word in BENCH["command"]:
        if os.path.exists(os.path.join(ROOT, word)) and "/" in word:
            assert any(word.startswith(p + "/") for p in BENCH["paths"])
        assert not word.startswith("/") and ".." not in word


def test_names_are_unique():
    for group in ("configs", "workloads"):
        names = [e["name"] for e in BENCH[group]]
        assert len(set(names)) == len(names)
    names = [m["name"] for m in METRICS]
    assert len(set(names)) == len(names)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)


def test_four_chip_share():
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in BENCH["workloads"])
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)


@pytest.mark.parametrize("name", CONFIGS)
def test_config_entry_and_file(name):
    c = next(c for c in BENCH["configs"] if c["name"] == name)
    assert set(c) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(name) and line(c["source"]) and line(c["why"])
    assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
    assert PATH.match(c["file"])
    assert len(c["reduced"]) <= 16
    assert all(NAME.match(k) and not WIDTH.search(k) for k in c["reduced"])
    assert any(w["config"] == name for w in BENCH["workloads"])
    cfg = load(c["file"])
    assert cfg["name"] == name and cfg["source"] == c["source"]
    assert cfg["reduced"] == c["reduced"]
    assert all(k in cfg for k in c["reduced"])
    assert {"loss_rel", "grad_norm_rel", "grad_diff_rel", "why"} \
        <= set(cfg["check"])
    family = importlib.import_module(f"perfbench.models.{cfg['family']}")
    assert callable(family.build)
    assert "rehearsal" in cfg


@pytest.mark.parametrize("name", CELLS)
def test_cell_entry_and_files(name):
    w = next(w for w in BENCH["workloads"] if w["name"] == name)
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(name) and NAME.match(w["traffic"])
    assert w["config"] in CONFIGS and line(w["why"])
    cell = load(f"perfbench/workloads/{name}.json")
    assert cell["config"] == w["config"] and cell["chips"] == w["chips"]
    mode = importlib.import_module(f"perfbench.modes.{cell['mode']}")
    assert callable(mode.run)
    assert cell["rows_per_chip"] >= 1 and "rehearsal" in cell
    reported = {m["name"] for m in BENCH["end_to_end"]
                if name in m.get("workloads", [name])}
    assert "setup_s" in reported and len(reported) >= 2
    assert any(name in m.get("workloads", [name])
               for m in BENCH["per_layer"])


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    end_to_end = metric in BENCH["end_to_end"]
    keys = {"name", "unit", "better", "source"} | (
        {"bound"} if end_to_end else {"layer", "moves"})
    assert keys <= set(metric) <= keys | {"workloads"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    assert set(metric.get("workloads", CELLS)) <= set(CELLS)
    if end_to_end:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        assert line(metric["layer"])
        moved = next(m for m in BENCH["end_to_end"]
                     if m["name"] == metric["moves"])
        # a per-layer metric is reported only where the metric it moves is
        assert set(metric.get("workloads", CELLS)) \
            <= set(moved.get("workloads", CELLS))
        reader = importlib.import_module(
            f"perfbench.layer_metrics.{metric['name']}")
        assert callable(reader.read)
    if metric["name"].endswith("_roofline_pct"):
        assert metric["unit"] == "%"


def test_every_layer_is_in_perf_md():
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    for layer in {m["layer"] for m in BENCH["per_layer"]}:
        assert layer in perf, layer


def test_files_under_paths_are_named_from_allowed_characters():
    for p in BENCH["paths"]:
        for base, dirs, files in os.walk(os.path.join(ROOT, p)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(base, f), ROOT)
                assert PATH.match(rel), rel
