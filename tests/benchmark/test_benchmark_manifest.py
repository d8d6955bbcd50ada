"""BENCHMARK.json against the contract it was written to, and against the
files it names: every per-cell file is found by name, so a later PR adds a
cell with new files and one new entry."""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WIDTH_KEYS = re.compile(
    r"(hidden_size|intermediate_size|_dim$|_rank$|head_dim|num_experts_per_tok)")

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    M = json.load(_f)
E2E = {m["name"]: m for m in M["end_to_end"]}
CELLS = {w["name"]: w for w in M["workloads"]}
CONFIGS = {c["name"]: c for c in M["configs"]}


def find(sub, filename):
    from benchmark import find_data

    return find_data(ROOT, M["paths"], sub, filename)


def cells_reporting(metric):
    return metric.get("workloads", list(CELLS))


def test_top_level_keys_and_limits():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(M["paths"]) <= 16 and 1 <= len(M["command"]) <= 32
    assert isinstance(M["run_seconds"], int) and 1 <= M["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    runs = 2 + 14 * 24
    assert (runs * (M["run_seconds"] + 60) + 24 * 180 + 1200) <= 43200
    four = sum(1 for w in M["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(M["workloads"]) // 4)


def test_command_names_only_files_under_paths():
    for word in M["command"]:
        assert not word.startswith("/") and ".." not in word.split("/")
        if os.path.exists(os.path.join(ROOT, word)):
            assert any(word.startswith(p + "/") for p in M["paths"]), word


@pytest.mark.parametrize("entry", M["end_to_end"] + M["per_layer"]
                         + M["workloads"] + M["configs"],
                         ids=lambda e: e["name"])
def test_names_units_and_lines(entry):
    assert NAME.match(entry["name"])
    if "unit" in entry:
        assert UNIT.match(entry["unit"]), entry["unit"]
    # a metric's `source` is one of four words; a configuration's is a line
    lines = ("why", "layer") + (("source",) if "file" in entry else ())
    for key in lines:
        if key in entry:
            text = entry[key]
            assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    if "better" in entry:
        assert entry["better"] in ("lower", "higher")


def test_names_are_unique():
    for group in (M["end_to_end"] + M["per_layer"], M["workloads"], M["configs"]):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in M["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("metric", M["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_metric(metric):
    assert set(metric) <= {"name", "unit", "better", "bound", "source", "workloads"}
    assert metric["source"] in ("host_clock", "device_trace")
    assert 0.01 <= metric["bound"] <= 0.1
    for w in cells_reporting(metric):
        assert w in CELLS


def test_setup_s_is_reported_by_every_cell():
    assert "setup_s" in E2E and "workloads" not in E2E["setup_s"]
    assert E2E["setup_s"]["bound"] <= 0.1


@pytest.mark.parametrize("metric", M["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric(metric):
    assert set(metric) <= {"name", "unit", "better", "source", "layer",
                           "moves", "workloads"}
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    moved = E2E[metric["moves"]]
    for w in cells_reporting(metric):
        assert w in cells_reporting(moved), (
            f"{metric['name']} moves {moved['name']}, which {w} does not report")
    assert find("layer_metrics", f"{metric['name']}.py"), "no reader file"
    if metric["name"].endswith("_roofline") or "mfu" in metric["name"]:
        assert metric["unit"] == "%"


@pytest.mark.parametrize("cell", M["workloads"], ids=lambda w: w["name"])
def test_cell_files_are_found_by_name(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] in (1, 4) and cell["config"] in CONFIGS
    assert NAME.match(cell["traffic"])
    mix_file = find("traffic", f"{cell['traffic']}.json")
    assert mix_file, "no traffic file"
    with open(mix_file) as f:
        kind = json.load(f)["kind"]
    assert os.path.exists(os.path.join(ROOT, "benchmark", "drivers", f"{kind}.py"))
    assert find("limits", f"{cell['name']}.json"), "no limits file"
    reported = [m for m in M["end_to_end"] if cell["name"] in cells_reporting(m)]
    assert len(reported) >= 2, "setup_s and at least one other"
    assert any(cell["name"] in cells_reporting(m) for m in M["per_layer"])


@pytest.mark.parametrize("config", M["configs"], ids=lambda c: c["name"])
def test_config_file_against_its_source(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert any(config["file"].startswith(p + "/") for p in M["paths"])
    assert any(w["config"] == config["name"] for w in M["workloads"])
    assert len(config["reduced"]) <= 16
    for key in config["reduced"]:
        assert NAME.match(key) and not WIDTH_KEYS.search(key), key
    with open(os.path.join(ROOT, config["file"])) as f:
        held = json.load(f)
    assert held["source"] == config["source"]
    assert held["reduced"] == config["reduced"]
    # a new architecture brings its reference and its counts, found by name;
    # its file says which kernels the lowered step has to contain
    assert find("reference", f"{held['reference']}.py"), "no reference"
    assert find("counts", f"{held['reference']}.py"), "no operation counts"
    assert held["step_kernels"] and all(
        isinstance(k, str) and k for k in held["step_kernels"])
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows if r["source_url"] == config["source"])
    for key, value in row["config"].items():
        if key not in config["reduced"]:
            assert key in held and held[key] == value, key
