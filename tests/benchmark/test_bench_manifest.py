"""BENCHMARK.json against the files it names and the contract's format."""

import importlib
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_and_limits(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs", "workloads",
                             "end_to_end", "per_layer"}
    assert 1 <= manifest["run_seconds"] <= 51 and isinstance(manifest["run_seconds"], int)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= len(manifest["workloads"]) <= 24 and 1 <= len(manifest["configs"]) <= 24
    for word in manifest["command"]:
        assert not word.startswith("/") and ".." not in word
    four = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(manifest["workloads"]) // 4)


def test_names_and_units(manifest):
    seen = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in manifest[group]]
        assert len(names) == len(set(names))
        for n in names:
            assert NAME.match(n), n
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert m["name"] not in seen
        seen.add(m["name"])
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for w in manifest["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_every_cell_has_its_files(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    used = set()
    for w in manifest["workloads"]:
        c = configs[w["config"]]
        used.add(c["name"])
        assert any(c["file"].startswith(p + "/") for p in manifest["paths"])
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
        assert os.path.isfile(os.path.join(ROOT, "benchmark", "builders", cfg["builder"] + ".py"))
        with open(os.path.join(ROOT, "benchmark", "traffic", w["traffic"] + ".json")) as f:
            traffic = json.load(f)
        for key in ("num_clients", "cohort", "examples_per_client", "schedule_epoch"):
            assert key in traffic
        with open(os.path.join(ROOT, "benchmark", "limits", w["name"] + ".json")) as f:
            limits = json.load(f)["limits"]
        assert {"loss1_gap", "grad_gap", "update_gap"} <= set(limits) <= {
            "loss1_gap", "loss_gap", "grad_gap", "update_gap"}
        assert all(0 < v < 1 for v in limits.values())  # an unchanged state reads 1
    assert used == set(configs)
    files = [c["file"] for c in manifest["configs"]]
    assert len(files) == len(set(files))


def test_metrics_are_wired(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    cells = {w["name"] for w in manifest["workloads"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in e2e.values():
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")

    def reports(metric, cell):
        return "workloads" not in metric or cell in metric["workloads"]

    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["moves"] in e2e
        assert 1 <= len(m["layer"]) <= 200
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert reports(e2e[m["moves"]], cell)
        reader = importlib.import_module("benchmark.layer_metrics." + m["name"])
        assert callable(reader.read)
    for cell in cells:
        assert sum(reports(m, cell) for m in e2e.values()) >= 2
        assert any(reports(m, cell) for m in manifest["per_layer"])
    # a kernel's share is named <kernel>_roofline in %, and the whole step's
    # share of the peak stands beside it with mfu in its name
    rooflines = [m for m in manifest["per_layer"] if m["name"].endswith("_roofline")]
    assert rooflines and all(m["unit"] == "%" for m in rooflines)
    for r in rooflines:
        assert any("mfu" in m["name"] and m["moves"] == r["moves"]
                   for m in manifest["per_layer"])


def test_allowed_units_of_this_benchmark(manifest):
    units = {m["unit"] for m in manifest["end_to_end"] + manifest["per_layer"]}
    assert units <= {"updates/s", "ms/round", "%", "s"}
