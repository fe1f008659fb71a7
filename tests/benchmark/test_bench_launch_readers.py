"""The readers PR 35 added, each on gauges set by hand: what a queued round
adds to the wall beyond its operations and the device line's drift against
the ready stamps, both from the capture's launch summary (gauges in the
program's process-wide registry, `profile_launch_pairs` queued pairs)."""

import importlib

import pytest

from benchmark.harness import Context
from commefficient_tpu.obs import registry as obreg

READERS = {"program_gap_ms": "gap", "timeline_drift_ms": "drift"}
LAUNCH_MS = {"gap": 3.75, "drift": 0.125, "between": 3.0, "head": 0.25}


def read(name):
    return importlib.import_module("benchmark.layer_metrics." + name).read(Context())


@pytest.fixture()
def launch_gauges():
    """What ProfileWindow publishes after a capture; the pairs are the
    test's to set."""
    reg = obreg.default()
    for part, ms in LAUNCH_MS.items():
        reg.gauge(f"profile_launch_{part}_ms").set(ms)
    yield reg.gauge("profile_launch_pairs")
    reg.gauge("profile_launch_pairs").set(0)


@pytest.mark.parametrize("name", sorted(READERS))
def test_launch_readers_copy_their_gauge(launch_gauges, name):
    launch_gauges.set(9)
    assert read(name) == LAUNCH_MS[READERS[name]]
    launch_gauges.set(3)  # the fewest pairs that make a reading
    assert read(name) == LAUNCH_MS[READERS[name]]


@pytest.mark.parametrize("name", sorted(READERS))
@pytest.mark.parametrize("pairs", [0, 1, 2])
def test_launch_readers_read_nothing_under_three_pairs(launch_gauges, name, pairs):
    launch_gauges.set(pairs)
    assert read(name) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_launch_readers_read_nothing_from_a_program_without_the_gauges(name, monkeypatch):
    """A parent of PR 35 publishes no such gauge: a fresh registry reads
    nothing and does not raise."""
    monkeypatch.setattr(obreg, "_DEFAULT", obreg.Registry())
    assert read(name) is None


def test_manifest_registers_both_for_every_cell():
    from benchmark import harness

    entries = {m["name"]: m for m in harness.load_manifest()["per_layer"]}
    for name in READERS:
        m = entries[name]
        assert "workloads" not in m
        assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) == (
            "ms/round", "lower", "program_span", "device", "client_updates_per_s")
