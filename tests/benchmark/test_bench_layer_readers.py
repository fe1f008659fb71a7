"""The readers PR 25 added, each on a context built by hand: the loop's ready
stamps (window deltas of three histograms) and the capture's summary by phase
(gauges in the program's process-wide registry)."""

import importlib

import pytest

from benchmark.harness import Context
from commefficient_tpu.obs import registry as obreg

PHASE_MS = {"client_grad": 46.0, "cohort_reduce": 15.5, "compress": 1.0,
            "server_algebra": 4.0, "server_query": 0.75, "server_topk": 15.0,
            "apply": 1.5, "other": 2.0}


def read(name, **ctx):
    return importlib.import_module("benchmark.layer_metrics." + name).read(Context(**ctx))


@pytest.fixture()
def summary_gauges():
    """What ProfileWindow publishes after a capture of 10 rounds."""
    reg = obreg.default()
    for phase, ms in PHASE_MS.items():
        reg.gauge(f"profile_phase_device_ms_{phase}").set(ms)
    reg.gauge("profile_traced_rounds").set(10)
    yield reg
    reg.gauge("profile_traced_rounds").set(0)


def hist(total, count):
    return {"sum": total, "count": count}


def test_loop_bubble_is_first_less_chained_once_a_drain():
    # 100 rounds at depth 2: 50 drains, 49 of them with a stamp before them
    registry = {"runner_round_interval_first_ms": hist(49 * 94.0, 49),
                "runner_round_interval_chained_ms": hist(50 * 84.0, 50),
                "runner_bubble_host_ms": hist(49 * 3.0, 49)}
    assert read("loop_bubble_ms", registry=registry, rounds=100) == pytest.approx(
        (94.0 - 84.0) * 49 / 100)
    assert read("loop_bubble_host_ms", registry=registry, rounds=100) == pytest.approx(
        3.0 * 49 / 100)


@pytest.mark.parametrize("registry", [
    {},  # the parent of PR 25: no such histograms
    {"runner_round_interval_first_ms": hist(0.0, 0),
     "runner_round_interval_chained_ms": hist(840.0, 10),
     "runner_bubble_host_ms": hist(0.0, 0)},
    {"runner_round_interval_first_ms": hist(940.0, 10),
     "runner_round_interval_chained_ms": hist(0.0, 0),
     "runner_bubble_host_ms": hist(0.0, 0)},  # depth 1: nothing is chained
])
def test_loop_readers_read_nothing_without_counts(registry):
    assert read("loop_bubble_ms", registry=registry, rounds=100) is None
    assert read("loop_bubble_host_ms", registry=registry, rounds=100) is None


def test_phase_readers_add_the_summary_gauges(summary_gauges):
    sketch = {"mode": "sketch"}
    assert read("client_phase_ms", facts=sketch) == pytest.approx(46.0 + 15.5)
    assert read("cohort_reduce_ms", facts=sketch) == pytest.approx(15.5)
    assert read("server_phase_ms", facts=sketch) == pytest.approx(
        1.0 + 4.0 + 0.75 + 15.0 + 1.5)
    assert read("topk_ms", facts=sketch) == pytest.approx(15.0)
    assert read("topk_ms", facts={"mode": "uncompressed"}) is None
    assert read("phase_other_ms", facts=sketch) == pytest.approx(2.0)


def test_phase_readers_read_nothing_without_a_summary(summary_gauges):
    summary_gauges.gauge("profile_traced_rounds").set(0)
    for name in ("client_phase_ms", "cohort_reduce_ms", "server_phase_ms", "topk_ms",
                 "phase_other_ms"):
        assert read(name, facts={"mode": "sketch"}) is None, name
