"""`correct` for the sketch cell at a size a test run can hold: a sound run
passes the committed limits; the lower-precision control and each planted
fault fail them."""

import pytest

from bench_tiny import run_tiny

CELL = "resnet9_sketch_w128"


def test_sound_run_is_correct():
    res = run_tiny(CELL, seed=2_147_483_659)  # more than 32 signed bits hold
    assert res["correct"], res["compared"]
    assert res["attempted"] == 2 * res["window"]["rounds"] and res["failed"] == 0
    assert list(res)[-1] == "compared"
    assert set(res["metrics"]) == {"client_updates_per_s", "setup_s"}
    assert res["metrics"]["client_updates_per_s"]["value"] > 0


def test_control_bfloat16_is_not_correct():
    """The reference computed in bfloat16 throughout, in the program's place."""
    res = run_tiny(CELL, seed=5, control=True)
    assert not res["correct"], res["compared"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_fault_is_not_correct(fault):
    res = run_tiny(CELL, seed=6, fault=fault)
    assert not res["correct"], res["compared"]
    over = [n for n, c in res["compared"].items() if c["value"] > c["limit"]]
    assert over, res["compared"]
    if fault == "state_unchanged":
        assert res["compared"]["update_gap"]["value"] == pytest.approx(1.0)
