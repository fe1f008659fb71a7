"""`correct` through the GPT-2 builder at a toy width: a sound run passes the
limits committed for gpt2s_sketch_w8; the lower-precision control and each
planted fault fail them. Also the builder's own refusals."""

import pytest

from bench_tiny import run_tiny_gpt2


def test_sound_run_is_correct():
    res = run_tiny_gpt2(seed=2_147_483_777)
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] == 4 * res["window"]["rounds"]


def test_control_bfloat16_is_not_correct():
    res = run_tiny_gpt2(seed=3, control=True)
    assert not res["correct"], res["compared"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_fault_is_not_correct(fault):
    res = run_tiny_gpt2(seed=4, fault=fault)
    assert not res["correct"], res["compared"]


def test_a_cut_width_under_the_models_name_is_refused(monkeypatch):
    """Trap 1 of ISSUE 24: the cell must run the published d, or not at all."""
    from benchmark import counting

    monkeypatch.setattr(counting, "gpt2_params", lambda *a: 124_443_648)
    with pytest.raises(SystemExit, match="d=137,280"):
        run_tiny_gpt2(seed=5)
