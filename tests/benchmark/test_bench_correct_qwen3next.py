"""`correct` through the Qwen3-Next builder at a toy width: a sound run passes
the limits committed for qwen3next_sketch_w8_t2048; the reference computed in
bfloat16 throughout, put in the program's place, and a planted fault fail
them. Also the builder's refusals and the blocked query of the reference."""

import jax
import numpy as np
import pytest

from bench_tiny_qwen3next import run_tiny_qwen3next, tiny_config


def test_sound_run_is_correct():
    res = run_tiny_qwen3next(seed=2_147_483_777)
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] == 4 * res["window"]["rounds"]


def test_control_bfloat16_is_not_correct():
    res = run_tiny_qwen3next(seed=3, control=True)
    assert not res["correct"], res["compared"]


def test_half_batch_is_not_correct():
    res = run_tiny_qwen3next(seed=4, fault="half_batch")
    assert not res["correct"], res["compared"]


def test_a_cut_under_the_models_name_is_refused():
    """The cell runs the d its configuration states, or not at all; and the
    model block may not drift from the keys the driver compares."""
    config = tiny_config()
    config["expect_d"] += 1
    with pytest.raises(SystemExit, match="the configuration states"):
        run_tiny_qwen3next(seed=5, config=config)
    config = tiny_config()
    config["model"]["head_dim"] = 8
    with pytest.raises(SystemExit, match="head_dim"):
        run_tiny_qwen3next(seed=5, config=config)


def test_blocked_sketch_is_the_plain_sketch_bit_for_bit():
    from benchmark.reference import fetchsgd_blocked

    plain = fetchsgd_blocked.BlockedCountSketch.__bases__[0]  # fetchsgd.py's own class
    d, r, c = 70_000, 5, 1024  # 69 slabs: three blocks of 27, the last one ragged
    a, b = plain(d, r, c, 42), fetchsgd_blocked.BlockedCountSketch(d, r, c, 42)
    table = jax.random.normal(jax.random.PRNGKey(0), (r, c))
    assert a.query_all(table).shape == b.query_all(table).shape == (d,)
    np.testing.assert_array_equal(np.asarray(a.query_all(table)), np.asarray(b.query_all(table)))
    v = jax.random.normal(jax.random.PRNGKey(1), (d,))
    np.testing.assert_array_equal(np.asarray(jax.jit(a.accumulate)(v)),
                                  np.asarray(jax.jit(b.accumulate)(v)))
