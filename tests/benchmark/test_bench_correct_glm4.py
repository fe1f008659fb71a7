"""`correct` through the GLM-4.7-Flash builder at a toy width: a sound run
passes the limits committed for glm47flash_sketch_w8_t2048; the reference
computed in bfloat16 throughout, put in the program's place, and a planted
fault fail them. Also the builder's refusals, the frozen selection bias in the
session's state, and the reference's server step taken block by block."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_tiny_glm4 import run_tiny_glm4, tiny_config


def test_sound_run_is_correct():
    res = run_tiny_glm4(seed=2_147_483_777)
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] == 4 * res["window"]["rounds"]


def test_control_bfloat16_is_not_correct():
    res = run_tiny_glm4(seed=3, control=True)
    assert not res["correct"], res["compared"]


def test_half_batch_is_not_correct():
    res = run_tiny_glm4(seed=4, fault="half_batch")
    assert not res["correct"], res["compared"]


def test_a_cut_under_the_models_name_is_refused():
    """The cell runs the d its configuration states, or not at all; and the
    model block may not drift from the keys the driver compares."""
    config = tiny_config()
    config["expect_d"] += 1
    with pytest.raises(SystemExit, match="the configuration states"):
        run_tiny_glm4(seed=5, config=config)
    config = tiny_config()
    config["model"]["v_head_dim"] = 8
    with pytest.raises(SystemExit, match="v_head_dim"):
        run_tiny_glm4(seed=5, config=config)


def test_the_builder_seeds_the_bias_into_net_state_and_the_reference_alike():
    import importlib

    from benchmark.reference import glm4_moe_lite as ref

    config = tiny_config()
    traffic = {"num_clients": 16, "cohort": 4, "examples_per_client": 1,
               "schedule_epoch": 0.5, "argv": ["--client_chunk", "1"]}
    cell = importlib.import_module("benchmark.builders.glm4_moe_lite").build(config, traffic, 11)
    held = cell.session.state["net_state"]["buffers"]["layers_1"]["moe"][ref.BIAS]
    given = cell.client_loss.keywords["buffers"]["layers_1"]["moe"][ref.BIAS]
    np.testing.assert_array_equal(np.asarray(held), np.asarray(given))
    assert held.shape == (8,) and 0 < float(jnp.abs(held).max()) < 0.05  # N(0, 0.01)
    # another seed, another bias; and the bias is no repeat of a parameter's draw
    other = ref.init_buffers(jax.random.PRNGKey(12), ref.buffer_shapes(config["model"]))
    assert float(jnp.abs(other["layers_1"]["moe"][ref.BIAS] - held).max()) > 0
    assert cell.facts["d"] == config["expect_d"]


def test_blocked_topk_server_step_is_the_plain_server_step():
    """fetchsgd_topk_blocked against fetchsgd.py's own step on one table: the
    same k coordinates in the same order, the same values, the same V and E."""
    import importlib.util

    from benchmark.reference import fetchsgd, fetchsgd_topk_blocked as lean

    # fetchsgd.py as committed, whatever a builder has installed in the module
    spec = importlib.util.spec_from_file_location("fetchsgd_as_committed", fetchsgd.__file__)
    plain = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(plain)
    plain_step, plain_cs = plain.sketch_server_step, plain.CountSketch
    d, r, c, k = 70_000, 5, 1024, 300  # 69 slabs: three blocks of 27, the last one ragged
    a, b = plain_cs(d, r, c, 42), lean.TopKBlockedCountSketch(d, r, c, 42)
    S = jax.jit(a.accumulate)(jax.random.normal(jax.random.PRNGKey(0), (d,)) ** 3)
    V = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (r, c))
    E = 0.1 * jax.random.normal(jax.random.PRNGKey(2), (r, c))
    want = plain_step(a, k, 0.9, S, V, E, 0.05)
    got = lean.sketch_server_step(b, k, 0.9, S, V, E, 0.05)
    for x, y in zip(got, want):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert int(jnp.max(got[0])) < d


@pytest.mark.parametrize("later", ["qwen3_next_builder", "nothing"])
def test_installed_step_serves_whichever_sketch_a_later_builder_installs(later, monkeypatch):
    """One process, the GLM cell built first and the Qwen3-Next builder
    imported after it (pytest's alphabetical order over tests/benchmark/):
    that builder assigns fetchsgd.CountSketch = BlockedCountSketch, which has
    no query_topk, and reference/rounds.py then calls the installed step on
    it. The step hands such a sketch to fetchsgd.py's own."""
    from benchmark.reference import fetchsgd, fetchsgd_blocked, fetchsgd_topk_blocked as lean

    monkeypatch.setattr(fetchsgd, "CountSketch", fetchsgd.CountSketch)
    monkeypatch.setattr(fetchsgd, "sketch_server_step", fetchsgd.sketch_server_step)
    lean.install()
    if later == "qwen3_next_builder":  # what builders/qwen3_next.py does at import
        fetchsgd.CountSketch = fetchsgd_blocked.BlockedCountSketch
    d, r, c, k = 9_000, 5, 1024, 100
    cs = fetchsgd.CountSketch(d, r, c, 42)
    assert hasattr(cs, "query_topk") == (later == "nothing")
    S = jax.jit(cs.accumulate)(jax.random.normal(jax.random.PRNGKey(0), (d,)) ** 3)
    Z = jnp.zeros((r, c))
    got = fetchsgd.sketch_server_step(cs, k, 0.9, S, Z, Z, 0.05)
    want = lean.plain_step(fetchsgd_blocked.BlockedCountSketch(d, r, c, 42), k, 0.9, S, Z, Z, 0.05)
    for x, y in zip(got, want):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    # every build installs again: fetchsgd.py's own step stays the one handed to
    lean.install()
    assert fetchsgd.sketch_server_step is lean.sketch_server_step
    assert lean.plain_step.__module__.endswith(".fetchsgd")
