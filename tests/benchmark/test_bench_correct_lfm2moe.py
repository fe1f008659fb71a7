"""`correct` through the LFM2-24B-A2B builder at a toy width: a sound run
passes the limits committed for lfm2moe_sketch_w8_t2048; the reference computed
in bfloat16 throughout, put in the program's place, and a planted fault fail
them. Also the builder's refusals, the frozen bias in the session's state, and
the three expert families' builders in one process in any order."""

import importlib
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_tiny_lfm2moe import TRAFFIC, run_tiny_lfm2moe, tiny_config


def test_sound_run_is_correct():
    res = run_tiny_lfm2moe(seed=2_147_483_777)
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] == 4 * res["window"]["rounds"]


def test_control_bfloat16_is_not_correct():
    res = run_tiny_lfm2moe(seed=3, control=True)
    assert not res["correct"], res["compared"]


def test_half_batch_is_not_correct():
    res = run_tiny_lfm2moe(seed=4, fault="half_batch")
    assert not res["correct"], res["compared"]


@pytest.mark.parametrize("change, why", [
    (lambda c: c.update(expect_d=c["expect_d"] + 1), "the configuration states"),
    (lambda c: c["model"].update(moe_intermediate_size=8), "moe_intermediate_size"),
    # the model block's layers are the published list's at layers_kept, and no others
    (lambda c: c["model"].update(layer_types=["conv", "conv", "conv"]), "layer_types"),
    (lambda c: c["model"].update(num_dense_layers=2), "num_dense_layers"),
    (lambda c: c["model"].update(layers_kept=[0, 2]), "differ"),
])
def test_a_cut_under_the_models_name_is_refused(change, why):
    """The cell runs the d its configuration states, or not at all; and the
    model block may not drift from the keys the driver compares."""
    config = tiny_config()
    change(config)
    with pytest.raises(SystemExit, match=why):
        run_tiny_lfm2moe(seed=5, config=config)


def test_the_builder_seeds_the_bias_into_net_state_and_the_reference_alike():
    from benchmark.reference import lfm2_moe as ref

    config = tiny_config()
    cell = importlib.import_module("benchmark.builders.lfm2_moe").build(config, TRAFFIC, 11)
    for layer in ("layers_1", "layers_2"):
        held = cell.session.state["net_state"]["buffers"][layer]["moe"][ref.BIAS]
        given = cell.client_loss.keywords["buffers"][layer]["moe"][ref.BIAS]
        np.testing.assert_array_equal(np.asarray(held), np.asarray(given))
        assert held.shape == (8,) and 0 < float(jnp.abs(held).max()) < 0.05  # N(0, 0.01)
    assert "layers_0" not in cell.session.state["net_state"]["buffers"]  # the dense layer has none
    # another seed, another bias
    other = ref.init_buffers(jax.random.PRNGKey(12), ref.buffer_shapes(config["model"]))
    assert float(jnp.abs(other["layers_1"]["moe"][ref.BIAS] - held).max()) > 0
    assert cell.facts["d"] == config["expect_d"]
    # the tied head: one leaf on both sides
    assert "lm_head" not in cell.params0 and "lm_head" not in cell.session.state["params"]


BUILDERS = {"glm4_moe_lite": "bench_tiny_glm4", "qwen3_next": "bench_tiny_qwen3next",
            "lfm2_moe": "bench_tiny_lfm2moe"}


@pytest.mark.parametrize("order", list(itertools.permutations(sorted(BUILDERS))),
                         ids=lambda order: ">".join(o.split("_")[0] for o in order))
def test_the_three_expert_builders_build_in_any_order_in_one_process(order, monkeypatch):
    """Each builder installs the reference sketch its cell needs (Qwen3-Next's
    at import, the other two when a cell is built): whichever came before, a
    built cell's reference is its own, and the server step serves either."""
    from benchmark.reference import fetchsgd, fetchsgd_blocked, fetchsgd_topk_blocked as lean

    monkeypatch.setattr(fetchsgd, "CountSketch", fetchsgd.CountSketch)
    monkeypatch.setattr(fetchsgd, "sketch_server_step", fetchsgd.sketch_server_step)
    traffic = {"qwen3_next": dict(TRAFFIC, argv=["--client_chunk", "2"])}
    for name in order:
        builder = importlib.reload(importlib.import_module("benchmark.builders." + name))
        config = importlib.import_module(BUILDERS[name]).tiny_config()
        cell = builder.build(config, traffic.get(name, TRAFFIC), 13)
        assert cell.facts["d"] == config["expect_d"]
        want = (fetchsgd_blocked.BlockedCountSketch if name == "qwen3_next"
                else lean.TopKBlockedCountSketch)
        assert fetchsgd.CountSketch is want, (order, name)
        cs = fetchsgd.CountSketch(9_000, 5, 1024, 42)
        S = jax.jit(cs.accumulate)(jax.random.normal(jax.random.PRNGKey(0), (9_000,)) ** 3)
        Z = jnp.zeros((5, 1024))
        got = fetchsgd.sketch_server_step(cs, 100, 0.9, S, Z, Z, 0.05)
        plain = lean.plain_step(fetchsgd_blocked.BlockedCountSketch(9_000, 5, 1024, 42),
                                100, 0.9, S, Z, Z, 0.05)
        for x, y in zip(got, plain):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
