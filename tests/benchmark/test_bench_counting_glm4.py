"""counting_glm4_moe_lite.py against numbers worked out by hand from the public
config.json (ISSUE 31's table) and against the model's own leaf count at two
small sizes, and the two new readers of the capture's summary by block on
gauges set by hand."""

import dataclasses
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import counting_glm4_moe_lite as counting
from benchmark.harness import HERE, Context
from commefficient_tpu.obs import registry as obreg


@pytest.fixture(scope="module")
def model():
    with open(os.path.join(HERE, "configs", "glm47_flash_fetchsgd.json")) as f:
        return json.load(f)["model"]


def test_parameters_by_hand(model):
    # q down 2048 x 768, its norm, q up 768 x 20 x 256, kv down 2048 x (512 + 64), its norm,
    # kv up 512 x 20 x (192 + 256), o 5120 x 2048
    assert counting.mla_params(model) == (
        1_572_864 + 768 + 3_932_160 + 1_179_648 + 512 + 4_587_520 + 10_485_760) == 21_759_232
    assert counting.dense_mlp_params(model) == 3 * 2048 * 10_240 == 62_914_560
    assert counting.routed_expert_params(model) == 3 * 2048 * 1536 == 9_437_184
    # router 2048 x 64 (its bias is a buffer, not counted), one shared expert
    assert counting.moe_shared_params(model) == 131_072 + 9_437_184
    assert counting.dense_layer_params(model) == 4_096 + 21_759_232 + 62_914_560 == 84_677_888
    assert counting.expert_layer_params(model) == (
        4_096 + 21_759_232 + 131_072 + 9_437_184 + 8 * 9_437_184) == 106_829_056
    # + embedding, head and final norm over 19,360 rows
    assert counting.params(model) == 84_677_888 + 4 * 106_829_056 + 79_300_608 == 591_294_720
    assert counting.params(dict(model, n_routed_experts=64)) == 591_294_720 + 4 * 56 * 9_437_184


@pytest.mark.parametrize("change", [
    {}, dict(num_hidden_layers=3, first_k_dense_replace=2, n_routed_experts=2, n_shared_experts=2,
             num_attention_heads=2, v_head_dim=8)])
def test_parameters_equal_the_models_own_leaf_count(change):
    from commefficient_tpu.models.glm4_moe_lite import TINY, Glm4MoeLiteLM

    cfg = dataclasses.replace(TINY, **change)
    shapes = jax.eval_shape(lambda: Glm4MoeLiteLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32), train=False))["params"]
    d = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert counting.params(dataclasses.asdict(cfg)) == d


def test_operations_by_hand(model):
    macs = counting.macs_per_token(model, 2048)
    # a latent-attention block: its five matrices, and scores over 2048 x 20 x 256
    # and values over 2048 x 20 x 256
    assert counting.mla_projection_params(model) == 21_757_952
    assert macs["mla"] == 5 * (21_757_952 + 2048 * 20 * (192 + 64 + 256))
    assert macs["dense_mlp"] == 62_914_560
    # router + shared expert + 4 x 8 / 64 routed experts a token, four layers
    assert macs["moe"] == 4 * (131_072 + 9_437_184 + 0.5 * 9_437_184)
    assert macs["lm_head"] == 19_360 * 2048
    # FLOPs by block add up to the total: ISSUE 31's 746.7 MFLOP a token forward
    assert 2 * sum(macs.values()) == pytest.approx(746.7e6, rel=1e-4)
    assert 2 * macs["mla"] == pytest.approx(427e6, rel=2e-3)
    assert 2 * macs["dense_mlp"] == pytest.approx(125.8e6, rel=1e-3)
    assert 2 * macs["lm_head"] == pytest.approx(79.3e6, rel=1e-3)
    total = counting.train_flops_per_token(model, 2048)
    assert total == 6 * sum(macs.values())
    assert 16_384 * total == pytest.approx(36.7e12, rel=1e-3)  # a round of 8 x 2,048 tokens


def read(name):
    return importlib.import_module("benchmark.layer_metrics." + name).read(Context())


def test_block_readers_read_the_second_summarys_gauges():
    reg = obreg.default()
    reg.gauge("profile_block_device_ms_mla").set(480.0)
    reg.gauge("profile_block_device_ms_dense_mlp").set(90.0)
    reg.gauge("profile_block_traced_rounds").set(10)
    try:
        assert read("mla_ms") == 480.0 and read("dense_mlp_ms") == 90.0
    finally:
        reg.gauge("profile_block_traced_rounds").set(0)
    # no second summary (a parent that names no such block, or no capture)
    assert read("mla_ms") is None and read("dense_mlp_ms") is None
