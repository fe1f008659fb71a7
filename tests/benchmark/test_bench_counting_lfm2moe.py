"""counting_lfm2_moe.py against numbers worked out by hand from the public
config.json (ISSUE 33's table) and against the model's own leaf count at three
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

from benchmark import counting_lfm2_moe as counting
from benchmark.harness import HERE, Context
from commefficient_tpu.obs import registry as obreg


@pytest.fixture(scope="module")
def model():
    with open(os.path.join(HERE, "configs", "lfm2_24b_a2b_fetchsgd.json")) as f:
        return json.load(f)["model"]


def test_parameters_by_hand(model):
    # in_proj 2048 x 6144, three taps of 2048, out_proj 2048 x 2048
    assert counting.short_conv_params(model) == 12_582_912 + 6_144 + 4_194_304 == 16_783_360
    # q and o 2048 x 2048, k and v 2048 x 512, two head norms of 64
    assert counting.attention_params(model) == (
        2 * 4_194_304 + 2 * 1_048_576 + 128) == 10_485_888
    assert counting.dense_mlp_params(model) == 3 * 2048 * 11_776 == 72_351_744
    assert counting.routed_expert_params(model) == 3 * 2048 * 1536 == 9_437_184
    # router 2048 x 64 (its bias is a buffer, not counted); no shared expert
    assert counting.router_params(model) == 131_072
    # layer 0: convolution + dense; layer 1: attention + experts; layers 2-4: convolution + experts
    assert counting.layer_params(model, 0) == 4_096 + 16_783_360 + 72_351_744 == 89_139_200
    assert counting.layer_params(model, 1) == (
        4_096 + 10_485_888 + 131_072 + 8 * 9_437_184) == 86_118_528
    assert [counting.layer_params(model, i) for i in (2, 3, 4)] == [
        4_096 + 16_783_360 + 75_628_544] * 3 == [92_416_000] * 3
    # + the embedding over 8,192 rows, once (the head is tied to it), and the final norm
    assert counting.params(model) == (
        89_139_200 + 86_118_528 + 3 * 92_416_000 + 16_777_216 + 2_048) == 469_284_992
    assert counting.params(dict(model, num_experts=64)) == 469_284_992 + 4 * 56 * 9_437_184


@pytest.mark.parametrize("change", [
    {},
    dict(num_hidden_layers=4, layer_types=("full_attention", "conv", "conv", "full_attention"),
         num_dense_layers=2, num_experts=2, num_attention_heads=2, num_key_value_heads=1),
    dict(num_dense_layers=0, conv_L_cache=5, vocab_size=100),
])
def test_parameters_equal_the_models_own_leaf_count(change):
    from commefficient_tpu.models.lfm2_moe import TINY, Lfm2MoeLM

    cfg = dataclasses.replace(TINY, **change)
    shapes = jax.eval_shape(lambda: Lfm2MoeLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32), train=False))["params"]
    d = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert counting.params(cfg.model_block()) == d


def test_operations_by_hand(model):
    macs = counting.macs_per_token(model, 2048)
    # four convolution layers: both projections and the three taps
    assert macs["short_conv"] == 4 * (12_582_912 + 4_194_304 + 6_144)
    # one attention layer: its four matrices, scores over 2048 x 32 x 64 and values over the same
    assert counting.attention_projection_params(model) == 10_485_760
    assert macs["gqa_attn"] == 10_485_760 + 2048 * 32 * (64 + 64)
    assert macs["dense_mlp"] == 72_351_744
    # router + 4 x 8 / 64 routed experts a token, four layers; no shared expert
    assert macs["moe"] == 4 * (131_072 + 0.5 * 9_437_184)
    assert macs["lm_head"] == 8_192 * 2048
    # FLOPs by block add up to the total: ISSUE 33's 389.1 MFLOP a token forward
    assert 2 * sum(macs.values()) == pytest.approx(389.1e6, rel=1e-4)
    assert 2 * macs["short_conv"] == pytest.approx(134.3e6, rel=1e-3)
    assert 2 * macs["dense_mlp"] == pytest.approx(144.7e6, rel=1e-3)
    assert 2 * macs["gqa_attn"] == pytest.approx(37.7e6, rel=2e-3)
    assert 2 * 2048 * 32 * 128 == pytest.approx(16.8e6, rel=2e-3)  # of which the T x T square
    assert 2 * macs["moe"] == pytest.approx(38.8e6, rel=1e-3)
    assert 2 * macs["lm_head"] == pytest.approx(33.6e6, rel=2e-3)
    total = counting.train_flops_per_token(model, 2048)
    assert total == 6 * sum(macs.values())
    assert 16_384 * total == pytest.approx(19.1e12, rel=2e-3)  # a round of 8 x 2,048 tokens


def read(name):
    return importlib.import_module("benchmark.layer_metrics." + name).read(Context())


def test_block_readers_read_the_second_summarys_gauges():
    reg = obreg.default()
    reg.gauge("profile_block_device_ms_short_conv").set(46.6)
    reg.gauge("profile_block_device_ms_gqa_attn").set(79.1)
    reg.gauge("profile_block_traced_rounds").set(12)
    try:
        assert read("short_conv_ms") == 46.6 and read("gqa_attn_ms") == 79.1
    finally:
        reg.gauge("profile_block_traced_rounds").set(0)
    # no second summary (a parent that names no such block, or no capture)
    assert read("short_conv_ms") is None and read("gqa_attn_ms") is None


def test_the_expert_reader_counts_no_shared_expert_in_this_model():
    """`moe_ms` sums the router's, the held experts' and the shared expert's
    scopes; this model names no `moe_shared`, so its gauge stays where it was
    and the reader reads the other two."""
    reg = obreg.default()
    was = reg.gauge("profile_block_device_ms_moe_shared").value
    reg.gauge("profile_block_device_ms_moe_route").set(9.9)
    reg.gauge("profile_block_device_ms_moe_experts").set(123.5)
    reg.gauge("profile_block_device_ms_moe_shared").set(0.0)
    reg.gauge("profile_block_traced_rounds").set(12)
    try:
        assert read("moe_ms") == pytest.approx(133.4)
    finally:
        reg.gauge("profile_block_traced_rounds").set(0)
        reg.gauge("profile_block_device_ms_moe_shared").set(was)
