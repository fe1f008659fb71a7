"""counting_qwen3next.py against numbers worked out by hand from the public
config.json (ISSUE 27's table), and the four readers of the capture's summary
by block on gauges set by hand."""

import importlib
import json
import os

import pytest

from benchmark import counting_qwen3next as counting
from benchmark.harness import HERE, Context
from commefficient_tpu.obs import registry as obreg


@pytest.fixture(scope="module")
def model():
    with open(os.path.join(HERE, "configs", "qwen3next_80b_a3b_fetchsgd.json")) as f:
        return json.load(f)["model"]


def test_parameters_by_hand(model):
    # q/k/v/z 2048 x 12288, b/a 2048 x 64, conv 8192 x 4, A_log 32, dt_bias 32,
    # gated norm 128, out 4096 x 2048
    assert counting.delta_mixer_params(model) == (
        25_165_824 + 131_072 + 32_768 + 32 + 32 + 128 + 8_388_608) == 33_718_464
    # q (query and gate) 2048 x 8192, k and v 2048 x 512, o 4096 x 2048, two norms of 256
    assert counting.attention_mixer_params(model) == (
        16_777_216 + 2 * 1_048_576 + 8_388_608 + 512) == 27_263_488
    # router 2048 x 512, shared expert 3 x 2048 x 512, shared gate 2048
    assert counting.moe_shared_params(model) == 1_048_576 + 3_145_728 + 2_048 == 4_196_352
    assert counting.routed_expert_params(model) == 3 * 2048 * 512 == 3_145_728
    assert counting.period_params_outside_experts(model) == (
        3 * 37_918_912 + 31_463_936) == 145_220_672
    # + 4 layers x 16 experts + embedding, head and final norm over 18,992 rows
    assert counting.params(model) == 145_220_672 + 201_326_592 + 77_793_280 == 424_340_544
    assert counting.params(dict(model, num_experts=8)) == 323_677_248
    assert counting.params(dict(model, num_experts=32)) == 625_667_136


def test_operations_by_hand(model):
    macs = counting.macs_per_token(model, 2048)
    # a DeltaNet layer: its matmul weights and taps, and 3 x 32 heads x 128 x 128 for the rule
    assert macs["gdn"] == 3 * (25_165_824 + 131_072 + 8_388_608 + 32_768 + 3 * 32 * 16_384)
    # the attention layer: its projections, and scores and values over 2048 x 4096
    assert macs["gated_attn"] == 16_777_216 + 2_097_152 + 8_388_608 + 2 * 2048 * 4096
    # router + shared expert + 10 x 16 / 512 routed experts a token, four layers
    assert macs["moe"] == 4 * (4_196_352 + 0.3125 * 3_145_728)
    assert macs["lm_head"] == 18_992 * 2048
    total = counting.train_flops_per_token(model, 2048)
    assert total == 6 * sum(macs.values()) == pytest.approx(1.257e9, rel=1e-3)
    assert 16_384 * total == pytest.approx(20.6e12, rel=1e-2)  # ISSUE 27: about 21 TFLOP a round


BLOCK_MS = {"gdn": 300.0, "gated_attn": 60.0, "moe_route": 20.0, "moe_experts": 90.0,
            "moe_shared": 30.0, "lm_head": 40.0}


def read(name):
    return importlib.import_module("benchmark.layer_metrics." + name).read(Context())


def test_block_readers_add_the_second_summarys_gauges():
    reg = obreg.default()
    for block, ms in BLOCK_MS.items():
        reg.gauge(f"profile_block_device_ms_{block}").set(ms)
    reg.gauge("profile_block_traced_rounds").set(10)
    try:
        assert read("gdn_ms") == 300.0 and read("gated_attn_ms") == 60.0
        assert read("moe_ms") == 20.0 + 90.0 + 30.0 and read("lm_head_ms") == 40.0
    finally:
        reg.gauge("profile_block_traced_rounds").set(0)
    # no second summary (the parent of PR 27, or a model that names no block)
    for name in ("gdn_ms", "gated_attn_ms", "moe_ms", "lm_head_ms"):
        assert read(name) is None, name
