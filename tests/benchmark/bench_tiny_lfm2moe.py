"""The LFM2-24B-A2B builder at a toy width (the trainer's Lfm2MoeLM takes any):
the committed configuration's flags with a smaller sketch; published layer 0
(short convolution + dense feed-forward) and layers 2 and 3 (attention and a
short convolution, both with expert blocks) at narrow widths, 4 of 8 experts
held, sequences of 40 tokens, clients taken one at a time as in the cell."""

import copy
import json
import os

from benchmark import check, counting_lfm2_moe, harness

CELL = "lfm2moe_sketch_w8_t2048"
MODEL = dict(vocab_size=320, hidden_size=32, num_hidden_layers=3, intermediate_size=48,
             num_attention_heads=4, num_key_value_heads=2, moe_intermediate_size=16,
             num_experts_per_tok=3, num_experts=4, router_num_experts=8, experts_held_first=2,
             rope_parameters={"rope_theta": 10000, "rope_type": "default"})
# what the layers kept had in the published list, which the top level keeps whole
KEPT = dict(layers_kept=[0, 2, 3], layer_types=["conv", "full_attention", "conv"],
            num_dense_layers=1)


def tiny_config() -> dict:
    with open(os.path.join(harness.HERE, "configs", "lfm2_24b_a2b_fetchsgd.json")) as f:
        config = json.load(f)
    config["model"].update(MODEL, **KEPT)
    config.update({k: v for k, v in MODEL.items() if k in config})
    config["input"].update(vocab=320, seq_len=40, persona_pool=32)
    config["expect_d"] = counting_lfm2_moe.params(config["model"])
    argv = config["argv"]
    argv[argv.index("--num_cols") + 1] = "4096"
    argv[argv.index("--k") + 1] = "500"
    return config


TRAFFIC = {"num_clients": 16, "cohort": 4, "examples_per_client": 1,
           "schedule_epoch": 0.5, "argv": ["--client_chunk", "1"]}


def run_tiny_lfm2moe(seed: int, *, fault=None, limits=None, control=False, config=None):
    config = copy.deepcopy(config) if config else tiny_config()
    entry = {"name": CELL, "config": config["name"], "traffic": "sketch_w8_t2048_chunk1",
             "chips": 1}
    return harness.run_cell(
        CELL, seed, 0.1, False, require_tpu=False, manifest=harness.load_manifest(),
        loaded={"entry": entry, "config": config, "traffic": dict(TRAFFIC)},
        limits=limits or check.load_limits(CELL), fault=fault, control=control,
        warm_rounds=1, min_rounds=2, log=lambda *a: None)
