"""The Qwen3-Next builder at a toy width (the trainer's Qwen3NextLM takes
any): the committed configuration's flags with a smaller sketch, one narrow
Gated DeltaNet layer and one attention layer, 4 of 8 experts held, sequences
of 40 tokens (not a multiple of the delta rule's chunk), clients taken 2 at a
time as in the cell."""

import copy
import json
import os

from benchmark import check, counting_qwen3next, harness

CELL = "qwen3next_sketch_w8_t2048"
MODEL = dict(vocab_size=320, hidden_size=32, num_hidden_layers=2, full_attention_interval=2, num_attention_heads=4, num_key_value_heads=2,
             head_dim=16, rope_theta=10000, linear_num_key_heads=2, linear_num_value_heads=4,
             linear_key_head_dim=8, linear_value_head_dim=8, moe_intermediate_size=16,
             shared_expert_intermediate_size=16, num_experts_per_tok=3, num_experts=4,
             router_num_experts=8, experts_held_first=2, gdn_chunk=16)


def tiny_config() -> dict:
    with open(os.path.join(harness.HERE, "configs", "qwen3next_80b_a3b_fetchsgd.json")) as f:
        config = json.load(f)
    config["model"].update(MODEL)
    config.update({k: v for k, v in MODEL.items() if k in config})
    config["input"].update(vocab=320, seq_len=40, persona_pool=32)
    config["expect_d"] = counting_qwen3next.params(config["model"])
    config["reference_block"] = 2
    argv = config["argv"]
    argv[argv.index("--num_cols") + 1] = "4096"
    argv[argv.index("--k") + 1] = "500"
    return config


def run_tiny_qwen3next(seed: int, *, fault=None, limits=None, control=False, config=None):
    traffic = {"num_clients": 16, "cohort": 4, "examples_per_client": 1,
               "schedule_epoch": 0.5, "argv": ["--client_chunk", "2"]}
    config = copy.deepcopy(config) if config else tiny_config()
    entry = {"name": CELL, "config": config["name"], "traffic": "sketch_w8_t2048", "chips": 1}
    return harness.run_cell(
        CELL, seed, 0.1, False, require_tpu=False, manifest=harness.load_manifest(),
        loaded={"entry": entry, "config": config, "traffic": traffic},
        limits=limits or check.load_limits(CELL), fault=fault, control=control,
        warm_rounds=1, min_rounds=2, log=lambda *a: None)
