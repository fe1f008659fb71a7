"""The GLM-4.7-Flash builder at a toy width (the trainer's Glm4MoeLiteLM takes
any): the committed configuration's flags with a smaller sketch, one dense
layer and one expert layer of narrow latent attention, 4 of 8 experts held,
sequences of 40 tokens, clients taken one at a time as in the cell."""

import copy
import json
import os

from benchmark import check, counting_glm4_moe_lite, harness

CELL = "glm47flash_sketch_w8_t2048"
MODEL = dict(vocab_size=320, hidden_size=32, num_hidden_layers=2, intermediate_size=48,
             num_attention_heads=3, num_key_value_heads=3, q_lora_rank=24, kv_lora_rank=16,
             qk_nope_head_dim=12, qk_rope_head_dim=8, v_head_dim=16, rope_theta=10000,
             moe_intermediate_size=16, num_experts_per_tok=3, n_routed_experts=4,
             router_num_experts=8, experts_held_first=2)


def tiny_config() -> dict:
    with open(os.path.join(harness.HERE, "configs", "glm47_flash_fetchsgd.json")) as f:
        config = json.load(f)
    config["model"].update(MODEL)
    config.update({k: v for k, v in MODEL.items() if k in config})
    config["input"].update(vocab=320, seq_len=40, persona_pool=32)
    config["expect_d"] = counting_glm4_moe_lite.params(config["model"])
    argv = config["argv"]
    argv[argv.index("--num_cols") + 1] = "4096"
    argv[argv.index("--k") + 1] = "500"
    return config


def run_tiny_glm4(seed: int, *, fault=None, limits=None, control=False, config=None):
    traffic = {"num_clients": 16, "cohort": 4, "examples_per_client": 1,
               "schedule_epoch": 0.5, "argv": ["--client_chunk", "1"]}
    config = copy.deepcopy(config) if config else tiny_config()
    entry = {"name": CELL, "config": config["name"], "traffic": "sketch_w8_t2048_chunk1",
             "chips": 1}
    return harness.run_cell(
        CELL, seed, 0.1, False, require_tpu=False, manifest=harness.load_manifest(),
        loaded={"entry": entry, "config": config, "traffic": traffic},
        limits=limits or check.load_limits(CELL), fault=fault, control=control,
        warm_rounds=1, min_rounds=2, log=lambda *a: None)
