"""A cell small enough for a test run: the committed configuration (ResNet-9
keeps its published width, the trainer has no other) with a smaller sketch and
a federation of a few clients. run_cell skips its look for a chip and drives
the rest of a run: builder, first rounds, warm-up, window, reference, check,
under the limits committed for the real cell."""

import copy

from benchmark import check, harness

TRAFFIC = {"num_clients": 12, "cohort": 2, "examples_per_client": 2,
           "label_skew": "one_class", "schedule_epoch": 1.0, "argv": []}


def run_tiny(cell_name: str, seed: int, *, fault=None, extra_argv=(), control=False):
    manifest = harness.load_manifest()
    loaded = copy.deepcopy(harness.load_cell(manifest, cell_name))
    argv = loaded["config"]["argv"]
    argv[argv.index("--num_cols") + 1] = "65536"
    argv[argv.index("--k") + 1] = "5000"
    loaded["config"]["reference_block"] = 2
    loaded["traffic"] = dict(TRAFFIC, argv=loaded["traffic"]["argv"])
    return harness.run_cell(
        cell_name, seed, 0.1, False, require_tpu=False, manifest=manifest, loaded=loaded,
        limits=check.load_limits(cell_name), fault=fault, extra_argv=extra_argv,
        control=control, warm_rounds=1, min_rounds=2, log=lambda *a: None)


def run_tiny_gpt2(seed: int, *, fault=None, extra_argv=(), limits=None, control=False):
    """The GPT-2 builder at a toy width (the trainer's GPT2LMHead takes any):
    2 layers of 64, 517 tokens; the cell's own flags with a smaller sketch."""
    import json
    import os

    from benchmark import counting

    with open(os.path.join(harness.HERE, "configs",
                           "gpt2_small_personachat_fetchsgd.json")) as f:
        config = json.load(f)
    config["model"].update(vocab_size=517, n_positions=64, n_embd=64, n_layer=2, n_head=2)
    config["input"].update(vocab=512, seq_len=32, persona_pool=32, context_tokens=20,
                           speaker1_id=515, speaker2_id=516)
    config["expect_d"], config["min_d"] = counting.gpt2_params(517, 64, 64, 2), 0
    config["reference_block"] = 2
    argv = config["argv"]
    argv[argv.index("--num_cols") + 1] = "4096"
    argv[argv.index("--k") + 1] = "500"
    traffic = {"num_clients": 16, "cohort": 4, "examples_per_client": 2,
               "schedule_epoch": 0.5, "argv": []}
    entry = {"name": "gpt2s_sketch_w8", "config": config["name"], "traffic": "sketch_w8",
             "chips": 1}
    return harness.run_cell(
        "gpt2s_sketch_w8", seed, 0.1, False, require_tpu=False,
        manifest=harness.load_manifest(),
        loaded={"entry": entry, "config": config, "traffic": traffic},
        limits=limits or check.load_limits("gpt2s_sketch_w8"), fault=fault,
        extra_argv=extra_argv, control=control, warm_rounds=1, min_rounds=2,
        log=lambda *a: None)
