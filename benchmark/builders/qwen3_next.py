"""Qwen3-Next cells: the trainer's own model (models/qwen3_next.Qwen3NextLM,
built from the configuration file's `model` block, as `gpt2_train.py
--model_config` builds it) handed to the same FederatedSession and run_loop as
the GPT-2 cells, with the language-model loss the trainer uses, over the
benchmark's own weights and token federation (every next token predicted:
labels are the ids themselves)."""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from benchmark import counting_qwen3next as counting, federation
from benchmark.builders import common
from benchmark.reference import fetchsgd, fetchsgd_blocked, qwen3_next as ref_model

# the reference's query of all d coordinates, a block of slabs at a time: the
# same numbers, and it fits the chip at this d (fetchsgd_blocked.py; PERF.md
# section 7 asks a `benchmark` PR to move the blocking into fetchsgd itself)
fetchsgd.CountSketch = fetchsgd_blocked.BlockedCountSketch


def build(config: dict, traffic: dict, seed: int, extra_argv=()) -> common.Cell:
    from commefficient_tpu.data.personachat import FedTextDataset
    from commefficient_tpu.models.losses import make_lm_loss
    from commefficient_tpu.models.qwen3_next import Qwen3NextConfig, Qwen3NextLM

    m, inp = config["model"], config["input"]
    differ = [k for k in m if k in config and config[k] != m[k]]
    if differ or inp["vocab"] != m["vocab_size"]:
        raise SystemExit(f"the configuration's model block and its top level differ: {differ}")
    cohort, clients = int(traffic["cohort"]), int(traffic["num_clients"])
    per = int(traffic["examples_per_client"])
    args = common.trainer_args("gpt2", config, traffic,
                               list(extra_argv) + ["--seq_len", str(inp["seq_len"])])

    fed = federation.generate(inp, traffic, seed)
    ids = fed["arrays"]["input_ids"]
    fed["arrays"] = {"input_ids": ids, "token_type_ids": np.zeros_like(ids), "labels": ids}
    a = fed["arrays"]
    train_set = FedTextDataset(a["input_ids"], a["token_type_ids"], a["labels"],
                               list(fed["shards"]))

    shapes = ref_model.param_shapes(m)
    params = jax.jit(functools.partial(ref_model.init_params, shapes=shapes))(
        jax.random.PRNGKey(seed % 2**32))
    model = Qwen3NextLM(Qwen3NextConfig.from_model_block(m))
    want = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, inp["seq_len"]), jnp.int32), train=False))["params"]
    if jax.tree.map(lambda x: x.shape, params) != jax.tree.map(lambda x: x.shape, want):
        raise SystemExit("the configuration's Qwen3-Next and the trainer's differ in shape")

    session, mode_cfg, sketch_line = common.make_session(
        args, train_loss=make_lm_loss(model, train=True, model_metrics=True),
        eval_loss=make_lm_loss(model, train=False), params=params, net_state={},
        train_set=train_set, sampling_seed=seed % 2**32)
    d = mode_cfg.d
    if d != int(config["expect_d"]) or d != counting.params(m):
        raise SystemExit(f"d={d:,}; the configuration states {config['expect_d']:,} "
                         f"and its shapes give {counting.params(m):,}")
    opt, rpe, start = common.schedule(args, clients, cohort, traffic["schedule_epoch"])

    tokens = cohort * per * inp["seq_len"]
    facts, recipe = common.facts_and_recipe(
        mode_cfg, args, traffic, sketch_line,
        tokens * counting.train_flops_per_token(m, inp["seq_len"]))
    facts["tokens_per_round"] = tokens
    return common.Cell(
        session=session, opt=opt, args=args, cohort=cohort, facts=facts,
        client_loss=functools.partial(ref_model.client_loss, model=m),
        params0=jax.device_get(params), federation=fed,
        to_reference_batch=lambda rows: {k: jnp.asarray(v) for k, v in rows.items()},
        recipe=recipe, reference_block=int(config.get("reference_block", 1)),
        lr_at=common.plain_schedule(args, rpe),
        start_position=start)
