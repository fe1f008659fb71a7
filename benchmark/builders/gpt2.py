"""GPT-2 cells. gpt2_train.build cannot make the published model on a machine
with no network (its tokenizer falls back to 261 byte tokens, and it sizes the
position table to the sequence), so this builder hands the same
FederatedSession and run_loop a GPT2LMHead at the configuration's vocabulary
and position table, with the language-model loss the trainer uses, over the
benchmark's own weights and persona-grouped token federation."""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from benchmark import counting, federation
from benchmark.builders import common
from benchmark.reference import gpt2 as ref_model


def _dialogues(fed: dict, inp: dict) -> dict:
    """Speaker segments and labels over the generated token ids: the first
    `context` tokens of a sequence are persona and history (speaker 1, not
    predicted), the rest the reply (speaker 2, predicted)."""
    ids = fed["arrays"]["input_ids"]
    n, t = ids.shape
    ctx = int(inp["context_tokens"])
    reply = np.arange(t)[None, :] >= ctx
    types = np.where(reply, inp["speaker2_id"], inp["speaker1_id"]).astype(np.int32)
    types = np.broadcast_to(types, (n, t)).copy()
    labels = np.where(reply, ids, -100).astype(np.int32)
    return {"input_ids": ids, "token_type_ids": types, "labels": labels}


def build(config: dict, traffic: dict, seed: int, extra_argv=()) -> common.Cell:
    from commefficient_tpu.data.personachat import FedTextDataset
    from commefficient_tpu.models.gpt2 import GPT2Config, GPT2LMHead
    from commefficient_tpu.models.losses import make_lm_loss

    m, inp = config["model"], config["input"]
    cohort, clients = int(traffic["cohort"]), int(traffic["num_clients"])
    per = int(traffic["examples_per_client"])
    args = common.trainer_args("gpt2", config, traffic,
                               list(extra_argv) + ["--seq_len", str(inp["seq_len"])])

    fed = federation.generate(inp, traffic, seed)
    fed["arrays"] = _dialogues(fed, inp)
    a = fed["arrays"]
    train_set = FedTextDataset(a["input_ids"], a["token_type_ids"], a["labels"],
                               list(fed["shards"]))

    shapes = ref_model.param_shapes(m["vocab_size"], m["n_positions"], m["n_embd"], m["n_layer"])
    params = jax.jit(functools.partial(ref_model.init_params, shapes=shapes,
                                       n_layer=m["n_layer"]))(jax.random.PRNGKey(seed % 2**32))
    cfg = GPT2Config(vocab_size=m["vocab_size"], n_positions=m["n_positions"],
                     n_embd=m["n_embd"], n_layer=m["n_layer"], n_head=m["n_head"],
                     dropout=0.0, dtype=args.dtype, attn_impl=args.attn_impl)
    model = GPT2LMHead(cfg)
    want = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, inp["seq_len"]), jnp.int32), train=False))["params"]
    if jax.tree.map(lambda x: x.shape, params) != jax.tree.map(lambda x: x.shape, want):
        raise SystemExit("the configuration's GPT-2 and the trainer's differ in shape")

    session, mode_cfg, sketch_line = common.make_session(
        args, train_loss=make_lm_loss(model, train=True),
        eval_loss=make_lm_loss(model, train=False), params=params, net_state={},
        train_set=train_set, sampling_seed=seed % 2**32)
    d = mode_cfg.d
    if d != int(config["expect_d"]) or d < int(config.get("min_d", 0)):
        raise SystemExit(f"d={d:,}; the configuration states {config['expect_d']:,} "
                         f"(not under {config.get('min_d', 0):,})")
    opt, rpe, start = common.schedule(args, clients, cohort, traffic["schedule_epoch"])

    tokens = cohort * per * inp["seq_len"]
    facts, recipe = common.facts_and_recipe(
        mode_cfg, args, traffic, sketch_line,
        tokens * counting.gpt2_train_flops_per_token(
            m["vocab_size"], m["n_embd"], m["n_layer"], inp["seq_len"]))
    facts["tokens_per_round"] = tokens
    return common.Cell(
        session=session, opt=opt, args=args, cohort=cohort, facts=facts,
        client_loss=functools.partial(ref_model.client_loss, n_head=m["n_head"]),
        params0=jax.device_get(params), federation=fed,
        to_reference_batch=lambda rows: {k: jnp.asarray(v) for k, v in rows.items()},
        recipe=recipe, reference_block=int(config.get("reference_block", 1)),
        lr_at=common.plain_schedule(args, rpe),
        start_position=start)
