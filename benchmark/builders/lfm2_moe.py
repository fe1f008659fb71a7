"""LFM2-24B-A2B cells: the trainer's own model (models/lfm2_moe.Lfm2MoeLM, built
from the configuration file's `model` block, as `gpt2_train.py --model_config`
builds it) handed to the same FederatedSession and run_loop as the other
language-model cells, with the language-model loss the trainer uses, over the
benchmark's own weights and token federation (every next token predicted:
labels are the ids themselves). The routers' bias is the session's net_state,
seeded here and handed to the reference beside the weights, as in the
GLM-4.7-Flash cells (builders/glm4_moe_lite.py, which this follows line for
line in what it checks).

The file's top level keeps the published `layer_types` and `num_dense_layers`
(the cut in depth is `num_hidden_layers`'); the model block's are what the
layers it keeps (`layers_kept`, by published index) had there."""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from benchmark import counting_lfm2_moe as counting, federation
from benchmark.builders import common
from benchmark.reference import fetchsgd_topk_blocked, lfm2_moe as ref_model


def build(config: dict, traffic: dict, seed: int, extra_argv=()) -> common.Cell:
    from commefficient_tpu.data.personachat import FedTextDataset
    from commefficient_tpu.models.lfm2_moe import Lfm2MoeConfig, Lfm2MoeLM
    from commefficient_tpu.models.losses import make_lm_loss

    # the reference's sketch and server step block by block, as in the GLM
    # cells: at d = 469M fetchsgd.py's own step needs about 13.8 GB beside the
    # reference's gradients. Installed when a cell is built and not at import,
    # so that whichever builder a process imported last, this cell's reference
    # is this one
    fetchsgd_topk_blocked.install()
    m, inp = config["model"], config["input"]
    kept = m["layers_kept"]
    cut = {"layer_types": [config["layer_types"][i] for i in kept],
           "num_dense_layers": sum(i < config["num_dense_layers"] for i in kept)}
    differ = [k for k in m if k in config and m[k] != cut.get(k, config[k])]
    if differ or len(kept) != m["num_hidden_layers"] or inp["vocab"] != m["vocab_size"]:
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

    key = jax.random.PRNGKey(seed % 2**32)
    params = jax.jit(functools.partial(ref_model.init_params, shapes=ref_model.param_shapes(m)))(key)
    buffers = ref_model.init_buffers(key, ref_model.buffer_shapes(m))
    model = Lfm2MoeLM(Lfm2MoeConfig.from_model_block(m))
    want = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, inp["seq_len"]), jnp.int32), train=False))
    shape = lambda tree: jax.tree.map(lambda x: x.shape, tree)  # noqa: E731
    if shape(params) != shape(want["params"]) or shape(buffers) != shape(want["buffers"]):
        raise SystemExit("the configuration's LFM2-24B-A2B and the trainer's differ in shape")

    session, mode_cfg, sketch_line = common.make_session(
        args, train_loss=make_lm_loss(model, train=True, model_metrics=True),
        eval_loss=make_lm_loss(model, train=False), params=params,
        net_state={"buffers": buffers}, train_set=train_set, sampling_seed=seed % 2**32)
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
        client_loss=functools.partial(ref_model.client_loss, model=m,
                                      buffers=jax.device_get(buffers)),
        params0=jax.device_get(params), federation=fed,
        to_reference_batch=lambda rows: {k: jnp.asarray(v) for k, v in rows.items()},
        recipe=recipe, reference_block=int(config.get("reference_block", 1)),
        lr_at=common.plain_schedule(args, rpe),
        start_position=start)
