"""ResNet-9 cells: the session cv_train.build would make, from the cell's
flags, over the benchmark's own weights and federation."""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from benchmark import counting, federation
from benchmark.builders import common
from benchmark.reference import resnet9 as ref_model


def build(config: dict, traffic: dict, seed: int, extra_argv=()) -> common.Cell:
    from commefficient_tpu.data.fed_dataset import FedDataset
    from commefficient_tpu.models.losses import make_classification_loss
    from commefficient_tpu.models.resnet9 import ResNet9

    model_cfg, inp = config["model"], config["input"]
    cohort, clients = int(traffic["cohort"]), int(traffic["num_clients"])
    per = int(traffic["examples_per_client"])
    args = common.trainer_args("cv", config, traffic, extra_argv)

    fed = federation.generate(inp, traffic, seed)
    train_set = FedDataset(fed["arrays"]["x"], fed["arrays"]["y"], list(fed["shards"]))

    shapes = ref_model.param_shapes(tuple(model_cfg["channels"]), inp["classes"],
                                    inp["shape"][2])
    params = jax.jit(functools.partial(ref_model.init_params, shapes=shapes))(
        jax.random.PRNGKey(seed % 2**32))
    model = ResNet9(num_classes=inp["classes"], dtype=args.dtype)
    want = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1,) + tuple(inp["shape"])), train=False))
    got = jax.tree.map(lambda a: a.shape, params)
    if got != jax.tree.map(lambda a: a.shape, want["params"]):
        raise SystemExit("the configuration's ResNet-9 and the trainer's differ in shape")
    net_state = {"batch_stats": jax.tree.map(
        lambda a: jnp.zeros(a.shape, a.dtype), want["batch_stats"])}
    net_state = jax.tree_util.tree_map_with_path(
        lambda p, a: a + 1.0 if p[-1].key == "var" else a, net_state)

    session, mode_cfg, sketch_line = common.make_session(
        args, train_loss=make_classification_loss(model, train=True),
        eval_loss=make_classification_loss(model, train=False),
        params=params, net_state=net_state, train_set=train_set,
        sampling_seed=seed % 2**32)
    d = mode_cfg.d
    if d != int(config["expect_d"]):
        raise SystemExit(f"d={d}, the configuration states {config['expect_d']}")
    opt, rpe, start = common.schedule(args, clients, cohort, traffic["schedule_epoch"])

    facts, recipe = common.facts_and_recipe(
        mode_cfg, args, traffic, sketch_line,
        cohort * per * counting.resnet9_train_flops_per_image(
            channels=tuple(model_cfg["channels"]), image=inp["shape"][0],
            in_ch=inp["shape"][2], num_classes=inp["classes"]))

    def client_loss(p, batch):
        mean, total = ref_model.client_loss(p, batch)
        return mean, total, jnp.float32(batch["y"].shape[0])

    return common.Cell(
        session=session, opt=opt, args=args, cohort=cohort, facts=facts,
        client_loss=client_loss, params0=jax.device_get(params), federation=fed,
        to_reference_batch=lambda rows: {"x": jnp.asarray(rows["x"]),
                                         "y": jnp.asarray(rows["y"])},
        recipe=recipe, reference_block=int(config.get("reference_block", 32)),
        lr_at=common.plain_schedule(args, rpe),
        start_position=start)
