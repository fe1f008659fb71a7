"""What every family's builder shares: a built cell, and the session made
with the trainers' own keyword arguments (cv_train.build / gpt2_train.build
pass the same ones; the benchmark passes its own weights, federation and
sampling seed, and leaves the sketch's hash seed at the trainer's)."""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable


@dataclasses.dataclass
class Cell:
    session: Any
    opt: Any  # FedOptimizer, placed where the window sits in the schedule
    args: Any  # the trainer's parsed flags
    cohort: int
    facts: dict  # d, mode, rows, cols, k, train_flops_per_round, ...
    # for the reference: nothing below is made by the program
    client_loss: Callable  # plain loss of one client (reference package)
    params0: Any  # the seeded weights, on the host
    federation: dict
    to_reference_batch: Callable  # cohort_rows -> batch pytree of the plain loss
    recipe: Any  # reference.rounds.Recipe
    reference_block: int
    lr_at: Callable[[int], float]  # plain schedule, by schedule position
    start_position: int


def trainer_args(kind: str, config: dict, traffic: dict, extra_argv=()):
    """The trainer's parsed flags for a cell: the configuration's, then the
    traffic's, then the federation's shape."""
    from commefficient_tpu.utils.config import make_parser, resolve_defaults

    argv = (list(config["argv"]) + list(traffic.get("argv", [])) + list(extra_argv)
            + ["--num_clients", str(traffic["num_clients"]),
               "--num_workers", str(traffic["cohort"]),
               "--local_batch_size", str(traffic["examples_per_client"])])
    return resolve_defaults(make_parser(kind).parse_args(argv))


def make_session(args, *, train_loss, eval_loss, params, net_state, train_set,
                 sampling_seed: int):
    """FederatedSession with the keyword arguments the trainers pass for a
    run with no serving, no faults and no checkpoint directory."""
    from commefficient_tpu.federated.api import FederatedSession
    from commefficient_tpu.sketch import csvec
    from commefficient_tpu.utils.config import mode_config_from_args
    from jax.flatten_util import ravel_pytree

    d = int(ravel_pytree(params)[0].size)
    mode_cfg = mode_config_from_args(args, d)
    sketch_line = None
    if mode_cfg.mode == "sketch":
        sketch_line = csvec.describe_impl(mode_cfg.sketch_spec)
    session = FederatedSession(
        train_loss_fn=train_loss, eval_loss_fn=eval_loss, params=params,
        net_state=net_state, mode_cfg=mode_cfg, train_set=train_set,
        num_workers=args.num_workers, local_batch_size=args.local_batch_size,
        weight_decay=args.weight_decay, seed=sampling_seed, mesh=None,
        dp_clip=args.dp_clip, dp_noise=args.dp_noise,
        client_dropout=args.client_dropout,
        client_update_clip=args.client_update_clip,
        quarantine_window=args.quarantine_window,
        quarantine_scope=args.quarantine_scope,
        merge_policy=args.merge_policy, merge_trim=args.merge_trim,
        requeue_policy=args.requeue_policy, sketch_path=args.sketch_path,
        split_compile=args.split_compile, client_chunk=args.client_chunk,
        on_nonfinite=args.on_nonfinite, donate_state=True,
    )
    return session, mode_cfg, sketch_line


def schedule(args, num_clients: int, cohort: int, schedule_epoch: float):
    """(FedOptimizer at the window's place in the schedule, rounds an epoch,
    start position). The window stands for the middle of a study, so the
    schedule starts at `schedule_epoch`, as a resumed run's would."""
    from commefficient_tpu.federated.api import FedOptimizer
    from commefficient_tpu.utils.schedules import triangular

    rounds_per_epoch = max(1, math.ceil(num_clients / cohort))
    opt = FedOptimizer(triangular(args.lr_scale, args.pivot_epoch, args.num_epochs),
                       rounds_per_epoch)
    start = int(round(schedule_epoch * rounds_per_epoch))
    opt.round = start
    return opt, rounds_per_epoch, start


def facts_and_recipe(mode_cfg, args, traffic: dict, sketch_line, train_flops_per_round: float):
    """(what the per-layer readers are told about the cell, what the
    reference is told about the optimiser)."""
    from benchmark.reference import rounds

    cohort, per = int(traffic["cohort"]), int(traffic["examples_per_client"])
    facts = {"mode": mode_cfg.mode, "d": mode_cfg.d, "rows": mode_cfg.num_rows,
             "cols": mode_cfg.num_cols, "k": mode_cfg.k, "cohort": cohort,
             "examples_per_round": cohort * per,
             "train_flops_per_round": train_flops_per_round,
             "sketch_line": sketch_line, "dtype": args.dtype}
    recipe = rounds.Recipe(
        mode=mode_cfg.mode, d=mode_cfg.d, k=mode_cfg.k, rows=mode_cfg.num_rows,
        cols=mode_cfg.num_cols, hash_seed=mode_cfg.seed, momentum=mode_cfg.momentum,
        weight_decay=args.weight_decay)
    return facts, recipe


def plain_schedule(args, rounds_per_epoch: int):
    """The learning rate by schedule position, from the reference's own
    triangular schedule and the flags' values."""
    from benchmark.reference import fetchsgd

    return lambda pos: fetchsgd.triangular_lr(
        args.lr_scale, args.pivot_epoch, args.num_epochs, rounds_per_epoch, pos)
