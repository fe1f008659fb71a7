#!/usr/bin/env python
"""CV federated training CLI (SURVEY.md L6: reference `cv_train.py` —
CIFAR-10/100 + FEMNIST experiment driver, same flag surface, dispatching to
the TPU engine instead of worker processes).

Example (paper config #2, SURVEY.md §6):
    python cv_train.py --dataset cifar10 --mode sketch --num_clients 10000 \
        --num_workers 100 --k 50000 --num_rows 5 --num_cols 500000 \
        --num_epochs 24 --lr_scale 0.4 --pivot_epoch 5
Smoke test (BASELINE config #1):
    python cv_train.py --dataset cifar10 --mode uncompressed --num_clients 10 \
        --num_workers 2 --num_rounds 20
"""

from __future__ import annotations

import math
import sys

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree

from commefficient_tpu import obs
from commefficient_tpu.data.cifar import load_cifar_fed
from commefficient_tpu.data.femnist import load_femnist_fed
from commefficient_tpu.federated.api import FederatedSession, FedModel, FedOptimizer
from commefficient_tpu.models.femnist_cnn import FEMNISTCNN
from commefficient_tpu.models.losses import make_classification_loss
from commefficient_tpu.models.resnet9 import ResNet9
from commefficient_tpu.parallel import mesh as meshlib
from commefficient_tpu.resilience import FaultPlan, RetryPolicy
from commefficient_tpu.runner import RunnerConfig, run_loop
from commefficient_tpu.serve.service import service_from_args
from commefficient_tpu.sketch import csvec
from commefficient_tpu.utils import checkpoint as ckpt
from commefficient_tpu.utils.config import make_parser, mode_config_from_args, resolve_defaults
from commefficient_tpu.utils.logging import TableLogger
from commefficient_tpu.utils.schedules import triangular


def build(args, fault_plan=None, retry_policy=None):
    # direct callers (tests) pass args only; main() parses once and shares
    # the SAME plan with distributed init and checkpoint IO so per-site
    # injection counters stay coherent across the whole run
    if fault_plan is None:
        fault_plan = FaultPlan.parse(args.fault_plan)
    if retry_policy is None:
        retry_policy = RetryPolicy(max_retries=args.max_retries)
    if args.dataset == "femnist":
        train_set, test_set, num_classes = load_femnist_fed(
            args.data_root, args.num_clients, args.seed
        )
        model = FEMNISTCNN(num_classes=num_classes, dtype=args.dtype)
        sample_shape = (1, 28, 28, 1)
    else:
        train_set, test_set, num_classes = load_cifar_fed(
            args.dataset, args.num_clients, args.iid, args.data_root, args.seed,
            synthetic_separation=args.synthetic_separation,
            synthetic_train=args.synthetic_train,
        )
        model = ResNet9(num_classes=num_classes, dtype=args.dtype)
        sample_shape = (1, 32, 32, 3)
    args.num_clients = train_set.num_clients  # actual shard count

    variables = model.init(jax.random.PRNGKey(args.seed), jnp.zeros(sample_shape), train=False)
    params = variables["params"]
    net_state = {k: v for k, v in variables.items() if k != "params"}
    d = ravel_pytree(params)[0].size
    print(f"model: {type(model).__name__}  d={d:,}  clients={train_set.num_clients}  "
          f"mode={args.mode}", flush=True)

    mode_cfg = mode_config_from_args(args, d)
    if args.mesh:
        mesh = meshlib.make_mesh_from_spec(args.mesh)
    elif jax.device_count() > 1:
        mesh = meshlib.make_mesh(args.num_devices or None)
    else:
        mesh = None
    if mesh is not None:
        from commefficient_tpu.parallel.distributed import mesh_info

        print(f"mesh: {mesh_info(mesh)}", flush=True)
    if mode_cfg.mode == "sketch":
        # resolved here, outside any trace: a kernel that does not compile
        # on this TPU raises at start-up, and an oracle run says why
        print(f"sketch: {csvec.describe_impl(mode_cfg.sketch_spec)}",
              flush=True)
    session = FederatedSession(
        train_loss_fn=make_classification_loss(model, train=True),
        eval_loss_fn=make_classification_loss(model, train=False),
        params=params,
        net_state=net_state,
        mode_cfg=mode_cfg,
        train_set=train_set,
        num_workers=args.num_workers,
        local_batch_size=args.local_batch_size,
        weight_decay=args.weight_decay,
        seed=args.seed,
        mesh=mesh,
        dp_clip=args.dp_clip,
        dp_noise=args.dp_noise,
        client_dropout=args.client_dropout,
        client_update_clip=args.client_update_clip,
        quarantine_window=args.quarantine_window,
        quarantine_scope=args.quarantine_scope,
        # Byzantine-robust table merge (trimmed/median run the per-client-
        # table round; trim=0 trimmed IS sum, bit-identically);
        # --robust_residual on arms the error-feedback-aware residual
        merge_policy=args.merge_policy,
        merge_trim=args.merge_trim,
        robust_residual=getattr(args, "robust_residual", "off") == "on",
        requeue_policy=args.requeue_policy,
        sketch_path=args.sketch_path,
        # --serve_payload sketch inverts the round into the two-program
        # wire shape (client tables + table merge) the service round-trips
        wire_payloads=(getattr(args, "serve", "off") != "off"
                       and args.serve_payload == "sketch"),
        # --serve_async: size the stale-fold merge variant to one cohort's
        # worth of late tables (the buffer trigger bounds how many can
        # straggle per round; the band bounds how long they stay foldable)
        stale_slots=(args.num_workers
                     if getattr(args, "serve_async", False) else 0),
        # --serve_edges >= 2 (linear merge): compile the two-tier edge
        # merge variants (grouped flat twin + partials root). A robust
        # merge_policy runs the tree in FORWARD mode against the plain
        # robust program instead, so the session stays at 0 there.
        serve_edges=(getattr(args, "serve_edges", 0)
                     if args.merge_policy == "sum"
                     or (args.merge_policy == "trimmed"
                         and args.merge_trim == 0) else 0),
        client_chunk=args.client_chunk,
        on_nonfinite=args.on_nonfinite,
        fault_plan=fault_plan,
        retry_policy=retry_policy,
        # sketch-health estimators compiled into the round program at the
        # --health_every cadence; --ledger adds per-round state
        # fingerprints (both read-only: armed == unarmed, bit-for-bit).
        health_every=getattr(args, "health_every", 0),
        ledger_fingerprint=bool(getattr(args, "ledger", "")),
        # a checkpoint dir arms the watchdog's mid-round emergency save,
        # which needs the live (non-donated) server state readable; the
        # opt-out keeps donation for HBM-tight runs
        donate_state=not (args.checkpoint_dir
                          and not args.no_emergency_checkpoint),
    )
    print(f"cohort backward: {session.cohort_backward}", flush=True)
    print(f"approx top-k partial maxima: {session.topk_partial_maxima}",
          flush=True)
    return session, test_set


def main(argv=None):
    args = resolve_defaults(make_parser("cv").parse_args(argv))
    # arm (or disarm) the obs tracer before anything emits — a traced run
    # is pinned bit-identical to an untraced one (tests/test_obs.py)
    obs.configure_from_args(args)
    fault_plan = FaultPlan.parse(args.fault_plan)
    retry_policy = RetryPolicy(max_retries=args.max_retries)
    from commefficient_tpu.parallel import distributed
    if distributed.initialize_from_args(args, fault_plan=fault_plan,
                                        retry_policy=retry_policy):
        print(f"multihost: {distributed.process_info()}", flush=True)
    session, test_set = build(args, fault_plan, retry_policy)

    rounds_per_epoch = max(1, math.ceil(args.num_clients / session.num_workers))
    total_rounds = args.num_rounds or int(args.num_epochs * rounds_per_epoch)
    if fault_plan is not None:
        # launch-time schedule check: a client_* site at round >=
        # total_rounds could never fire (a vacuous chaos run); likewise a
        # wire_* site on a run with no payload seam to inject at
        fault_plan.validate_rounds(total_rounds)
        fault_plan.validate_wire_context(
            args.serve != "off" and args.serve_payload == "sketch")
        fault_plan.validate_stale_context(
            args.serve != "off" and args.serve_payload == "sketch"
            and getattr(args, "serve_async", False))
        fault_plan.validate_edge_context(
            args.serve != "off" and args.serve_payload == "sketch"
            and getattr(args, "serve_edges", 0) >= 2,
            getattr(args, "serve_edges", 0))
        fault_plan.validate_shard_context(
            args.serve == "socket"
            and getattr(args, "serve_shards", 0) >= 2
            and getattr(args, "serve_shard_mode", "thread") == "process",
            getattr(args, "serve_shards", 0))
    schedule = triangular(args.lr_scale, args.pivot_epoch, args.num_epochs)
    opt = FedOptimizer(schedule, rounds_per_epoch)
    model = FedModel(session)

    if args.resume and args.checkpoint_dir:
        # newest VERIFIED checkpoint; falls back loudly past damaged ones
        path = ckpt.restore_latest(args.checkpoint_dir, session)
        if path:
            opt.round = session.round
            print(f"resumed from {path} at round {session.round}", flush=True)

    if args.profile_dir and not args.profile_rounds:
        # whole-run profiler capture; with --profile_rounds the runner owns
        # a start/stop window around the named rounds instead
        jax.profiler.start_trace(args.profile_dir)

    logger = TableLogger(args.log_jsonl or None)

    def build_row(rnd, m, totals, ev, time_s, nonfinite_total):
        return {
            "round": rnd,
            "epoch": rnd / rounds_per_epoch,
            "lr": m["lr"],
            "train_loss": totals.get("loss_sum", 0.0) / max(totals.get("count", 0.0), 1),
            "train_acc": totals.get("correct", 0.0) / max(totals.get("count", 0.0), 1),
            "test_loss": ev["loss_sum"] / max(ev["count"], 1),
            "test_acc": ev["correct"] / max(ev["count"], 1),
            # measured cumulative wire-cost (checkpointed/restored by
            # the session, so resumed runs stay exact under dropout)
            "comm_mb": session.comm_mb_total,
            "time_s": time_s,
            # always present: TableLogger freezes its columns on the
            # first row, so a count first added mid-run would never
            # reach the stdout table an operator actually watches
            "nonfinite_rounds": nonfinite_total,
        }

    # --health_every / --slo / --ledger: sketch-health monitor, SLO
    # engine, durable round ledger + postmortem bundle — attached AFTER
    # restore so the ledger's resume truncation keys off the restored
    # round (one gap-free, duplicate-free file across preemptions)
    wiring = obs.attach_from_args(args, session)

    # --serve: the streaming aggregation service drives the loop from its
    # push arrival stream (built AFTER restore so a resumed service picks
    # up the persisted pending-submission queue)
    service = service_from_args(args, session)

    # the shared harness owns the loop: block planning, async prefetch /
    # deferred metrics / overlapped checkpoint writes (or the --sync_loop
    # serial path), watchdog escalation, preemption, non-finite halt
    try:
        run_loop(
            session, opt,
            RunnerConfig.from_args(args, total_rounds, args.eval_every or rounds_per_epoch),
            eval_fn=lambda: model.eval(test_set, args.eval_batch_size),
            build_row=build_row,
            logger=logger,
            source=service.source() if service is not None else None,
            slo=wiring.slo_engine,
            postmortem=wiring.postmortem,
        )
    except Exception as e:
        # unhandled-exception postmortem (the watchdog-abort and exit-75
        # bundles are written inside run_loop, where os._exit/sys.exit
        # would skip or outrun this handler)
        if wiring.postmortem is not None:
            wiring.postmortem(f"exception:{type(e).__name__}: {e}")
        raise
    finally:
        wiring.close()
        if service is not None:
            print(f"serve: final metrics {service.metrics_snapshot()}",
                  flush=True)
            service.close()
        # flush the Chrome trace even on the preemption/halt exit paths
        # (sys.exit raises through here): a truncated run with no trace
        # would be useless exactly when the trace matters most
        obs.flush_trace()

    if args.profile_dir and not args.profile_rounds:
        jax.profiler.stop_trace()
    return session


if __name__ == "__main__":
    from commefficient_tpu.utils.compile_cache import ensure_compile_cache

    ensure_compile_cache()
    main(sys.argv[1:])
