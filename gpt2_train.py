#!/usr/bin/env python
"""GPT-2 PersonaChat federated fine-tuning CLI (SURVEY.md L6 / §3.2:
reference `gpt2_train.py` — same skeleton as cv_train with the FedPersona
dataset, GPT-2 LM loss, and validation NLL -> PPL).

Example (paper config #4):
    python gpt2_train.py --mode sketch --num_clients 17500 --num_workers 4 \
        --k 50000 --num_cols 1000000 --num_rows 5 --num_blocks 20
Smoke test:
    python gpt2_train.py --model_size tiny --num_clients 50 --num_workers 4 \
        --num_rounds 10 --mode uncompressed
Tensor parallel (2-D mesh: clients x model):
    python gpt2_train.py --model_size small --model_parallel 4 ...
"""

from __future__ import annotations

import dataclasses
import math
import sys

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree

from commefficient_tpu import obs
from commefficient_tpu.data.personachat import load_personachat_fed
from commefficient_tpu.federated.api import FederatedSession, FedModel, FedOptimizer
from commefficient_tpu.models.gpt2 import SMALL, TINY, GPT2LMHead
from commefficient_tpu.models.losses import make_lm_loss
from commefficient_tpu.parallel import mesh as meshlib, tp
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
    if args.mc_coef > 0 and args.num_candidates < 2:
        raise SystemExit(
            "--mc_coef > 0 needs --num_candidates >= 2 (the MC head scores "
            "a gold reply against at least one distractor)"
        )
    if args.mc_coef > 0 and args.moe_experts > 0:
        raise SystemExit("--mc_coef with --moe_experts is not supported yet")
    num_candidates = args.num_candidates if args.mc_coef > 0 else 1
    train_set, valid_set, tok = load_personachat_fed(
        args.data_root, args.num_clients, args.seq_len, args.seed,
        num_candidates=num_candidates,
        mc_hard_negatives=args.mc_hard_negatives,
    )
    args.num_clients = train_set.num_clients
    model_metrics, net_state = False, {}
    if args.model_config:
        if (args.init_from or args.moe_experts > 0 or args.mc_coef > 0
                or args.model_parallel > 1 or args.attn_impl != "dense"
                or args.eval_f1 > 0 or args.dtype != "float32"):
            raise SystemExit(
                "--model_config builds its own float32 model: it goes with none "
                "of --init_from, --moe_experts, --mc_coef, --model_parallel, "
                "--attn_impl ring, --eval_f1, --dtype bfloat16")
        import json

        from commefficient_tpu.federated.engine import READ_ONLY_COLLECTIONS
        from commefficient_tpu.models import from_model_block

        with open(args.model_config) as f:
            block = json.load(f)["model"]
        try:
            cfg, model = from_model_block(block)
        except ValueError as e:
            raise SystemExit(f"--model_config {args.model_config}: {e}") from None
        if tok.vocab_size > cfg.vocab_size:
            raise SystemExit(
                f"the tokenizer's {tok.vocab_size} ids do not fit the "
                f"configuration's vocabulary of {cfg.vocab_size}")
        ids0 = jnp.zeros((1, args.seq_len), dtype=jnp.int32)
        variables = model.init(jax.random.PRNGKey(args.seed), ids0, train=False)
        params = variables["params"]
        # what the model reads beside its parameters (a router's selection
        # bias) is the session's net_state: outside d, and no round changes it
        net_state = {k: variables[k] for k in READ_ONLY_COLLECTIONS if k in variables}
        model_metrics = True  # the expert layers' counters
        init_note = f"  model_config={args.model_config}"
    elif args.init_from:
        if args.moe_experts > 0:
            raise SystemExit(
                "--moe_experts with --init_from is not supported: HF GPT-2 "
                "checkpoints carry no expert weights"
            )
        # pretrained HF GPT-2 (SURVEY.md §2 Models: the reference fine-tunes
        # HF GPT-2-small); wte grows to cover the dialog special tokens
        from commefficient_tpu.models.gpt2_loader import load_hf_gpt2

        params, cfg = load_hf_gpt2(
            args.init_from, target_vocab_size=tok.vocab_size,
            n_positions=max(args.seq_len, 1),
        )
        cfg = dataclasses.replace(
            cfg, attn_impl=args.attn_impl, with_mc_head=args.mc_coef > 0,
            dtype=args.dtype,
        )
        model = GPT2LMHead(cfg)
        if cfg.with_mc_head:
            # the HF checkpoint has no MC head; initialize it fresh
            params = dict(params)
            params["mc_head"] = 0.02 * jax.random.normal(
                jax.random.PRNGKey(args.seed), (cfg.n_embd,), jnp.float32
            )
        # structural sanity: loaded tree must match what init would build
        # (eval_shape: shapes/structure only, no allocation of a second tree)
        ids0 = jnp.zeros((1, args.seq_len), dtype=jnp.int32)
        ref = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), ids0, train=False)
        )["params"]
        if jax.tree.structure(ref) != jax.tree.structure(params) or any(
            a.shape != b.shape for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(params))
        ):
            raise ValueError(f"checkpoint {args.init_from} does not match the model tree")
        init_note = f"  init_from={args.init_from}"
    else:
        base = TINY if args.model_size == "tiny" else SMALL
        cfg = dataclasses.replace(
            base, vocab_size=tok.vocab_size, n_positions=max(args.seq_len, 1),
            attn_impl=args.attn_impl, with_mc_head=args.mc_coef > 0,
            dtype=args.dtype, moe_experts=args.moe_experts,
        )
        model = GPT2LMHead(cfg)
        ids0 = jnp.zeros((1, args.seq_len), dtype=jnp.int32)
        params = model.init(jax.random.PRNGKey(args.seed), ids0, train=False)["params"]
        init_note = ""
    d = ravel_pytree(params)[0].size
    named = type(model).__name__ if args.model_config else f"GPT2({args.model_size})"
    print(f"model: {named}  d={d:,}  vocab={cfg.vocab_size}  "
          f"clients={train_set.num_clients}  mode={args.mode}{init_note}", flush=True)
    if args.model_config and hasattr(cfg, "gdn_chunk"):
        # Qwen3-Next's Gated DeltaNet mixers: what inverts their chunk matrices
        from commefficient_tpu.ops.gated_delta import inverse_implementation

        print(f"delta-rule inverse: {inverse_implementation(cfg.gdn_chunk)}", flush=True)

    if args.attn_impl == "ring" and args.seq_parallel <= 1:
        raise SystemExit(
            "--attn_impl ring needs --seq_parallel > 1: without a 'seq' mesh "
            "axis the model silently runs dense attention, which defeats the "
            "point of asking for ring (the math is identical; the memory/"
            "scaling behavior is not)"
        )
    mesh = None
    if args.mesh:
        mesh = meshlib.make_mesh_from_spec(
            args.mesh,
            model_parallel=args.model_parallel,
            seq_parallel=args.seq_parallel,
        )
        if args.model_parallel > 1:
            params = tp.shard_params(mesh, params)
    elif args.model_parallel > 1 or args.seq_parallel > 1:
        mesh = meshlib.make_mesh(
            args.num_devices or None,
            model_parallel=args.model_parallel,
            seq_parallel=args.seq_parallel,
        )
        if args.model_parallel > 1:
            params = tp.shard_params(mesh, params)
    elif jax.device_count() > 1:
        mesh = meshlib.make_mesh(args.num_devices or None)
    if mesh is not None:
        from commefficient_tpu.parallel.distributed import mesh_info

        print(f"mesh: {mesh_info(mesh)}", flush=True)

    if args.mc_coef > 0:
        from commefficient_tpu.models.losses import make_lm_mc_loss

        train_loss = make_lm_mc_loss(model, True, args.mc_coef, tok.pad_id)
        eval_loss = make_lm_mc_loss(model, False, args.mc_coef, tok.pad_id)
    else:
        aux = args.moe_aux_coef if args.moe_experts > 0 else 0.0
        train_loss = make_lm_loss(model, train=True, moe_aux_coef=aux,
                                  model_metrics=model_metrics)
        eval_loss = make_lm_loss(model, train=False, moe_aux_coef=aux)
    mode_cfg = mode_config_from_args(args, d)
    if mode_cfg.mode == "sketch":
        # resolved here, outside any trace: a kernel that does not compile
        # on this TPU raises at start-up, and an oracle run says why
        print(f"sketch: {csvec.describe_impl(mode_cfg.sketch_spec)}",
              flush=True)
    session = FederatedSession(
        train_loss_fn=train_loss,
        eval_loss_fn=eval_loss,
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
        # sketch-health estimators + ledger fingerprints: read-only
        # in-program observability (armed == unarmed bit-for-bit)
        health_every=getattr(args, "health_every", 0),
        ledger_fingerprint=bool(getattr(args, "ledger", "")),
        # a checkpoint dir arms the watchdog's mid-round emergency save,
        # which needs the live (non-donated) server state readable; the
        # opt-out keeps donation for HBM-tight runs
        donate_state=not (args.checkpoint_dir
                          and not args.no_emergency_checkpoint),
    )
    if args.attn_impl == "ring" and session.mesh is None:
        raise SystemExit(
            "--attn_impl ring: the session has no seq mesh, which would "
            "silently degrade ring attention to dense; check --seq_parallel "
            "and the device count"
        )
    print(f"cohort backward: {session.cohort_backward}", flush=True)
    print(f"approx top-k partial maxima: {session.topk_partial_maxima}",
          flush=True)
    return session, valid_set, {"model": model, "tok": tok}


def make_f1_eval(args, model, tok, valid_set):
    """Generation/F1 evaluator for --eval_f1 (SURVEY.md §2: the reference
    lineage's "F1/sampling" eval half; PPL is the other). Decodes the first
    --eval_f1 validation dialogs from their packed prompts (reply region
    blanked to <pad>) and scores ConvAI2 word-F1 vs the gold replies.
    Returns eval(params, rnd) -> mean F1."""
    import numpy as np

    from commefficient_tpu.models.generate import (
        decode_reply, make_generate, word_f1,
    )

    ids, types, labels = (np.asarray(a) for a in valid_set.decode_examples(args.eval_f1))
    labelled = labels != -100
    keep = labelled.any(axis=1)  # drop label-less rows (fully-truncated packs)
    if not keep.any():
        raise SystemExit(
            f"--eval_f1 {args.eval_f1}: none of the sampled validation packs "
            f"carry a reply at --seq_len {args.seq_len} (all labels "
            "truncated); raise --seq_len or --eval_f1"
        )
    ids, types, labels, labelled = ids[keep], types[keep], labels[keep], labelled[keep]
    prompt_len = labelled.argmax(axis=1).astype(np.int32)
    golds = [
        tok.decode([t for t in row[m] if t != tok.eos_id])
        for row, m in zip(labels, labelled)
    ]
    # blank the gold reply out of the conditioning buffers
    tail = np.arange(ids.shape[1])[None] >= prompt_len[:, None]
    p_ids = jnp.asarray(np.where(tail, tok.pad_id, ids))
    p_types = jnp.asarray(np.where(tail, tok.pad_id, types))
    plen = jnp.asarray(prompt_len)
    generate = make_generate(
        model, eos_id=tok.eos_id, pad_id=tok.pad_id,
        reply_type_id=tok.speaker2_id, max_new=args.decode_max_new,
        temperature=args.decode_temperature, top_p=args.decode_top_p,
    )

    def evaluate(params, rnd: int) -> float:
        out, lengths = generate(
            params, p_ids, p_types, plen, jax.random.PRNGKey(10_000 + rnd)
        )
        out, lengths = np.asarray(out), np.asarray(lengths)
        preds = [
            decode_reply(tok, row, int(p), int(ln))
            for row, p, ln in zip(out, prompt_len, lengths)
        ]
        return float(np.mean([word_f1(p, g) for p, g in zip(preds, golds)]))

    return evaluate


def main(argv=None):
    args = resolve_defaults(make_parser("gpt2").parse_args(argv))
    # arm (or disarm) the obs tracer before anything emits — a traced run
    # is pinned bit-identical to an untraced one (tests/test_obs.py)
    obs.configure_from_args(args)
    fault_plan = FaultPlan.parse(args.fault_plan)
    retry_policy = RetryPolicy(max_retries=args.max_retries)
    from commefficient_tpu.parallel import distributed
    if distributed.initialize_from_args(args, fault_plan=fault_plan,
                                        retry_policy=retry_policy):
        print(f"multihost: {distributed.process_info()}", flush=True)
    session, valid_set, extras = build(args, fault_plan, retry_policy)
    f1_eval = (
        make_f1_eval(args, extras["model"], extras["tok"], valid_set)
        if args.eval_f1 > 0 else None
    )

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
    opt = FedOptimizer(triangular(args.lr_scale, args.pivot_epoch, args.num_epochs),
                       rounds_per_epoch)
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
        train_nll = totals.get("loss_sum", 0.0) / max(totals.get("count", 0.0), 1)
        val_nll = ev["loss_sum"] / max(ev["count"], 1)
        row = {
            "round": rnd,
            "epoch": rnd / rounds_per_epoch,
            "lr": m["lr"],
            "train_nll": train_nll,
            "train_ppl": math.exp(min(train_nll, 20)),
            "val_nll": val_nll,
            "val_ppl": math.exp(min(val_nll, 20)),
            # measured cumulative wire-cost (checkpointed/restored by the
            # session, so resumed runs stay exact under dropout)
            "comm_mb": session.comm_mb_total,
            "time_s": time_s,
            # always present: TableLogger freezes its columns on the
            # first row, so a count first added mid-run would never
            # reach the stdout table an operator actually watches
            "nonfinite_rounds": nonfinite_total,
        }
        if args.mc_coef > 0:
            row["mc_acc"] = totals.get("mc_correct", 0.0) / max(totals.get("mc_count", 0.0), 1)
            row["val_mc_acc"] = ev.get("mc_correct", 0.0) / max(ev.get("mc_count", 0.0), 1)
        if f1_eval is not None:
            row["val_f1"] = f1_eval(model.params, rnd)
        return row

    # --health_every / --slo / --ledger: attached AFTER restore so the
    # ledger's resume truncation keys off the restored round
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
            RunnerConfig.from_args(
                args, total_rounds, args.eval_every or min(rounds_per_epoch, 200)),
            eval_fn=lambda: model.eval(valid_set, args.eval_batch_size),
            build_row=build_row,
            logger=logger,
            source=service.source() if service is not None else None,
            slo=wiring.slo_engine,
            postmortem=wiring.postmortem,
        )
    except Exception as e:
        # unhandled-exception postmortem (abort/exit-75 bundles are
        # written inside run_loop, which this handler can't reach)
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
