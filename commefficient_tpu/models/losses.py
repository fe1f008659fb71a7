"""Loss-function adapters binding flax models to the engine's protocol
(engine.py: loss_fn(params, net_state, batch, rng) -> (loss, aux))."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def make_classification_loss(model, train: bool):
    """Masked softmax cross-entropy for image classifiers with BN state.

    batch = {"x": [B, H, W, C], "y": [B] int, "mask": [B] 0/1}. Metrics are
    sums (loss_sum, count, correct) so they aggregate across clients/batches.
    """

    def loss_fn(params, net_state, batch, rng):
        variables = {"params": params, **net_state}
        if train:
            logits, new_model_state = model.apply(
                variables, batch["x"], train=True, mutable=["batch_stats"]
            )
            new_net_state = dict(new_model_state)
        else:
            logits = model.apply(variables, batch["x"], train=False)
            new_net_state = net_state
        logp = jax.nn.log_softmax(logits)
        per_ex = -jnp.take_along_axis(logp, batch["y"][:, None], axis=1)[:, 0]
        mask = batch["mask"].astype(per_ex.dtype)
        count = jnp.maximum(mask.sum(), 1.0)
        loss = (per_ex * mask).sum() / count
        correct = ((logits.argmax(-1) == batch["y"]) * mask).sum()
        return loss, {
            "net_state": new_net_state,
            "metrics": {
                "loss_sum": (per_ex * mask).sum(),
                "count": mask.sum(),
                "correct": correct,
            },
        }

    return loss_fn


def make_lm_mc_loss(model, train: bool, mc_coef: float = 1.0, pad_id: int = 0):
    """Joint LM + next-utterance-classification loss (the transfer-learning-
    conv-ai double-head objective the reference inherits — SURVEY.md §3.2).

    batch = {"input_ids": [B, C, T], "token_type_ids": [B, C, T],
    "labels": [B, C, T] (-100 = ignore; only the gold candidate carries
    reply labels), "mc_label": [B] int (gold candidate index; -100 = padded
    example)}. Every candidate runs through the transformer (flattened to
    [B*C, T]); the MC head scores each candidate's last non-pad token and a
    softmax CE over the C candidates is added with weight `mc_coef`.
    Metrics add mc_correct / mc_count (mc_acc = mc_correct / mc_count).
    """

    def loss_fn(params, net_state, batch, rng):
        ids = batch["input_ids"]
        B, C, T = ids.shape
        flat = lambda a: a.reshape(B * C, T)  # noqa: E731
        # last non-pad position of every candidate (pad is only ever a tail)
        lengths = jnp.maximum((flat(ids) != pad_id).sum(-1), 1)
        lm_logits, mc_logits = model.apply(
            {"params": params},
            flat(ids),
            train=train,
            token_type_ids=flat(batch["token_type_ids"]),
            mc_positions=lengths - 1,
            rngs={"dropout": rng} if (train and rng is not None) else None,
        )
        # LM term: only the gold candidate carries labels (distractors are
        # all -100 by construction), so gather it BEFORE the vocab softmax —
        # the [B*C, T, V] log_softmax would be C-fold wasted work/memory
        gold = jnp.maximum(batch["mc_label"], 0)  # [B]; pad rows -> 0 (masked)
        V = lm_logits.shape[-1]
        lm_lgt = jnp.take_along_axis(
            lm_logits.reshape(B, C, T, V), gold[:, None, None, None], axis=1
        )[:, 0, :-1]
        labels = jnp.take_along_axis(
            batch["labels"], gold[:, None, None], axis=1
        )[:, 0, 1:]
        mask = (labels != -100).astype(lm_lgt.dtype)
        safe = jnp.maximum(labels, 0)
        logp = jax.nn.log_softmax(lm_lgt)
        per_tok = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
        count = jnp.maximum(mask.sum(), 1.0)
        lm_loss = (per_tok * mask).sum() / count
        lm_correct = ((lm_lgt.argmax(-1) == safe) * mask).sum()

        # MC term: softmax CE over candidates
        scores = mc_logits.reshape(B, C)
        mc_label = batch["mc_label"]
        mc_mask = (mc_label >= 0).astype(scores.dtype)
        safe_mc = jnp.maximum(mc_label, 0)
        mc_logp = jax.nn.log_softmax(scores, axis=-1)
        per_ex = -jnp.take_along_axis(mc_logp, safe_mc[:, None], axis=1)[:, 0]
        mc_count = jnp.maximum(mc_mask.sum(), 1.0)
        mc_loss = (per_ex * mc_mask).sum() / mc_count
        mc_correct = ((scores.argmax(-1) == safe_mc) * mc_mask).sum()

        loss = lm_loss + mc_coef * mc_loss
        return loss, {
            "net_state": net_state,
            "metrics": {
                "loss_sum": (per_tok * mask).sum(),
                "count": mask.sum(),
                "correct": lm_correct,
                "mc_loss_sum": (per_ex * mc_mask).sum(),
                "mc_count": mc_mask.sum(),
                "mc_correct": mc_correct,
            },
        }

    return loss_fn


def make_lm_loss(model, train: bool, moe_aux_coef: float = 0.0,
                 model_metrics: bool = False):
    """Next-token cross-entropy for causal LMs.

    batch = {"input_ids": [B, T] int, "labels": [B, T] int with -100 = ignore,
    optionally "token_type_ids": [B, T] int (PersonaChat speaker segments)}.
    Metrics: loss_sum / count (token-level) -> PPL = exp(loss_sum / count).
    `moe_aux_coef > 0` (MoE models) adds the Switch load-balancing aux sown
    by MoEMLP, averaged over MoE layers. `model_metrics` adds what the model
    sows under its `metrics` collection (sums, and counts to divide them by:
    the engine sums metrics over clients), each name summed over the layers.
    `net_state` holds the collections the model reads beside its parameters
    (`buffers`: a router's selection bias); it is handed on as it came.
    """

    def loss_fn(params, net_state, batch, rng):
        kwargs = dict(
            train=train,
            token_type_ids=batch.get("token_type_ids"),
            rngs={"dropout": rng} if (train and rng is not None) else None,
        )
        moe_aux = jnp.float32(0.0)
        sown = {}
        variables = {"params": params, **net_state}
        if model_metrics:
            logits, sown = model.apply(
                variables, batch["input_ids"], mutable=["metrics"], **kwargs)
        elif moe_aux_coef > 0:
            logits, inter = model.apply(
                variables, batch["input_ids"],
                mutable=["intermediates"], **kwargs,
            )
            auxs = jax.tree.leaves(inter)
            moe_aux = sum(jnp.asarray(a).mean() for a in auxs) / max(len(auxs), 1)
        else:
            logits = model.apply(variables, batch["input_ids"], **kwargs)
        # shift: predict token t+1 from prefix ..t
        logits = logits[:, :-1]
        labels = batch["labels"][:, 1:]
        mask = (labels != -100).astype(logits.dtype)
        safe_labels = jnp.maximum(labels, 0)
        logp = jax.nn.log_softmax(logits)
        per_tok = -jnp.take_along_axis(logp, safe_labels[..., None], axis=-1)[..., 0]
        count = jnp.maximum(mask.sum(), 1.0)
        loss = (per_tok * mask).sum() / count + moe_aux_coef * moe_aux
        correct = ((logits.argmax(-1) == safe_labels) * mask).sum()
        metrics = {
            "loss_sum": (per_tok * mask).sum(),
            "count": mask.sum(),
            "correct": correct,
        }
        if moe_aux_coef > 0:
            # sum + count pair: the engine SUMS metrics over clients/local
            # iters (and evaluate() over batches), so a bare mean would read
            # cohort-size-inflated — normalize via moe_aux_sum/moe_aux_count
            metrics["moe_aux_sum"] = moe_aux
            metrics["moe_aux_count"] = jnp.float32(1.0)
        for path, value in jax.tree_util.tree_leaves_with_path(sown):
            name = next(k.key for k in reversed(path) if hasattr(k, "key"))
            metrics[name] = metrics.get(name, 0.0) + value
        return loss, {"net_state": net_state, "metrics": metrics}

    return loss_fn
