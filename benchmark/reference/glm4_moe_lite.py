"""GLM-4.7-Flash (huggingface.co/zai-org/GLM-4.7-Flash, `model_type`
glm4_moe_lite), forward pass and language-model loss written plainly, from the
public config.json and the DeepSeek-V3 family's published description of latent
attention and of the bias-balanced router, which glm4_moe_lite follows.
float32 arrays; JAX differentiates it. It shares no code with the program.

T tokens, hidden D, eps = rms_norm_eps, H heads.

  RMSNorm(x) = x / sqrt(mean(x^2) + eps) * w
  layer:       h = x + MLA(RMSNorm_1(x));  out = h + FFN(RMSNorm_2(h))
  FFN of layer i is a dense SwiGLU MLP of width intermediate_size if
  i < first_k_dense_replace, the expert block otherwise.

Latent attention (q_lora_rank rq, kv_lora_rank rkv, head dims nope / rope / v):
  c_q = RMSNorm(x W_qa)                      [T, rq]
  q = c_q W_qb viewed [T, H, nope + rope]    split [q_nope | q_rope]
  [c_kv | k_rope] = x W_kva                  [T, rkv | rope]: ONE rotary key head
  c_kv = RMSNorm(c_kv)
  c_kv W_kvb viewed [T, H, nope + v]         split [k_nope | v]
  rotate-half rotary positions, base rope_theta, on all rope dims of q_rope and
  of k_rope; k_h = [k_nope_h | k_rope] for every head h;
  causal softmax(q k^T / sqrt(nope + rope)) v; heads concatenated; W_o.

Expert block: s = sigmoid(x W_r) over all router_num_experts; the
  num_experts_per_tok largest of s + b (b = e_score_correction_bias, a buffer
  passed in beside the parameters; one group, so no group limit); weights
  s_j / (sum of the chosen s + 1e-20) * routed_scaling_factor;
  y = sum_j w_j E_j(x) over the chosen experts THAT ARE HELD (ids
  experts_held_first .. + n_routed_experts - 1), E(x) = W_down(silu(W_gate x) * W_up x);
  plus E_shared(x), ungated, of width n_shared_experts * moe_intermediate_size.
  Here every held expert is applied to every token and selected after.

Head: final RMSNorm, logits = x W_head (untied), cross-entropy on the next
token.

Departures from the published model, all shared with the program:
  - what the experts that are not held would have added is left out, and the
    partial result goes on (one chip's share of an expert-parallel layer);
  - the vocabulary is the configuration's slice;
  - the multi-token-prediction layer (num_nextn_predict_layers 1) is left out:
    the config gives it no loss weight, and the public loader drops its weights;
  - b is frozen at its seeded values: the rule that moves it toward balance
    during pre-training has no rate in the config; no auxiliary loss;
  - rotary pairs dim i with dim i + rope / 2 (rotate-half). The family's
    checkpoints store the rotary dims interleaved and are permuted on loading;
    it matters only for loading weights, which nothing here does;
  - the router's matmul runs at `highest` precision (everything else at the
    backend's default): which expert comes 4th is a discrete outcome;
  - each layer is checkpointed at its input when the loss is differentiated
    (memory only, the numbers are the same: `sequence_logits`).

Parameters are a nested dict; its sorted-key leaf order is the flat coordinate
order the sketch hashes. The buffers are a dict of the same nesting that holds
the expert layers' b alone.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

BIAS = "e_score_correction_bias"


def is_dense(m: dict, layer: int) -> bool:
    return layer < m["first_k_dense_replace"]


def param_shapes(m: dict) -> dict:
    D, V, H = m["hidden_size"], m["vocab_size"], m["num_attention_heads"]
    rq, rkv = m["q_lora_rank"], m["kv_lora_rank"]
    nope, rope, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    G, F, I = m["n_routed_experts"], m["moe_intermediate_size"], m["intermediate_size"]
    Fs = F * m["n_shared_experts"]
    attn = {"q_a_proj": (D, rq), "q_a_norm": (rq,), "q_b_proj": (rq, H * (nope + rope)),
            "kv_a_proj": (D, rkv + rope), "kv_a_norm": (rkv,),
            "kv_b_proj": (rkv, H * (nope + dv)), "o_proj": (H * dv, D)}
    experts = {"router": (D, m["router_num_experts"]), "experts_gate": (G, D, F),
               "experts_up": (G, D, F), "experts_down": (G, F, D), "shared_gate": (D, Fs),
               "shared_up": (D, Fs), "shared_down": (Fs, D)}
    dense = {"gate": (D, I), "up": (D, I), "down": (I, D)}
    shapes = {"embed": (V, D), "lm_head": (D, V), "norm_f": (D,)}
    for i in range(m["num_hidden_layers"]):
        layer = {"norm_1": (D,), "norm_2": (D,), "attn": dict(attn)}
        layer.update({"mlp": dense} if is_dense(m, i) else {"moe": experts})
        shapes[f"layers_{i}"] = layer
    return shapes


def buffer_shapes(m: dict) -> dict:
    return {f"layers_{i}": {"moe": {BIAS: (m["router_num_experts"],)}}
            for i in range(m["num_hidden_layers"]) if not is_dense(m, i)}


def _seeded(key, shapes: dict, leaf) -> dict:
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))
    return jax.tree.unflatten(treedef, [
        leaf(k, path[-1].key, shape) for k, (path, shape) in zip(
            jax.random.split(key, len(paths)), paths)])


def init_params(key, shapes: dict) -> dict:
    """Seeded weights: N(0, 0.02) matrices, norm weights 1."""
    return _seeded(key, shapes, lambda k, name, shape: (
        jnp.ones(shape, jnp.float32) if "norm" in name
        else 0.02 * jax.random.normal(k, shape, jnp.float32)))


def init_buffers(key, shapes: dict) -> dict:
    """b ~ N(0, 0.01): wide enough beside sigmoid scores near 1/2 (weights
    N(0, 0.02), inputs of unit scale) that choosing by s + b and by s differ
    for a measurable share of the tokens. The key is folded so that b does
    not repeat the parameters' draws."""
    return _seeded(jax.random.fold_in(key, 1), shapes,
                   lambda k, name, shape: 0.01 * jax.random.normal(k, shape, jnp.float32))


def _rms(x, w, eps):
    y = x.astype(jnp.float32)
    y = y / jnp.sqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _rotary(t, theta):
    """t [T, heads, rope]: every dim is rotated."""
    T, _, rope = t.shape
    inv = 1.0 / (theta ** (jnp.arange(0, rope, 2, dtype=jnp.float32) / rope))
    freqs = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)[:, None, :]  # [T, 1, rope]
    cos, sin = jnp.cos(emb).astype(t.dtype), jnp.sin(emb).astype(t.dtype)
    x1, x2 = t[..., : rope // 2], t[..., rope // 2:]
    return t * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def _attention(p, x, m):
    T, H = x.shape[0], m["num_attention_heads"]
    rkv, nope, rope, dv = (m["kv_lora_rank"], m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                           m["v_head_dim"])
    c_q = _rms(x @ p["q_a_proj"], p["q_a_norm"], m["rms_norm_eps"])
    q_nope, q_rope = jnp.split((c_q @ p["q_b_proj"]).reshape(T, H, nope + rope), [nope], axis=-1)
    c_kv, k_rope = jnp.split(x @ p["kv_a_proj"], [rkv], axis=-1)
    c_kv = _rms(c_kv, p["kv_a_norm"], m["rms_norm_eps"])
    k_nope, v = jnp.split((c_kv @ p["kv_b_proj"]).reshape(T, H, nope + dv), [nope], axis=-1)
    q = jnp.concatenate([q_nope, _rotary(q_rope, m["rope_theta"])], axis=-1)
    k_rope = _rotary(k_rope.reshape(T, 1, rope), m["rope_theta"])
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (T, H, rope))], axis=-1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.asarray(nope + rope, q.dtype))
    causal = jnp.tril(jnp.ones((T, T), bool))
    scores = jnp.where(causal, scores.astype(jnp.float32), -jnp.inf)
    att = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("hqk,khd->qhd", att, v).reshape(T, H * dv) @ p["o_proj"]


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def route(p, bias, x, m):
    """(experts [T, k], weights [T, k]) of the tokens x [T, D]."""
    logits = jnp.dot(x, p["router"], precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, experts = jax.lax.top_k(scores + bias.astype(jnp.float32), m["num_experts_per_tok"])
    top = jnp.take_along_axis(scores, experts, axis=-1)
    return experts, top / (top.sum(axis=-1, keepdims=True) + 1e-20) * m["routed_scaling_factor"]


def _moe(p, bias, x, m, choices=None):
    experts, weights = route(p, bias, x, m)
    if choices is not None:
        choices.append(experts)
    held = m["experts_held_first"] + jnp.arange(m["n_routed_experts"])
    # share[t, e]: the weight token t gives the held expert e (0 if not chosen)
    share = jnp.sum(weights[:, :, None] * (experts[:, :, None] == held[None, None, :]), axis=1)

    def add_expert(y, e):
        w_gate, w_up, w_down, s = e
        return y + s[:, None].astype(x.dtype) * _swiglu(x, w_gate, w_up, w_down), None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(x),
                        (p["experts_gate"], p["experts_up"], p["experts_down"], share.T))
    return y + _swiglu(x, p["shared_gate"], p["shared_up"], p["shared_down"])


def _layer(p, bias, x, m, choices=None):
    """One decoder layer; `bias` is None for a dense layer."""
    x = x + _attention(p["attn"], _rms(x, p["norm_1"], m["rms_norm_eps"]), m)
    h = _rms(x, p["norm_2"], m["rms_norm_eps"])
    if bias is None:
        return x + _swiglu(h, p["mlp"]["gate"], p["mlp"]["up"], p["mlp"]["down"])
    return x + _moe(p["moe"], bias, h, m, choices)


def sequence_logits(params, buffers, ids, m, choices=None):
    """One sequence ids [T] -> logits [T, V]. Each layer is checkpointed at
    its input (memory only: at the cell's size five layers' T x T scores and
    softmaxes, kept for the backward pass beside four [d] vectors, pass the
    chip's memory; the numbers are the same)."""
    x = params["embed"][ids]
    for i in range(m["num_hidden_layers"]):
        bias = None if is_dense(m, i) else buffers[f"layers_{i}"]["moe"][BIAS]
        if choices is None:
            x = jax.checkpoint(lambda p, b, x: _layer(p, b, x, m))(params[f"layers_{i}"], bias, x)
        else:
            x = _layer(params[f"layers_{i}"], bias, x, m, choices)
    return _rms(x, params["norm_f"], m["rms_norm_eps"]) @ params["lm_head"]


def client_loss(params, batch, model: dict, buffers: dict):
    """Mean next-token cross-entropy over one client's labelled tokens
    (labels -100 are not predicted); also the sum and the count."""
    lg = jax.vmap(lambda ids: sequence_logits(params, buffers, ids, model))(
        batch["input_ids"])[:, :-1]
    labels = batch["labels"][:, 1:]
    mask = (labels != -100).astype(jnp.float32)
    logp = jax.nn.log_softmax(lg.astype(jnp.float32))
    per_tok = -jnp.take_along_axis(logp, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    total, count = (per_tok * mask).sum(), mask.sum()
    return total / jnp.maximum(count, 1.0), total, count


def routing_choices(params, buffers, ids, model: dict):
    """The experts each token of one sequence chooses, expert layer by expert
    layer ([expert layers, T, k]): for counting how many choices differ from
    the program's."""
    choices = []
    sequence_logits(params, buffers, ids, model, choices)
    return jnp.stack(choices)
