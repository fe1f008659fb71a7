"""LFM2-24B-A2B (huggingface.co/LiquidAI/LFM2-24B-A2B, `model_type` lfm2_moe),
forward pass and language-model loss written plainly, from the public
config.json and the family's published description of its gated short
convolution, its grouped-query attention and its bias-balanced router.
float32 arrays; JAX differentiates it. It shares no code with the program.

T tokens, hidden D, eps = norm_eps.

  RMSNorm(x) = x / sqrt(mean(x^2) + eps) * w
  layer i:     h = x + op_i(RMSNorm_1(x));  out = h + FFN_i(RMSNorm_2(h))
  op_i is attention where layer_types[i] is "full_attention", the short
  convolution where it is "conv". FFN_i is a dense SwiGLU MLP of width
  intermediate_size if i < num_dense_layers, the expert block otherwise.

Short convolution (conv_L_cache L = 3 taps, no bias, no activation anywhere):
  [B | C | x] = u W_in                       three streams of D columns each
  z = B * x
  c[t] = sum_j w[j] * z[t - (L - 1) + j]     per channel, z before token 0 is 0:
         w[0] z[t-2] + w[1] z[t-1] + w[2] z[t]
  y = (C * c) W_out

Attention (H query heads, KV key/value heads, head width hd = D / H):
  q = RMSNorm_q(u W_q) and k = RMSNorm_k(u W_k) over the hd dims of each head
  (one weight vector of hd for all query heads, one for all key heads);
  rotate-half rotary positions, base rope_theta, on all hd dims of q and k;
  each key/value head serves H / KV query heads;
  causal softmax(q k^T / sqrt(hd)) v; heads concatenated; W_o. No bias, no gate.

Expert block: s = sigmoid(u W_r) over all router_num_experts; the
  num_experts_per_tok largest of s + b (b = expert_bias, a buffer passed in
  beside the parameters); weights s_j / (sum of the chosen s + 1e-6) *
  routed_scaling_factor; y = sum_j w_j E_j(u) over the chosen experts THAT ARE
  HELD (ids experts_held_first .. + num_experts - 1),
  E(u) = W_down(silu(W_gate u) * W_up u). There is no shared expert.
  Here every held expert is applied to every token and selected after.

Head: final RMSNorm, logits = x W_embed^T (the head is the embedding: one
leaf that gets both gradients), cross-entropy on the next token.

Departures from the published model, all shared with the program:
  - what the experts that are not held would have added is left out, and the
    partial result goes on (one chip's share of an expert-parallel layer);
  - the vocabulary is the configuration's slice;
  - the layers are those the configuration keeps of the published 40, with the
    mixer and feed-forward each had there;
  - b is frozen at its seeded values: the published buffer starts at zero and
    is moved toward balance by a rule whose rate the config does not give; no
    auxiliary loss;
  - the three streams are the columns [B | C | x] of W_in in that order, and
    rotary pairs dim i with dim i + hd / 2 (rotate-half): both matter only for
    loading weights, which nothing here does;
  - the head is tied (the family's convention: its configuration class
    defaults `tie_embedding` to true; the catalogued config has no key);
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

BIAS = "expert_bias"


def is_dense(m: dict, layer: int) -> bool:
    return layer < m["num_dense_layers"]


def is_attention(m: dict, layer: int) -> bool:
    return m["layer_types"][layer] == "full_attention"


def param_shapes(m: dict) -> dict:
    D, V, H, KV = (m["hidden_size"], m["vocab_size"], m["num_attention_heads"],
                   m["num_key_value_heads"])
    hd = D // H
    G, F, I = m["num_experts"], m["moe_intermediate_size"], m["intermediate_size"]
    conv = {"in_proj": (D, 3 * D), "conv": (m["conv_L_cache"], D), "out_proj": (D, D)}
    attn = {"q_proj": (D, H * hd), "k_proj": (D, KV * hd), "v_proj": (D, KV * hd),
            "q_norm": (hd,), "k_norm": (hd,), "o_proj": (H * hd, D)}
    experts = {"router": (D, m["router_num_experts"]), "experts_gate": (G, D, F),
               "experts_up": (G, D, F), "experts_down": (G, F, D)}
    dense = {"gate": (D, I), "up": (D, I), "down": (I, D)}
    shapes = {"embed": (V, D), "norm_f": (D,)}
    for i in range(m["num_hidden_layers"]):
        layer = {"norm_1": (D,), "norm_2": (D,),
                 "mixer": dict(attn if is_attention(m, i) else conv)}
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
    """Seeded weights: N(0, 0.02) matrices, norm weights 1, the convolution's
    taps N(0, 1/L) (L taps: the sum keeps its input's scale)."""

    def leaf(k, name, shape):
        if "norm" in name:
            return jnp.ones(shape, jnp.float32)
        std = shape[0] ** -0.5 if name == "conv" else 0.02
        return std * jax.random.normal(k, shape, jnp.float32)

    return _seeded(key, shapes, leaf)


def init_buffers(key, shapes: dict) -> dict:
    """b ~ N(0, 0.01), as the GLM-4.7-Flash reference seeds its bias: wide
    enough beside sigmoid scores near 1/2 that choosing by s + b and by s
    differ for a measurable share of the tokens. The key is folded so that b
    does not repeat the parameters' draws."""
    return _seeded(jax.random.fold_in(key, 1), shapes,
                   lambda k, name, shape: 0.01 * jax.random.normal(k, shape, jnp.float32))


def _rms(x, w, eps):
    y = x.astype(jnp.float32)
    y = y / jnp.sqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _rotary(t, theta):
    """t [T, heads, hd]: every dim is rotated."""
    T, _, hd = t.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    freqs = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)[:, None, :]  # [T, 1, hd]
    cos, sin = jnp.cos(emb).astype(t.dtype), jnp.sin(emb).astype(t.dtype)
    x1, x2 = t[..., : hd // 2], t[..., hd // 2:]
    return t * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def _short_conv(p, u):
    T, D = u.shape
    B, C, x = jnp.split(u @ p["in_proj"], 3, axis=-1)
    z = B * x
    L = p["conv"].shape[0]
    before = jnp.concatenate([jnp.zeros((L - 1, D), z.dtype), z])  # z[t] at row t + L - 1
    c = sum(p["conv"][j] * before[j: j + T] for j in range(L))
    return (C * c) @ p["out_proj"]


def _attention(p, u, m):
    T, D = u.shape
    H, KV = m["num_attention_heads"], m["num_key_value_heads"]
    hd, theta = D // H, m["rope_parameters"]["rope_theta"]
    q = _rms((u @ p["q_proj"]).reshape(T, H, hd), p["q_norm"], m["norm_eps"])
    k = _rms((u @ p["k_proj"]).reshape(T, KV, hd), p["k_norm"], m["norm_eps"])
    v = (u @ p["v_proj"]).reshape(T, KV, hd)
    q, k = _rotary(q, theta), _rotary(k, theta)
    # query head h reads key/value head h // (H / KV)
    k, v = jnp.repeat(k, H // KV, axis=1), jnp.repeat(v, H // KV, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.asarray(hd, q.dtype))
    causal = jnp.tril(jnp.ones((T, T), bool))
    scores = jnp.where(causal, scores.astype(jnp.float32), -jnp.inf)
    att = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("hqk,khd->qhd", att, v).reshape(T, H * hd) @ p["o_proj"]


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def route(p, bias, x, m):
    """(experts [T, k], weights [T, k]) of the tokens x [T, D]."""
    logits = jnp.dot(x, p["router"], precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, experts = jax.lax.top_k(scores + bias.astype(jnp.float32), m["num_experts_per_tok"])
    top = jnp.take_along_axis(scores, experts, axis=-1)
    return experts, top / (top.sum(axis=-1, keepdims=True) + 1e-6) * m["routed_scaling_factor"]


def _moe(p, bias, x, m, choices=None):
    experts, weights = route(p, bias, x, m)
    if choices is not None:
        choices.append(experts)
    held = m["experts_held_first"] + jnp.arange(m["num_experts"])
    # share[t, e]: the weight token t gives the held expert e (0 if not chosen)
    share = jnp.sum(weights[:, :, None] * (experts[:, :, None] == held[None, None, :]), axis=1)

    def add_expert(y, e):
        w_gate, w_up, w_down, s = e
        return y + s[:, None].astype(x.dtype) * _swiglu(x, w_gate, w_up, w_down), None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(x),
                        (p["experts_gate"], p["experts_up"], p["experts_down"], share.T))
    return y


def _layer(p, bias, x, m, attention: bool, choices=None):
    """One decoder layer; `bias` is None for a dense layer."""
    u = _rms(x, p["norm_1"], m["norm_eps"])
    x = x + (_attention(p["mixer"], u, m) if attention else _short_conv(p["mixer"], u))
    h = _rms(x, p["norm_2"], m["norm_eps"])
    if bias is None:
        return x + _swiglu(h, p["mlp"]["gate"], p["mlp"]["up"], p["mlp"]["down"])
    return x + _moe(p["moe"], bias, h, m, choices)


def sequence_logits(params, buffers, ids, m, choices=None):
    """One sequence ids [T] -> logits [T, V]. Each layer is checkpointed at
    its input (memory only: the numbers are the same)."""
    x = params["embed"][ids]
    for i in range(m["num_hidden_layers"]):
        bias = None if is_dense(m, i) else buffers[f"layers_{i}"]["moe"][BIAS]
        attention = is_attention(m, i)
        if choices is None:
            x = jax.checkpoint(lambda p, b, x, a=attention: _layer(p, b, x, m, a))(
                params[f"layers_{i}"], bias, x)
        else:
            x = _layer(params[f"layers_{i}"], bias, x, m, attention, choices)
    return _rms(x, params["norm_f"], m["norm_eps"]) @ params["embed"].T


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
