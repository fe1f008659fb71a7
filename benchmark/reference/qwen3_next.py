"""Qwen3-Next-80B-A3B (huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct), forward
pass and language-model loss written plainly, from the public config.json, the
Gated DeltaNet paper (arXiv:2412.06464) and the public modeling_qwen3_next.py.
float32 arrays; JAX differentiates it. It shares no code with the program.

T tokens, hidden D, eps = rms_norm_eps.

  RMSNorm(x) = x / sqrt(mean(x^2) + eps) * (1 + w)                (zero-centred)
  layer:       h = x + Mixer(RMSNorm_1(x));  out = h + MoE(RMSNorm_2(h))
  layer i is gated softmax attention if (i + 1) % full_attention_interval == 0,
  a Gated DeltaNet otherwise.

Gated DeltaNet mixer (Hk key heads, Hv value heads, head dims dk / dv):
  [q | k | v | z] = x W_qkvz   (Hk dk + Hk dk + Hv dv + Hv dv columns)
  [b | a] = x W_ba             (Hv + Hv)
  [q | k | v] together through a causal depthwise convolution of width 4 (left
  pad 3, no bias), then SiLU.  beta = sigmoid(b);
  g = -exp(A_log) * softplus(a + dt_bias), per value head.
  q, k L2-normalised over their head dim, q scaled by dk^-0.5; key head h
  serves the value heads h * Hv / Hk .. (h + 1) * Hv / Hk - 1.
  Per value head, S in R^{dk x dv}, S_0 = 0, token by token:
    S' = exp(g_t) S_{t-1};  u_t = beta_t (v_t - S'^T k_t);
    S_t = S' + k_t u_t^T;   o_t = S_t^T q_t
  out = concat_heads(w * o / sqrt(mean(o^2) + eps) * silu(z)) W_o.

Gated attention (H query heads, KV key/value heads, head dim hd):
  [q | gate] = x W_q viewed [T, H, 2 hd] and split in two;  k = x W_k, v = x W_v
  as [T, KV, hd]; q and k through a zero-centred RMSNorm over hd (one weight
  vector each, shared by the heads); rotate-half rotary positions on the first
  hd * partial_rotary_factor dims, base rope_theta; key/value head j serves
  query heads j H / KV ..; causal softmax(q k^T / sqrt(hd)) v; times
  sigmoid(gate); heads concatenated; W_o.

MoE: p = softmax(x W_r) over all router_num_experts; the num_experts_per_tok
  largest, weights p_j / sum of those (norm_topk_prob);
  y = sum_j w_j E_j(x) over the chosen experts THAT ARE HELD (ids
  experts_held_first .. + num_experts - 1), E(x) = W_down(silu(W_gate x) * W_up x);
  plus sigmoid(x . w_sg) * E_shared(x). Here every held expert is applied to
  every token and selected after.

Head: final RMSNorm, logits = x W_head (untied), cross-entropy on the next
token.

Departures from the published model, all shared with the program:
  - what the experts that are not held would have added is left out, and the
    partial result goes on (one chip's share of an expert-parallel layer);
  - the vocabulary is the configuration's slice;
  - the multi-token-prediction module and the router's auxiliary loss are left
    out (the catalogued config has no key for either);
  - the order of the fused projections' columns is [q | k | v | z] and [b | a]
    with heads contiguous inside each part (the published checkpoint
    interleaves them per key head; it matters only for loading weights);
  - the router's matmul runs at `highest` precision (everything else at the
    backend's default): which expert comes 10th is a discrete outcome.

Parameters are a nested dict; its sorted-key leaf order is the flat coordinate
order the sketch hashes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

SEGMENT = 64  # the recurrence is checkpointed every 64 tokens (memory only)


def is_attention(m: dict, layer: int) -> bool:
    return (layer + 1) % m["full_attention_interval"] == 0


def param_shapes(m: dict) -> dict:
    D, V = m["hidden_size"], m["vocab_size"]
    key_dim = m["linear_num_key_heads"] * m["linear_key_head_dim"]
    value_dim = m["linear_num_value_heads"] * m["linear_value_head_dim"]
    Hv, hd = m["linear_num_value_heads"], m["head_dim"]
    H, KV = m["num_attention_heads"], m["num_key_value_heads"]
    G, F, Fs = m["num_experts"], m["moe_intermediate_size"], m["shared_expert_intermediate_size"]
    delta = {"in_proj_qkvz": (D, 2 * key_dim + 2 * value_dim), "in_proj_ba": (D, 2 * Hv),
             "conv": (m["linear_conv_kernel_dim"], 2 * key_dim + value_dim), "A_log": (Hv,),
             "dt_bias": (Hv,), "norm": (m["linear_value_head_dim"],), "out_proj": (value_dim, D)}
    attention = {"q_proj": (D, 2 * H * hd), "k_proj": (D, KV * hd), "v_proj": (D, KV * hd),
                 "o_proj": (H * hd, D), "q_norm": (hd,), "k_norm": (hd,)}
    experts = {"router": (D, m["router_num_experts"]), "experts_gate": (G, D, F),
               "experts_up": (G, D, F), "experts_down": (G, F, D), "shared_gate": (D, Fs),
               "shared_up": (D, Fs), "shared_down": (Fs, D), "shared_expert_gate": (D,)}
    shapes = {"embed": (V, D), "lm_head": (D, V), "norm_f": (D,)}
    for i in range(m["num_hidden_layers"]):
        shapes[f"layers_{i}"] = {"norm_1": (D,), "norm_2": (D,), "moe": dict(experts),
                                 "mixer": dict(attention if is_attention(m, i) else delta)}
    return shapes


def init_params(key, shapes: dict) -> dict:
    """Seeded weights: N(0, 0.02) matrices and zero-centred norm weights, the
    convolution N(0, 1/2) (its taps are 4), the gated norm's weight near 1,
    A = exp(A_log) uniform on [1, 16] and softplus(dt_bias) log-uniform on
    [1e-3, 1e-1] (the published initialisation of the decay)."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))
    out = []
    for k, (path, shape) in zip(jax.random.split(key, len(paths)), paths):
        name = path[-1].key
        if name == "A_log":
            leaf = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
        elif name == "dt_bias":
            dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
            leaf = jnp.log(jnp.expm1(dt))
        else:
            std = 0.5 if name == "conv" else 0.02
            leaf = std * jax.random.normal(k, shape, jnp.float32)
            if name == "norm":
                leaf = leaf + 1.0
        out.append(leaf)
    return jax.tree.unflatten(treedef, out)


def _rms(x, w, eps):
    y = x.astype(jnp.float32)
    y = y / jnp.sqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True) + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def _delta_rule(q, k, v, g, beta):
    """[T, Hv, *] -> [T, Hv, dv], one token a step. The scan is nested (a
    checkpointed inner scan of SEGMENT steps) so that the backward pass holds
    T / SEGMENT + SEGMENT states and not T of them."""
    T, Hv, dk = q.shape
    dv = v.shape[-1]
    pad = (-T) % SEGMENT
    if pad:  # steps that write nothing
        q, k, v, g, beta = (jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))
                            for a in (q, k, v, g, beta))

    def step(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        S = S * jnp.exp(g_t)[:, None, None].astype(S.dtype)
        u = (v_t - jnp.einsum("hkv,hk->hv", S, k_t)) * b_t[:, None].astype(S.dtype)
        S = S + jnp.einsum("hk,hv->hkv", k_t, u)
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    @jax.checkpoint
    def segment(S, xs):
        return jax.lax.scan(step, S, xs)

    xs = tuple(a.reshape((-1, SEGMENT) + a.shape[1:]) for a in (q, k, v, g, beta))
    _, o = jax.lax.scan(segment, jnp.zeros((Hv, dk, dv), q.dtype), xs)
    return o.reshape((-1, Hv, dv))[:T]


def _delta_mixer(p, x, m):
    T = x.shape[0]
    Hk, Hv = m["linear_num_key_heads"], m["linear_num_value_heads"]
    dk, dv = m["linear_key_head_dim"], m["linear_value_head_dim"]
    key_dim, value_dim, width = Hk * dk, Hv * dv, m["linear_conv_kernel_dim"]
    q, k, v, z = jnp.split(x @ p["in_proj_qkvz"],
                           [key_dim, 2 * key_dim, 2 * key_dim + value_dim], axis=-1)
    b, a = jnp.split(x @ p["in_proj_ba"], 2, axis=-1)
    qkv = jnp.concatenate([q, k, v], axis=-1)
    padded = jnp.concatenate([jnp.zeros((width - 1, qkv.shape[1]), qkv.dtype), qkv])
    conv = jnp.zeros_like(qkv)
    for j in range(width):  # y_t = sum_j w_j x_{t - (width - 1) + j}
        conv = conv + padded[j: j + T] * p["conv"][j]
    q, k, v = jnp.split(jax.nn.silu(conv), [key_dim, 2 * key_dim], axis=-1)
    beta = jax.nn.sigmoid(b.astype(jnp.float32))
    g = -jnp.exp(p["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        a.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))

    def unit(t):
        return t / jnp.sqrt(jnp.sum(jnp.square(t), axis=-1, keepdims=True) + 1e-6)

    q = jnp.repeat(unit(q.reshape(T, Hk, dk)) / jnp.sqrt(jnp.asarray(dk, q.dtype)), Hv // Hk, axis=1)
    k = jnp.repeat(unit(k.reshape(T, Hk, dk)), Hv // Hk, axis=1)
    o = _delta_rule(q, k, v.reshape(T, Hv, dv), g, beta)
    o32 = o.astype(jnp.float32)
    o32 = o32 / jnp.sqrt(jnp.mean(jnp.square(o32), axis=-1, keepdims=True) + m["rms_norm_eps"])
    o = (o32 * p["norm"].astype(jnp.float32)).astype(x.dtype) * jax.nn.silu(z.reshape(T, Hv, dv))
    return o.reshape(T, value_dim) @ p["out_proj"]


def _rotary(t, theta, rotary_dim):
    T = t.shape[0]
    inv = 1.0 / (theta ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32) / rotary_dim))
    freqs = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)[:, None, :]  # [T, 1, rotary_dim]
    cos, sin = jnp.cos(emb).astype(t.dtype), jnp.sin(emb).astype(t.dtype)
    rot, keep = t[..., :rotary_dim], t[..., rotary_dim:]
    x1, x2 = rot[..., : rotary_dim // 2], rot[..., rotary_dim // 2:]
    rot = rot * cos + jnp.concatenate([-x2, x1], axis=-1) * sin
    return jnp.concatenate([rot, keep], axis=-1)


def _attention(p, x, m):
    T = x.shape[0]
    H, KV, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    q, gate = jnp.split((x @ p["q_proj"]).reshape(T, H, 2 * hd), 2, axis=-1)
    k = (x @ p["k_proj"]).reshape(T, KV, hd)
    v = (x @ p["v_proj"]).reshape(T, KV, hd)
    rotary_dim = int(hd * m["partial_rotary_factor"])
    q = _rotary(_rms(q, p["q_norm"], m["rms_norm_eps"]), m["rope_theta"], rotary_dim)
    k = _rotary(_rms(k, p["k_norm"], m["rms_norm_eps"]), m["rope_theta"], rotary_dim)
    k, v = jnp.repeat(k, H // KV, axis=1), jnp.repeat(v, H // KV, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.asarray(hd, q.dtype))
    causal = jnp.tril(jnp.ones((T, T), bool))
    scores = jnp.where(causal, scores.astype(jnp.float32), -jnp.inf)
    att = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    y = jnp.einsum("hqk,khd->qhd", att, v) * jax.nn.sigmoid(gate)
    return y.reshape(T, H * hd) @ p["o_proj"]


def route(p, x, m):
    """(experts [T, k], weights [T, k]) of the tokens x [T, D]."""
    logits = jnp.dot(x, p["router"], precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top, experts = jax.lax.top_k(probs, m["num_experts_per_tok"])
    return experts, top / top.sum(axis=-1, keepdims=True)


def _moe(p, x, m, choices=None):
    experts, weights = route(p, x, m)
    if choices is not None:
        choices.append(experts)
    held = m["experts_held_first"] + jnp.arange(m["num_experts"])
    # share[t, e]: the weight token t gives the held expert e (0 if not chosen)
    share = jnp.sum(weights[:, :, None] * (experts[:, :, None] == held[None, None, :]), axis=1)

    def add_expert(y, e):
        w_gate, w_up, w_down, s = e
        return y + s[:, None].astype(x.dtype) * ((jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down), None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(x),
                        (p["experts_gate"], p["experts_up"], p["experts_down"], share.T))
    shared = (jax.nn.silu(x @ p["shared_gate"]) * (x @ p["shared_up"])) @ p["shared_down"]
    return y + jax.nn.sigmoid(x @ p["shared_expert_gate"])[:, None] * shared


def sequence_logits(params, ids, m, choices=None):
    """One sequence ids [T] -> logits [T, V]."""
    x = params["embed"][ids]
    for i in range(m["num_hidden_layers"]):
        p = params[f"layers_{i}"]
        mixer = _attention if is_attention(m, i) else _delta_mixer
        x = x + mixer(p["mixer"], _rms(x, p["norm_1"], m["rms_norm_eps"]), m)
        x = x + _moe(p["moe"], _rms(x, p["norm_2"], m["rms_norm_eps"]), m, choices)
    return _rms(x, params["norm_f"], m["rms_norm_eps"]) @ params["lm_head"]


def client_loss(params, batch, model: dict):
    """Mean next-token cross-entropy over one client's labelled tokens
    (labels -100 are not predicted); also the sum and the count."""
    lg = jax.vmap(lambda ids: sequence_logits(params, ids, model))(batch["input_ids"])[:, :-1]
    labels = batch["labels"][:, 1:]
    mask = (labels != -100).astype(jnp.float32)
    logp = jax.nn.log_softmax(lg.astype(jnp.float32))
    per_tok = -jnp.take_along_axis(logp, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    total, count = (per_tok * mask).sum(), mask.sum()
    return total / jnp.maximum(count, 1.0), total, count


def routing_choices(params, ids, model: dict):
    """The experts each token of one sequence chooses, layer by layer
    ([layers, T, k]): for counting how many choices differ from the program's."""
    choices = []
    sequence_logits(params, ids, model, choices)
    return jnp.stack(choices)
