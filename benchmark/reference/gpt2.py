"""GPT-2 (Radford et al. 2019), forward and language-model loss, written
plainly: token + position (+ speaker-segment) embeddings, pre-norm blocks of
causal multi-head attention and a 4x GELU MLP, final layer norm, logits from
the tied token embedding. float32 arrays, no dropout.

Parameters are a nested dict; its sorted-key leaf order is the flat
coordinate order the sketch hashes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

EPS = 1e-5


def param_shapes(vocab: int, n_positions: int, n_embd: int, n_layer: int) -> dict:
    e = n_embd

    def dense(i, o):
        return {"kernel": (i, o), "bias": (o,)}

    ln = {"scale": (e,), "bias": (e,)}
    block = {"ln_1": dict(ln), "attn": {"c_attn": dense(e, 3 * e), "c_proj": dense(e, e)},
             "ln_2": dict(ln), "mlp": {"c_fc": dense(e, 4 * e), "c_proj": dense(4 * e, e)}}
    shapes = {"wte": (vocab, e), "wpe": (n_positions, e), "ln_f": dict(ln)}
    for i in range(n_layer):
        shapes[f"h_{i}"] = jax.tree.map(lambda s: s, block, is_leaf=lambda s: isinstance(s, tuple))
    return shapes


def init_params(key, shapes: dict, n_layer: int) -> dict:
    """Seeded weights in GPT-2's own scheme: N(0, 0.02) matrices (residual
    projections scaled by 1/sqrt(2 n_layer)), N(0, 0.01) positions, layer-norm
    scale near 1; biases small but not zero."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))
    keys = jax.random.split(key, len(paths))
    out = []
    for k, (path, shape) in zip(keys, paths):
        names = [p.key for p in path]
        if len(shape) == 2:
            std = 0.01 if names[-1] == "wpe" else 0.02
            if names[-2:] == ["c_proj", "kernel"]:
                std /= (2 * n_layer) ** 0.5
            out.append(std * jax.random.normal(k, shape, jnp.float32))
        elif names[-1] == "scale":
            out.append(1.0 + 0.02 * jax.random.normal(k, shape, jnp.float32))
        else:
            out.append(0.02 * jax.random.normal(k, shape, jnp.float32))
    return jax.tree.unflatten(treedef, out)


def _layer_norm(p, x):
    mean = x.mean(-1, keepdims=True)
    var = jnp.square(x - mean).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + EPS) * p["scale"] + p["bias"]


def _dense(p, x):
    return x @ p["kernel"] + p["bias"]


def _attention(p, x, n_head):
    B, T, C = x.shape
    q, k, v = jnp.split(_dense(p["c_attn"], x), 3, axis=-1)
    heads = lambda t: t.reshape(B, T, n_head, C // n_head)  # noqa: E731
    q, k, v = heads(q), heads(k), heads(v)
    att = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(C // n_head))
    causal = jnp.tril(jnp.ones((T, T), bool))
    att = jax.nn.softmax(jnp.where(causal, att, jnp.finfo(jnp.float32).min), axis=-1)
    y = jnp.einsum("bhqk,bkhd->bqhd", att, v).reshape(B, T, C)
    return _dense(p["c_proj"], y)


def logits(params, input_ids, token_type_ids, n_head: int):
    T = input_ids.shape[1]
    wte = params["wte"]
    x = wte[input_ids] + params["wpe"][:T][None] + wte[token_type_ids]
    n_layer = sum(1 for k in params if k.startswith("h_"))
    for i in range(n_layer):
        p = params[f"h_{i}"]
        x = x + _attention(p["attn"], _layer_norm(p["ln_1"], x), n_head)
        h = jax.nn.gelu(_dense(p["mlp"]["c_fc"], _layer_norm(p["ln_2"], x)), approximate=True)
        x = x + _dense(p["mlp"]["c_proj"], h)
    return jnp.einsum("btc,vc->btv", _layer_norm(params["ln_f"], x), wte)


def client_loss(params, batch, n_head: int):
    """Mean next-token cross-entropy over one client's labelled tokens
    (labels -100 are not predicted); also the sum and the count."""
    lg = logits(params, batch["input_ids"], batch["token_type_ids"], n_head)[:, :-1]
    labels = batch["labels"][:, 1:]
    mask = (labels != -100).astype(jnp.float32)
    logp = jax.nn.log_softmax(lg)
    per_tok = -jnp.take_along_axis(logp, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    total, count = (per_tok * mask).sum(), mask.sum()
    return total / jnp.maximum(count, 1.0), total, count
