"""The latent-attention block of models/glm4_moe_lite.py against a direct loop
over heads and query positions written from its equations: one rotary key head
serves every query head, the score scale is (nope + rope)^-0.5, both latents
are RMS-normed before their up-projections, rotary positions reach the rope
dims only."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.models.glm4_moe_lite import TINY, LatentAttention

T = 13


@pytest.fixture(scope="module")
def block():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, T, TINY.hidden_size))
    params = LatentAttention(TINY).init(jax.random.PRNGKey(1), x)["params"]
    # weights wide enough that the softmax is far from uniform, and norm
    # weights away from 1 so that leaving a norm out shows
    params = {k: (1.0 + 0.3 * jax.random.normal(jax.random.PRNGKey(i), v.shape) if "norm" in k
                  else 10.0 * v) for i, (k, v) in enumerate(sorted(params.items()))}
    return x, params


def _turn(v, pos, theta):
    """Rotate-half rotary position `pos` on every dim of the vector v."""
    n = v.shape[0]
    inv = theta ** (-np.arange(0, n, 2) / n)
    ang = np.concatenate([pos * inv, pos * inv])
    return v * np.cos(ang) + np.concatenate([-v[n // 2:], v[: n // 2]]) * np.sin(ang)


def _by_hand(x, p, cfg, scale=None):
    """One sequence x [T, D], head by head and query by query, in float64."""
    p = {k: np.asarray(v, np.float64) for k, v in p.items()}
    x = np.asarray(x, np.float64)
    H, nope, rope, dv = (cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                         cfg.v_head_dim)
    rkv = cfg.kv_lora_rank
    scale = (nope + rope) ** -0.5 if scale is None else scale
    rms = lambda a, w: a / np.sqrt((a * a).mean(-1, keepdims=True) + cfg.rms_norm_eps) * w  # noqa: E731
    q = (rms(x @ p["q_a_proj"], p["q_a_norm"]) @ p["q_b_proj"]).reshape(T, H, nope + rope)
    kv_a = x @ p["kv_a_proj"]
    kv = (rms(kv_a[:, :rkv], p["kv_a_norm"]) @ p["kv_b_proj"]).reshape(T, H, nope + dv)
    k_rope = np.stack([_turn(kv_a[t, rkv:], t, cfg.rope_theta) for t in range(T)])  # ONE head
    out = np.zeros((T, H, dv))
    for h in range(H):
        for t in range(T):
            q_t = np.concatenate([q[t, h, :nope], _turn(q[t, h, nope:], t, cfg.rope_theta)])
            keys = np.concatenate([kv[: t + 1, h, :nope], k_rope[: t + 1]], axis=1)
            s = keys @ q_t * scale
            w = np.exp(s - s.max())
            out[t, h] = (w / w.sum()) @ kv[: t + 1, h, nope:]
    return out.reshape(T, H * dv) @ p["o_proj"]


def test_block_equals_the_loop_over_heads_and_positions(block):
    x, params = block
    with jax.default_matmul_precision("highest"):
        got = LatentAttention(TINY).apply({"params": params}, x)
    for b in range(2):
        want = _by_hand(x[b], params, TINY)
        # float32 against float64 through two norms and a softmax
        np.testing.assert_allclose(np.asarray(got[b]), want, rtol=2e-4, atol=2e-4 * np.abs(want).max())


def test_a_planted_scale_of_the_nope_width_alone_fails(block):
    """192^-0.5 in the place of 256^-0.5 (here 12 against 20) is another
    function by far more than the tolerance above."""
    x, params = block
    with jax.default_matmul_precision("highest"):
        got = np.asarray(LatentAttention(TINY).apply({"params": params}, x)[0])
    wrong = _by_hand(x[0], params, TINY, scale=TINY.qk_nope_head_dim ** -0.5)
    assert np.abs(got - wrong).max() > 0.01 * np.abs(got).max()
    assert np.abs(got - _by_hand(x[0], params, TINY)).max() < 2e-4 * np.abs(got).max()


def test_one_rotary_key_head_serves_all_query_heads(block):
    """The rotary key is 8 columns of kv_a_proj, whatever the number of heads:
    changing them moves every head's part of the result, and a second model
    with twice the heads has a kv_a_proj of the same shape."""
    x, params = block
    rkv, H, dv = TINY.kv_lora_rank, TINY.num_attention_heads, TINY.v_head_dim
    assert params["kv_a_proj"].shape == (TINY.hidden_size, rkv + TINY.qk_rope_head_dim)
    wide = dataclasses.replace(TINY, num_attention_heads=2 * H)
    shapes = jax.eval_shape(lambda: LatentAttention(wide).init(jax.random.PRNGKey(0), x))["params"]
    assert shapes["kv_a_proj"].shape == params["kv_a_proj"].shape
    assert shapes["kv_b_proj"].shape[1] == 2 * params["kv_b_proj"].shape[1]
    # per-head results before the output projection: o_proj as the identity
    eye = dict(params, o_proj=jnp.eye(H * dv, TINY.hidden_size))
    moved = dict(eye, kv_a_proj=eye["kv_a_proj"].at[:, rkv:].multiply(-1.0))
    a = LatentAttention(TINY).apply({"params": eye}, x)[..., : min(H * dv, TINY.hidden_size)]
    b = LatentAttention(TINY).apply({"params": moved}, x)[..., : min(H * dv, TINY.hidden_size)]
    per_head = np.abs(np.asarray(a - b)).reshape(2, T, -1, dv).max(axis=(0, 1, 3))
    assert (per_head > 1e-3).all(), per_head


def test_scores_are_recomputed_in_the_backward_pass(block):
    """The T x T scores sit under jax.checkpoint: the gradient's jaxpr holds a
    remat of the attention, and the gradient equals that of the plain
    reference's block (full T x T softmax, the key head broadcast and
    concatenated, nothing recomputed)."""
    from benchmark.reference import glm4_moe_lite as ref

    x, params = block
    m = dataclasses.asdict(TINY)
    grad = jax.grad(lambda p: (LatentAttention(TINY).apply({"params": p}, x) ** 2).sum())
    assert "remat" in str(jax.make_jaxpr(grad)(params))
    got = grad(params)
    want = jax.grad(lambda p: (jax.vmap(lambda xb: ref._attention(p, xb, m))(x) ** 2).sum())(params)
    for k in got:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want[k]), rtol=2e-3,
                                   atol=2e-3 * float(jnp.abs(want[k]).max()))
