"""The chunked gated delta rule (ops/gated_delta.py) against its own
definition, the token-by-token recurrence: values and gradients, at sequence
lengths that are not a multiple of the chunk, and under the engine's
transformation (vmap over clients inside a scan over chunks of clients)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.ops import gated_delta as gd


def _inputs(seed, B, T, H, dk, dv):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda a: a / jnp.sqrt((a * a).sum(-1, keepdims=True) + 1e-6)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (B, T, H, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (B, T, H, dk)))
    v = jax.random.normal(ks[2], (B, T, H, dv))
    g = -1.6 * jax.random.uniform(ks[3], (B, T, H))  # exp(A_log) * dt up to 16 * 0.1
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    return q, k, v, g, beta


@pytest.mark.parametrize("T, chunk", [(150, 64), (37, 8), (64, 64)])
def test_chunked_equals_recurrence_values_and_gradients(T, chunk):
    args = _inputs(T, 2, T, 3, 16, 24)
    got = gd.chunk_gated_delta_rule(*args, chunk=chunk)
    want = gd.recurrent_gated_delta_rule(*args)
    assert got.shape == want.shape == (2, T, 3, 24)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-6)

    def grads(fn):
        weigh = jnp.cos(jnp.arange(want.size, dtype=jnp.float32)).reshape(want.shape)
        return jax.grad(lambda *a: (fn(*a) * weigh).sum(), argnums=(0, 1, 2, 3, 4))(*args)

    for a, b in zip(grads(lambda *a: gd.chunk_gated_delta_rule(*a, chunk=chunk)),
                    grads(gd.recurrent_gated_delta_rule)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                                   atol=2e-5 * float(jnp.abs(b).max()) + 1e-7)


def test_strong_decay_neither_overflows_nor_leaks():
    """g far below 0: exp(G_i - G_j) above the diagonal would overflow if it
    were masked after the exponential; the state must simply be forgotten."""
    q, k, v, g, beta = _inputs(3, 1, 48, 2, 8, 8)
    got = gd.chunk_gated_delta_rule(q, k, v, 60.0 * g - 30.0, beta, chunk=16)
    want = gd.recurrent_gated_delta_rule(q, k, v, 60.0 * g - 30.0, beta)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-6)


def test_causal_and_the_padded_tail_writes_nothing():
    q, k, v, g, beta = _inputs(4, 1, 40, 2, 8, 8)
    whole = gd.chunk_gated_delta_rule(q, k, v, g, beta, chunk=16)
    head = gd.chunk_gated_delta_rule(q[:, :21], k[:, :21], v[:, :21], g[:, :21], beta[:, :21],
                                     chunk=16)
    np.testing.assert_allclose(np.asarray(whole[:, :21]), np.asarray(head), rtol=1e-5, atol=1e-6)


def test_vmap_over_clients_inside_a_scan_equals_one_client_at_a_time():
    """What engine._weighted_client_reduce does with --client_chunk: the
    backward pass of the chunked scan nested in vmap nested in lax.scan."""
    W, C = 4, 2
    per_client = [_inputs(10 + i, 1, 29, 2, 8, 8) for i in range(W)]
    stacked = tuple(jnp.stack(a) for a in zip(*per_client))

    def client_grad(q, k, v, g, beta):
        return jax.grad(lambda k_: (gd.chunk_gated_delta_rule(q, k_, v, g, beta, chunk=8) ** 2).sum())(k)

    def body(acc, xs):
        return acc + jax.vmap(client_grad)(*xs).sum(0), None

    xs = tuple(a.reshape((W // C, C) + a.shape[1:]) for a in stacked)
    got, _ = jax.jit(lambda xs: jax.lax.scan(body, jnp.zeros_like(per_client[0][1]), xs))(xs)
    want = sum(client_grad(*a) for a in per_client)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-6)


def test_grad_of_a_vmap_over_clients_inside_a_scan_equals_one_client_at_a_time():
    """engine._weighted_client_reduce's fused path with --client_chunk: one
    gradient of the masked sum of the clients' losses with respect to a
    SHARED weight (a per-channel scale of the keys here), the vmap over
    clients inside the grad, inside lax.scan; a masked client's inputs (NaN
    here) are zeroed before the forward pass and add nothing."""
    W, C = 4, 2
    per_client = [_inputs(10 + i, 1, 29, 2, 8, 8) for i in range(W)]
    stacked = tuple(jnp.stack(a) for a in zip(*per_client))
    live = jnp.asarray([1., 0., 1., 1.])
    scale = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(3), per_client[0][1].shape[-1:])

    def client_loss(s, q, k, v, g, beta):
        return (gd.chunk_gated_delta_rule(q, k * s, v, g, beta, chunk=8) ** 2).sum()

    def body(acc, chunk):
        xs, w = chunk
        xs = tuple(jnp.where(w.reshape((-1,) + (1,) * (a.ndim - 1)) > 0, a, 0) for a in xs)
        return acc + jax.grad(lambda s: jnp.where(
            w > 0, jax.vmap(lambda *a: client_loss(s, *a))(*xs), 0).sum())(scale), None

    poisoned = tuple(a.at[1].set(jnp.nan) for a in stacked)
    xs = tuple(a.reshape((W // C, C) + a.shape[1:]) for a in poisoned)
    got, _ = jax.jit(lambda xs, w: jax.lax.scan(body, jnp.zeros_like(scale), (xs, w)))(
        xs, live.reshape(W // C, C))
    want = sum(jax.grad(client_loss)(scale, *a) for a, w in zip(per_client, live) if w > 0)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-6)


def test_a_caller_that_keeps_the_named_inverses_solves_once():
    """models/qwen3_next.py recomputes the rule in the backward pass under a
    policy that keeps INVERSE: the compiled gradient then holds one triangular
    solve (forward), where recomputing everything holds two and a gradient
    through the solve itself would hold more."""
    import re

    args = _inputs(5, 1, 64, 2, 8, 8)

    def solves(policy):
        rule = jax.checkpoint(lambda *a: gd.chunk_gated_delta_rule(*a, chunk=16), policy=policy)
        text = jax.jit(jax.grad(lambda *a: (rule(*a) ** 2).sum(), argnums=(0, 1, 2, 3, 4))).lower(
            *args).compile().as_text()
        return len(re.findall(r"triangular-solve\(|trsm|TriangularSolve", text))

    assert solves(jax.checkpoint_policies.save_only_these_names(gd.INVERSE)) == 1
    assert solves(None) == 2
