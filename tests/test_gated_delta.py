"""The chunked gated delta rule (ops/gated_delta.py) against its own
definition, the token-by-token recurrence: values and gradients, at sequence
lengths that are not a multiple of the chunk, and under the engine's
transformation (vmap over clients inside a scan over chunks of clients). The
Pallas kernel that inverts the chunk matrices on a TPU, run here by the
interpreter: against numpy's float64 inverse, under vmap, and steered into
the rule in place of the triangular solve."""

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


def _checkpointed_gradient(policy):
    rule = jax.checkpoint(lambda *a: gd.chunk_gated_delta_rule(*a, chunk=16), policy=policy)
    return jax.grad(lambda *a: (rule(*a) ** 2).sum(), argnums=(0, 1, 2, 3, 4))


@pytest.mark.parametrize("path", ["solve", "kernel"])
def test_a_caller_that_keeps_the_named_inverses_solves_once(path, monkeypatch):
    """models/qwen3_next.py recomputes the rule in the backward pass under a
    policy that keeps INVERSE: the gradient then inverts once (forward), where
    recomputing everything inverts twice and a gradient through the inverse
    itself would more. On the solve path the compiled gradient's triangular
    solves are counted; steered to the kernel, its calls in the gradient's
    program (the interpreter leaves no call to count in the compiled text)."""
    import re

    args = _inputs(5, 1, 64, 2, 8, 8)
    if path == "kernel":
        monkeypatch.setattr(gd, "inverse_implementation", lambda C: "interpret")

    def inverses(policy):
        grad = _checkpointed_gradient(policy)
        if path == "kernel":
            return str(jax.make_jaxpr(grad)(*args)).count("pallas_call")
        text = jax.jit(grad).lower(*args).compile().as_text()
        return len(re.findall(r"triangular-solve\(|trsm|TriangularSolve", text))

    assert inverses(jax.checkpoint_policies.save_only_these_names(gd.INVERSE)) == 1
    assert inverses(None) == 2


# ---- the kernel (interpret mode) ----


def _chunk_matrices(seed, lead, C, common):
    """A [*lead, C, C] as the rule makes it: L2-normalised keys of 128 that
    share a direction of weight `common` (0: independent), beta in (0, 1),
    log decays in (-0.05, 0)."""
    rng = np.random.default_rng(seed)
    k = rng.normal(size=lead + (C, 128)) + common * rng.normal(size=lead + (1, 128))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    beta = rng.uniform(0, 1, lead + (C, 1))
    G = np.cumsum(rng.uniform(-0.05, 0, lead + (C,)), -1)
    D = np.exp(np.minimum(G[..., :, None] - G[..., None, :], 0))
    return np.tril(beta * np.einsum("...id,...jd->...ij", k, k) * D, -1).astype(np.float32)


def _worst_gap(X, A):
    """max over matrices of max |X - inv(I + A)| / max |inv(I + A)|, float64."""
    C = A.shape[-1]
    want = np.linalg.inv(np.eye(C) + np.asarray(A, np.float64).reshape(-1, C, C))
    got = np.asarray(X, np.float64).reshape(-1, C, C)
    return float((np.abs(got - want).max((1, 2)) / np.abs(want).max((1, 2))).max())


# float32 round-off of a 64 x 64 inverse is about 1e-7 of its largest entry
# (the solve reads 4e-8 to 1.4e-7 on these matrices)
TOL = 1e-6


@pytest.mark.parametrize("common", [0.0, 10.0], ids=["independent", "correlated"])
@pytest.mark.parametrize("lead", [(2, 64), (3, 5)], ids=["whole_blocks", "padded"])
@pytest.mark.parametrize("C", [16, 24, 32, 64])  # 24: blocks of 16 and 8 merge last
def test_kernel_inverse_equals_the_float64_inverse(C, lead, common):
    A = _chunk_matrices(C + len(lead), lead, C, common)
    X = gd.kernel_inverse(jnp.asarray(A), interpret=True)
    assert X.shape == A.shape and X.dtype == jnp.float32
    assert _worst_gap(X, A) < TOL
    assert _worst_gap(gd.solve_inverse(jnp.asarray(A)), A) < TOL
    # exactly zero above the diagonal, exactly one on it
    upper = np.triu(np.ones((C, C), bool), 1)
    assert not np.asarray(X)[..., upper].any()
    np.testing.assert_array_equal(np.diagonal(np.asarray(X), axis1=-2, axis2=-1), 1.0)


def test_a_planted_error_in_the_kernels_output_falls_far_outside_the_tolerance():
    A = _chunk_matrices(7, (3, 5), 64, 10.0)
    X = np.asarray(gd.kernel_inverse(jnp.asarray(A), interpret=True))
    scale = np.abs(X[2, 1]).max()
    planted = X.copy()
    planted[2, 1, 40, 13] += 1e-4 * scale
    assert _worst_gap(X, A) < TOL
    assert _worst_gap(planted, A) > 50 * TOL


def test_kernel_under_vmap_equals_one_call_a_client():
    """How the round program calls it: vmap over clients of [B, H, N, C, C]."""
    A = jnp.asarray(_chunk_matrices(11, (3, 1, 2, 3), 16, 3.0))
    got = jax.jit(jax.vmap(lambda a: gd.kernel_inverse(a, interpret=True)))(A)
    want = jnp.stack([gd.kernel_inverse(a, interpret=True) for a in A])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_the_rule_steered_to_the_kernel_equals_the_solve_path(monkeypatch):
    """chunk_gated_delta_rule's output and its gradients in all five inputs,
    with the kernel inverting the chunks in place of the solve."""
    args = _inputs(21, 2, 70, 3, 16, 24)
    weigh = jnp.cos(jnp.arange(2 * 70 * 3 * 24, dtype=jnp.float32)).reshape(2, 70, 3, 24)

    def run():
        rule = lambda *a: gd.chunk_gated_delta_rule(*a, chunk=32)  # noqa: E731
        return rule(*args), jax.grad(lambda *a: (rule(*a) * weigh).sum(), argnums=(0, 1, 2, 3, 4))(
            *args)

    want = run()
    monkeypatch.setattr(gd, "inverse_implementation", lambda C: "interpret")
    got = run()
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]), rtol=1e-5, atol=1e-6)
    for a, b in zip(got[1], want[1]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-5 * float(jnp.abs(b).max()))


def test_which_inverse_by_platform_and_shape(monkeypatch):
    assert gd.inverse_implementation(64) == "solve"  # the CPU
    monkeypatch.setattr(gd, "_on_tpu", lambda: True)
    assert [gd.inverse_implementation(C) for C in (8, 24, 64, 128)] == ["pallas"] * 4
    assert [gd.inverse_implementation(C) for C in (4, 12, 136, 256)] == ["solve"] * 4


def test_the_counter_says_which_path_each_traced_rule_took(monkeypatch):
    from commefficient_tpu.obs import registry

    counter = registry.default().counter("model_delta_inverse_kernel_total")
    args = _inputs(2, 1, 20, 2, 8, 8)
    before = counter.value
    jax.eval_shape(lambda *a: gd.chunk_gated_delta_rule(*a, chunk=8), *args)
    assert counter.value == before  # the solve
    monkeypatch.setattr(gd, "inverse_implementation", lambda C: "interpret")
    jax.eval_shape(lambda *a: gd.chunk_gated_delta_rule(*a, chunk=8), *args)
    assert counter.value == before + 1
