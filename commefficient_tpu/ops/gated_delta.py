"""Gated delta rule (Gated DeltaNet, arXiv:2412.06464), computed chunk by chunk.

Per head, with a state S in R^{dk x dv} (key x value), S_0 = 0:

    S' = exp(g_t) S_{t-1}
    u_t = beta_t (v_t - S'^T k_t)
    S_t = S' + k_t u_t^T
    o_t = S_t^T q_t

Token by token this is T dependent steps of rank-one updates, which leaves a
matrix unit idle. The chunked form (the published kernel's, chunk 64) solves
each chunk's C steps at once. With G the running sum of g inside the chunk and
D[i, j] = exp(G_i - G_j) for i >= j:

    A = strictly_lower(diag(beta) K K^T * D)           the steps' coupling
    U = (I + A)^{-1} diag(beta) V                      updates with S = 0
    W = (I + A)^{-1} diag(beta) diag(exp(G)) K         what the carried S adds
    U' = U - W S                                       [C, dv]
    O = diag(exp(G)) Q S + lower(Q K^T * D) U'
    S <- exp(G_C) S + (diag(exp(G_C - G)) K)^T U'

so a sequence costs T / C dependent steps of C x dk x dv matmuls, and
everything that does not read S is computed for all chunks at once. (I + A) is
unit lower triangular: it is inverted once a chunk (`unit_lower_inverse`, whose
gradient is the closed form -X^T dX X^T: on a TPU a batched triangular solve
of 2,048 chunks takes 5.5 ms, and differentiating through a solve costs two
more). The inverse carries the name
`INVERSE` for `jax.checkpoint` policies: a caller that recomputes this rule in
the backward pass can keep the C x C inverses (a sixteenth of the rule's
inputs) and skip the loop the second time. Everything else is differentiated
by JAX: the backward pass is the transpose of this program, a reversed scan
over the same chunks.

All arrays float32. q and k come in already L2-normalised (and q scaled):
normalisation belongs to the mixer, not to the rule.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.scipy.linalg import solve_triangular

CHUNK = 64  # the published kernel's
INVERSE = "delta_rule_inverse"
_HIGHEST = jax.lax.Precision.HIGHEST


def unit_lower_inverse(A):
    """(I + A)^{-1} for strictly lower triangular A [..., C, C]. The solve sees
    no gradient; the derivative -X dA X rides on a term whose value is exactly
    zero, so the backward pass is two small matmuls and no solve. The solve's
    result carries the name INVERSE."""
    eye = jnp.eye(A.shape[-1], dtype=A.dtype)
    fixed = jax.lax.stop_gradient(A)
    X = checkpoint_name(solve_triangular(
        fixed + eye, jnp.broadcast_to(eye, A.shape), lower=True, unit_diagonal=True), INVERSE)
    return X - jnp.matmul(jnp.matmul(X, A - fixed, precision=_HIGHEST), X, precision=_HIGHEST)


def chunk_gated_delta_rule(q, k, v, g, beta, *, chunk: int = CHUNK):
    """q, k: [B, T, H, dk]; v: [B, T, H, dv]; g (log decay, <= 0), beta
    (in (0, 1)): [B, T, H]. Returns o: [B, T, H, dv]. T need not be a
    multiple of `chunk`: the tail is padded with steps that write nothing
    (k = v = 0, beta = 0, g = 0) and their outputs are dropped."""
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    pad = (-T) % chunk
    if pad:
        widen = lambda a: jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))  # noqa: E731
        q, k, v, g, beta = (widen(a) for a in (q, k, v, g, beta))
    N = (T + pad) // chunk

    def chunks(a):  # [B, T, H, ...] -> [B, H, N, C, ...]
        a = a.reshape((B, N, chunk) + a.shape[2:])
        return jnp.moveaxis(a, 3, 1)

    q, k, v, g, beta = (chunks(a) for a in (q, k, v, g, beta))
    G = jnp.cumsum(g, axis=-1)  # [B, H, N, C]
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    # masked before the exponential: above the diagonal G_i - G_j > 0 can
    # overflow, and inf * 0 is not 0
    D = jnp.exp(jnp.where(lower, G[..., :, None] - G[..., None, :], -jnp.inf))
    kb = k * beta[..., None]
    A = jnp.where(jnp.tril(lower, -1), jnp.einsum("...id,...jd->...ij", kb, k) * D, 0.0)
    rhs = jnp.concatenate([v * beta[..., None], kb * jnp.exp(G)[..., None]], axis=-1)
    sol = jnp.matmul(unit_lower_inverse(A), rhs)
    U, W = sol[..., :dv], sol[..., dv:]
    QK = jnp.einsum("...id,...jd->...ij", q, k) * D  # zero above the diagonal
    Qg = q * jnp.exp(G)[..., None]
    Kd = k * jnp.exp(G[..., -1:] - G)[..., None]
    last = jnp.exp(G[..., -1])  # [B, H, N]

    def step(S, xs):
        U_n, W_n, QK_n, Qg_n, Kd_n, last_n = xs
        U_n = U_n - jnp.einsum("bhck,bhkv->bhcv", W_n, S)
        o = jnp.einsum("bhck,bhkv->bhcv", Qg_n, S) + jnp.einsum("bhij,bhjv->bhiv", QK_n, U_n)
        S = S * last_n[..., None, None] + jnp.einsum("bhck,bhcv->bhkv", Kd_n, U_n)
        return S, o

    xs = tuple(jnp.moveaxis(a, 2, 0) for a in (U, W, QK, Qg, Kd, last))
    _, o = jax.lax.scan(step, jnp.zeros((B, H, dk, dv), q.dtype), xs)
    o = jnp.moveaxis(o, 0, 2)  # [B, H, N, C, dv]
    return jnp.moveaxis(o, 1, 3).reshape(B, N * chunk, H, dv)[:, :T]


def recurrent_gated_delta_rule(q, k, v, g, beta):
    """The same rule, one token a step (a `lax.scan` over T): what the chunked
    form is tested against, and what a decoder would run."""
    B, T, H, dk = q.shape

    def step(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs  # [B, H, d] / [B, H]
        S = S * jnp.exp(g_t)[..., None, None]
        u = (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t)) * b_t[..., None]
        S = S + k_t[..., :, None] * u[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    _, o = jax.lax.scan(step, jnp.zeros((B, H, dk, v.shape[-1]), q.dtype), xs)
    return jnp.moveaxis(o, 0, 1)
