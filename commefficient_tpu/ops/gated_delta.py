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
gradient is the closed form -X^T dX X^T: differentiating through an inverse
would cost two more). Two implementations, chosen from the platform and C
(`inverse_implementation`):

- "pallas" (a TPU, C a multiple of 8 and at most 128): a kernel that reads a
  block of 128 matrices into VMEM, lays them out a matrix a lane, inverts the
  8 x 8 diagonal blocks by row substitution and merges them level by level by
  the 2 x 2 block rule X21 = -X22 A21 X11 (8 -> 16 -> 32 -> 64), all in
  float32 on the vector unit, and writes the inverses once. Not a power
  series (I - A)(I + A^2)(I + A^4)...: with keys that share a direction its
  products grow and cancel, and in float32 its error reached 10 and 200 times
  the inverse's largest entry; the block rule computes the solve's inverse in
  another order, to the solve's round-off.
- "solve" (anywhere else): XLA's batched triangular solve against I, the CPU
  path and the kernel's oracle in the tests. On a TPU it is a custom call that
  inverts the diagonal blocks, with relayouts and a product by I around it:
  6.5 ms for 2,048 chunks of 64 on a v5e, where the kernel takes 0.26 ms.

The inverse carries the name
`INVERSE` for `jax.checkpoint` policies: a caller that recomputes this rule in
the backward pass can keep the C x C inverses (a sixteenth of the rule's
inputs) and skip the loop the second time. Everything else is differentiated
by JAX: the backward pass is the transpose of this program, a reversed scan
over the same chunks. The registry's counter `model_delta_inverse_kernel_total`
advances once for every rule traced onto the kernel.

All arrays float32. q and k come in already L2-normalised (and q scaled):
normalisation belongs to the mixer, not to the rule.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.scipy.linalg import solve_triangular

from ..obs import registry as obs_registry

CHUNK = 64  # the published kernel's
INVERSE = "delta_rule_inverse"
_HIGHEST = jax.lax.Precision.HIGHEST

# the kernel's diagonal blocks, inverted by row substitution: one sublane tile
BASE = 8
# matrices a grid step: the lanes of the kernel's working layout
BLOCK = 128
_VMEM_LIMIT_BYTES = 100 * 1024 * 1024


def inverse_implementation(C: int) -> str:
    """"pallas" on a TPU where C is a multiple of BASE and at most 128,
    "solve" everywhere else."""
    if not _on_tpu() or C % BASE or C > 128:
        return "solve"
    return "pallas"


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def unit_lower_inverse(A):
    """(I + A)^{-1} for strictly lower triangular A [..., C, C]. The inverse
    sees no gradient; the derivative -X dA X rides on a term whose value is
    exactly zero, so the backward pass is two small matmuls and no inverse.
    The inverse carries the name INVERSE."""
    fixed = jax.lax.stop_gradient(A)
    impl = inverse_implementation(A.shape[-1])
    if impl == "solve":
        X = solve_inverse(fixed)
    else:
        obs_registry.default().counter("model_delta_inverse_kernel_total").inc()
        X = kernel_inverse(fixed, interpret=impl == "interpret")
    X = checkpoint_name(X, INVERSE)
    return X - jnp.matmul(jnp.matmul(X, A - fixed, precision=_HIGHEST), X, precision=_HIGHEST)


def solve_inverse(A):
    """XLA's batched triangular solve of (I + A) X = I."""
    eye = jnp.eye(A.shape[-1], dtype=A.dtype)
    return solve_triangular(A + eye, jnp.broadcast_to(eye, A.shape),
                            lower=True, unit_diagonal=True)


def kernel_inverse(A, *, block: int = BLOCK, interpret: bool = False):
    """The kernel's inverse of A [..., C, C]: the leading dimensions flattened
    and padded to whole blocks (a zero A inverts to I), then restored."""
    C = A.shape[-1]
    flat = A.reshape((-1, C, C)).astype(jnp.float32)
    M = flat.shape[0]
    pad = (-M) % block
    if pad:
        flat = jnp.pad(flat, ((0, pad), (0, 0), (0, 0)))
    return delta_inverse_call(flat, block=block, interpret=interpret)[:M].reshape(A.shape)


def _merges(C: int):
    """The 2 x 2 block merges, level by level: (start, rows above, rows
    below), from the diagonal blocks of BASE up to the whole C."""
    blocks = [(o, BASE) for o in range(0, C, BASE)]
    out = []
    while len(blocks) > 1:
        level = []
        for n in range(0, len(blocks) - 1, 2):
            (o, p), (_, q) = blocks[n], blocks[n + 1]
            out.append((o, p, q))
            level.append((o, p + q))
        if len(blocks) % 2:
            level.append(blocks[-1])
        blocks = level
    return out


def _inverse_kernel(a_ref, x_ref, at_ref, xt_ref, t_ref):
    """A block of L matrices [L, C, C] -> their inverses. The work is done
    lane-dense, a matrix a lane: at[i, j, :] = A[:, i, j], xt likewise, so
    every product is float32 arithmetic on the vector unit over L matrices at
    once, and a row of a matrix is a [C, L] slab."""
    L, C = a_ref.shape[0], a_ref.shape[1]
    for i in range(C):
        at_ref[i] = a_ref[:, i, :].T
    xt_ref[...] = jnp.zeros(xt_ref.shape, jnp.float32)

    def a(i, j):  # A[:, i, j] as a row of lanes
        return at_ref[i, pl.ds(j, 1), :]

    def x(i, j):
        return xt_ref[i, pl.ds(j, 1), :]

    # the diagonal blocks by row substitution: X_r = e_r - sum_{j<r} A_rj X_j
    unit = jax.lax.broadcasted_iota(jnp.int32, (BASE, L), 0)

    def diagonal(n, carry):
        o = pl.multiple_of(n * BASE, BASE)
        for r in range(BASE):
            row = jnp.where(unit == r, 1.0, 0.0).astype(jnp.float32)
            for j in range(r):
                row = row - a(o + r, o + j) * xt_ref[o + j, pl.ds(o, BASE), :]
            xt_ref[o + r, pl.ds(o, BASE), :] = row
        return carry

    jax.lax.fori_loop(0, C // BASE, diagonal, 0)

    # X21 = -X22 (A21 X11), level by level: T = A21 X11 into t, then the
    # rows of X22 T (X22 is unit lower triangular and zero above it)
    for o, p, q in _merges(C):
        def product(r, carry, o=o, p=p):
            t = jnp.zeros((p, L), jnp.float32)
            for j in range(p):
                t = t + a(o + p + r, o + j) * xt_ref[o + j, pl.ds(o, p), :]
            t_ref[r, pl.ds(0, p), :] = t
            return carry

        def merge(r, carry, o=o, p=p, q=q):
            row = jnp.zeros((p, L), jnp.float32)
            for k in range(q):
                row = row - x(o + p + r, o + p + k) * t_ref[k, pl.ds(0, p), :]
            xt_ref[o + p + r, pl.ds(o, p), :] = row
            return carry

        jax.lax.fori_loop(0, q, product, 0)
        jax.lax.fori_loop(0, q, merge, 0)

    for i in range(C):
        x_ref[:, i, :] = xt_ref[i].T


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def delta_inverse_call(A, *, block: int, interpret: bool):
    """(I + A)^{-1} of A [M, C, C] float32, strictly lower triangular, M a
    multiple of `block` (a multiple of 128: the matrices are the lanes)."""
    M, C = A.shape[0], A.shape[-1]
    spec = pl.BlockSpec((block, C, C), lambda m: (m, 0, 0))
    lanes = pltpu.VMEM((C, C, block), jnp.float32)
    return pl.pallas_call(
        _inverse_kernel,
        grid=(M // block,),
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(A.shape, jnp.float32),
        scratch_shapes=[lanes, lanes, lanes],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",),
                                             vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(A)


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
