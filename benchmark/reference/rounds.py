"""Follow the first federated rounds plainly: cohort mean gradient with weight
decay, then FetchSGD's sketched server step or the dense momentum step.

Everything comes in from the benchmark: the model's loss (a plain function of
this package), the seeded weights, the seeded federation, the cohorts the
program drew (client ids only) and the learning rates of the schedule. float32
arrays at the backend's default matmul precision, which is what the
configurations state. `dtype=bfloat16` computes the same rounds with every
array in bfloat16, weights, gradients and optimiser state alike: the control,
put in the program's place.
"""

from __future__ import annotations

import contextlib
import dataclasses

import numpy as np

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree

from . import fetchsgd


@dataclasses.dataclass(frozen=True)
class Recipe:
    """What a configuration states about the optimiser."""

    mode: str  # "sketch" | "uncompressed"
    d: int
    k: int = 0
    rows: int = 0
    cols: int = 0
    hash_seed: int = 0
    momentum: float = 0.9
    weight_decay: float = 5e-4


def cohort_mean_grad(client_loss, params, batches, block: int):
    """(mean over clients of grad client_loss, sum of example losses, count).
    `batches` has a leading client axis W; clients are taken `block` at a
    time so that activations of the whole cohort never coexist."""
    W = jax.tree.leaves(batches)[0].shape[0]
    if W % block:
        raise ValueError(f"block {block} does not divide the cohort {W}")
    blocks = jax.tree.map(lambda a: a.reshape((W // block, block) + a.shape[1:]), batches)

    def one_block(carry, b):
        def total(p):
            means, sums, counts = jax.vmap(lambda cb: client_loss(p, cb))(b)
            return means.sum(), (sums.sum(), counts.sum())

        (_, (s, n)), g = jax.value_and_grad(total, has_aux=True)(params)
        gsum, ssum, nsum = carry
        return (jax.tree.map(jnp.add, gsum, g), ssum + s.astype(jnp.float32),
                nsum + n.astype(jnp.float32)), None

    init = (jax.tree.map(jnp.zeros_like, params), jnp.float32(0), jnp.float32(0))
    (gsum, ssum, nsum), _ = jax.lax.scan(one_block, init, blocks)
    return jax.tree.map(lambda g: g / W, gsum), ssum, nsum


def follow(client_loss, params0, cohort_batches, lrs, recipe: Recipe, block: int,
           dtype=jnp.float32, precision: str | None = None) -> dict:
    """Run len(lrs) rounds from params0. `cohort_batches[t]` is round t's
    batch pytree with leading client axis. Returns, on the host and in
    float32: losses[t]; snaps[n] = {"params", "Vvelocity", "Verror"} after n
    rounds (n = 1 and the last), the shape in which the harness keeps the
    program's state; the first round's gradient as the optimiser gets it (the
    r x c sketch, or the dense vector) and its norms leaf by leaf."""
    cast = lambda a: a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating) else a  # noqa: E731
    flat0, unravel = ravel_pytree(jax.tree.map(lambda a: cast(jnp.asarray(a)), params0))
    cohort_batches = [jax.tree.map(cast, b) for b in cohort_batches]
    wd, rho = recipe.weight_decay, recipe.momentum
    cs = None
    if recipe.mode == "sketch":
        cs = fetchsgd.CountSketch(recipe.d, recipe.rows, recipe.cols, recipe.hash_seed)
        V = jnp.zeros((recipe.rows, recipe.cols), dtype)
        E = jnp.zeros_like(V)
    else:
        V = jnp.zeros_like(flat0)
        E = jnp.zeros_like(flat0)

    @jax.jit
    def grad_round(flat, batches):
        g, ssum, nsum = cohort_mean_grad(client_loss, unravel(flat), batches, block)
        return ravel_pytree(g)[0] + wd * flat, ssum / nsum

    @jax.jit
    def sketch_round(flat, gflat, V, E, lr):
        S = cs.accumulate(gflat)
        idx, vals, V, E = fetchsgd.sketch_server_step(cs, recipe.k, rho, S, V, E, lr)
        return flat.at[idx].add(-vals), V, E, S

    @jax.jit
    def dense_round(flat, gflat, V, lr):
        delta, V = fetchsgd.dense_server_step(rho, gflat, V, lr)
        return flat - delta, V

    def host(flat, V, E):
        f32 = lambda a: np.asarray(a.astype(jnp.float32))  # noqa: E731
        return {"params": jax.tree.map(f32, unravel(flat)), "Vvelocity": f32(V),
                "Verror": f32(E)}

    flat, losses, first, snaps = flat0, [], None, {}
    ctx = jax.default_matmul_precision(precision) if precision else contextlib.nullcontext()
    with ctx:
        for t, lr in enumerate(lrs):
            gflat, loss = grad_round(flat, cohort_batches[t])
            losses.append(float(loss))
            lr = jnp.asarray(lr, dtype)
            if recipe.mode == "sketch":
                flat, V, E, got = sketch_round(flat, gflat, V, E, lr)
            else:
                flat, V = dense_round(flat, gflat, V, lr)
                got = gflat
            if t == 0:
                first = {"optimizer_input": np.asarray(got.astype(jnp.float32)),
                         "grad_leaf_norms": leaf_norms(unravel(gflat))}
            if t == 0 or t == len(lrs) - 1:
                snaps[t + 1] = host(flat, V, E)
    return {"losses": losses, "first": first, "snaps": snaps}


def leaf_norms(tree) -> np.ndarray:
    return np.asarray([float(jnp.linalg.norm(jnp.ravel(jnp.asarray(x)).astype(jnp.float32)))
                       for x in jax.tree.leaves(tree)])
