"""Pipeline parallelism: GPipe-style microbatched stage execution over a
`pipe` mesh axis.

The reference has no pipeline parallelism (its models fit one device —
SURVEY.md §2 "Parallelism strategies present"); this op makes layer-sharded
execution available to the rebuild's larger-model paths the TPU-native way:
one compiled program, `shard_map` over the pipe axis, activations flowing
stage s -> s+1 by `ppermute` each step, a `lax.scan` over the
fill-drain schedule. Backward works by autodiff (the transpose of a
ppermute is the reverse ppermute), so `jax.grad` through `pipeline_apply`
yields the standard GPipe backward with no special handling.

Layout contract:
- `stage_params`: pytree whose leaves have leading axis [S] (one slice per
  stage), sharded `P("pipe")` on the mesh. Each stage applies
  `stage_fn(stage_slice, x)` — typically a scan over that stage's layers.
- `x`: [M, mb, ...] microbatches, replicated. Returns [M, mb, ...].

Schedule: T = M + S - 1 steps. At step t, stage 0 ingests microbatch t (if
t < M); every stage applies its layers to the buffer it holds; buffers
rotate one stage forward; the LAST stage's output at step t is microbatch
t - (S-1), written into the output buffer when valid. Bubble fraction is
(S-1)/T, the usual GPipe fill/drain cost — pick M >= 4*S in practice.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(
    stage_fn: Callable,
    stage_params,
    x: jnp.ndarray,
    *,
    mesh: Mesh,
    axis: str = "pipe",
):
    """Run microbatches [M, mb, ...] through S pipeline stages; see module
    docstring. `stage_fn(params_slice, x_mb) -> y_mb` applies ONE stage's
    layers (shapes of x_mb and y_mb must match — residual-block style)."""
    S = mesh.shape[axis]
    M = x.shape[0]
    if M < 1:
        raise ValueError("need at least one microbatch")
    for leaf in jax.tree.leaves(stage_params):
        if leaf.shape[0] != S:
            raise ValueError(
                f"stage_params leading axis must equal the {S}-stage pipe "
                f"axis, got {leaf.shape[0]} — per-layer stacks go through "
                "stack_stages(params, num_stages) first"
            )

    def per_stage(params, xs):
        # params: stage's slice, leading axis [1]; xs: [M, mb, ...] (full copy)
        stage = jax.lax.axis_index(axis)
        my_params = jax.tree.map(lambda a: a[0], params)
        # carries become device-varying (axis_index use) — mark them varying
        # up front so scan/where types agree (same dance as ring attention)
        varying = lambda a: jax.lax.pcast(a, (axis,), to="varying")  # noqa: E731
        buf = varying(jnp.zeros_like(xs[0]))
        out = varying(jnp.zeros_like(xs))
        fwd = [(i, (i + 1) % S) for i in range(S)]

        def step(carry, t):
            buf, out = carry
            # stage 0 ingests microbatch t (clamped; masked by validity)
            ingest = jnp.where(t < M, t, M - 1)
            buf = jnp.where(stage == 0, xs[ingest], buf)
            y = stage_fn(my_params, buf)
            # last stage completed microbatch t-(S-1) this step; record it
            # (unconditional masked write — a varying predicate can't drive
            # lax.cond)
            done_idx = t - (S - 1)
            valid = (stage == S - 1) & (done_idx >= 0)
            idx = jnp.maximum(done_idx, 0)
            cur = jax.lax.dynamic_index_in_dim(out, idx, axis=0, keepdims=False)
            out = jax.lax.dynamic_update_index_in_dim(
                out, jnp.where(valid, y, cur), idx, axis=0
            )
            # rotate buffers one stage forward (stage 0 receives garbage from
            # the last stage; it is overwritten by the next ingest)
            buf = jax.lax.ppermute(y, axis, fwd)
            return (buf, out), None

        (buf, out), _ = jax.lax.scan(step, (buf, out), jnp.arange(M + S - 1))
        # every stage holds a copy of `out`, but only the last stage's is
        # real — broadcast it so out_specs can be replicated
        out = jax.lax.psum(
            jnp.where(stage == S - 1, out, jnp.zeros_like(out)), axis
        )
        return out

    pspec = jax.tree.map(lambda _: P(axis), stage_params)
    return jax.shard_map(
        per_stage,
        mesh=mesh,
        in_specs=(pspec, P()),
        out_specs=P(),
    )(stage_params, x)


def stack_stages(per_layer_params, num_stages: int):
    """[L, ...] per-layer stacked params -> [S, L//S, ...] per-stage slices
    (stage s owns layers s*L//S .. (s+1)*L//S - 1)."""

    def reshape(a):
        L = a.shape[0]
        if L % num_stages:
            raise ValueError(f"{L} layers not divisible by {num_stages} stages")
        return a.reshape(num_stages, L // num_stages, *a.shape[1:])

    return jax.tree.map(reshape, per_layer_params)


def scan_stage(layer_fn: Callable):
    """Lift a per-layer fn into a stage fn: scans the stage's [Lps, ...]
    layer slice over the activation."""

    def stage_fn(stage_params, x):
        def body(h, layer_params):
            return layer_fn(layer_params, h), None

        y, _ = jax.lax.scan(body, x, stage_params)
        return y

    return stage_fn
