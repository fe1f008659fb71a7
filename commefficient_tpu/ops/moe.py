"""Expert parallelism: Switch-Transformer-style top-1 mixture-of-experts FFN
with the expert axis sharded over the mesh.

The reference has no MoE (its models are ResNet-9 and GPT-2-small —
SURVEY.md §2); this op completes the rebuild's parallelism coverage
(dp/tp/sp/pp/ep) the TPU-native way: routing is expressed as dense one-hot
dispatch/combine einsums (the GShard/Switch recipe — no gather/scatter, no
dynamic shapes, capacity overflow dropped), so sharding the expert axis of
the dispatched activations and expert weights over the mesh turns the
einsums into an all-to-all + per-device expert matmuls, all inserted by XLA
from the shardings alone.

Semantics (top-1, capacity factor c):
- router logits [T, E] -> gate = softmax; expert = argmax.
- each expert processes at most C = ceil(c * T / E) tokens (position within
  the expert's queue via a cumsum over arrival order); overflow tokens pass
  through unchanged (standard Switch behavior).
- output = gate * expert_out + (1 - routed) * x  (dropped tokens keep x).
- aux load-balancing loss = E * sum_e f_e * p_e (Switch eq. 4), returned so
  callers can add `aux_coef * aux` to their objective.
"""

from __future__ import annotations

import functools
import math
from typing import Callable

import jax
import jax.numpy as jnp


def top1_dispatch(router_logits: jnp.ndarray, capacity: int):
    """Dispatch/combine tensors for top-1 routing.

    router_logits: [T, E]. Returns (dispatch [T, E, C] bool-ish float,
    combine [T, E, C] float, aux scalar). Token t occupies slot
    (its arrival position among tokens routed to e) in expert e's queue iff
    that position < capacity.
    """
    T, E = router_logits.shape
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)  # [T, E]
    expert = jnp.argmax(probs, axis=-1)  # [T]
    onehot = jax.nn.one_hot(expert, E, dtype=jnp.float32)  # [T, E]
    # position of each token in its expert's queue (arrival order)
    pos = jnp.cumsum(onehot, axis=0) * onehot - onehot  # [T, E]; 0-based
    kept = onehot * (pos < capacity)  # [T, E]
    slot = jax.nn.one_hot(pos.sum(-1).astype(jnp.int32), capacity, dtype=jnp.float32)
    dispatch = kept[:, :, None] * slot[:, None, :]  # [T, E, C]
    gate = (probs * kept).sum(-1)  # [T]
    combine = dispatch * gate[:, None, None]
    # Switch load-balancing aux: E * sum_e (fraction routed to e) * (mean prob e)
    frac = onehot.mean(0)
    mean_p = probs.mean(0)
    aux = E * jnp.sum(frac * mean_p)
    return dispatch, combine, aux


def moe_ffn(
    x: jnp.ndarray,
    router_w: jnp.ndarray,
    expert_params,
    expert_fn: Callable,
    *,
    capacity_factor: float = 1.25,
):
    """Top-1 MoE FFN over tokens x [T, D].

    `expert_params` leaves have leading axis [E] (shard it over the mesh's
    expert axis; with x replicated or batch-sharded, XLA lowers the dispatch
    einsum to an all-to-all). `expert_fn(params_e, h [C, D]) -> [C, D]`
    applies one expert. Returns (y [T, D], aux).

    Capacity overflow and unrouted mass degrade to identity (residual MoE
    blocks add x outside), matching Switch's pass-through behavior.
    """
    T, D = x.shape
    E = jax.tree.leaves(expert_params)[0].shape[0]
    C = max(1, math.ceil(capacity_factor * T / E))
    logits = x.astype(jnp.float32) @ router_w  # [T, E]
    dispatch, combine, aux = top1_dispatch(logits, C)
    # [T, E, C] x [T, D] -> [E, C, D]: expert-major queues
    h = jnp.einsum("tec,td->ecd", dispatch, x.astype(jnp.float32))
    y = jax.vmap(expert_fn)(expert_params, h)  # [E, C, D]
    out = jnp.einsum("tec,ecd->td", combine, y.astype(jnp.float32))
    routed = combine.sum((1, 2))  # [T] gate mass that actually landed
    out = out + (1.0 - routed)[:, None] * x.astype(jnp.float32)
    return out.astype(x.dtype), aux


def dense_oracle(x, router_w, expert_params, expert_fn):
    """Every token through its argmax expert with NO capacity limit — the
    correctness oracle moe_ffn must match when capacity is not binding."""
    T, D = x.shape
    probs = jax.nn.softmax(x.astype(jnp.float32) @ router_w, axis=-1)
    expert = jnp.argmax(probs, axis=-1)
    gate = jnp.take_along_axis(probs, expert[:, None], axis=1)[:, 0]
    # run EVERY expert on ALL tokens, select after (oracle only — O(E*T*D))
    all_y = jax.vmap(lambda p: expert_fn(p, x.astype(jnp.float32)))(expert_params)
    sel = all_y[expert, jnp.arange(T)]  # [T, D]
    # same residual convention as moe_ffn: (1 - gate) of every token's mass
    # stays on x (no token is dropped here, so routed == gate)
    return (gate[:, None] * sel + (1.0 - gate)[:, None] * x.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Top-k routing over the experts held here (one chip's share of an
# expert-parallel deployment), no dropped token.
#
# The router keeps its published width: every token scores all E experts and
# takes its k best with renormalised weights. This chip holds the experts
# [first, first + count) and computes their part of the result; what absent
# experts would have added is left out (no code stands in for other chips or
# their exchange). The token-to-expert assignments are sorted by expert, held
# ones first, and the held experts' matmuls run grouped over the sorted rows
# (`jax.lax.ragged_dot`: on a TPU a tiled kernel), a block of assignments at a
# time, for as many blocks as assignments landed here: a loop whose trip count
# is data, so the cost follows the assignments that land here and not E, and
# no assignment can be dropped however unevenly the router spreads them.
# ---------------------------------------------------------------------------

BLOCK_ROWS = 1024  # the unit a block is sized in


def held_block_rows(assignments: int, held: int, experts: int) -> int:
    """Rows of one block of sorted assignments: whole units of BLOCK_ROWS that
    hold one and a half times what a uniform router sends to the `held` of
    `experts` experts. A pass over a block costs the held experts' weights
    (read forward, recomputed and backward, their gradient added) whatever
    its rows, so a typical client and layer should take one pass: at 2,048
    tokens, top-10 of 512 and 16 held 640 land here (1,024 rows), at top-4
    of 64 and 8 held 1,024 (2,048 rows: with 1,024 about half the clients and
    layers took two passes and a round's time followed the seed by 2%)."""
    return BLOCK_ROWS * max(1, math.ceil(1.5 * assignments * held / experts / BLOCK_ROWS))


def _sequential_vmap(fn):
    """`fn` as a `custom_vmap` whose batching rule takes the batch one element
    at a time. The loop over blocks must stay a loop whose length is one
    client's data (under a plain `vmap` it would run every client for the
    longest), the TPU's ragged-dot kernel takes no batch dimension, and under
    the engine's `vmap` over clients the weights' cotangents are batched while
    the weights are not."""
    wrapped = jax.custom_batching.custom_vmap(fn)

    @wrapped.def_vmap
    def rule(axis_size, in_batched, *args):
        def one(i):
            return wrapped(*jax.tree.map(lambda a, b: a[i] if b else a, args, tuple(in_batched)))

        out = jax.lax.map(one, jnp.arange(axis_size))
        return out, jax.tree.map(lambda _: True, out)

    return wrapped


def _grouped(x, w, group_sizes):
    """x [M, K] with its rows sorted by group, w [G, K, N], group_sizes [G]
    (sum <= M) -> [M, N]: row i times the matrix of its group. `ragged_dot`
    leaves the rows past the last group undefined: they are zeroed."""
    out = jax.lax.ragged_dot(x, w, group_sizes)
    return jnp.where(jnp.arange(x.shape[0])[:, None] < group_sizes.sum(), out, 0.0)


def _block_rows(rows, experts, weight, group_sizes):
    """One block of sorted assignments through their experts:
    weight * down(silu(gate x) * up x), row by row."""
    h = jax.nn.silu(_grouped(rows, experts["gate"], group_sizes)) * _grouped(
        rows, experts["up"], group_sizes)
    return _grouped(h, experts["down"], group_sizes) * weight[:, None]


def _blocks(token, weight, group_sizes, block_rows):
    """(number of blocks that hold an assignment, b -> (tokens, weights, group
    sizes) of block b). The groups' rows are contiguous from row 0, so a
    block's share of each group is a clipped difference of offsets."""
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes

    def block(b):
        lo = b * block_rows
        clip = lambda a: jnp.clip(a, lo, lo + block_rows)  # noqa: E731
        return (jax.lax.dynamic_slice(token, (lo,), (block_rows,)),
                jax.lax.dynamic_slice(weight, (lo,), (block_rows,)),
                (clip(ends) - clip(starts)).astype(jnp.int32))

    return (ends[-1] + block_rows - 1) // block_rows, block


def _held_forward(x, experts, token, weight, group_sizes, block_rows):
    count, block = _blocks(token, weight, group_sizes, block_rows)

    def add_block(b, y):
        tok, w, sizes = block(b)
        return y.at[tok].add(_block_rows(x[tok], experts, w, sizes))

    return jax.lax.fori_loop(0, count, add_block, jnp.zeros_like(x))


def _held_backward(x, experts, token, weight, group_sizes, dy, block_rows):
    """Cotangents of (x, experts, weight), block by block: each block's
    forward is recomputed and transposed by JAX."""
    count, block = _blocks(token, weight, group_sizes, block_rows)

    def add_block(b, carry):
        dx, dexperts, dweight = carry
        tok, w, sizes = block(b)
        _, transpose = jax.vjp(lambda r, e, w_: _block_rows(r, e, w_, sizes), x[tok], experts, w)
        drows, de, dw = transpose(dy[tok])
        # the transposed products leave rows past the last group undefined too
        drows = jnp.where(jnp.arange(block_rows)[:, None] < sizes.sum(), drows, 0.0)
        return (dx.at[tok].add(drows), jax.tree.map(jnp.add, dexperts, de),
                jax.lax.dynamic_update_slice(dweight, dw, (b * block_rows,)))

    init = (jnp.zeros_like(x), jax.tree.map(jnp.zeros_like, experts), jnp.zeros_like(weight))
    return jax.lax.fori_loop(0, count, add_block, init)


@functools.cache
def _held_experts(block_rows: int):
    """held(x [T, D], experts, token [M], weight [M], group_sizes [G]) ->
    y [T, D]: y[token[i]] += weight[i] * E_group(i)(x[token[i]]) over the
    assignments i that the groups cover (M a multiple of block_rows, sorted by
    group). The gradient is written out so that forward and backward each are
    one `_sequential_vmap` leaf with a data-dependent loop inside."""
    forward = _sequential_vmap(functools.partial(_held_forward, block_rows=block_rows))
    backward = _sequential_vmap(functools.partial(_held_backward, block_rows=block_rows))

    @jax.custom_vjp
    def held(x, experts, token, weight, group_sizes):
        return forward(x, experts, token, weight, group_sizes)

    def fwd(x, experts, token, weight, group_sizes):
        return forward(x, experts, token, weight, group_sizes), (x, experts, token, weight, group_sizes)

    def bwd(res, dy):
        x, experts, token, weight, group_sizes = res
        dx, dexperts, dweight = backward(x, experts, token, weight, group_sizes, dy)
        return dx, dexperts, None, dweight, None

    held.defvjp(fwd, bwd)
    return held


def _router_logits(x, router_w):
    """The one matmul that runs at `highest` precision: at a TPU's default a
    float32 matmul rounds its inputs to bfloat16, and which expert comes
    k-th and which next is a discrete outcome."""
    return jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)


def topk_route(x, router_w, k: int):
    """x [T, D], router_w [D, E] -> (experts [T, k] int32, weights [T, k]):
    softmax over all E in float32, the k largest, weights renormalised to
    sum 1 (Qwen3-Next's rule, and `topk_moe_ffn`'s when it is given none)."""
    top, experts = jax.lax.top_k(jax.nn.softmax(_router_logits(x, router_w), axis=-1), k)
    return experts.astype(jnp.int32), top / top.sum(-1, keepdims=True)


def sigmoid_topk_route(x, router_w, k: int, bias=None, scale: float = 1.0,
                       eps: float = 1e-20):
    """The `noaux_tc` rule with one group (DeepSeek-V3's, GLM-4.7's; LFM2's
    with `eps` 1e-6): every expert scored by sigmoid on its own, the k largest
    of score + `bias` chosen ([E], a buffer that balances load and that no
    gradient reaches: the choice is discrete), and the UNBIASED scores of the
    chosen renormalised (over their sum + `eps`) and multiplied by `scale`."""
    scores = jax.nn.sigmoid(_router_logits(x, router_w))
    _, experts = jax.lax.top_k(scores if bias is None else scores + bias, k)
    top = jnp.take_along_axis(scores, experts, axis=-1)
    return experts.astype(jnp.int32), top / (top.sum(-1, keepdims=True) + eps) * scale


def topk_moe_ffn(x, router_w, expert_params, held: tuple[int, int], k: int,
                 block_rows: int | None = None, route: Callable = topk_route):
    """Top-k expert layer over tokens x [T, D], for the experts held here.

    `expert_params` = {"gate": [G, D, F], "up": [G, D, F], "down": [G, F, D]}
    are the G = held[1] experts with ids held[0] .. held[0] + G - 1 of the
    E = router_w.shape[1] the router scores; E(x) = down(silu(gate x) * up x).
    `route(x, router_w, k) -> (experts [T, k], weights [T, k])` is the model's
    routing rule (`topk_route`, or `sigmoid_topk_route` with its bias and
    scale bound); everything after it is one path for every rule.
    `block_rows` defaults to `held_block_rows` of the shapes.
    Returns (y [T, D], counts): y = sum over a token's chosen experts that
    are held here of weight * E(x); counts = {"assignments": T * k,
    "assignments_held", "expert_load_max"} as float32 scalars, and "experts",
    the [T, k] choices themselves.
    """
    T, D = x.shape
    first, G = held
    if block_rows is None:
        block_rows = held_block_rows(T * k, G, router_w.shape[1])
    with jax.named_scope("moe_route"):
        experts, weights = route(x, router_w, k)
        local = experts.reshape(-1) - first
        here = (local >= 0) & (local < G)
        key = jnp.where(here, local, G)  # absent experts sort last
        order = jnp.argsort(key, stable=True)
        group_sizes = (key[:, None] == jnp.arange(G)[None, :]).sum(0).astype(jnp.int32)
        pad = (-T * k) % block_rows
        token = jnp.pad(order // k, (0, pad)).astype(jnp.int32)
        # rows past the last group come out of the experts as zeros, whatever their weight
        weight = jnp.pad(weights.reshape(-1).astype(x.dtype)[order], (0, pad))
    with jax.named_scope("moe_experts"):
        y = _held_experts(block_rows)(x, expert_params, token, weight, group_sizes)
    counts = {"assignments": jnp.float32(T * k),
              "assignments_held": group_sizes.sum().astype(jnp.float32),
              "expert_load_max": group_sizes.max().astype(jnp.float32),
              "experts": experts}
    return y, counts


def topk_dense_oracle(x, router_w, expert_params, held: tuple[int, int], k: int,
                      route: Callable = topk_route):
    """Every held expert over ALL tokens, selected after: what topk_moe_ffn
    must equal (O(G T D F); tests only)."""
    first, G = held
    experts, weights = route(x, router_w, k)
    ids = first + jnp.arange(G)
    gate = (weights[:, :, None] * (experts[:, :, None] == ids[None, None, :])).sum(1)  # [T, G]
    every = jax.vmap(lambda g, u, d: (jax.nn.silu(x @ g) * (x @ u)) @ d)(
        expert_params["gate"], expert_params["up"], expert_params["down"])  # [G, T, D]
    return jnp.einsum("tg,gtd->td", gate.astype(x.dtype), every)
