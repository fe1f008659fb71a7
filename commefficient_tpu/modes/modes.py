"""Compression-mode transforms — the `functions.py` equivalent (SURVEY.md L2).

Every mode is expressed as three pure functions over static-shape arrays so the
whole round compiles into one XLA program:

- `client_compress(cfg, update, cstate) -> (wire, cstate')` — per-client
  transform of the raw update (gradient, or weight delta for fedavg/localSGD).
- `aggregate(cfg, wires) -> agg` — combine the W sampled clients' wires
  (leading axis W). Linear modes reduce with a mean that XLA lowers to
  `psum`-style collectives over the client-sharded mesh axis.
- `server_step(cfg, agg, sstate, lr) -> (delta, sstate')` — server momentum +
  error feedback per mode; `delta` is the dense [d] vector to *subtract* from
  the flat parameters.

Server/virtual state (`Vvelocity`, `Verror` — dense [d] vectors, or [r, c]
sketch tables for mode=sketch) matches the reference's `FedOptimizer` state
(SURVEY.md §2 "Fed API + server"); the sketch-mode algebra is FetchSGD Alg. 1
(SURVEY.md §3.1): momentum and error feedback live in sketch space, top-k is
extracted via `unSketch`, and the extracted sketch is subtracted from both
error and momentum ("momentum factor masking").

Wire formats (pytrees with static shapes):
    dense:  {"dense": [d]}
    sketch: {"table": [r, c]}
    sparse: {"idx": [k] int32, "vals": [k]}   (idx = -1 padding allowed)

For linear modes (sketch, true_topk, uncompressed, fedavg — sketching and
averaging commute) the engine may compress once on the client-mean update
instead of per client; `is_linear` advertises this. local_topk is the
nonlinear one: top-k per client, then average of sparse vectors.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..sketch import csvec
from .config import ModeConfig


def topk_dense(
    v: jnp.ndarray, k: int, impl: str = "exact", recall: float = 0.95
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(idx[k], vals[k]) of the k largest-|.| coordinates of dense v.

    impl="approx" uses `lax.approx_max_k` (TPU PartialReduce lowering at
    `recall`; exact on backends without the lowering), and where the
    reduction leaves enough partial maxima picks the exact k largest of
    them by selection, not by approx_max_k's sort of them all
    (csvec.topk_abs). impl="exact" no longer sorts all of v at d in the
    millions (csvec.select_topk_abs).
    The paper-scale 2x2 seed replication found exact-vs-approx@0.99
    accuracy differences within seed variance (results/README.md);
    ModeConfig.topk_recall exposes the dial.

    impl="oversample": approx preselect of 4k candidates + exact top_k
    over them. approx_max_k's misses concentrate near the selection
    boundary, so the true top-k (comfortably inside a 4x-oversampled
    candidate set) survive preselection with probability ~1 — near-exact
    selection at PartialReduce speed (the exact refine sorts only 4k
    elements)."""
    idx = csvec.topk_abs(v, k, impl=impl, recall=recall)
    return idx, v[idx]


def is_linear(cfg: ModeConfig) -> bool:
    return cfg.mode != "local_topk"


# ---------------------------------------------------------------- state init


def init_server_state(cfg: ModeConfig) -> dict:
    """Vvelocity / Verror, shaped for the mode. Always present (zeros) so the
    step signature is mode-independent; unused pieces are never touched.
    server_state="sketch" keeps the state as r x c tables for the top-k
    release modes too (see ModeConfig.server_state) — O(r*c) server memory
    instead of O(2d)."""
    if cfg.mode == "sketch" or cfg.server_state == "sketch":
        shape = cfg.sketch_spec.table_shape
    else:
        shape = (cfg.d,)
    # two distinct buffers — the step donates its input state, and donating
    # one aliased buffer twice is an XLA error
    return {
        "Vvelocity": jnp.zeros(shape, dtype=jnp.float32),
        "Verror": jnp.zeros(shape, dtype=jnp.float32),
    }


def init_client_state(cfg: ModeConfig, num_clients: int | None = None) -> dict | None:
    """[num_clients, d] error/momentum for client-local state (local_topk with
    local error feedback). This is the reference's memory wall (SURVEY.md
    §3.3); shard it over the client mesh axis at scale."""
    if not cfg.needs_local_state:
        return None
    n = num_clients if num_clients is not None else cfg.num_clients
    if n <= 0:
        raise ValueError("local state requires num_clients > 0")
    out = {}
    if cfg.error_type == "local":
        out["error"] = jnp.zeros((n, cfg.d), dtype=jnp.float32)
    if cfg.momentum_type == "local":
        out["momentum"] = jnp.zeros((n, cfg.d), dtype=jnp.float32)
    return out


def empty_client_row(cfg: ModeConfig) -> dict:
    """A zero per-client state row (for modes without local state the engine
    passes this through untouched)."""
    out = {}
    if cfg.needs_local_state:
        if cfg.error_type == "local":
            out["error"] = jnp.zeros((cfg.d,), dtype=jnp.float32)
        if cfg.momentum_type == "local":
            out["momentum"] = jnp.zeros((cfg.d,), dtype=jnp.float32)
    return out


# ------------------------------------------------------------ client side


def client_compress(cfg: ModeConfig, update: jnp.ndarray, cstate: dict) -> tuple[dict, dict]:
    """Per-client transform of the raw update (flat [d]).

    `update` is the client's gradient (grad-based modes) or its weight delta
    w_start - w_local (fedavg/localSGD); `cstate` is this client's slice of
    the local state (possibly empty dict).
    """
    if cfg.mode == "sketch":
        return {"table": csvec.sketch_vec(cfg.sketch_spec, update)}, cstate

    if cfg.mode == "local_topk":
        acc = update
        new_state = dict(cstate)
        if cfg.momentum_type == "local":
            m = cfg.momentum * cstate["momentum"] + update
            new_state["momentum"] = m
            acc = m
        if cfg.error_type == "local":
            u = cstate["error"] + acc
        else:
            u = acc
        idx, vals = topk_dense(u, cfg.k, cfg.topk_impl, cfg.topk_recall)
        if cfg.error_type == "local":
            new_state["error"] = u - csvec.to_dense(cfg.d, idx, vals)
        return {"idx": idx, "vals": vals}, new_state

    # true_topk / uncompressed / fedavg / localSGD: wire is the dense update;
    # all server-side work happens in server_step.
    return {"dense": update}, cstate


# ------------------------------------------------------------- aggregation


def bcast(w: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """Broadcast a [W] per-client weight vector against [W, ...] data."""
    return w.reshape((-1,) + (1,) * (x.ndim - 1)).astype(x.dtype)


def mask_rows(w: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """NaN-safe re-expression of `x * bcast(w, x)` for 0/1 participation /
    validity / quarantine masks: a plain multiply propagates a poisoned
    client's NaNs straight through its ZERO weight (0 * nan = nan), turning
    "this client contributes nothing" into "this client poisons the sum".
    Zero-weight rows are hard-zeroed; live rows keep the exact multiply, so
    for finite data the result is bit-identical to the multiply form."""
    wb = bcast(w, x)
    return jnp.where(wb > 0, x * wb, jnp.zeros_like(x))


def aggregate(cfg: ModeConfig, wires: dict, weights=None) -> dict:
    """Combine the W client wires (leading axis W) with cfg.agg_op (mean by
    default; sum reproduces FetchSGD Alg. 1's Σ-of-sketches with the scaling
    in the lr — see ModeConfig.agg_op). Sparse wires are densified then
    reduced — in the simulator the sparse form exists for faithful semantics
    + communication accounting, not for saving FLOPs.

    `weights` (optional) must be a [W] 0/1 participation mask (engine
    client-dropout simulation): mean divides by the SURVIVOR COUNT, clamped
    to 1 so an all-dropped round aggregates to zero. Fractional importance
    weights are NOT supported — the clamp would silently mis-normalize
    masses below 1. None = all participate."""

    def op(x):
        if weights is None:
            return jnp.sum(x, 0) if cfg.agg_op == "sum" else jnp.mean(x, 0)
        # mask_rows, not a multiply: a masked client may carry NaN/Inf (an
        # engine-quarantined poisoned update) and must still contribute an
        # exact zero
        s = mask_rows(weights, x).sum(0)
        return s if cfg.agg_op == "sum" else s / jnp.maximum(weights.sum(), 1.0)

    if cfg.mode == "sketch":
        return {"table": op(wires["table"])}
    if cfg.mode == "local_topk":
        dense = jax.vmap(lambda i, v: csvec.to_dense(cfg.d, i, v))(wires["idx"], wires["vals"])
        return {"dense": op(dense)}
    return {"dense": op(wires["dense"])}


# graftlint: robust-merge — THE declared robust-order-sensitivity boundary
# (G012): the one place order statistics run over client-stacked wires.
# Everything else in parity scope merges by the ORDERED SUM; a sort/median
# over a client axis anywhere else silently changes the aggregation
# semantics the parity pins rest on. The buffered-async composition also
# lives HERE: staleness-weighted stale tables join the order statistics
# inside this one boundary (weighted trimmed mean / weighted median over
# the union stack), so the G013 stale-wire values are sanctioned inside
# this function and nowhere else in this file.
def _robust_table_merge(stacked, live, policy: str, trim: int,
                        stale_tables=None, stale_weights=None,
                        want_residual: bool = False):
    """Coordinate-wise Byzantine-robust location estimate over the [W, ...]
    stacked client wires, dead rows (live == 0) excluded. Returns the
    robust MEAN-scale array (the caller rescales for agg_op="sum").

    - "median": per coordinate, the median over the live rows — the same
      lo/hi even-count convention as the quarantine's `_masked_median`
      (dead rows are keyed to +inf and indexed past).
    - "trimmed": per coordinate, rank the live rows (stable argsort —
      ties break by CLIENT INDEX, so the verdict is deterministic and,
      over the gathered full-cohort stack, mesh-shape-invariant), drop the
      `trim` lowest and `trim` highest LIVE values, and take the ordered
      masked sum of the survivors IN CLIENT-INDEX ORDER (the same fp
      association as the plain merge) divided by the survivor count.

    A cohort degraded below 2*trim+1 live clients keeps nothing — the
    aggregate is zero, the fully-dropped-round semantics. A live row
    carrying ANY non-finite value is excluded exactly like a dead row —
    from the order statistics AND from the live count — so a NaN table
    can neither poison the estimate nor burn a slot of the trim budget
    (an adversary pairing one NaN client with `trim` oversized clients
    must not smuggle an outlier past the trimmed window). With the
    quarantine armed, non-finite clients are already masked upstream and
    this screen is value-transparent.

    EXTENDED (buffered-async / error-feedback-aware) form — armed by
    `stale_tables`/`stale_weights` (the per-buffer robust merge) or
    `want_residual` (the error-feedback residual), returning the tuple
    ``(robust, total_weight, extras)`` instead of the bare array:

    - The order statistics run over the UNION stack {on-time cohort ∪
      staleness-weighted stale slots}: on-time tables enter at weight 1,
      stale slot i at weight ``stale_weights[i]`` ((1+lag)^-alpha, a pure
      function of round lag). Ranks are over raw VALUES (a stale outlier
      is trimmed exactly like an on-time one — the point of the
      composition); the weights shape the location estimate (weighted
      survivor mean / weighted median) and ``total_weight`` = Σ live
      weights feeds the caller's survivor normalization, the same place
      the linear stale fold's weight mass joins. Slot order — the union
      stack order, cohort positions then slot order — stays a pure
      function of the submission set, so the verdict is deterministic and
      mesh-shape-invariant. Empty slots (weight 0, zero table) are
      excluded like dead rows. With zero stale entries the weighted forms
      reduce to the unweighted ones VALUE-exactly (unit weights: the
      weighted survivor sum is the masked sum, the weighted denominator
      the survivor count, the weighted-median ranks the lo/hi ranks);
      the bitwise async==sync contract still comes from program identity
      (zero-stale rounds dispatch the plain program), not from this
      reduction.

    - `want_residual`: `extras["residual"]` is the WINSORIZED-mean-minus-
      robust residual at mean scale — the mass the robust statistic
      declined to pass this round, with every contribution clamped into
      the policy's kept window ([rank trim, rank n-trim) for "trimmed",
      the interquartile ranks for "median") before averaging, so an
      adversary's residual contribution is bounded by the clean cohort's
      value range. Accumulated into Verror by the engine (error-feedback-
      aware robust merges: honest mass the trim clipped re-enters through
      error feedback, so telescoping survives; the clamp is what keeps
      Verror — and the PR 12 `verror_ratio` estimator — bounded under a
      sustained in-screen attack)."""
    if stale_tables is None and not want_residual:
        W = stacked.shape[0]
        finite = jnp.isfinite(stacked).reshape(W, -1).all(axis=1)
        live = live * finite.astype(live.dtype)
        expand = live.reshape((-1,) + (1,) * (stacked.ndim - 1))
        keyed = jnp.where(expand > 0, stacked, jnp.inf)
        n = live.sum().astype(jnp.int32)
        if policy == "median":
            s = jnp.sort(keyed, axis=0)
            lo = jnp.clip((n - 1) // 2, 0, W - 1)
            hi = jnp.clip(n // 2, 0, W - 1)
            med = 0.5 * (jnp.take(s, lo, axis=0) + jnp.take(s, hi, axis=0))
            return jnp.where(n > 0, med, jnp.zeros_like(med))
        if policy != "trimmed":
            raise ValueError(f"unknown robust merge policy {policy!r}")
        order = jnp.argsort(keyed, axis=0, stable=True)
        ranks = jnp.argsort(order, axis=0, stable=True)  # inverse perm
        keep = (ranks >= trim) & (ranks < n - trim) & (expand > 0)
        kept = jnp.where(keep, stacked, jnp.zeros_like(stacked))
        denom = jnp.maximum((n - 2 * trim).astype(stacked.dtype), 1.0)
        return kept.sum(axis=0) / denom

    if policy not in ("median", "trimmed"):
        raise ValueError(f"unknown robust merge policy {policy!r}")
    if stale_tables is not None:
        # the union stack: on-time cohort first (client-index order), then
        # the stale slots in slot order — deterministic, submission-set-pure
        stacked = jnp.concatenate(
            [stacked, stale_tables.astype(stacked.dtype)], axis=0)
        weights = jnp.concatenate(
            [live.astype(jnp.float32), stale_weights.astype(jnp.float32)])
    else:
        weights = live.astype(jnp.float32)
    W = stacked.shape[0]
    finite = jnp.isfinite(stacked).reshape(W, -1).all(axis=1)
    w_eff = weights * finite.astype(weights.dtype)
    expand = w_eff.reshape((-1,) + (1,) * (stacked.ndim - 1))
    keyed = jnp.where(expand > 0, stacked, jnp.inf)
    n = (w_eff > 0).sum().astype(jnp.int32)
    total_w = w_eff.sum()
    order = jnp.argsort(keyed, axis=0, stable=True)
    svals = jnp.take_along_axis(keyed, order, axis=0)
    sw = jnp.take_along_axis(
        jnp.broadcast_to(expand, stacked.shape), order, axis=0)
    if policy == "median":
        # weighted median: the value where the cumulative sorted weight
        # crosses half the total (lo = first >=, hi = first >) — the
        # weighted generalization of the lo/hi even-count convention
        # (unit weights reduce to ranks (n-1)//2 and n//2 exactly)
        cum = jnp.cumsum(sw, axis=0)
        half = total_w / 2.0
        lo_idx = jnp.argmax(cum >= half, axis=0)
        hi_idx = jnp.argmax(cum > half, axis=0)
        v_lo = jnp.take_along_axis(svals, lo_idx[None], axis=0)[0]
        v_hi = jnp.take_along_axis(svals, hi_idx[None], axis=0)[0]
        med = 0.5 * (v_lo + v_hi)
        robust = jnp.where(n > 0, med, jnp.zeros_like(med))
        ok = n > 0
        win_lo = n // 4  # interquartile kept window for the residual
    else:
        ranks = jnp.argsort(order, axis=0, stable=True)
        keep = (ranks >= trim) & (ranks < n - trim) & (expand > 0)
        kept_v = jnp.where(keep, stacked * expand,
                           jnp.zeros_like(stacked))
        kept_w = jnp.where(keep, jnp.broadcast_to(expand, stacked.shape),
                           jnp.zeros_like(stacked))
        # weighted survivor mean; unit weights make the denominator the
        # survivor count (n - 2*trim) exactly
        denom = jnp.maximum(kept_w.sum(axis=0), 1e-12)
        robust = jnp.where(n > 2 * trim, kept_v.sum(axis=0) / denom, 0.0)
        ok = n > 2 * trim
        win_lo = jnp.int32(trim)
    extras: dict = {}
    if stale_tables is not None:
        extras["stale_folded"] = (stale_weights > 0).sum()
        extras["stale_weight"] = stale_weights.sum()
    if want_residual:
        # winsorized weighted mean: every live entry clamped into the kept
        # window's edge values, so the residual an adversary can inject is
        # bounded by the clean value range per coordinate
        lo_i = jnp.clip(win_lo, 0, W - 1)
        hi_i = jnp.clip(n - win_lo - 1, 0, W - 1)
        v_floor = jnp.take(svals, lo_i, axis=0)
        v_ceil = jnp.take(svals, hi_i, axis=0)
        clamped = jnp.clip(stacked, v_floor, v_ceil)
        wins = (jnp.where(expand > 0, clamped * expand,
                          jnp.zeros_like(stacked)).sum(axis=0)
                / jnp.maximum(total_w, 1e-12))
        extras["residual"] = jnp.where(ok, wins - robust,
                                       jnp.zeros_like(robust))
    return robust, total_w, extras


def merge_partial_wires(cfg: ModeConfig, stacked: dict, *,
                        policy: str = "sum", live=None,
                        trim: int = 0, stale_tables=None,
                        stale_weights=None, want_residual: bool = False):
    """Merge S per-shard partial wires (leaves stacked on a leading [S] axis,
    in shard-index order) into one wire — the cross-device reduction of the
    data-parallel round. Linear modes only: the partial wires are compressions
    of PARTIAL client sums, and linearity is exactly what makes their ordered
    sum equal the compression of the full sum.

    Sketch tables route through `csvec.merge_tables` (the documented merge
    entry point); dense wires are the same ordered sum. The ordered reduce —
    not a psum — is what lets the mesh execution and the single-device
    reference of the sharded round stay bit-identical (see merge_tables).

    `policy` != "sum" is the Byzantine-robust table merge (--merge_policy):
    the stacked leaves must then be PER-CLIENT [W, r, c] tables (mode=sketch
    — the wire-payload round shape; robust statistics over per-shard
    partial SUMS would screen shards, not clients), `live` the [W] 0/1 mask
    of clients in the merge, and the returned table is the coordinate-wise
    robust MEAN (see `_robust_table_merge`) — the caller rescales by the
    live count for agg_op="sum" instead of normalizing. "trimmed" with
    trim=0 never reaches here: the engine compiles it as "sum" by
    construction (trimming nothing IS the sum — that is the bit-identity
    contract, not an fp coincidence).

    EXTENDED robust form (buffered-async per-buffer merge and/or the
    error-feedback residual): passing `stale_tables`/`stale_weights` (the
    staleness-weighted fold slots) or `want_residual=True` forwards them
    into the boundary and returns ``({"table": robust}, total_weight,
    extras)`` instead of the bare wire — see `_robust_table_merge`'s
    extended contract. Callers only FORWARD the stale stacks here (G013);
    every piece of arithmetic over them happens inside the boundary."""
    if not is_linear(cfg):
        raise ValueError(
            f"mode={cfg.mode!r} is nonlinear: partial per-shard wires cannot "
            "be merged by addition (per-client top-k does not commute with "
            "the cross-shard sum)"
        )
    if policy != "sum":
        if cfg.mode != "sketch":
            raise ValueError(
                f"robust merge policy {policy!r} operates on per-client "
                f"Count-Sketch tables; mode={cfg.mode!r} has no table wire"
            )
        if live is None:
            raise ValueError(
                "robust merge needs the [W] live-client mask: dead rows "
                "must be excluded from the order statistics, not counted "
                "as zero-valued contributions"
            )
        W = stacked["table"].shape[0]
        if policy == "trimmed" and 2 * trim >= W:
            raise ValueError(
                f"merge_trim={trim} would trim the whole cohort "
                f"(2*{trim} >= W={W}); need 2*trim < num_workers"
            )
        if stale_tables is not None or want_residual:  # graftlint: disable=G013 — presence check routing INTO the boundary, no stale arithmetic
            robust, total_w, extras = _robust_table_merge(
                stacked["table"], live, policy, trim,
                stale_tables, stale_weights, want_residual)
            return {"table": robust}, total_w, extras
        return {"table": _robust_table_merge(
            stacked["table"], live, policy, trim)}
    if cfg.mode == "sketch":
        return {"table": csvec.merge_tables(cfg.sketch_spec, stacked["table"])}
    return {"dense": stacked["dense"].sum(axis=0)}


# ------------------------------------------------------- edge-tree merge


def edge_grouped_sum(tables: jnp.ndarray, live: jnp.ndarray,
                     assign: jnp.ndarray, n_edges: int) -> jnp.ndarray:
    """The two-tier (edge-tree) table reduction over the full [W, r, c]
    client stack: per-EDGE partials accumulated in cohort-position order,
    then the partials folded in FIXED edge-index order through
    `merge_edge_partials` — the exact arithmetic the scale-out serving
    topology performs when each edge aggregator sums its shard's tables
    and forwards ONE r x c partial to the root (serve/scale/edge.py).

    Both levels are EXPLICIT sequential folds (lax.scan — XLA honors scan's
    loop-carried order, unlike a `.sum(axis=0)` reduce whose association is
    the compiler's), and the per-client contribution is `where(live > 0,
    table, 0)` — a select, not a multiply, so no FMA contraction can round
    differently between this in-program grouping and an edge aggregator's
    own shard-local fold. That is what pins the edge-tree serving path
    BITWISE equal to the flat serving path over the same surviving cohort:
    the flat path runs THIS grouping over the full stack, the edge path
    folds edge-computed partials whose per-lane add sequence is identical
    (tests/test_scale.py). The grouping is a different fp association than
    the plain `merge_tables` ordered sum, so an edge-armed session differs
    from an unarmed one in last bits (MIGRATION.md)."""
    if tables.ndim < 1 or n_edges < 1:
        raise ValueError(
            f"edge_grouped_sum needs a [W, ...] stack and n_edges >= 1, "
            f"got shape {tables.shape}, n_edges={n_edges}")
    zero = jnp.zeros((n_edges,) + tables.shape[1:], tables.dtype)

    def fold_client(acc, x):
        t, m, e = x
        # select (never multiply): a dead row contributes an exact zero —
        # NaN-safe like mask_rows, and add-only so the per-lane sequence
        # is pure fp adds an edge's own fold reproduces bit-for-bit
        contrib = jnp.where(m > 0, t, jnp.zeros_like(t))
        return acc.at[e].add(contrib), None

    partials, _ = jax.lax.scan(
        fold_client, zero,
        (tables, live.astype(tables.dtype), assign.astype(jnp.int32)))
    return merge_edge_partials(partials)


def merge_edge_partials(partials: jnp.ndarray) -> jnp.ndarray:
    """THE edge-partial merge entry: fold the [E, r, c] per-edge partial
    tables into one [r, c] table in FIXED edge-index order (an explicit
    lax.scan left fold — sketch linearity makes the tree merge exact, the
    pinned order makes it deterministic). Shared by the edge-armed flat
    merge program (after its in-program per-edge grouping) and the
    edge-tree root program (over wire-forwarded partials): same code, same
    association — the root of the edge == flat bitwise pin. A dead edge's
    partial is an exact zero row, which folds transparently — an edge
    dying IS its shard's clients dropped."""
    if partials.ndim < 1:
        raise ValueError(f"expected [E, ...] partials, got {partials.shape}")

    def fold_edge(acc, p):
        return acc + p, None

    out, _ = jax.lax.scan(
        fold_edge, jnp.zeros(partials.shape[1:], partials.dtype), partials)
    return out


# ------------------------------------------------------------- server side


@jax.named_scope("server_algebra")
def server_step_sparse(
    cfg: ModeConfig, agg: dict, sstate: dict, lr: jnp.ndarray
) -> tuple[dict, dict]:
    """Server momentum + error feedback; returns (delta_wire, new_state)
    with the delta in wire form: {"idx", "vals"} (k-sparse; sketch /
    true_topk / local_topk-virtual) or {"dense"} (the other modes). New
    params are `apply_delta(pflat, delta_wire)`.

    Why wire form: at GPT-2 scale (d ~ 124M) densifying a 50k-sparse delta
    just so the caller can subtract it costs ~1 GB of HBM traffic per round
    (write d + read d); a k-element scatter-subtract is bit-identical
    (x - 0.0 == x and x - v == x + (-v) in IEEE; top-k indices are unique)
    and touches only the selected rows. The dense-state updates below use
    the same scatter forms for the same reason."""
    rho = cfg.momentum if cfg.momentum_type == "virtual" else 0.0

    if cfg.mode == "sketch":
        # FetchSGD Alg. 1 in sketch space (SURVEY.md §3.1)
        spec = cfg.sketch_spec
        S = agg["table"]
        V = rho * sstate["Vvelocity"] + S
        E = sstate["Verror"] + lr * V
        idx, vals = csvec.unsketch_topk(spec, E, cfg.k, impl=cfg.topk_impl,
                                        recall=cfg.topk_recall)
        # Error subtract + momentum factor masking, sketch-space: zero V's
        # (estimated) mass at the transmitted coordinates — the sketch
        # analogue of true_topk's V * (1 - mask). Subtracting V's own
        # queried values (not lr-scaled delta) keeps units consistent, so
        # agg_op sum/mean stay exactly lr-translatable (ModeConfig.agg_op).
        # Fused into one hash evaluation (csvec.mask_transmitted).
        V, E = csvec.mask_transmitted(spec, V, E, idx, vals)
        return {"idx": idx, "vals": vals}, {"Vvelocity": V, "Verror": E}

    g = agg["dense"]

    if (cfg.server_state == "sketch"
            and cfg.mode in ("true_topk", "local_topk")):
        # Count-sketched server optimizer state (arXiv:1902.00179): the
        # client wire stays dense (DP noise above already calibrated to
        # it), but momentum and virtual error feedback live as r x c
        # tables — V = rho*V + sketch(g) — and the release is
        # unsketch_topk, exactly the FetchSGD tail. Server memory is
        # O(r*c) instead of O(2d). With c >= d (rotation family) every
        # row is a signed permutation, estimates are exact, and this
        # branch is BIT-identical to the dense branches below (pinned in
        # tests/test_layerwise.py); with c < d it is the sketch
        # approximation. local_topk reaches here only with
        # error_type='virtual' (ModeConfig validation): the other error
        # types release dense deltas a sketch-resident V cannot produce.
        spec = cfg.sketch_spec
        V = rho * sstate["Vvelocity"] + csvec.sketch_vec(spec, g)
        use_error = cfg.error_type == "virtual"
        E = sstate["Verror"] + lr * V if use_error else lr * V
        idx, vals = csvec.unsketch_topk(spec, E, cfg.k, impl=cfg.topk_impl,
                                        recall=cfg.topk_recall)
        if use_error:
            V, E = csvec.mask_transmitted(spec, V, E, idx, vals)
            return {"idx": idx, "vals": vals}, {"Vvelocity": V, "Verror": E}
        # no error accumulator: mask V's transmitted mass only (the sketch
        # analogue of true_topk's V.at[idx].set(0))
        V = V - csvec.sketch_sparse(spec, idx, csvec.query(spec, V, idx))
        return {"idx": idx, "vals": vals}, {
            "Vvelocity": V, "Verror": sstate["Verror"]}

    if cfg.mode == "true_topk":
        V = rho * sstate["Vvelocity"] + g
        use_error = cfg.error_type != "none"
        E = sstate["Verror"] + lr * V if use_error else lr * V
        with jax.named_scope("server_topk"):
            idx, vals = topk_dense(E, cfg.k, cfg.topk_impl, cfg.topk_recall)
        # mask from the selected indices, not delta's values: a transmitted
        # coordinate whose value happens to be 0 must still be masked.
        E = E.at[idx].add(-vals) if use_error else sstate["Verror"]
        V = V.at[idx].set(0.0)  # momentum factor masking
        return {"idx": idx, "vals": vals}, {"Vvelocity": V, "Verror": E}

    if cfg.mode == "local_topk":
        # Clients already applied per-client top-k (and local momentum/error
        # when configured). error_type="virtual" keeps ONE server-side error
        # accumulator on the aggregated sparse update instead of a
        # [num_clients, d] per-client residual — the FetchSGD paper's answer
        # to the local-error memory wall (SURVEY.md §3.3): accumulate the
        # aggregate into Verror, release its top-k, retain the rest.
        V = rho * sstate["Vvelocity"] + g
        if cfg.error_type == "virtual":
            E = sstate["Verror"] + lr * V
            with jax.named_scope("server_topk"):
                idx, vals = topk_dense(E, cfg.k, cfg.topk_impl,
                                       cfg.topk_recall)
            return {"idx": idx, "vals": vals}, {
                "Vvelocity": V.at[idx].set(0.0),
                "Verror": E.at[idx].add(-vals),
            }
        return {"dense": lr * V}, {"Vvelocity": V, "Verror": sstate["Verror"]}

    if cfg.mode in ("fedavg", "localSGD"):
        # agg is the mean weight delta (w_start - w_local); local steps already
        # carry the client lr, so server lr defaults to 1 (slowmo via momentum).
        V = rho * sstate["Vvelocity"] + g
        return {"dense": lr * V}, {"Vvelocity": V, "Verror": sstate["Verror"]}

    # uncompressed: plain SGD with (virtual) momentum — the bit-for-bit control
    V = rho * sstate["Vvelocity"] + g
    return {"dense": lr * V}, {"Vvelocity": V, "Verror": sstate["Verror"]}


@jax.named_scope("apply")
def apply_delta(pflat: jnp.ndarray, delta: dict) -> jnp.ndarray:
    """params - delta for a wire-form delta (see server_step_sparse).
    Honors idx = -1 padding (zero contribution) like every other sparse
    consumer (to_dense, sketch_sparse): clip + zero. BOTH bounds matter —
    a raw -1 would wrap to pflat[d-1], and an idx >= d clips to d-1, so
    either side with a nonzero val would silently corrupt the last
    parameter."""
    if "dense" in delta:
        return pflat - delta["dense"]
    idx = delta["idx"]
    vals = delta["vals"].astype(pflat.dtype)
    d = pflat.shape[0]
    safe = jnp.clip(idx, 0, d - 1)
    return pflat.at[safe].add(-jnp.where((idx >= 0) & (idx < d), vals, 0.0))


def delta_support(d: int, delta: dict) -> jnp.ndarray:
    """Nonzero-coordinate count of the broadcast delta (local_topk downlink
    accounting). Sparse wires have unique indices, so counting nonzero vals
    equals counting the nonzero coordinates of the densified delta."""
    target = delta["dense"] if "dense" in delta else delta["vals"]
    return jnp.count_nonzero(target).astype(jnp.float32)


def server_step(
    cfg: ModeConfig, agg: dict, sstate: dict, lr: jnp.ndarray
) -> tuple[jnp.ndarray, dict]:
    """Server momentum + error feedback; returns (delta[d], new_state).
    New params are `params - delta`. Densifying wrapper over
    server_step_sparse — the engine's hot path uses the sparse form; this
    form serves callers that want the dense delta (tests, analysis)."""
    delta, new_state = server_step_sparse(cfg, agg, sstate, lr)
    if "dense" in delta:
        return delta["dense"], new_state
    return csvec.to_dense(cfg.d, delta["idx"], delta["vals"]), new_state
