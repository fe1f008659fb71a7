"""The federated round engine — one compiled step per round.

TPU-native replacement for the reference's entire L3-L5 stack (SURVEY.md §1:
`fed_aggregator`/`fed_ps` + `fed_worker` + torch.multiprocessing queues +
shared-memory tensors).  Where the reference spawns a process per GPU and
streams (client, batch) work items through queues (SURVEY.md §3.1 hot loop),
here the sampled clients of a round are a leading batch axis: per-client
forward/backward is a `vmap`, compression is a mode transform, aggregation is
a mean that XLA lowers to collectives over the client-sharded mesh axis, and
the server update runs in the same XLA program.  Weight "broadcast" is
replicated-array residency — there is no transport code to get right.

Loss-function protocol (model-agnostic):

    loss_fn(params, net_state, batch, rng) -> (loss, aux)

where `loss` is the masked mean loss used for the gradient, and
`aux = {"net_state": new_net_state, "metrics": {...sums incl "count"}}`.
`net_state` carries mutable collections (BN batch_stats); per-round new stats
are averaged across clients and EMA'd by the caller's model wrapper.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree

from ..modes import modes
from ..modes.config import ModeConfig

# The phases of the compiled round, as `jax.named_scope` names them in every
# round-step factory below, in modes.server_step_sparse / apply_delta and in
# csvec.unsketch_topk: metadata on the compiled operations (no arithmetic
# and no instruction name changes), which is how a profiler capture says
# where a round's device time goes after a refactor has renamed every
# fusion. Scopes nest (the query and the top-k inside the server algebra,
# the ravel of a client's gradient inside client_grad); the innermost names
# the operation (obs/profiler.py phase_of). ONE tuple, shared by the
# program, the capture's summary and the tests.
ROUND_PHASES = (
    "client_grad",     # per-client forward and backward (the vmap)
    "cohort_reduce",   # ravel, screen/clip, weighted sum over clients, finalize
    "compress",        # sketch accumulate (or top-k / nothing, by mode), merge
    "server_algebra",  # momentum, error feedback, the masking tail
    "server_query",    # the estimate of all d coordinates
    "server_topk",     # exact or approximate top-k
    "apply",           # sparse or dense apply, unravel
)


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    mode: ModeConfig
    weight_decay: float = 0.0  # applied to the gradient client-side, as in the
    # reference workers (SURVEY.md §3.1 hot loop)
    # Differential privacy (SURVEY.md §0.5 / §2 "fork deltas": upstream grew
    # per-update clipping + Gaussian noise). dp_clip > 0 clips each client's
    # update to L2 norm ≤ dp_clip before aggregation; dp_noise > 0 is the
    # central-DP noise multiplier — N(0, (dp_noise·sens)²) is added to the
    # aggregated wire (the object that would be transmitted), where the
    # aggregate's L2 sensitivity `sens` is dp_clip/W for agg_op="mean" over W
    # sampled clients and dp_clip for agg_op="sum".
    dp_clip: float = 0.0
    dp_noise: float = 0.0
    # Straggler / client-dropout simulation (rebuild-side robustness knob;
    # the reference has none — SURVEY.md §5 "a dead worker hangs the run").
    # Each round every sampled client independently drops with this
    # probability BEFORE its update is aggregated: aggregation becomes a
    # survivor-weighted mean/sum, metrics count survivors only, dropped
    # clients keep their persistent local-state rows, and DP noise
    # calibrates to the surviving cohort. A fully-dropped round contributes
    # a zero aggregate (momentum still decays, the round still counts).
    client_dropout: float = 0.0
    # HBM ceiling for large models (SURVEY.md §7 hard part (e)): > 0 runs
    # the client phase as a lax.scan over chunks of this many clients,
    # accumulating the weighted reduce additively, so GPT-2-scale rounds can
    # sample far larger cohorts per chip. With no per-client transform armed
    # (client_update_clip, dp_clip both 0) no per-client gradient exists at
    # all — one backward pass a chunk writes the reduced tree — and the knob
    # is sized by the chunk's ACTIVATIONS alone; with one armed it also
    # bounds the per-client gradient trees alive at once (W full gradients
    # never coexist). Linearity makes the chunk accumulation exact; applies
    # to linear grad modes without client-local state (elsewhere the
    # per-client wires are needed all at once and the knob is ignored).
    client_chunk: int = 0
    # Non-finite-update guard (resilience/): "skip" detects NaN/Inf in the
    # aggregated wire (or the new mutable collections) INSIDE the compiled
    # step and treats the round like a fully-dropped cohort — zero aggregate
    # in, so momentum decays but never absorbs the poison, error feedback
    # stays clean, per-client rows and BN stats keep their pre-round values,
    # and metrics carry nonfinite_rounds=1 so the skip is loud. "off" keeps
    # the seed behavior (poison propagates) and the seed's exact compiled
    # program. When every update is finite, "skip" is bit-identical to "off"
    # (jnp.where with a true predicate), so enabling it costs nothing.
    on_nonfinite: str = "off"
    # Data-parallel shard count of the sampled cohort (the device-mesh round,
    # make_sharded_round_step): > 1 splits the W clients into this many
    # equal shards, each shard's clients reduce locally and COMPRESS locally
    # (the partial Count Sketch), and the partial wires merge with one
    # ordered cross-shard sum — so on a mesh the cross-device traffic is the
    # r x c table, never the dense [d] gradient. Like client_chunk, the
    # shard count is part of the round's numerical contract (it fixes the fp
    # summation order): a given client_shards produces identical bits on one
    # device and on a client_shards-way mesh (pinned by the CPU-mesh parity
    # tests), while different shard counts differ at fp-reassociation level.
    client_shards: int = 1
    # How the round's sketch table is built (mode=sketch only). Both paths
    # share ONE cohort reduce (_weighted_client_reduce): the reduced
    # gradient is a pytree (one backward pass for the cohort; under a
    # quarantine or dp_clip per-client trees summed leaf by leaf), so
    # neither writes a [W, d] / [chunk, d] stack of flat gradients, and
    # quarantine/dp_clip client norms are folded from per-leaf partial sums
    # on both. What differs is only where the sketch is folded and how the
    # delta is applied:
    # - "ravel" (default): the REDUCED tree is concatenated into one flat
    #   [d] vector (ravel_pytree, once a round) and compressed in one shot;
    #   the delta is applied on the flat params view and unraveled.
    # - "layerwise": each reduced leaf folds DIRECTLY into the running
    #   r x c table (sketch/layerwise.py) — not even the one reduced flat
    #   [d] gradient, nor the flat params copy for the delta apply,
    #   materializes. Pinned BIT-identical to the ravel path (fused,
    #   sharded): sketch addition is the same ordered float sum either way
    #   (csvec._sketch_vec_rotation's explicit slab fold). Caveat: the
    #   random hash family requires num_blocks == 1 (the blocked ravel
    #   oracle associates differently).
    sketch_path: str = "ravel"
    # Sketch-space quarantine (cohort-level fault tolerance): > 0 rejects any
    # client whose update L2 norm exceeds this multiple of the RUNNING MEDIAN
    # of live client norms (kept in server state, seeded by the first round's
    # cohort median) — and always rejects non-finite updates. A quarantined
    # client is zeroed out of the merge AND removed from the survivor
    # renormalization, exactly like a dropped client, so one poisoned or
    # adversarially large update costs one client, not the round (the
    # on_nonfinite guard only has to catch what slips past). The norms come
    # from the per-client (per-shard-partial on the mesh) updates BEFORE the
    # DP clip — after the clip every norm is <= dp_clip and screening is
    # vacuous. 0 = off: the compiled program is unchanged.
    client_update_clip: float = 0.0
    # Quarantine baseline window (rounds): 1 (default) keeps the pre-window
    # behavior BIT-identically — the threshold baseline is the last
    # non-empty round's live-cohort median, in the exact same state tree.
    # K > 1 keeps a [K] ring of recent per-round medians in server state and
    # screens against the MEDIAN OVER THE WINDOW, so a model whose update
    # norms drift fast (early training, lr pivots) doesn't quarantine
    # healthy clients just because this round's norms moved: one outlier
    # round perturbs one window slot, not the whole threshold.
    quarantine_window: int = 1
    # Wire-payload round (--serve_payload sketch): the round's aggregate is
    # the ordered sum of PER-CLIENT Count-Sketch tables instead of the
    # compress-once linearity shortcut — the arithmetic a serving layer
    # that merges client-computed payloads actually performs. The batch
    # simulator runs the identical two-program shape (client tables +
    # table-merge server step), which is what pins a served round with
    # real wire-crossed payloads bit-identical to the batch round.
    wire_payloads: bool = False
    # Byzantine-robust table merge (--merge_policy): how the per-client
    # r x c tables combine. "sum" (pinned default) is the linear ordered
    # sum — FetchSGD's merge, and exactly what a colluding minority
    # exploits (linearity means any admitted table moves the aggregate by
    # its full mass). "trimmed" drops the merge_trim highest and lowest
    # LIVE contributions per table coordinate before the ordered sum
    # (coordinate-wise trimmed mean, deterministic tie-break by client
    # index — mesh-shape-invariant over the gathered [W, r, c] stack);
    # "median" is the coordinate-wise median. Robust policies need
    # per-client tables, so they run the wire-payload round SHAPE even in
    # the batch simulator (the linearity shortcut is forfeited — that IS
    # the defense's price) and require mode=sketch + sketch_path="ravel".
    # "trimmed" with merge_trim=0 compiles the EXACT "sum" program
    # (trimming nothing is the sum), so the k=0 bit-identity pin holds by
    # construction. Caveat: robust merges break the error-feedback
    # telescoping exactly where they help (the retained error no longer
    # equals the untransmitted mass of the true cohort mean) — see the
    # README threat-model section.
    merge_policy: str = "sum"
    merge_trim: int = 0
    # Quarantine screen granularity (--quarantine_scope): "cohort"
    # (default) keeps the PR 4 scalar screen — one L2 norm per client vs
    # the running cohort median. "layer" ADDS per-LAYER screens on top:
    # each client's update is sliced into per-leaf blocks (the exact
    # (offset, size) segments PR 8's BlockPlan is built from, so screen
    # and sketch can never disagree about layer boundaries), each leaf's
    # L2 is screened against that leaf's own running median ring
    # (--quarantine_window semantics preserved per leaf), and a client
    # quarantined in ANY layer is dropped — bitwise the same drop as the
    # scalar screen's. A targeted attack that hides inside the flat norm
    # (all its mass in one layer, e.g. an embedding-row replacement) moves
    # one leaf's norm by sqrt(d/d_leaf) more than the flat norm moves, so
    # the per-leaf screen catches what the scalar screen dilutes away.
    # On the UPDATE-norm rounds (fused/sharded announce, where the scalar
    # screen reads the flat update norm) a single-leaf model's per-leaf
    # norm IS the flat norm — same reduction — so window=1 layer scope is
    # bit-identical to the scalar screen there. On the per-client-TABLE
    # rounds the scalar screen is sketch-space (table norms) while the
    # per-leaf screens are update-space, so layer scope genuinely ADDS a
    # second statistic even single-leaf (by design: the table superimposes
    # all layers and cannot be screened per leaf).
    quarantine_scope: str = "cohort"
    # Buffered-ASYNC serving (--serve_async, FedBuff-shaped): > 0 sizes the
    # stale-fold slot stack of the payload MERGE program — late tables
    # (submissions answering an already-closed round) fold into the merged
    # wire as an ordered staleness-weighted sum AFTER the live cohort's
    # ordered sum, inside the ONE declared staleness-fold boundary
    # (engine._stale_fold, graftlint G013). Count-Sketch linearity makes
    # the staged fold exact; the weights ((1+lag)^-alpha, computed by the
    # serving layer as a pure function of round lag) down-weight staleness
    # FedBuff-style. The parity contract: the session keeps the PLAIN merge
    # program compiled alongside and dispatches it whenever a round has
    # ZERO stale entries, so async-with-everyone-on-time runs the exact
    # sync program — bit-identity by construction, not fp luck. 0 = off
    # (the stale program is never built).
    # Composed with a robust merge_policy (the per-BUFFER robust merge),
    # the stale slots do NOT fold linearly: they join the robust order
    # statistics as weighted entries of the union stack {current buffer ∪
    # staleness-weighted stale folds} inside the ONE G012 boundary
    # (modes._robust_table_merge's extended form) — on-time tables at
    # weight 1, stale tables at their (1+lag)^-alpha weight, so a stale
    # adversarial table is trimmed/outvoted exactly like an on-time one.
    # A zero-stale robust round dispatches the plain robust program — the
    # PR 10 sync robust round, by program identity.
    stale_slots: int = 0
    # Error-feedback-aware robust merges (--robust_residual; no effect
    # unless a robust merge_policy is effective): accumulate the
    # robust-vs-mean merge residual into the Verror table before the
    # server step, with the "mean" evaluated over the WINSORIZED stack
    # (every contribution clamped into the robust policy's kept window),
    # so the honest mass the trim clips re-enters through error feedback
    # — telescoping survives robust merges — while an adversary's
    # residual contribution stays bounded by the clean cohort's value
    # range (the PR 12 `verror_ratio` estimator stays bounded under
    # sustained in-screen attack; pinned in tests/test_async_robust.py).
    # Default OFF: the residual arithmetic is a different compiled robust
    # program, and the PR 10 robust pins (mesh == single-device bitwise)
    # stay on the exact shipped program until this soaks — MIGRATION.md
    # records the intent to flip the default.
    robust_residual: bool = False
    # Sketch-health observability (--health_every, obs/health.py): True
    # compiles the per-round compression-quality estimators INTO the round
    # program — estimated heavy-hitter mass / recall proxy, table
    # saturation, error-feedback Verror telescoping health, per-leaf
    # gradient-norm distribution — gated by the reserved `_health_on`
    # batch leaf through a lax.cond (the --health_every cadence is a flag
    # VALUE, never a recompile) and resolved at the runner's existing
    # drain boundary under the reserved "health/" metrics prefix. The
    # estimators only READ round state — a health-enabled run is pinned
    # bit-identical (params + every logged row) to a disabled one.
    # mode=sketch only (the quantities are sketch-wire quantities).
    health: bool = False
    # Two-tier edge-aggregation serving (--serve_edges, serve/scale/): >= 2
    # arms the EDGE-TREE merge variants of the wire-payload round. The
    # serving topology hash-partitions each round's cohort over E edge
    # aggregators; each edge ordered-sums its shard's validated tables and
    # forwards ONE r x c partial to the root, which folds the partials in
    # FIXED edge order (modes.merge_edge_partials). Two sibling merge
    # programs compile beside the plain one:
    #   - the GROUPED flat program (full [W, r, c] stack in, reduction
    #     restructured as the same per-edge grouping — the flat-serving
    #     reference the edge path is pinned bitwise against), and
    #   - the PARTIALS root program ([E, r, c] edge partials in, plus the
    #     per-client metadata the screens need — the wire-side L2 norms the
    #     edges forward — everything downstream identical code on identical
    #     values).
    # Both take the per-client table norms as an INPUT (computed once, by
    # the shared wire-formula helper, partition-invariantly per client)
    # instead of in-program, so the quarantine screen/ring can never
    # diverge between the two. The grouping (and the input norms) is a
    # different fp association than the plain program — an edge-armed
    # session differs from serve_edges=0 in last bits (MIGRATION.md);
    # edge-armed flat vs edge-armed tree is the bitwise pin. Robust merge
    # policies need per-client tables and never compile edge variants: the
    # serving tree then FORWARDS per-client tables (bandwidth trade-off
    # documented in the README) and dispatches the plain robust program.
    # 0/1 = off: every compiled program is byte-identical to before.
    serve_edges: int = 0
    # Round-ledger fingerprints (--ledger, obs/ledger.py): True adds
    # order-fixed fp fingerprints of the round's committed params and
    # optimizer state to every round's metrics under the reserved
    # "ledger/" prefix — deterministic per program, so two runs of one
    # config produce identical sequences and the ledger diff CLI can name
    # the first divergent round. Reads only; bit-transparent like health.
    ledger_fingerprint: bool = False

    def __post_init__(self):
        if self.client_shards < 1:
            raise ValueError(
                f"client_shards must be >= 1, got {self.client_shards}"
            )
        if not 0.0 <= self.client_dropout < 1.0:
            raise ValueError(
                f"client_dropout must be in [0, 1), got {self.client_dropout}"
            )
        if self.client_chunk < 0:
            raise ValueError(
                f"client_chunk must be >= 0, got {self.client_chunk}"
            )
        if self.client_update_clip < 0:
            raise ValueError(
                f"client_update_clip must be >= 0, got "
                f"{self.client_update_clip}"
            )
        if self.on_nonfinite not in ("off", "skip"):
            raise ValueError(
                f"on_nonfinite must be 'off' or 'skip', got {self.on_nonfinite!r}"
            )
        if self.sketch_path not in ("ravel", "layerwise"):
            raise ValueError(
                f"sketch_path must be 'ravel' or 'layerwise', got "
                f"{self.sketch_path!r}"
            )
        if self.sketch_path == "layerwise":
            if self.mode.mode != "sketch":
                raise ValueError(
                    "sketch_path='layerwise' accumulates per-layer gradient "
                    "blocks into the Count-Sketch table, so it requires "
                    f"mode='sketch'; mode={self.mode.mode!r} has no table "
                    "to accumulate into"
                )
            if self.mode.hash_family == "random" and self.mode.num_blocks != 1:
                raise ValueError(
                    "sketch_path='layerwise' with hash_family='random' "
                    "requires num_blocks=1: the blocked ravel oracle sums "
                    "per-block partial tables (a different fp association "
                    "than the continuous coordinate fold), which would "
                    "break the layerwise==ravel bit-parity contract. Use "
                    "num_blocks=1 (layerwise transients are O(leaf) anyway) "
                    "or hash_family='rotation'."
                )
        if self.quarantine_window < 1:
            raise ValueError(
                f"quarantine_window must be >= 1, got {self.quarantine_window}"
            )
        if self.wire_payloads:
            if self.mode.mode != "sketch":
                raise ValueError(
                    "wire_payloads (serve_payload='sketch') merges per-client "
                    "Count-Sketch tables, so it requires mode='sketch'; "
                    f"mode={self.mode.mode!r} has no table wire"
                )
            if self.sketch_path != "ravel":
                raise ValueError(
                    "wire_payloads requires sketch_path='ravel': the client-"
                    "side table is sketched from the client's flat gradient "
                    "(the object that crosses the wire); layerwise "
                    "accumulation is a server-memory optimization with no "
                    "client wire to ship"
                )
            if self.client_dropout > 0:
                raise ValueError(
                    "wire_payloads with client_dropout is double-counting: "
                    "on the payload path the ARRIVAL STREAM is the dropout — "
                    "a client that doesn't submit is the straggler; use the "
                    "serving layer's traffic model instead"
                )
        if self.merge_policy not in ("sum", "trimmed", "median"):
            raise ValueError(
                f"merge_policy must be 'sum', 'trimmed' or 'median', got "
                f"{self.merge_policy!r}"
            )
        if self.merge_trim < 0:
            raise ValueError(
                f"merge_trim must be >= 0, got {self.merge_trim}"
            )
        if self.merge_trim > 0 and self.merge_policy != "trimmed":
            raise ValueError(
                f"merge_trim={self.merge_trim} names the trimmed policy's "
                f"per-coordinate drop count; merge_policy="
                f"{self.merge_policy!r} has no use for it"
            )
        if robust_policy(self):
            if self.mode.mode != "sketch":
                raise ValueError(
                    f"merge_policy={self.merge_policy!r} is the robust "
                    "TABLE merge over per-client Count-Sketch tables, so it "
                    f"requires mode='sketch'; mode={self.mode.mode!r} has "
                    "no table wire"
                )
            if self.sketch_path != "ravel":
                raise ValueError(
                    "robust merge policies run the per-client-table round "
                    "(each client's table is sketched from its flat "
                    "update); sketch_path='layerwise' is a server-memory "
                    "optimization of the compress-once shortcut the robust "
                    "merge forfeits — use sketch_path='ravel'"
                )
        if self.quarantine_scope not in ("cohort", "layer"):
            raise ValueError(
                f"quarantine_scope must be 'cohort' or 'layer', got "
                f"{self.quarantine_scope!r}"
            )
        if self.quarantine_scope == "layer" and self.client_update_clip <= 0:
            raise ValueError(
                "quarantine_scope='layer' refines the --client_update_clip "
                "screen; with the clip at 0 there is no quarantine to scope "
                "— set client_update_clip > 0"
            )
        if self.stale_slots < 0:
            raise ValueError(
                f"stale_slots must be >= 0, got {self.stale_slots}"
            )
        if self.stale_slots > 0 and not self.wire_payloads:
            raise ValueError(
                "stale_slots (--serve_async) folds LATE WIRE TABLES "
                "into the payload merge; without wire_payloads there is "
                "no per-client table wire to arrive late — arm "
                "--serve_payload sketch"
            )
        if self.serve_edges < 0:
            raise ValueError(
                f"serve_edges must be >= 0, got {self.serve_edges}")
        if self.serve_edges >= 2:
            if not self.wire_payloads:
                raise ValueError(
                    "serve_edges (--serve_edges) is the two-tier edge-"
                    "aggregation topology over WIRE tables; without "
                    "wire_payloads there is no per-client table for an edge "
                    "to sum — arm --serve_payload sketch"
                )
            if robust_policy(self) is not None:
                raise ValueError(
                    f"serve_edges={self.serve_edges} with merge_policy="
                    f"{self.merge_policy!r}: a robust merge runs order "
                    "statistics over PER-CLIENT tables, which a pre-summed "
                    "edge partial has destroyed — the serving tree forwards "
                    "per-client tables instead (set serve_edges=0 on the "
                    "session; serve/scale/edge.py runs the tree in forward "
                    "mode against the plain robust program)"
                )
            if self.stale_slots > 0:
                raise ValueError(
                    "serve_edges does not compose with the buffered-async "
                    "stale fold yet (a stale table's edge assignment is a "
                    "cross-round question the tree does not answer) — drop "
                    "--serve_async or --serve_edges"
                )
            if self.quarantine_scope == "layer":
                raise ValueError(
                    "serve_edges with quarantine_scope='layer' is not "
                    "supported: the per-leaf median rings are root state "
                    "the edges cannot screen against — use the cohort "
                    "scope (the wire-side L2 screen still runs per edge)"
                )
        if self.robust_residual and robust_policy(self) is None:
            raise ValueError(
                "robust_residual is the robust merge's error-feedback "
                f"repair; merge_policy={self.merge_policy!r}"
                f"{f' with merge_trim=0' if self.merge_policy == 'trimmed' else ''} "
                "compiles the plain sum program, which has no residual to "
                "accumulate — arm merge_policy='trimmed' (trim > 0) or "
                "'median', or drop the flag (a silent no-op would be "
                "discovered at the postmortem)"
            )
        if self.health and self.mode.mode != "sketch":
            raise ValueError(
                "health (--health_every) computes SKETCH-wire quality "
                "estimators — recall proxy, table saturation, sketched "
                f"Verror health; mode={self.mode.mode!r} has no table to "
                "estimate from (use mode='sketch')"
            )
        if self.dp_noise > 0 and self.dp_clip <= 0:
            raise ValueError("dp_noise > 0 requires dp_clip > 0 (unbounded "
                             "sensitivity has no meaningful noise scale)")
        if self.dp_noise > 0 and self.mode.needs_local_state:
            raise ValueError(
                "dp_noise with client-local error/momentum state is unsound: the "
                "transmitted wire is topk(error_accumulator + update), whose norm "
                "is unbounded across rounds, so dp_clip does not bound sensitivity. "
                "Use local_topk with error_type=none and momentum_type=none/virtual, "
                "or a mode without client-local state."
            )
        if self.dp_noise > 0 and self.mode.mode == "sketch":
            raise ValueError(
                "dp_noise with mode=sketch is unsound: a count-sketch table's "
                "worst-case L2 sensitivity under an L2 clip is l1-scale (an "
                "adversarial update aligned with the public hash can pile its "
                "mass into one bucket per row), so dp_clip-calibrated Gaussian "
                "noise on the table under-delivers the configured privacy. Use "
                "a dense-wire mode (uncompressed/true_topk/fedavg/localSGD) or "
                "local_topk without local state."
            )


def robust_policy(cfg: EngineConfig) -> str | None:
    """The EFFECTIVE robust merge policy, or None for the linear ordered
    sum. "trimmed" with merge_trim=0 IS the sum (dropping zero values per
    coordinate trims nothing), so it resolves to None here and the engine
    compiles the exact sum program — the k=0 bit-identity contract holds
    by construction, not by fp luck."""
    if cfg.merge_policy == "median":
        return "median"
    if cfg.merge_policy == "trimmed" and cfg.merge_trim > 0:
        return "trimmed"
    return None


def uses_table_round(cfg: EngineConfig) -> bool:
    """Whether the round must produce PER-CLIENT tables (the wire-payload
    two-program shape): a real wire (wire_payloads) or a robust merge —
    order statistics need the individual contributions the compress-once
    linearity shortcut never materializes."""
    return cfg.wire_payloads or robust_policy(cfg) is not None


def _leaf_segments(params) -> tuple[tuple[int, int], ...]:
    """Static (offset, size) per non-empty params leaf in ravel order — the
    per-layer quarantine's block boundaries, shared with the sketch block
    plan (sketch/layerwise.py) so the two can never disagree."""
    from ..sketch import layerwise as sketch_layerwise

    return sketch_layerwise.leaf_segments(params)


def init_server_state(cfg: EngineConfig, params: Any, net_state: Any) -> dict:
    if cfg.dp_noise > 0 and jax.tree.leaves(net_state):
        raise ValueError(
            "dp_noise with mutable model collections (e.g. BatchNorm batch_stats) "
            "is unsound: per-client statistics are averaged into the released "
            "model without clipping or noise, bypassing the DP mechanism. Use a "
            "normalization-free or GroupNorm model for DP runs."
        )
    state = {
        "params": params,
        "net_state": net_state,
        "mode_state": modes.init_server_state(cfg.mode),
        "round": jnp.zeros((), dtype=jnp.int32),
    }
    if cfg.client_update_clip > 0:
        # running median of live client-update L2 norms — the quarantine
        # threshold's baseline. 0 = "no baseline yet": the first round only
        # screens non-finite updates and then seeds the median.
        state["quarantine"] = {"median": jnp.zeros((), dtype=jnp.float32)}
        if cfg.quarantine_window > 1:
            # bounded ring of the last K non-empty rounds' cohort medians
            # (newest last); "median" above stays the ACTIVE threshold (the
            # median over the filled window slots). window=1 keeps the
            # pre-window state tree so checkpoints stay shape-compatible.
            state["quarantine"]["window"] = jnp.zeros(
                (cfg.quarantine_window,), dtype=jnp.float32)
            state["quarantine"]["count"] = jnp.zeros((), dtype=jnp.int32)
        if cfg.quarantine_scope == "layer":
            # per-LEAF median rings beside the scalar one (the scalar screen
            # stays armed — layer scope tightens it, it never replaces it).
            # One ring per non-empty params leaf, same window semantics.
            # NOTE this widens the checkpoint state tree: a cohort-scope
            # checkpoint cannot restore into a layer-scope run (MIGRATION).
            L = len(_leaf_segments(params))
            state["quarantine"]["layer_median"] = jnp.zeros(
                (L,), dtype=jnp.float32)
            if cfg.quarantine_window > 1:
                state["quarantine"]["layer_window"] = jnp.zeros(
                    (L, cfg.quarantine_window), dtype=jnp.float32)
                state["quarantine"]["layer_count"] = jnp.zeros(
                    (L,), dtype=jnp.int32)
    return state


# Reserved per-client batch leaf: a [W] 0/1 float validity mask the caller
# (FederatedSession) threads through every round-step variant by riding the
# batch pytree — it shards/stacks/scans exactly like the client data it
# masks. 0 = this client is DEAD for the round (failed batch load after
# retries, an injected client_drop): it contributes zero to the partial
# sketch, its weight is removed from the renormalization, its persistent
# state rows keep their pre-round values, and metrics count survivors only —
# a round with W-k live clients equals the round over just those W-k clients.
VALID_KEY = "_valid"


def split_valid(batch):
    """Pop the reserved validity-mask leaf off a round batch. Returns
    (batch_without_mask, valid_or_None); absence = all clients valid (the
    engine-level default, zero program change)."""
    if isinstance(batch, dict) and VALID_KEY in batch:
        batch = dict(batch)
        return batch, batch.pop(VALID_KEY)
    return batch, None


# Reserved per-client batch leaf: the health-estimator cadence gate
# (cfg.health / --health_every, obs/health.py). A [W] float — all 1.0 on
# rounds where the in-program estimators run, all 0.0 elsewhere. It rides
# the batch pytree like `_valid` so it shards/stacks/scans with the client
# data and the compiled program's shape is round-invariant: the cadence is
# a lax.cond on the flag's VALUE, never a recompile.
HEALTH_KEY = "_health_on"


def split_health(batch):
    """Pop the reserved health-cadence leaf off a round batch. Returns
    (batch_without_it, flag_array_or_None); absence = no in-program health
    (sessions built without health_every never add the leaf — zero program
    change, the seed behavior bit-for-bit)."""
    if isinstance(batch, dict) and HEALTH_KEY in batch:
        batch = dict(batch)
        return batch, batch.pop(HEALTH_KEY)
    return batch, None


def _tree_sq_sum(tree) -> jnp.ndarray:
    """Sum of squared entries over every leaf, folded in fixed leaf order
    (f32 accumulation) — the fingerprint reduction. No flat concatenation:
    the layerwise path's no-[d]-materialization contract extends here."""
    leaves = [jnp.sum(jnp.square(leaf.astype(jnp.float32)))
              for leaf in jax.tree.leaves(tree)]
    if not leaves:
        return jnp.float32(0.0)
    acc = leaves[0]
    for x in leaves[1:]:
        acc = acc + x
    return acc


def _tree_sum(tree) -> jnp.ndarray:
    """Plain entry sum over every leaf, same fixed-order fold."""
    leaves = [jnp.sum(leaf.astype(jnp.float32))
              for leaf in jax.tree.leaves(tree)]
    if not leaves:
        return jnp.float32(0.0)
    acc = leaves[0]
    for x in leaves[1:]:
        acc = acc + x
    return acc


def _ledger_fingerprints(cfg: EngineConfig, new_state) -> dict:
    """Order-fixed fp fingerprints of the round's COMMITTED state, emitted
    under the reserved "ledger/" metrics prefix on EVERY round when the
    round ledger is armed (cfg.ledger_fingerprint / --ledger). These are
    not cryptographic checksums — they are deterministic-per-program float
    reductions, which is exactly what the ledger diff CLI needs: two runs
    of the same config produce identical sequences, and the first round
    where params_l2sq differs names where the trajectories split. Reads
    only — a ledger-armed run stays bit-identical to an unarmed one."""
    if not cfg.ledger_fingerprint:
        return {}
    return {
        "ledger/params_l2sq": _tree_sq_sum(new_state["params"]),
        "ledger/params_sum": _tree_sum(new_state["params"]),
        "ledger/opt_state_l2sq": _tree_sq_sum(new_state["mode_state"]),
    }


def _health_metrics(cfg: EngineConfig, flag, raw_agg, delta, new_mode_state,
                    weighted=None, weighted_tree=None,
                    segments=None) -> dict:
    """The in-program sketch-health block (obs/health.py's device half),
    computed under a lax.cond on the `_health_on` cadence flag and emitted
    under the reserved "health/" metrics prefix — the session pops the
    prefix off the committed metrics before any row/totals consumer sees
    them, which (together with estimators that only READ) is why a
    health-armed run is pinned bit-identical to an unarmed one.

    `raw_agg` is the PRE-guard aggregate wire (a poisoned round's health
    block must show the poison the non-finite guard is about to discard);
    `delta`/`new_mode_state` are the server step's release and new
    Vvelocity/Verror tables; `weighted` (fused ravel path only) is the
    dense reduced update — the dense-comparable reference the recall proxy
    is validated against; `weighted_tree` is the layerwise path's per-leaf
    counterpart (leaf-norm distribution without materializing [d]);
    `segments` the BlockPlan leaf segments slicing `weighted`."""
    if not cfg.health or flag is None:
        return {}
    from ..obs import health as obhealth
    from ..sketch import csvec

    mcfg = cfg.mode
    spec = mcfg.sketch_spec

    def on():
        out: dict = {}
        table = raw_agg["table"]
        mass = obhealth.table_mass_estimate(table)
        out["grad_mass_est"] = mass
        out["grad_norm_est"] = jnp.sqrt(jnp.maximum(mass, 0.0))
        out["row_mass_cv"] = obhealth.row_mass_cv(table)
        out["table_occupancy"] = obhealth.table_occupancy(table)
        # recall proxy (bracketed — see obs/health.py): the naive
        # same-rows estimate inflates under saturation (selection picks
        # noise), the split-row cross-estimate deflates (selection misses
        # hitters); their midpoint is the proxy and their gap the
        # estimator's own saturation-driven uncertainty
        _, pvals = csvec.unsketch_topk(spec, table, mcfg.k,
                                       impl=mcfg.topk_impl,
                                       recall=mcfg.topk_recall)
        naive = obhealth.energy_fraction(obhealth.topk_energy(pvals), mass)
        if spec.r >= 2:
            pess = obhealth.split_topk_energy_fraction(
                spec, table, mcfg.k, mass)
            out["topk_mass_proxy"] = 0.5 * (naive + pess)
            out["topk_proxy_width"] = naive - pess
        else:
            out["topk_mass_proxy"] = naive
            out["topk_proxy_width"] = jnp.zeros_like(naive)
        # telescoping health: the energy actually released this round vs
        # the energy the error accumulator retained — release_frac falling
        # toward 0 while verror_ratio climbs is the diverging-Verror
        # signature (error feedback no longer telescopes)
        rel = (obhealth.topk_energy(delta["vals"]) if "vals" in delta
               else jnp.float32(0.0))
        out["release_energy"] = rel
        vmass = obhealth.table_mass_estimate(new_mode_state["Verror"])
        out["verror_norm_est"] = jnp.sqrt(jnp.maximum(vmass, 0.0))
        out["release_frac"] = obhealth.energy_fraction(rel, rel + vmass)
        out["verror_ratio"] = obhealth.energy_fraction(
            out["verror_norm_est"], out["grad_norm_est"])
        if weighted is not None:
            # dense-comparable reference (fused ravel path): the true
            # top-k energy fraction the proxy above estimates, plus the
            # per-leaf norm distribution over the SAME segments the
            # BlockPlan/per-layer quarantine use
            gsq = jnp.sum(jnp.square(weighted.astype(jnp.float32)))
            out["grad_norm_true"] = jnp.sqrt(gsq)
            t_idx = csvec.topk_abs(weighted, mcfg.k, impl="exact")
            out["topk_mass_true"] = obhealth.energy_fraction(
                obhealth.topk_energy(weighted[t_idx]), gsq)
            if segments is not None:
                out["leaf_norms"] = jnp.stack([
                    jnp.sqrt(jnp.sum(jnp.square(
                        weighted[off:off + n].astype(jnp.float32))))
                    for off, n in segments])
        elif weighted_tree is not None:
            leaf_norms = jnp.stack([
                jnp.sqrt(jnp.sum(jnp.square(leaf.astype(jnp.float32))))
                for leaf in jax.tree.leaves(weighted_tree)
                if leaf.size])
            out["leaf_norms"] = leaf_norms
            gsq = jnp.sum(jnp.square(leaf_norms))
            out["grad_norm_true"] = jnp.sqrt(gsq)
        return out

    shapes = jax.eval_shape(on)

    def off():
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)

    gate = flag if jnp.ndim(flag) == 0 else flag.max()
    block = jax.lax.cond(gate > 0, on, off)
    return {f"health/{k}": v for k, v in block.items()}


def participation_mask(rng, num_sampled: int, dropout: float) -> jnp.ndarray:
    """[W] float 0/1 survivor mask: each sampled client independently drops
    with probability `dropout`. Pure function of (rng, W, dropout) so tests
    and the engine derive identical masks."""
    if dropout <= 0.0:
        return jnp.ones((num_sampled,), jnp.float32)
    return (
        jax.random.uniform(rng, (num_sampled,)) >= jnp.float32(dropout)
    ).astype(jnp.float32)


def _clip_updates(cfg: EngineConfig, updates: jnp.ndarray) -> jnp.ndarray:
    """Per-client L2 clip (DP): nonlinear, so it must happen before the
    client mean — the linear-mode shortcut stays exact."""
    if cfg.dp_clip <= 0:
        return updates

    def clip(u):
        nrm = jnp.linalg.norm(u)
        return u * jnp.minimum(1.0, cfg.dp_clip / jnp.maximum(nrm, 1e-12))

    return jax.vmap(clip)(updates)


def _dp_noise_agg(cfg: EngineConfig, agg: dict, participants, noise_rng) -> dict:
    """Central DP: noise the aggregated dense wire. Over W L2-clipped updates
    the aggregate's L2 sensitivity is dp_clip/W for mean aggregation and
    dp_clip for sum — and mean divides by the SURVIVING count, so sensitivity
    must too (noising by /num_sampled would under-deliver privacy whenever
    clients drop). A fully-dropped cohort transmits nothing, so it must
    release nothing: without the (participants > 0) gate an empty round
    would inject pure noise at full sens=dp_clip. (Sketch tables are
    rejected in EngineConfig — their worst-case sensitivity under an L2
    clip is l1-scale, not dp_clip.)"""
    n_live = jnp.maximum(participants, 1.0)
    sens = cfg.dp_clip if cfg.mode.agg_op == "sum" else cfg.dp_clip / n_live
    std = jnp.float32(cfg.dp_noise) * sens * (participants > 0)
    return {
        k: v + std * jax.random.normal(
            jax.random.fold_in(noise_rng, i), v.shape, v.dtype)
        for i, (k, v) in enumerate(sorted(agg.items()))
    }


def _client_norms(updates: jnp.ndarray) -> jnp.ndarray:
    """[W] L2 norm of each client's flat update (f32 accumulation)."""
    u = updates.astype(jnp.float32)
    return jnp.sqrt(jnp.sum(jnp.square(u), axis=1))


def _quarantine_mask(cfg: EngineConfig, norms: jnp.ndarray, qmed) -> jnp.ndarray:
    """[W] bool: client rejected by the sketch-space quarantine. Non-finite
    norms always quarantine (NaN compares false everywhere, so they need the
    explicit check); the magnitude screen arms only once a running median
    exists (qmed > 0)."""
    bad = ~jnp.isfinite(norms)
    return bad | ((qmed > 0) & (norms > cfg.client_update_clip * qmed))


def _masked_median(values, live, n):
    """Median over the `live` entries of `values` (sort with dead entries
    pushed to +inf, then index by the live count `n`). Undefined (garbage)
    when n == 0 — callers gate on n > 0."""
    # the quarantine's screening median over [W] NORM vectors (a threshold,
    # never merged values); the robust MERGE's order statistics live in
    # modes._robust_table_merge alone
    # graftlint: disable=G012 — screening median over norms, not a merge
    s = jnp.sort(jnp.where(live, values, jnp.inf))
    lo = jnp.clip((n - 1) // 2, 0, values.shape[0] - 1)
    hi = jnp.clip(n // 2, 0, values.shape[0] - 1)
    return 0.5 * (s[lo] + s[hi])


def _round_median(norms, part_eff):
    """(median, live count) of this round's LIVE, non-quarantined client
    norms — the per-round observation every quarantine baseline (windowed
    or not) is built from."""
    live = (part_eff > 0) & jnp.isfinite(norms)
    n_live = live.sum()
    return _masked_median(norms, live, n_live), n_live


def _advance_quarantine(cfg: EngineConfig, qstate: dict, norms, part_eff) -> dict:
    """One round's update of the quarantine server state.

    quarantine_window == 1 (default): {"median": <this round's live-cohort
    median>}, keeping the previous median when the whole cohort dropped or
    was quarantined — an empty round must not zero the threshold.

    quarantine_window K > 1: push this round's live-cohort median into a
    [K] ring (empty rounds push nothing) and set the ACTIVE threshold
    baseline to the median over the filled slots — a norm distribution that
    drifts across rounds moves the threshold at window speed instead of
    snapping to the newest round, so fast-drifting models don't quarantine
    healthy clients (and one outlier round perturbs one slot, not the whole
    baseline)."""
    med, n_live = _round_median(norms, part_eff)
    has = n_live > 0
    if cfg.quarantine_window <= 1:
        return {"median": jnp.where(has, med, qstate["median"])}
    K = cfg.quarantine_window
    window = jnp.where(
        has, jnp.concatenate([qstate["window"][1:], med[None]]),
        qstate["window"])
    count = jnp.where(has, jnp.minimum(qstate["count"] + 1, K),
                      qstate["count"])
    # the ring fills from the tail: the newest `count` slots are live
    filled = jnp.arange(K) >= (K - count)
    wmed = _masked_median(window, filled, count)
    return {
        "median": jnp.where(count > 0, wmed, qstate["median"]),
        "window": window,
        "count": count,
    }


def _client_layer_norms(updates: jnp.ndarray, segments) -> jnp.ndarray:
    """[W, L] per-leaf L2 norms of each client's FLAT update, sliced by the
    block plan's static (offset, size) ranges (f32 accumulation, like
    `_client_norms`). On a single-leaf model the one column is the full-
    width slice — the identical reduction `_client_norms` runs, which is
    what makes single-leaf layer scope bit-identical to the scalar screen."""
    u = updates.astype(jnp.float32)
    cols = [
        jnp.sqrt(jnp.sum(jnp.square(
            jax.lax.slice_in_dim(u, off, off + n, axis=1)), axis=1))
        for off, n in segments
    ]
    return jnp.stack(cols, axis=1)


def _client_layer_norms_tree(updates_tree) -> jnp.ndarray:
    """[W, L] per-leaf norms from a PYTREE of [W, ...] leaves — the linear
    grad modes' twin of `_client_layer_norms` (leaf order == ravel order,
    so column l is the same layer as that one's segment l)."""
    cols = [
        jnp.sqrt(jnp.sum(jnp.square(leaf.astype(jnp.float32)),
                         axis=tuple(range(1, leaf.ndim))))
        for leaf in jax.tree.leaves(updates_tree) if leaf.size
    ]
    return jnp.stack(cols, axis=1)


def _quarantine_layer_mask(cfg: EngineConfig, lnorms: jnp.ndarray,
                           lmed: jnp.ndarray) -> jnp.ndarray:
    """[W] bool: client rejected by ANY per-leaf screen — a non-finite leaf
    norm, or a leaf norm past the clip multiple of THAT leaf's running
    median (each leaf's screen arms independently once its median seeds,
    exactly the scalar screen's arming rule per ring)."""
    bad = ~jnp.isfinite(lnorms)
    bad = bad | ((lmed[None, :] > 0)
                 & (lnorms > cfg.client_update_clip * lmed[None, :]))
    return bad.any(axis=1)


def _advance_quarantine_layers(cfg: EngineConfig, qstate: dict,
                               lnorms: jnp.ndarray, part_eff) -> dict:
    """One round's update of the per-leaf median rings: the scalar
    `_advance_quarantine` vmapped over the leaf axis (each leaf keeps its
    own ring with the exact window semantics — an empty round advances no
    ring, a leaf whose norms went non-finite cohort-wide keeps its old
    median, same as the scalar rule)."""
    sub = {"median": qstate["layer_median"]}
    if cfg.quarantine_window > 1:
        sub["window"] = qstate["layer_window"]
        sub["count"] = qstate["layer_count"]
    out = jax.vmap(
        lambda st, nl: _advance_quarantine(cfg, st, nl, part_eff),
        in_axes=(0, 1),
    )(sub, lnorms)
    new = {"layer_median": out["median"]}
    if cfg.quarantine_window > 1:
        new["layer_window"] = out["window"]
        new["layer_count"] = out["count"]
    return new


def _robust_scope_check(cfg: EngineConfig):
    """Robust merge policies need per-client tables: the linear round
    builders (fused / sharded — built on the compress-once or
    per-shard-partial shortcut) cannot apply them. The session routes
    robust-policy configs through make_payload_round_steps; a direct
    caller reaching a linear builder with one armed gets a loud error
    instead of a silently-linear merge."""
    if robust_policy(cfg) is not None:
        raise ValueError(
            f"merge_policy={cfg.merge_policy!r} (trim={cfg.merge_trim}) "
            "needs the per-client-table round: use make_payload_round_steps"
            " (FederatedSession routes this automatically); the linear "
            "round builders merge by the ordered sum only"
        )


def _tree_finite(tree) -> jnp.ndarray:
    """Scalar bool: every float leaf of `tree` is fully finite (int leaves —
    sparse wire indices, counters — are finite by construction)."""
    checks = [
        jnp.isfinite(leaf).all()
        for leaf in jax.tree.leaves(tree)
        if jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.floating)
    ]
    if not checks:
        return jnp.bool_(True)
    ok = checks[0]
    for c in checks[1:]:
        ok = ok & c
    return ok


def _guard_nonfinite(cfg: EngineConfig, agg, new_net_state, net_state,
                     new_rows, client_rows, out_metrics):
    """EngineConfig.on_nonfinite="skip": if the aggregated wire or the new
    mutable collections carry NaN/Inf, zero the aggregate's float leaves
    (the fully-dropped-round semantics: momentum decays, state stays clean)
    and keep the previous net_state / per-client rows. The skip is recorded
    in metrics as nonfinite_rounds. Also returns the `ok` verdict so the
    caller can gate the DP participant count — a skipped round transmits
    nothing, so it must release nothing (noising the zeroed wire would feed
    pure noise into momentum/error feedback, breaking the clean-state
    promise). On the finite path every jnp.where predicate is true, so the
    guard is bit-transparent."""
    if cfg.on_nonfinite != "skip":
        return agg, new_net_state, new_rows, out_metrics, jnp.bool_(True)
    ok = (_tree_finite(agg) & _tree_finite(new_net_state)
          & _tree_finite(new_rows))

    def zero_floats(a):
        if jnp.issubdtype(a.dtype, jnp.floating):
            return jnp.where(ok, a, jnp.zeros_like(a))
        return a

    agg = jax.tree.map(zero_floats, agg)
    new_net_state = jax.tree.map(
        lambda new, old: jnp.where(ok, new, old), new_net_state, net_state
    )
    new_rows = jax.tree.map(
        lambda new, old: jnp.where(ok, new, old), new_rows, client_rows
    )
    out_metrics = _skip_metrics(ok, out_metrics)
    return agg, new_net_state, new_rows, out_metrics, ok


def _skip_metrics(ok, out_metrics) -> dict:
    """A skipped round's metric semantics: zero the round's training-stat sums
    (loss_sum/count/... came from the poisoned forward pass, and one NaN
    loss_sum would NaN the whole eval window), keep participants (the
    clients DID transmit; only the server discards), and emit the
    nonfinite_rounds flag. The quarantine keys survive the zeroing like
    participants: the quarantine verdicts/median are server-side bookkeeping,
    not training stats from the poisoned forward pass."""
    keep = ("participants", "clients_quarantined", "quarantine_median")
    out_metrics = {
        k: v if k in keep else jnp.where(ok, v, jnp.zeros_like(v))
        for k, v in out_metrics.items()
    }
    out_metrics["nonfinite_rounds"] = (~ok).astype(jnp.float32)
    return out_metrics


def _advance_quarantine_full(cfg: EngineConfig, qstate: dict, norms, lnorms,
                             part_eff) -> dict:
    """Scalar ring + (layer scope) per-leaf rings, one round's advance —
    the single entry every fused path uses so the state tree cannot drift
    between the batch, sharded, and payload rounds."""
    new_q = _advance_quarantine(cfg, qstate, norms, part_eff)
    if lnorms is not None:
        new_q.update(_advance_quarantine_layers(cfg, qstate, lnorms,
                                                part_eff))
    return new_q


# The collections of net_state that a loss only reads (`buffers`: a router's
# selection bias, models/glm4_moe_lite.py). Every client hands them back as
# they came, and the mean of W equal float32 copies need not be the copy: the
# merges below hand them on untouched, bit for bit.
READ_ONLY_COLLECTIONS = ("buffers",)


def _merge_collections(merge, returned, net_state) -> Any:
    """`merge(client results, previous)` leaf by leaf over net_state, but for
    its read-only collections, which stay as they were."""
    merged = jax.tree.map(merge, returned, net_state)
    if isinstance(net_state, dict):
        merged.update({k: net_state[k] for k in READ_ONLY_COLLECTIONS if k in net_state})
    return merged


@jax.named_scope("cohort_reduce")
def _merge_net_state(nstates, net_state, part) -> Any:
    """Mutable model collections (BN stats): average the SURVIVING clients'
    results; with no survivors, keep the previous stats. mask_rows keeps a
    quarantined client's NaN stats out of the live average."""
    n_live = jnp.maximum(part.sum(), 1.0)
    return _merge_collections(
        lambda s, prev: jnp.where(
            part.sum() > 0, modes.mask_rows(part, s).sum(0) / n_live, prev
        ),
        nstates, net_state,
    )


@jax.named_scope("cohort_reduce")
def _survivor_metrics(metrics, part) -> dict:
    """Metric sums over the surviving cohort + the participants count that
    run_round uses to scale the measured uplink (NaN-safe: a masked client's
    poisoned metrics contribute exact zeros)."""
    out = jax.tree.map(lambda m: modes.mask_rows(part, m).sum(axis=0), metrics)
    out["participants"] = part.sum()
    return out


def _client_norms_tree(updates_tree) -> jnp.ndarray:
    """[W] per-client update L2 norms from a PYTREE of [W, ...] leaves:
    per-leaf squared sums folded in ravel leaf order (f32 accumulation).
    The linear grad modes' counterpart of `_client_norms` — equal to the
    flat-vector norm only up to fp association (that one reduces one
    contiguous [d] axis; this folds per-leaf partials)."""
    total = None
    for leaf in jax.tree.leaves(updates_tree):
        s = jnp.sum(jnp.square(leaf.astype(jnp.float32)),
                    axis=tuple(range(1, leaf.ndim)))
        total = s if total is None else total + s
    return jnp.sqrt(total)


def _clip_updates_tree(cfg: EngineConfig, updates_tree):
    """Per-client L2 clip over a pytree of [W, ...] leaves (DP) — the tree
    mirror of `_clip_updates` (same clip factor formula; the norm folds per
    leaf, see _client_norms_tree)."""
    if cfg.dp_clip <= 0:
        return updates_tree
    nrm = _client_norms_tree(updates_tree)
    fac = jnp.minimum(1.0, cfg.dp_clip / jnp.maximum(nrm, 1e-12))
    return jax.tree.map(lambda l: l * modes.bcast(fac, l), updates_tree)


# graftlint: sketch-boundary — THE ravel of the linear grad modes: the
# cohort's already-reduced gradient tree becomes the flat [d] the compress
# and the server step take, once a round (never one client's gradient)
@jax.named_scope("cohort_reduce")
def _ravel_reduced(wsum_tree):
    return ravel_pytree(wsum_tree)[0]


def _needs_client_gradients(cfg: EngineConfig) -> bool:
    """Whether a transform is armed that must see one client's gradient on
    its own: the quarantine's norm screen or the DP clip."""
    return cfg.client_update_clip > 0 or cfg.dp_clip > 0


def cohort_backward_fused(cfg: EngineConfig) -> bool:
    """Whether the round's client phase is ONE backward pass for the whole
    cohort (`_weighted_client_reduce`'s fused path) and not one a client: a
    linear grad mode on the compress-once shortcut with no transform armed
    that needs a single client's gradient. The non-linear modes, the
    weight-delta modes and the per-client-table round never had the
    shortcut."""
    return (supports_sharded_round(cfg.mode) and not uses_table_round(cfg)
            and not _needs_client_gradients(cfg))


def _weighted_client_reduce(
    cfg: EngineConfig, loss_fn: Callable,
    params, net_state, batch, client_rngs, part,
    *, qmed=None, nan_safe: bool = False, lmed=None, ravel: bool = True,
):
    """Participation-weighted SUMS over the sampled clients of (clipped)
    updates, mutable-collection contributions, and metric values — the whole
    client phase of a linear-mode round before normalization. Returns
    (wsum, ns_sum, m_sum, part_eff, norms, lnorms): `part_eff` is the [W]
    mask of clients that actually contributed (the input mask minus any
    quarantined clients), `norms` the [W] per-client update L2 norms (None
    with the quarantine off), `lnorms` the [W, L] per-leaf norms
    (quarantine_scope="layer" only — `lmed` carries that scope's per-leaf
    medians; a client over ANY leaf's screen is quarantined exactly like a
    scalar-screen rejection).

    Two paths, chosen by what the configuration asks for:

    - FUSED (no quarantine, no dp_clip): sum_i w_i grad L_i == grad sum_i
      w_i L_i, so the forward pass is one vmap over the clients (per-client
      batch statistics, dropout keys and metrics as ever) and ONE reverse
      pass with the cotangent w on the [W] losses gives the reduced tree
      directly. The params are unbatched under the vmap, so each weight
      gradient contracts the client axis together with the example axis:
      no per-client gradient exists at all, in HBM or anywhere, and weight
      decay is added once to the reduced tree (wd * sum_i w_i * theta).
    - PER-CLIENT (client_update_clip > 0 or dp_clip > 0: a screen or a clip
      that must see g_i alone): updates stay a PYTREE of [W, ...leaf]
      gradients (`_make_grad_client_tree`) through the screen, the clip and
      the mask, and the weighted sum is taken per leaf.

    Either way sum_i w_i ravel(g_i) == ravel(sum_i w_i g_i), so only the
    reduced tree is raveled (`ravel`, the flat [d] `wsum` every caller but
    sketch_path="layerwise" takes) and no [W, d] or [chunk, d] stack of flat
    gradients is ever written.

    One vmap when cfg.client_chunk is 0; otherwise a lax.scan over chunks of
    client_chunk clients (each chunk vmapped) that carries the tree of sums:
    on the fused path that bounds the ACTIVATIONS alive at once (the carry
    is one reduced tree whatever the chunk), on the per-client path also the
    client_chunk per-leaf gradients (SURVEY.md §7 hard part (e)). Linearity
    of the weighted sum makes chunking exact up to fp summation order —
    which is also what lets the quarantine run per chunk against the
    replicated running-median threshold (`qmed`, from server state): the
    verdict never needs the other chunks' norms.

    nan_safe switches the 0/1 weighting from multiply to modes.mask_rows so
    a masked client carrying NaN/Inf (poisoned update, zeroed dead-client
    batch) still contributes an exact zero; it is forced on whenever the
    quarantine is armed, and value-identical to the multiply form on finite
    data. A zero cotangent times a NaN is a NaN, so the fused path under
    nan_safe also zeroes the floating leaves of a masked client's batch
    BEFORE the forward pass: whatever its rows held, the round is the round
    a zeroed batch yields, bit for bit."""
    nan_safe = nan_safe or cfg.client_update_clip > 0
    fused = not _needs_client_gradients(cfg)
    if nan_safe:
        rows = modes.mask_rows
    else:
        rows = lambda w, a: a * modes.bcast(w, a)  # noqa: E731

    @jax.named_scope("cohort_reduce")
    def reduce(updates, nstates, metrics, cpart):
        norms_c = lnorms_c = None
        if cfg.client_update_clip > 0:
            norms_c = _client_norms_tree(updates)
            bad = _quarantine_mask(cfg, norms_c, qmed)
            if lmed is not None:
                lnorms_c = _client_layer_norms_tree(updates)
                bad = bad | _quarantine_layer_mask(cfg, lnorms_c, lmed)
            cpart = cpart * (1.0 - bad.astype(cpart.dtype))
        updates = _clip_updates_tree(cfg, updates)
        wsum, ns_sum, m_sum = jax.tree.map(
            lambda a: rows(cpart, a).sum(axis=0), (updates, nstates, metrics))
        return wsum, ns_sum, m_sum, cpart, norms_c, lnorms_c

    def per_client_chunk(cb, crngs, cpart):
        grad_client_tree = _make_grad_client_tree(loss_fn, cfg)
        with jax.named_scope("client_grad"):
            updates, nstates, metrics = jax.vmap(
                lambda b, r: grad_client_tree(params, net_state, b, r)
            )(cb, crngs)
        return reduce(updates, nstates, metrics, cpart)

    def fused_chunk(cb, crngs, cpart):
        if nan_safe:
            cb = jax.tree.map(
                lambda a: (jnp.where(modes.bcast(cpart, a) > 0, a, 0)
                           if jnp.issubdtype(a.dtype, jnp.floating) else a),
                cb)

        def cohort_loss(p):
            losses, aux = jax.vmap(
                lambda b, r: loss_fn(p, net_state, b, r))(cb, crngs)
            return rows(cpart, losses).sum(), aux

        with jax.named_scope("client_grad"):
            wsum, aux = jax.grad(cohort_loss, has_aux=True)(params)
        with jax.named_scope("cohort_reduce"):
            ns_sum, m_sum = jax.tree.map(
                lambda a: rows(cpart, a).sum(axis=0),
                (aux["net_state"], aux["metrics"]))
        return wsum, ns_sum, m_sum, cpart, None, None

    chunk = fused_chunk if fused else per_client_chunk
    W = part.shape[0]
    C = cfg.client_chunk
    if not C or C >= W:
        out = chunk(batch, client_rngs, part)
    else:
        if W % C:
            raise ValueError(
                f"client_chunk={C} must divide the sampled cohort ({W})"
            )
        re = lambda a: a.reshape((W // C, C) + a.shape[1:])  # noqa: E731
        xs = (jax.tree.map(re, batch),
              client_rngs.reshape((W // C, C) + client_rngs.shape[1:]),
              part.reshape(W // C, C))
        shapes = jax.eval_shape(chunk, *jax.tree.map(lambda a: a[0], xs))
        init = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes[:3])

        def body(carry, x):
            wsum, ns_sum, m_sum, cpart_eff, norms_c, lnorms_c = chunk(*x)
            with jax.named_scope("cohort_reduce"):
                carry = jax.tree.map(jnp.add, carry, (wsum, ns_sum, m_sum))
            return carry, (cpart_eff, norms_c, lnorms_c)

        acc, (pe, norms, lnorms) = jax.lax.scan(body, init, xs)
        out = acc + (pe.reshape(W),
                     None if norms is None else norms.reshape(W),
                     None if lnorms is None else lnorms.reshape(W, -1))
    wsum, rest = out[0], out[1:]
    if fused and cfg.weight_decay:
        # sum_i w_i (g_i + wd * theta) == g + wd * (sum_i w_i) * theta: once,
        # on the reduced tree, where the per-client path adds it W times
        with jax.named_scope("cohort_reduce"):
            decay = cfg.weight_decay * rest[2].sum()
            wsum = jax.tree.map(lambda g, p: g + decay * p, wsum, params)
    if ravel:
        wsum = _ravel_reduced(wsum)
    return (wsum,) + rest


@jax.named_scope("cohort_reduce")
def _finalize_client_reduce(mcfg: ModeConfig, wsum, ns_sum, m_sum, net_state, part):
    """Normalize the weighted SUMS from `_weighted_client_reduce`: the reduced
    update (survivor mean unless agg_op=sum), the survivor-mean mutable
    collections (previous stats when no survivors), and the metrics dict with
    the participants count."""
    n_live = jnp.maximum(part.sum(), 1.0)
    weighted = wsum if mcfg.agg_op == "sum" else wsum / n_live
    new_net_state = _merge_collections(
        lambda s, prev: jnp.where(part.sum() > 0, s / n_live, prev),
        ns_sum, net_state,
    )
    out_metrics = dict(m_sum)
    out_metrics["participants"] = part.sum()
    return weighted, new_net_state, out_metrics


@jax.named_scope("compress")
def _compress_reduced(mcfg: ModeConfig, weighted) -> dict:
    """Compress the reduced update once and lift it to the aggregate wire —
    the linearity shortcut's server-side entry point."""
    agg, _ = modes.client_compress(mcfg, weighted, {})
    return modes.aggregate(mcfg, jax.tree.map(lambda x: x[None], agg))


# graftlint: sketch-boundary — THE ravel path's sanctioned flat params
# materialization: every round-path `pflat, unravel` routes through here so
# the step bodies themselves stay G010-guarded (a ravel_pytree added inside
# one fires the rule; the layerwise path never calls this)
def _ravel_params(params):
    """Flat [d] params view + unravel for sketch_path="ravel"."""
    return ravel_pytree(params)


# graftlint: sketch-boundary — the NON-LINEAR modes' declared flat boundary:
# per-client compression (local_topk, client-state modes) and the payload
# round need each client's own flat [d] update, so the per-client gradient
# is raveled here ON PURPOSE; the linear grad modes reduce the pytree of
# _make_grad_client_tree instead and ravel once (_ravel_reduced)
def _make_grad_client(loss_fn: Callable, cfg: EngineConfig) -> Callable:
    """One client's contribution as a flat [d] gradient (+ weight decay,
    applied client-side as in the reference workers — SURVEY.md §3.1), new
    mutable collections, metric sums — for the rounds that compress per
    client."""

    def grad_client(params, pflat, net_state, cbatch, rng):
        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, net_state, cbatch, rng
        )
        # the ravel is where the [W, d] stack of per-client gradients is
        # written: the cohort reduce's cost, not the backward pass's
        with jax.named_scope("cohort_reduce"):
            gflat, _ = ravel_pytree(grads)
            gflat = gflat + cfg.weight_decay * pflat
        return gflat, aux["net_state"], aux["metrics"]

    return grad_client


def _make_grad_client_tree(loss_fn: Callable, cfg: EngineConfig) -> Callable:
    """One client's contribution for the linear grad modes on
    `_weighted_client_reduce`'s PER-CLIENT path (quarantine or dp_clip armed;
    otherwise no per-client gradient is computed at all): the gradient
    stays a pytree of per-layer leaves — nothing is raveled per client.
    Weight decay applies per leaf, client-side and unconditionally like
    `_make_grad_client`'s `gflat + wd * pflat` (same per-coordinate
    arithmetic, so wd == 0 keeps the identical ±0.0 additions)."""

    def grad_client(params, net_state, cbatch, rng):
        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, net_state, cbatch, rng
        )
        with jax.named_scope("cohort_reduce"):
            grads = jax.tree.map(
                lambda g, p: g + cfg.weight_decay * p, grads, params)
        return grads, aux["net_state"], aux["metrics"]

    return grad_client


@jax.named_scope("cohort_reduce")
def _layerwise_normalize(mcfg: ModeConfig, wsum_tree, n_live):
    """Survivor normalization of the per-leaf weighted sums — the tree
    mirror of `_finalize_client_reduce`'s `wsum / n_live` (elementwise, so
    the downstream sketch sees the identical values)."""
    if mcfg.agg_op == "sum":
        return wsum_tree
    return jax.tree.map(lambda l: l / n_live, wsum_tree)


@jax.named_scope("compress")
def _layerwise_compress(mcfg: ModeConfig, tree, plan) -> dict:
    """Fold a (normalized or partial) update pytree into the sketch wire —
    the layerwise counterpart of `_compress_reduced`/`client_compress` for
    mode=sketch, bit-identical to sketching the raveled vector."""
    from ..sketch import layerwise as sketch_layerwise

    return {"table": sketch_layerwise.sketch_tree(
        mcfg.sketch_spec, tree, plan)}


def _layerwise_plan(mcfg: ModeConfig, params):
    from ..sketch import layerwise as sketch_layerwise

    return sketch_layerwise.make_block_plan(mcfg.sketch_spec, params)


@jax.named_scope("apply")
def _layerwise_apply(params, delta: dict, plan):
    from ..sketch import layerwise as sketch_layerwise

    return sketch_layerwise.apply_delta_tree(params, delta, plan)


@jax.named_scope("apply")
def _flat_apply(pflat, unravel, delta: dict):
    """The ravel path's apply: params - delta on the flat view, unraveled."""
    return unravel(modes.apply_delta(pflat, delta))


def make_round_step(
    loss_fn: Callable, cfg: EngineConfig
) -> Callable[[dict, Any, dict, jnp.ndarray, jnp.ndarray], tuple[dict, dict, dict]]:
    """Build the jittable round step.

    step(state, batch, client_rows, lr, rng) -> (state', client_rows', metrics)

    - `batch`: pytree of arrays with leading axis W (sampled clients); for
      fedavg/localSGD modes the per-client arrays additionally have a
      [num_local_iters] microbatch axis right after W.
    - `client_rows`: per-sampled-client slices of persistent local state
      ({} when the mode needs none); caller gathers/scatters by client id.
    - `lr`: scalar client learning rate (schedule value). Weight-delta modes
      consume it in the local SGD loop and the server applies the averaged
      delta at unit rate; grad modes apply it server-side.
    - metrics are summed over clients (and local iters); caller normalises.
    """
    mcfg = cfg.mode
    _robust_scope_check(cfg)
    grad_client = _make_grad_client(loss_fn, cfg)
    layerwise = cfg.sketch_path == "layerwise"
    layer_q = (cfg.client_update_clip > 0
               and cfg.quarantine_scope == "layer")

    # graftlint: sketch-boundary — weight-delta modes (fedavg/localSGD) run
    # their local-SGD loop over the flat params by design; out of the
    # layerwise scope (mode=sketch never takes this branch)
    def local_sgd_client(params, pflat, net_state, cbatch, rng, lr):
        _, unravel = ravel_pytree(params)
        # client-local momentum over the local iterations (fedavg "local
        # momentum"; within-round only — sampled clients are stateless across
        # rounds in fedavg). mu = 0 when momentum is virtual/none.
        mu = mcfg.momentum if mcfg.momentum_type == "local" else 0.0

        def body(carry, xs):
            p_cur, nstate, mom = carry
            micro, step_rng = xs
            (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                unravel(p_cur), nstate, micro, step_rng
            )
            gflat, _ = ravel_pytree(grads)
            gflat = gflat + cfg.weight_decay * p_cur
            mom = mu * mom + gflat
            return (p_cur - lr * mom, aux["net_state"], mom), aux["metrics"]

        iters = mcfg.num_local_iters
        rngs = jax.random.split(rng, iters)
        init = (pflat, net_state, jnp.zeros_like(pflat))
        (p_final, nstate, _), metrics = jax.lax.scan(body, init, (cbatch, rngs))
        delta = pflat - p_final
        return delta, nstate, jax.tree.map(lambda m: m.sum(0), metrics)

    def step(state, batch, client_rows, lr, rng):
        batch, health_flag = split_health(batch)
        batch, valid = split_valid(batch)
        params, net_state = state["params"], state["net_state"]
        if layerwise:
            plan = _layerwise_plan(mcfg, params)
        else:
            pflat, unravel = _ravel_params(params)
        num_sampled = jax.tree.leaves(batch)[0].shape[0]
        # Dedicated streams: in JAX's threefry PRNG, fold_in(key, i) ==
        # split(key, n)[i], so deriving the DP noise key by folding the same
        # rng that client keys are split from would collide with client
        # fold_in(rng, 0x0D9)=217's stream at large cohorts — voiding noise
        # independence exactly when DP matters. Split first, then derive.
        crng, noise_rng, drop_rng = jax.random.split(rng, 3)
        client_rngs = jax.random.split(crng, num_sampled)
        part = participation_mask(drop_rng, num_sampled, cfg.client_dropout)
        if valid is not None:
            # dead clients (failed load / injected drop) fold into the same
            # survivor machinery random dropout uses: zero weight, removed
            # from every renormalization, state rows untouched
            part = part * valid
        qmed = (state["quarantine"]["median"]
                if cfg.client_update_clip > 0 else None)
        lmed = state["quarantine"]["layer_median"] if layer_q else None
        segments = _leaf_segments(params) if layer_q else None
        norms = lnorms = None

        if (modes.is_linear(mcfg) and not mcfg.needs_local_state
                and not mcfg.uses_weight_delta):
            # grad modes on the linearity shortcut: sketching/reduction
            # commute, so compress once on the reduced update instead of per
            # client — exactly equal, much cheaper. Participation weighting
            # folds into the same reduction (survivor mean = sum(part·u) /
            # count(part); sum drops the /), and the reduce itself may run
            # chunked (cfg.client_chunk). With no per-client transform armed
            # the same linearity is taken one step earlier: one backward
            # pass of the masked sum of losses, no per-client gradient.
            wsum, ns_sum, m_sum, part_eff, norms, lnorms = (
                _weighted_client_reduce(
                    cfg, loss_fn, params, net_state, batch,
                    client_rngs, part, qmed=qmed, nan_safe=valid is not None,
                    lmed=lmed, ravel=not layerwise,
                ))
            if layerwise:
                # sketch-as-you-backprop: the per-leaf sums fold straight
                # into the running r x c table — not even the reduced flat
                # [d] gradient materializes (see EngineConfig.sketch_path)
                weighted = _layerwise_normalize(
                    mcfg, wsum, jnp.maximum(part_eff.sum(), 1.0))
                new_net_state, out_metrics = _merged_survivor_finalize(
                    ns_sum, m_sum, part_eff, net_state)
                agg = _layerwise_compress(mcfg, weighted, plan)
            else:
                weighted, new_net_state, out_metrics = _finalize_client_reduce(
                    mcfg, wsum, ns_sum, m_sum, net_state, part_eff
                )
                agg = _compress_reduced(mcfg, weighted)
            new_rows = client_rows
        else:
            with jax.named_scope("client_grad"):
                if mcfg.uses_weight_delta:
                    updates, nstates, metrics = jax.vmap(
                        lambda cb, r: local_sgd_client(params, pflat, net_state, cb, r, lr)
                    )(batch, client_rngs)
                else:
                    updates, nstates, metrics = jax.vmap(
                        lambda cb, r: grad_client(params, pflat, net_state, cb, r)
                    )(batch, client_rngs)
            part_eff = part
            with jax.named_scope("cohort_reduce"):
                if cfg.client_update_clip > 0:
                    norms = _client_norms(updates)
                    bad = _quarantine_mask(cfg, norms, qmed)
                    if layer_q:
                        lnorms = _client_layer_norms(updates, segments)
                        bad = bad | _quarantine_layer_mask(cfg, lnorms, lmed)
                    part_eff = part * (1.0 - bad.astype(part.dtype))
                    # hard-zero the rejected updates so downstream per-client
                    # transforms (top-k, local error rows) never see the poison
                    updates = jnp.where(bad[:, None], jnp.zeros_like(updates),
                                        updates)
                updates = _clip_updates(cfg, updates)
                n_live = jnp.maximum(part_eff.sum(), 1.0)

            if modes.is_linear(mcfg) and not mcfg.needs_local_state:
                # weight-delta modes (fedavg/localSGD) on the shortcut: the
                # local-iteration scan already holds per-client state, so no
                # chunked reduce — just the survivor-weighted mean of deltas
                with jax.named_scope("cohort_reduce"):
                    weighted = modes.mask_rows(part_eff, updates).sum(axis=0)
                    if mcfg.agg_op != "sum":
                        weighted = weighted / n_live
                agg = _compress_reduced(mcfg, weighted)
                new_rows = client_rows
            else:
                with jax.named_scope("compress"):
                    wires, vrows = jax.vmap(lambda u, row: modes.client_compress(mcfg, u, row))(
                        updates, client_rows
                    )
                    agg = modes.aggregate(mcfg, wires, weights=part_eff)
                    # dropped/quarantined clients never transmitted (usably):
                    # their persistent local state (error/momentum rows) stays
                    # exactly as it was
                    new_rows = jax.tree.map(
                        lambda new, old: jnp.where(modes.bcast(part_eff, new) > 0, new, old),
                        vrows, client_rows,
                    )
            new_net_state = _merge_net_state(nstates, net_state, part_eff)
            out_metrics = _survivor_metrics(metrics, part_eff)

        new_q = None
        if cfg.client_update_clip > 0:
            out_metrics["clients_quarantined"] = part.sum() - part_eff.sum()
            new_q = _advance_quarantine_full(cfg, state["quarantine"], norms,
                                             lnorms, part_eff)
            out_metrics["quarantine_median"] = new_q["median"]
        # the health block measures the PRE-guard wire: a poisoned round's
        # estimators must show the poison the guard is about to discard
        raw_agg = agg
        agg, new_net_state, new_rows, out_metrics, fin_ok = _guard_nonfinite(
            cfg, agg, new_net_state, net_state, new_rows, client_rows,
            out_metrics,
        )
        if cfg.dp_noise > 0:
            # fin_ok gates the count: a skipped round is a fully-dropped
            # cohort, and _dp_noise_agg releases nothing for an empty round.
            # part_eff: a quarantined client released nothing either, so DP
            # sensitivity calibrates to the clients that actually merged.
            agg = _dp_noise_agg(cfg, agg, part_eff.sum() * fin_ok, noise_rng)

        # weight-delta modes: local steps already carry the client lr; the
        # server applies the averaged delta at the configured server rate
        # ("slowmo" when combined with virtual momentum)
        server_lr = jnp.float32(mcfg.server_lr) if mcfg.uses_weight_delta else lr
        delta, mode_state = modes.server_step_sparse(
            mcfg, agg, state["mode_state"], server_lr)
        new_params = (_layerwise_apply(params, delta, plan) if layerwise
                      else _flat_apply(pflat, unravel, delta))
        new_state = {
            "params": new_params,
            "net_state": new_net_state,
            "mode_state": mode_state,
            "round": state["round"] + 1,
        }
        if new_q is not None:
            new_state["quarantine"] = new_q
        if cfg.health and mcfg.mode == "sketch":
            # mode=sketch always takes the linearity-shortcut branch above,
            # so `weighted` is the dense reduced update (ravel) or the
            # per-leaf tree (layerwise) — the dense-comparable reference
            dense_w = tree_w = segs = None
            if layerwise:
                tree_w = weighted
            else:
                dense_w = weighted
                segs = _leaf_segments(params)
            out_metrics.update(_health_metrics(
                cfg, health_flag, raw_agg, delta, mode_state,
                weighted=dense_w, weighted_tree=tree_w, segments=segs))
        out_metrics.update(_ledger_fingerprints(cfg, new_state))
        if mcfg.mode == "local_topk":
            # support of the actually-broadcast delta (SURVEY.md §6 row 4):
            # the union of client supports when momentum keeps nothing extra
            # (momentum none), but with virtual momentum it carries past
            # rounds' coordinates, and DP noise densifies it entirely — the
            # accounting in run_round caps the pair encoding at the dense-
            # float cost a real server would switch to past the crossover.
            out_metrics["down_support"] = modes.delta_support(mcfg.d, delta)
        return new_state, new_rows, out_metrics

    return step


def supports_sharded_round(mcfg: ModeConfig) -> bool:
    """Scope of the SPMD data-parallel round (make_sharded_round_step):
    linear grad modes without client-local state and without the local-SGD
    weight-delta loop — compression must commute with the cross-shard sum,
    which is exactly FetchSGD's sketch linearity (and trivially holds for
    dense wires): the flagship configuration.
    Everything else keeps the GSPMD-annotation path (XLA partitions the
    unchanged round program; cross-device reduction is the dense wire)."""
    return (modes.is_linear(mcfg) and not mcfg.needs_local_state
            and not mcfg.uses_weight_delta)


def _sharded_scope_check(mcfg: ModeConfig):
    if not supports_sharded_round(mcfg):
        raise ValueError(
            "sharded round supports linear grad modes without client-local "
            f"state (the flagship sketch config); mode={mcfg.mode!r} "
            f"error_type={mcfg.error_type!r} momentum_type="
            f"{mcfg.momentum_type!r} needs the GSPMD path (make_round_step "
            "over a sharded batch)"
        )


def _cohort_streams(cfg: EngineConfig, rng, num_sampled: int):
    """The full cohort's device-side streams, derived EXACTLY as the fused
    step derives them (split-first; see make_round_step's collision comment):
    per-client rng rows, participation mask, DP noise key. The sharded round
    computes these replicated and hands each shard its contiguous row slice,
    so client i sees the same rng stream at every shard count and on every
    mesh shape — the cohort-to-device assignment preserves per-client RNG
    streams."""
    crng, noise_rng, drop_rng = jax.random.split(rng, 3)
    client_rngs = jax.random.split(crng, num_sampled)
    part = participation_mask(drop_rng, num_sampled, cfg.client_dropout)
    return client_rngs, part, noise_rng


@jax.named_scope("cohort_reduce")
def _merged_survivor_finalize(ns_sum, m_sum, part, net_state):
    """Survivor-mean mutable collections + metrics/participants from MERGED
    cross-shard sums — the sharded round's counterpart of
    _finalize_client_reduce, the ONE place for these semantics so the fused
    layerwise round, the sharded tail and the payload merge cannot drift
    apart."""
    n_live = jnp.maximum(part.sum(), 1.0)
    new_net_state = _merge_collections(
        lambda s, prev: jnp.where(part.sum() > 0, s / n_live, prev),
        ns_sum, net_state,
    )
    out_metrics = dict(m_sum)
    out_metrics["participants"] = part.sum()
    return new_net_state, out_metrics


@jax.named_scope("compress")
def _normalize_merged_wire(mcfg: ModeConfig, wire_sum: dict, n_live) -> dict:
    """Survivor normalization IN WIRE SPACE (compression is homogeneous only
    up to fp order, so every sharded path normalizes after the merge — one
    place, shared by the sharded tail and the payload merge)."""
    if mcfg.agg_op == "sum":
        return dict(wire_sum)
    return {k: v / n_live for k, v in wire_sum.items()}


def _merged_sharded_tail(
    cfg: EngineConfig, state, stacked_wire, stacked_ns, stacked_m, part_eff,
    lr, noise_rng, part=None, norms=None, lnorms=None, health_flag=None,
):
    """Everything after the per-shard client phase, shared verbatim by the
    mesh execution and the single-device reference so they cannot drift:
    ordered merge of the stacked [S, ...] partials (modes.merge_partial_wires
    — an ordered sum, NOT a psum, which is what makes mesh == single-device
    bit-identical), survivor normalization, quarantine bookkeeping (the
    running-median update from the gathered [W] norms), non-finite guard, DP
    noise, and the replicated server step. `part_eff` is the [W] effective
    contribution mask (dropout x validity x quarantine) reassembled from the
    shards; `part`/`norms` only exist with the quarantine armed (part = the
    pre-quarantine mask, for the quarantined count)."""
    mcfg = cfg.mode
    layerwise = cfg.sketch_path == "layerwise"
    with jax.named_scope("compress"):
        wire_sum = modes.merge_partial_wires(mcfg, stacked_wire)
    with jax.named_scope("cohort_reduce"):
        ns_sum = jax.tree.map(lambda x: x.sum(axis=0), stacked_ns)
        m_sum = jax.tree.map(lambda x: x.sum(axis=0), stacked_m)
    if not layerwise:
        pflat, unravel = _ravel_params(state["params"])
    agg = _normalize_merged_wire(mcfg, wire_sum,
                                 jnp.maximum(part_eff.sum(), 1.0))
    new_net_state, out_metrics = _merged_survivor_finalize(
        ns_sum, m_sum, part_eff, state["net_state"])
    new_q = None
    if cfg.client_update_clip > 0:
        out_metrics["clients_quarantined"] = part.sum() - part_eff.sum()
        new_q = _advance_quarantine_full(cfg, state["quarantine"], norms,
                                         lnorms, part_eff)
        out_metrics["quarantine_median"] = new_q["median"]
    raw_agg = agg  # pre-guard wire: the health block must show the poison
    agg, new_net_state, _, out_metrics, fin_ok = _guard_nonfinite(
        cfg, agg, new_net_state, state["net_state"], {}, {}, out_metrics,
    )
    if cfg.dp_noise > 0:
        agg = _dp_noise_agg(cfg, agg, part_eff.sum() * fin_ok, noise_rng)
    delta, mode_state = modes.server_step_sparse(
        mcfg, agg, state["mode_state"], lr)
    new_params = (
        _layerwise_apply(state["params"], delta,
                         _layerwise_plan(mcfg, state["params"]))
        if layerwise else _flat_apply(pflat, unravel, delta))
    new_state = {
        "params": new_params,
        "net_state": new_net_state,
        "mode_state": mode_state,
        "round": state["round"] + 1,
    }
    if new_q is not None:
        new_state["quarantine"] = new_q
    if cfg.health and mcfg.mode == "sketch":
        # sharded rounds merge WIRES, so only the wire-side estimators
        # exist here (the dense reduced update never materializes — that
        # is the sharded path's whole point); the dense-comparable
        # reference stays a fused-path quantity
        out_metrics.update(_health_metrics(
            cfg, health_flag, raw_agg, delta, mode_state))
    out_metrics.update(_ledger_fingerprints(cfg, new_state))
    return new_state, out_metrics


def _kernels_replicated(mesh, fn: Callable) -> Callable:
    """Trace `fn` so that Pallas kernel calls at jit top level — the
    replicated server tail, outside the client-phase shard_map — run under a
    shard_map over `mesh` with replicated in/out specs (every device runs the
    same r x c -> top-k step on the same gathered operands). The SPMD
    compiler cannot partition a Mosaic custom call, so without this the
    multi-device program does not lower on a TPU mesh; calls already inside
    a shard_map body are left alone (pallas_kernels.replicated_on)."""
    if mesh is None:
        return fn

    def wrapped(*args, **kwargs):
        from ..sketch import pallas_kernels

        with pallas_kernels.replicated_on(mesh):
            return fn(*args, **kwargs)

    return wrapped


def _mesh_shard_info(mesh):
    from ..parallel import mesh as meshlib

    return meshlib.client_shards(mesh), meshlib.client_axis_names(mesh)


def _shard_index(mesh, axis_names) -> jnp.ndarray:
    """This device's shard position along the (possibly hybrid) client axes,
    row-major over (slices, clients) — the same order shard_client_batch
    lays the cohort out in and all_gather stacks partials in, so slice i of
    the replicated per-client streams is exactly shard i's cohort."""
    idx = jnp.int32(0)
    for name in axis_names:
        idx = idx * mesh.shape[name] + jax.lax.axis_index(name)
    return idx


def make_sharded_round_step(
    loss_fn: Callable, cfg: EngineConfig, mesh=None
) -> Callable[[dict, Any, dict, jnp.ndarray, jnp.ndarray], tuple[dict, dict, dict]]:
    """The data-parallel round as an explicit SPMD program — the device mesh
    realized in the ENGINE rather than left to GSPMD's partitioner.

    Per shard (= per device on a mesh): the shard's W/S clients run the
    vmapped (or client_chunk-scanned) fwd/bwd, reduce to ONE local weighted
    update, and compress it locally — for mode=sketch that is the shard's
    partial Count Sketch, via the same csvec path (Pallas when routed) the
    single-device round uses. The cross-device merge is then a single
    ordered sum of the r x c partial tables (modes.merge_partial_wires /
    csvec.merge_tables): FetchSGD's linearity means sketches of partial
    client sums ADD to the sketch of the cohort sum, so per-device uplink
    stays the paper's sketch size while client compute scales linearly with
    devices — a dense [d] gradient never crosses the mesh. The merge is
    implemented as all_gather + ordered sum rather than a psum: measured on
    an 8-way CPU mesh, a ring psum reassociates the reduce and breaks the
    bit-parity this program pins (at table scale the extra gather bytes are
    noise next to the d/(r*c) savings vs a dense all-reduce). Overlap with
    compute comes from the runner's in-flight chain: round N+1's dispatch
    queues behind round N's collectives, so XLA's scheduler hides the
    (ICI/DCN) merge behind the next round's client phase.

    mesh=None runs the SAME shard-structured program on one device (a
    lax.map over the cfg.client_shards shards, merged by the same ordered
    sum) — the bit-parity reference the CPU-mesh tests compare against, and
    the numerical contract: client_shards=S produces identical bits on one
    device and on an S-way mesh. Signature matches make_round_step
    (client_rows pass through untouched — the scope has no local state)."""
    mcfg = cfg.mode
    _sharded_scope_check(mcfg)
    _robust_scope_check(cfg)
    if mesh is not None:
        S, axis_names = _mesh_shard_info(mesh)
        if cfg.client_shards > 1 and cfg.client_shards != S:
            raise ValueError(
                f"cfg.client_shards={cfg.client_shards} disagrees with the "
                f"{S}-way client mesh"
            )
    else:
        S = cfg.client_shards
    if S <= 1:
        raise ValueError(
            "sharded round needs client_shards > 1 (or a mesh with > 1 "
            "client shard); use make_round_step for the unsharded round"
        )
    layerwise = cfg.sketch_path == "layerwise"
    quarantine = cfg.client_update_clip > 0
    layer_q = quarantine and cfg.quarantine_scope == "layer"

    def local_phase(params, net_state, qmed, lmed, batch_l, rngs_l, part_l):
        """One shard's client phase. Returns (wire, ns_sum, m_sum, part_eff)
        plus, with the quarantine armed, (part_valid, norms[, lnorms]) — the
        per-shard slices the merged tail reassembles into cohort-order [W]
        vectors (lnorms only under layer scope: the per-leaf screens run
        per shard against the replicated per-leaf medians, exactly like the
        scalar screen). On the layerwise path the shard's partial Count
        Sketch accumulates straight from the per-leaf weighted sums — the
        shard's dense [d] partial never exists either."""
        batch_l, valid_l = split_valid(batch_l)
        if valid_l is not None:
            part_l = part_l * valid_l
        wsum, ns_sum, m_sum, part_eff_l, norms_l, lnorms_l = (
            _weighted_client_reduce(
                cfg, loss_fn, params, net_state, batch_l, rngs_l,
                part_l, qmed=qmed, nan_safe=valid_l is not None, lmed=lmed,
                ravel=not layerwise,
            ))
        if layerwise:
            wire = _layerwise_compress(mcfg, wsum,
                                       _layerwise_plan(mcfg, params))
        else:
            with jax.named_scope("compress"):
                wire, _ = modes.client_compress(mcfg, wsum, {})
        if layer_q:
            return wire, ns_sum, m_sum, part_eff_l, part_l, norms_l, lnorms_l
        if quarantine:
            return wire, ns_sum, m_sum, part_eff_l, part_l, norms_l
        return wire, ns_sum, m_sum, part_eff_l

    def _tail(cfg_state, stacked, lr, noise_rng, health_flag=None):
        """Unpack the per-shard stacks ([S, wl] leaves, shard-index order =
        cohort order row-major) and run the shared merged tail."""
        if layer_q:
            wire_s, ns_s, m_s, pe_s, pv_s, norms_s, lnorms_s = stacked
            return _merged_sharded_tail(
                cfg, cfg_state, wire_s, ns_s, m_s, pe_s.reshape(-1), lr,
                noise_rng, part=pv_s.reshape(-1), norms=norms_s.reshape(-1),
                lnorms=lnorms_s.reshape((-1,) + lnorms_s.shape[2:]),
                health_flag=health_flag)
        if quarantine:
            wire_s, ns_s, m_s, pe_s, pv_s, norms_s = stacked
            return _merged_sharded_tail(
                cfg, cfg_state, wire_s, ns_s, m_s, pe_s.reshape(-1), lr,
                noise_rng, part=pv_s.reshape(-1), norms=norms_s.reshape(-1),
                health_flag=health_flag)
        wire_s, ns_s, m_s, pe_s = stacked
        return _merged_sharded_tail(
            cfg, cfg_state, wire_s, ns_s, m_s, pe_s.reshape(-1), lr,
            noise_rng, health_flag=health_flag)

    if mesh is None:
        def step(state, batch, client_rows, lr, rng):
            batch, health_flag = split_health(batch)
            params, net_state = state["params"], state["net_state"]
            W = jax.tree.leaves(batch)[0].shape[0]
            if W % S:
                raise ValueError(
                    f"sampled cohort ({W}) not divisible by "
                    f"client_shards={S}"
                )
            wl = W // S
            all_rngs, part, noise_rng = _cohort_streams(cfg, rng, W)
            qmed = state["quarantine"]["median"] if quarantine else None
            lmed = state["quarantine"]["layer_median"] if layer_q else None
            shards = (
                jax.tree.map(
                    lambda a: a.reshape((S, wl) + a.shape[1:]), batch),
                all_rngs.reshape((S, wl) + all_rngs.shape[1:]),
                part.reshape(S, wl),
            )
            # lax.map (sequential scan) over shards: the body executes the
            # per-shard phase exactly as each mesh device executes it, and
            # the stacked result feeds the same merged tail. Parity with
            # the shard_map program is bit-exact for params and every
            # metric (pinned in tests/test_sharded_round.py); the sketch
            # server-state tables can carry last-bit (~1e-9) differences
            # because XLA:CPU's value-dependent vectorization of an
            # identical subgraph differs between a while-loop body and the
            # inlined shard_map body — no structuring of the reference
            # (unrolled, length-1 map, top-level tail) removes it for
            # every mode at once, it only moves which ops carry the ulp.
            stacked = jax.lax.map(
                lambda xs: local_phase(params, net_state, qmed, lmed, *xs),
                shards,
            )
            new_state, out_metrics = _tail(state, stacked, lr, noise_rng,
                                           health_flag)
            return new_state, client_rows, out_metrics

        return step

    from jax.sharding import PartitionSpec as P

    from ..parallel import mesh as meshlib

    batch_spec = P(meshlib.client_axes(mesh))

    # Only the CLIENT PHASE + gather runs inside shard_map; the merged tail
    # (ordered reduce + server algebra) runs at jit top level on the
    # replicated gathered stacks — the same compile context the reference's
    # tail has after its lax.map. Running the tail inside the shard_map body
    # instead compiles it in a per-shard module where XLA's value-dependent
    # fusion (fma contraction) can differ from the reference's at the last
    # bit (observed: ~6 table entries at 1e-9 after one momentum round),
    # which would break the bit-identity pin on the server state.
    n_local_outs = 7 if layer_q else (6 if quarantine else 4)

    def body(state, batch_l, lr, rng):
        params, net_state = state["params"], state["net_state"]
        wl = jax.tree.leaves(batch_l)[0].shape[0]
        # replicated derivation of the FULL cohort's streams on every
        # device, then this shard's contiguous slice — per-client rng
        # streams are mesh-shape-invariant (see _cohort_streams)
        all_rngs, part, noise_rng = _cohort_streams(cfg, rng, wl * S)
        qmed = state["quarantine"]["median"] if quarantine else None
        lmed = state["quarantine"]["layer_median"] if layer_q else None
        lo = _shard_index(mesh, axis_names) * wl
        rngs_l = jax.lax.dynamic_slice_in_dim(all_rngs, lo, wl)
        part_l = jax.lax.dynamic_slice_in_dim(part, lo, wl)
        locals_ = local_phase(
            params, net_state, qmed, lmed, batch_l, rngs_l, part_l)
        # THE cross-device move: gather the [S] partial wires (plus the tiny
        # per-shard effective-mask/norm rows) in shard order; the ordered
        # reduce happens outside, shared with the reference (merged tail)
        stacked = jax.tree.map(
            lambda x: jax.lax.all_gather(x, axis_names, axis=0),
            locals_,
        )
        return stacked + (noise_rng,)

    mapped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), batch_spec, P(), P()),
        out_specs=tuple(P() for _ in range(n_local_outs + 1)),
        # outputs ARE replicated (all_gather results and the replicated
        # stream derivations are identical on every device); the static
        # checker just can't see through all_gather
        check_vma=False,
    )

    def step(state, batch, client_rows, lr, rng):
        # popped BEFORE shard_map (the tail runs at jit top level on the
        # replicated gathered stacks — the flag gates it there)
        batch, health_flag = split_health(batch)
        outs = mapped(state, batch, lr, rng)
        stacked, noise_rng = outs[:-1], outs[-1]
        new_state, out_metrics = _tail(state, stacked, lr, noise_rng,
                                       health_flag)
        return new_state, client_rows, out_metrics

    return _kernels_replicated(mesh, step)


def _table_norms(tables: jnp.ndarray) -> jnp.ndarray:
    """[W] sketch-space L2 norm of each client's r x c payload table (f32
    accumulation) — the quarantine observable of the wire-payload round: the
    table IS the only object the server sees, so the screen (and the running
    median it feeds) lives in sketch space. By the Count Sketch's isometry-
    in-expectation each row's squared norm estimates the update's, so the
    magnitude screen keeps its meaning; non-finite updates propagate into
    non-finite tables, so the non-finite screen is exact."""
    t = tables.astype(jnp.float32)
    return jnp.sqrt(jnp.sum(jnp.square(t), axis=(1, 2)))


# Reserved per-client batch leaves: the ADVERSARIAL transform of the table
# round (resilience/faults.py client_signflip / client_scale /
# client_collude). `_adv_scale` is a [W] float multiplier applied to each
# client's transmitted table (sketch linearity makes scaling the table
# EXACTLY scaling the update: sketch(a*u) == a*sketch(u) coordinate-wise);
# `_adv_src` is a [W] int source position — a colluding client transmits a
# (scaled) CLONE of the source's table instead of its own. `_adv_ride`
# (present only when the plan names client_normride) is a [W] float ride
# fraction in (0, 1]: a riding client rescales its table so its sketch-
# space L2 sits at ride * clip_multiple * running_median — just UNDER the
# quarantine screen, probing the running median the server state carries
# (0 = honest row). Identity defaults (src=arange, scale=1, ride=0) keep
# the program's shapes constant from round 0, so the first attack never
# triggers a mid-run recompile. The leaves ride the batch pytree like
# `_valid` and are popped before the client fwd/bwd ever sees them.
ADV_SCALE_KEY = "_adv_scale"
ADV_SRC_KEY = "_adv_src"
ADV_RIDE_KEY = "_adv_ride"


def split_adv(batch):
    """Pop the reserved adversarial-transform leaves off a round batch.
    Returns (batch_without_them, (scale, src, ride_or_None) or None)."""
    if isinstance(batch, dict) and ADV_SCALE_KEY in batch:
        batch = dict(batch)
        scale = batch.pop(ADV_SCALE_KEY)
        src = batch.pop(ADV_SRC_KEY)
        return batch, (scale, src, batch.pop(ADV_RIDE_KEY, None))
    return batch, None


def _apply_adv(tables: jnp.ndarray, adv, clip: float = 0.0,
               qmed=None) -> jnp.ndarray:
    """Apply the adversarial wire transform to the replicated [W, r, c]
    table stack (AFTER any cross-shard gather, so the crafted table is
    mesh-shape-invariant): row i becomes scale[i] * tables[src[i]]. With
    the identity defaults this is a gather of every row in order times
    1.0 — the same values bit-for-bit.

    `clip`/`qmed` arm the client_normride transform (the ride leaf): a
    riding row is rescaled so its table L2 equals ride * clip * qmed —
    the norm-riding adversary sits just under the quarantine multiple of
    the RUNNING median it is probing (sketch linearity: scaling the table
    is exactly scaling the update, and the gauntlet/merge screens read
    the table norm). Unarmed screens (qmed == 0, round 0's unseeded
    baseline) leave the row untouched — with no threshold to ride there
    is nothing to scale to."""
    if adv is None:
        return tables
    scale, src, ride = adv if len(adv) == 3 else (*adv, None)
    cloned = jnp.take(tables, src.astype(jnp.int32), axis=0)
    out = cloned * scale.astype(tables.dtype)[:, None, None]
    if ride is not None and qmed is not None:
        norms = jnp.sqrt(jnp.sum(
            jnp.square(out.astype(jnp.float32)), axis=(1, 2)))
        target = ride.astype(jnp.float32) * jnp.float32(clip) * qmed
        factor = jnp.where((ride > 0) & (target > 0) & (norms > 0),
                           target / jnp.maximum(norms, 1e-12), 1.0)
        out = out * factor.astype(out.dtype)[:, None, None]
    return out


# graftlint: staleness-fold — THE one sanctioned staleness-weighted fold:
# late tables join the merged wire HERE and nowhere else (rule G013). A
# second fold site would be a second, undeclared aggregation semantics —
# two places that disagree about fold order or weight handling silently
# un-pin the async==sync bit-identity contract.
def _stale_fold(table, live_weight, stale_tables, stale_weights):
    """Ordered staleness-weighted fold of late client tables into a merged
    wire table (the buffered-async mode's FedBuff-shaped update): slot i
    adds `stale_weights[i] * stale_tables[i]` in SLOT ORDER — an explicit
    lax.scan left fold, so the fp association is a pure function of the
    slot assignment (the serving layer fills slots in (source round asc,
    cohort position asc, admission order) — deterministic and replayable,
    never wall-clock). Empty slots carry weight 0 and a zero table.
    Returns (folded table, live_weight + total stale weight, metrics) —
    the weight total feeds the same survivor normalization the live
    cohort uses, so agg_op="mean" becomes the staleness-weighted mean.
    EVERY piece of arithmetic over the stale stack lives in this one
    function: a second touch point would be a second, undeclared
    aggregation semantics (rule G013's whole argument)."""

    def body(carry, xs):
        tbl, wsum = carry
        t, w = xs
        return (tbl + w * t, wsum + w), None

    (folded, total), _ = jax.lax.scan(
        body, (table, live_weight), (stale_tables, stale_weights))
    metrics = {
        "stale_folded": (stale_weights > 0).sum(),
        "stale_weight": stale_weights.sum(),
    }
    return folded, total, metrics


def make_payload_round_steps(
    loss_fn: Callable, cfg: EngineConfig, mesh=None, *,
    allow_batch_tables: bool = False, stale_slots: int = 0,
    edge_input: str = "none",
) -> tuple[Callable, Callable]:
    """The wire-payload round (cfg.wire_payloads) as TWO jittable programs —
    the shape a serving deployment actually has:

        client_step(state, batch, rng) -> (tables[W, r, c], nstates, mvals,
                                           part, noise_rng)
        merge_step(state, tables, nstates, mvals, part, arrived, lr,
                   noise_rng) -> (state', metrics)

    The client program is "the clients": each sampled client's fwd/bwd, DP
    clip, and its OWN Count-Sketch table (the same csvec path the engine
    compresses with) — one [r, c] table per client, the object that crosses
    the wire. The merge program is "the server": it consumes ONLY the
    per-client tables plus tiny per-client masks/metric rows — an ordered
    masked sum through the SAME merge entry point the sharded path uses
    (modes.merge_partial_wires), survivor normalization in wire space,
    sketch-space quarantine (window-capable), non-finite guard, and the
    FetchSGD server algebra.

    The batch simulator composes the two back-to-back with arrived = ones;
    the serving layer runs the client program, round-trips each client's
    table through the transport (serialize -> socket -> validate), and feeds
    the WIRE-DECODED tables + the arrival mask to the merge program. float32
    serialization is exact, both paths run these same two compiled programs,
    and a rejected/missing payload is a zero row under a 0 mask (exact zeros
    via mask_rows either way) — which is what pins a served round with real
    wire-crossed payloads BIT-identical to the server-computed batch round
    over the same surviving cohort, and a rejected payload bitwise equal to
    a dropped client.

    Unlike the announce path there is no compress-once linearity shortcut:
    the aggregate is the ordered sum of W per-client tables (a different fp
    association than sketching the summed update), so wire-payload params
    are NOT bit-comparable to announce-path params — equal in exact
    arithmetic only. That is why --serve_payload announce stays the default.

    client_shards S > 1 runs the client phase as a lax.map over S groups of
    W/S vmapped clients (bounding live per-client gradients to W/S — the
    payload path's chunking mechanism); per-client tables make the cross-
    group arithmetic per-client, so the merge is shard-count-invariant. With
    a mesh the groups become shard_map shards and the tables all_gather.

    Byzantine defenses live here, on both sides of the wire: the client
    program applies the adversarial transform of any armed attack faults
    (split_adv/_apply_adv — a sign-flipped, scaled, or colluding-clone
    table is EXACTLY what a malicious client would transmit, by sketch
    linearity), and the merge applies cfg.merge_policy — "sum" keeps the
    ordered masked sum; "trimmed"/"median" run the coordinate-wise robust
    statistic over the live [W, r, c] stack (modes._robust_table_merge,
    the declared G012 boundary) and rescale by the live count for
    agg_op="sum". Robust policies are why this round shape also serves
    the BATCH simulator (allow_batch_tables / robust_policy(cfg)): order
    statistics need the per-client tables the linearity shortcut never
    materializes."""
    mcfg = cfg.mode
    if not (uses_table_round(cfg) or allow_batch_tables):
        raise ValueError(
            "make_payload_round_steps requires cfg.wire_payloads=True, a "
            "robust merge_policy, or allow_batch_tables=True (the announce "
            "path compiles make_round_step and friends)"
        )
    # edge-tree merge variants (--serve_edges, serve/scale/edge.py):
    #   "tables"   — the GROUPED flat program: full [W, r, c] stack in, the
    #                reduction restructured as per-edge scan folds over the
    #                edge_assign partition (the flat-serving parity twin);
    #   "partials" — the ROOT program: [E, r, c] edge partials in, folded
    #                in fixed edge order; everything downstream identical.
    # Both take the per-client wire norms as an input (norms_wire) so the
    # quarantine arithmetic is shared, value-for-value, with the edges.
    if edge_input not in ("none", "tables", "partials"):
        raise ValueError(
            f"edge_input must be none|tables|partials, got {edge_input!r}")
    if edge_input != "none":
        if cfg.serve_edges < 2:
            raise ValueError(
                f"edge_input={edge_input!r} needs cfg.serve_edges >= 2, "
                f"got {cfg.serve_edges} (the edge partition size is part "
                "of the compiled program)")
        if stale_slots:
            raise ValueError(
                "edge merge variants do not compose with stale_slots "
                "(EngineConfig already rejects serve_edges + async)")
    n_edges = cfg.serve_edges if edge_input != "none" else 0
    _sharded_scope_check(mcfg)
    if mcfg.mode != "sketch":
        raise ValueError(
            f"the per-client-table round requires mode='sketch'; "
            f"mode={mcfg.mode!r} has no table wire"
        )
    grad_client = _make_grad_client(loss_fn, cfg)
    quarantine = cfg.client_update_clip > 0
    layer_q = quarantine and cfg.quarantine_scope == "layer"

    def per_client_tables(params, pflat, net_state, cb, crngs):
        """One group's client phase: per-client flat grads -> per-client
        DP-clipped updates -> one Count-Sketch table PER CLIENT (vmapped
        client_compress — the exact table a real client would transmit).
        Layer scope appends the [*, L] per-leaf update norms (pre-clip,
        like the scalar screen's norms) for the merge's per-leaf rings."""
        with jax.named_scope("client_grad"):
            updates, nstates, metrics = jax.vmap(
                lambda b, r: grad_client(params, pflat, net_state, b, r)
            )(cb, crngs)
        lnorms = None
        with jax.named_scope("cohort_reduce"):
            if layer_q:
                lnorms = _client_layer_norms(updates, _leaf_segments(params))
            updates = _clip_updates(cfg, updates)
        with jax.named_scope("compress"):
            tables = jax.vmap(
                lambda u: modes.client_compress(mcfg, u, {})[0]["table"]
            )(updates)
        if layer_q:
            return tables, nstates, metrics, lnorms
        return tables, nstates, metrics

    if mesh is None:
        S = max(cfg.client_shards, 1)

        def client_step(state, batch, rng):
            batch, _ = split_health(batch)  # the MERGE computes health
            batch, adv = split_adv(batch)
            batch, valid = split_valid(batch)
            params, net_state = state["params"], state["net_state"]
            pflat, _ = _ravel_params(params)
            W = jax.tree.leaves(batch)[0].shape[0]
            client_rngs, part, noise_rng = _cohort_streams(cfg, rng, W)
            if valid is not None:
                part = part * valid
            if S <= 1:
                outs = per_client_tables(
                    params, pflat, net_state, batch, client_rngs)
            else:
                if W % S:
                    raise ValueError(
                        f"sampled cohort ({W}) not divisible by "
                        f"client_shards={S}")
                wl = W // S
                groups = (
                    jax.tree.map(
                        lambda a: a.reshape((S, wl) + a.shape[1:]), batch),
                    client_rngs.reshape((S, wl) + client_rngs.shape[1:]),
                )
                stacked = jax.lax.map(
                    lambda xs: per_client_tables(
                        params, pflat, net_state, *xs),
                    groups,
                )
                outs = jax.tree.map(
                    lambda a: a.reshape((W,) + a.shape[2:]), stacked)
            tables, nstates, metrics = outs[:3]
            lnorms = outs[3] if layer_q else None
            tables = _apply_adv(
                tables, adv, cfg.client_update_clip,
                state["quarantine"]["median"] if quarantine else None)
            return tables, nstates, metrics, part, noise_rng, lnorms

    else:
        from jax.sharding import PartitionSpec as P

        from ..parallel import mesh as meshlib

        S, axis_names = _mesh_shard_info(mesh)
        batch_spec = P(meshlib.client_axes(mesh))
        n_gathered = 5 if layer_q else 4  # tables, ns, metrics[, lnorms], part

        def body(state, batch_l, rng):
            params, net_state = state["params"], state["net_state"]
            batch_l, _ = split_health(batch_l)  # the MERGE computes health
            batch_l, valid_l = split_valid(batch_l)
            pflat, _ = _ravel_params(params)
            wl = jax.tree.leaves(batch_l)[0].shape[0]
            all_rngs, part, noise_rng = _cohort_streams(cfg, rng, wl * S)
            lo = _shard_index(mesh, axis_names) * wl
            rngs_l = jax.lax.dynamic_slice_in_dim(all_rngs, lo, wl)
            part_l = jax.lax.dynamic_slice_in_dim(part, lo, wl)
            if valid_l is not None:
                part_l = part_l * valid_l
            locals_ = per_client_tables(
                params, pflat, net_state, batch_l, rngs_l) + (part_l,)
            stacked = jax.tree.map(
                lambda x: jax.lax.all_gather(x, axis_names, axis=0, tiled=True),
                locals_,
            )
            return stacked + (noise_rng,)

        mapped = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), batch_spec, P()),
            out_specs=tuple(P() for _ in range(n_gathered + 1)),
            check_vma=False,
        )

        def client_step(state, batch, rng):
            # the adversarial transform applies to the REPLICATED gathered
            # stack at jit top level (outside shard_map), so a colluding
            # clone of any source position is mesh-shape-invariant
            batch, adv = split_adv(batch)
            outs = mapped(state, batch, rng)
            tables, nstates, metrics = outs[:3]
            lnorms = outs[3] if layer_q else None
            part, noise_rng = outs[-2], outs[-1]
            tables = _apply_adv(
                tables, adv, cfg.client_update_clip,
                state["quarantine"]["median"] if quarantine else None)
            return tables, nstates, metrics, part, noise_rng, lnorms

    def merge_step(state, tables, nstates, mvals, part, arrived, lr,
                   noise_rng, lnorms=None, stale_tables=None,
                   stale_weights=None, norms_wire=None, edge_assign=None,
                   health_on=None):
        """The server side: the cfg.merge_policy reduction of the
        (wire-delivered) per-client tables. `part` is the client program's
        validity mask, `arrived` the serving layer's 0/1 admission mask
        (ones in the batch simulator) — a rejected or missing payload is a
        zero row under a 0 mask, exactly a dropped client. `lnorms` is the
        client program's [W, L] per-leaf norm stack (layer scope only):
        the per-leaf screens run beside the table-norm screen, and a
        client over ANY of them drops from the merge bitwise.

        Compiled with stale_slots > 0 (the buffered-async variant) the
        signature grows `stale_tables [stale_slots, r, c]` and
        `stale_weights [stale_slots]`: late tables fold into the merged
        wire staleness-weighted through engine._stale_fold (the declared
        G013 boundary), their weight total joining the survivor
        normalization. The session dispatches THIS program only on rounds
        that actually have stale entries; zero-stale rounds run the plain
        program, which is what pins async-with-everyone-on-time bitwise
        equal to sync. Stale rows were screened at the wire (their source
        round's gauntlet); they carry no net-state/metric rows — a stale
        fold contributes its gradient sketch, nothing else (documented in
        the README always-on section). Under a robust merge_policy the
        stale slots do NOT fold linearly: they enter the robust order
        statistics as staleness-weighted entries of the union stack (the
        per-buffer robust merge — a stale adversarial table is trimmed
        exactly like an on-time one), and a zero-stale round dispatches
        the plain robust program: the sync robust round, by program
        identity."""
        part = part * arrived
        part_eff = part
        norms = None
        qmed = state["quarantine"]["median"] if quarantine else None
        if quarantine:
            # edge variants take the per-client wire norms as an INPUT
            # (computed once by serve/scale/edge.py's shared host formula,
            # partition-invariantly per client) so the screen — and the
            # ring it advances — can never diverge between the grouped
            # flat program and the partials root program; the plain
            # program keeps computing them in-program from the stack
            norms = (norms_wire if edge_input != "none"
                     else _table_norms(tables))
            bad = _quarantine_mask(cfg, norms, qmed)
            if layer_q:
                bad = bad | _quarantine_layer_mask(
                    cfg, lnorms, state["quarantine"]["layer_median"])
            part_eff = part * (1.0 - bad.astype(part.dtype))
        pol = robust_policy(cfg)
        if pol is not None:
            # a non-finite table can never enter the order statistics
            # (modes._robust_table_merge screens it out internally) — so
            # it must leave the ROUND the same way: masked out of the
            # survivor count, the agg_op="sum" rescale, the metrics/
            # net-state folds, and the median rings. Without this, a NaN
            # table under a robust policy with the quarantine unarmed
            # would commit a round rescaled by the wrong live count while
            # the sum policy's non-finite guard skips it cleanly. With
            # the quarantine armed the screen above already zeroed these
            # rows and this mask is value-transparent.
            finite = jnp.isfinite(tables).reshape(
                tables.shape[0], -1).all(axis=1)
            part_eff = part_eff * finite.astype(part_eff.dtype)
        stale_metrics = {}
        residual_agg = None
        if pol is None:
            # THE merge: masked per-client tables through the same ordered-
            # sum entry point the sharded mesh round uses (client-index
            # order). merge_policy="trimmed" with trim=0 compiles THIS
            # branch — the k=0 == sum bit-identity by construction.
            if edge_input == "partials":
                # the edge-tree ROOT: `tables` is the [E, r, c] stack of
                # edge-forwarded partials; the fold is the one declared
                # edge-partial merge entry, fixed edge order
                wire_sum = {"table": modes.merge_edge_partials(tables)}
            elif edge_input == "tables":
                # the edge-armed FLAT twin: same two-level fold, computed
                # in-program over the full stack and the same partition
                wire_sum = {"table": modes.edge_grouped_sum(
                    tables, part_eff, edge_assign, n_edges)}
            else:
                with jax.named_scope("compress"):
                    masked = modes.mask_rows(part_eff, tables)
                    wire_sum = modes.merge_partial_wires(
                        mcfg, {"table": masked})
            total_w = part_eff.sum()
            if stale_slots:
                # buffered-async: the late tables' ordered weighted fold
                # joins AFTER the live cohort's ordered sum (linearity
                # makes the staging exact), and their weight mass joins
                # the survivor normalization
                folded, total_w, stale_metrics = _stale_fold(
                    wire_sum["table"], total_w, stale_tables, stale_weights)
                wire_sum = {"table": folded}
            agg = _normalize_merged_wire(mcfg, wire_sum,
                                         jnp.maximum(total_w, 1.0))
        elif stale_slots or cfg.robust_residual:
            # Byzantine-robust merge, extended form: the per-BUFFER robust
            # merge runs the order statistics over the union stack
            # {current buffer ∪ staleness-weighted stale folds} — on-time
            # tables at weight 1, stale slots at their (1+lag)^-alpha
            # weight — inside the ONE G012 boundary (the stale stacks are
            # only FORWARDED here, per G013's robust-merge sanction). The
            # returned total weight (live count + stale weight mass) takes
            # the place the linear path's _stale_fold total has in the
            # agg_op="sum" rescale, and the winsorized robust-vs-mean
            # residual (if armed) accumulates into Verror below so error-
            # feedback telescoping survives the robust merge.
            robust, total_w, extras = modes.merge_partial_wires(
                mcfg, {"table": tables}, policy=pol, live=part_eff,
                trim=cfg.merge_trim,
                stale_tables=stale_tables, stale_weights=stale_weights,
                want_residual=cfg.robust_residual)
            if stale_slots:
                stale_metrics = {"stale_folded": extras["stale_folded"],
                                 "stale_weight": extras["stale_weight"]}
            scale_w = jnp.maximum(total_w, 1.0)
            agg = (robust if mcfg.agg_op != "sum" else {
                k: v * scale_w for k, v in robust.items()})
            if cfg.robust_residual:
                res = extras["residual"]
                residual_agg = (res if mcfg.agg_op != "sum"
                                else res * scale_w)
        else:
            # Byzantine-robust merge: coordinate-wise trimmed mean / median
            # over the LIVE client tables (dead rows excluded from the
            # order statistics, not counted as zeros). The boundary returns
            # the robust MEAN; agg_op="sum" rescales by the live count so
            # the FetchSGD lr translation (sum@lr == mean@lr*W) survives.
            robust = modes.merge_partial_wires(
                mcfg, {"table": tables}, policy=pol, live=part_eff,
                trim=cfg.merge_trim)
            agg = (robust if mcfg.agg_op != "sum" else {
                k: v * jnp.maximum(part_eff.sum(), 1.0)
                for k, v in robust.items()})
        new_net_state, out_metrics = _merged_survivor_finalize(
            jax.tree.map(lambda s: modes.mask_rows(part_eff, s).sum(0),
                         nstates),
            jax.tree.map(lambda m: modes.mask_rows(part_eff, m).sum(axis=0),
                         mvals),
            part_eff, state["net_state"])
        out_metrics.update(stale_metrics)
        new_q = None
        if quarantine:
            out_metrics["clients_quarantined"] = part.sum() - part_eff.sum()
            new_q = _advance_quarantine_full(
                cfg, state["quarantine"], norms,
                lnorms if layer_q else None, part_eff)
            out_metrics["quarantine_median"] = new_q["median"]
        raw_agg = agg  # pre-guard wire for the health estimators
        agg, new_net_state, _, out_metrics, _ = _guard_nonfinite(
            cfg, agg, new_net_state, state["net_state"], {}, {}, out_metrics,
        )
        # dp_noise is unreachable here: EngineConfig rejects dp_noise with
        # mode=sketch, and wire_payloads requires mode=sketch
        mode_state_in = state["mode_state"]
        if residual_agg is not None:
            # error-feedback-aware robust merge: the winsorized robust-vs-
            # mean residual joins the error accumulator at the same lr
            # scale the server step applies to the aggregate, so E tracks
            # the untransmitted mass of the (winsorized) cohort mean and
            # the honest mass the trim clipped re-enters through the
            # normal top-k release instead of being lost forever. The
            # momentum stays on the robust (trusted) series.
            mode_state_in = dict(mode_state_in)
            mode_state_in["Verror"] = (
                mode_state_in["Verror"] + lr * residual_agg)
        delta, mode_state = modes.server_step_sparse(
            mcfg, agg, mode_state_in, lr)
        pflat, unravel = _ravel_params(state["params"])
        new_state = {
            "params": _flat_apply(pflat, unravel, delta),
            "net_state": new_net_state,
            "mode_state": mode_state,
            "round": state["round"] + 1,
        }
        if new_q is not None:
            new_state["quarantine"] = new_q
        if cfg.health:
            # served rounds see only wire tables, so the health block is
            # the wire-side estimator set — exactly what a real server
            # that never holds a dense gradient can still measure
            out_metrics.update(_health_metrics(
                cfg, health_on, raw_agg, delta, mode_state))
        out_metrics.update(_ledger_fingerprints(cfg, new_state))
        return new_state, out_metrics

    return (_kernels_replicated(mesh, client_step),
            _kernels_replicated(mesh, merge_step))


def compose_payload(client_step: Callable, merge_step: Callable) -> Callable:
    """Adapt the payload two-program pair to the fused-step signature, the
    batch simulator's wire_payloads execution: client tables flow straight
    into the merge (device-to-device — float32 wire serialization is exact,
    so this IS the served arithmetic) with every invitee 'arrived'.
    client_rows pass through untouched (the payload scope has no client-
    local state)."""

    def step(state, batch, client_rows, lr, rng):
        # the cadence flag gates the MERGE's health block; popped here (a
        # copy also rides into client_step, which discards its own)
        _, health_flag = split_health(batch)
        tables, nstates, mvals, part, noise_rng, lnorms = client_step(
            state, batch, rng)
        new_state, metrics = merge_step(
            state, tables, nstates, mvals, part, jnp.ones_like(part), lr,
            noise_rng, lnorms, health_on=health_flag)
        return new_state, client_rows, metrics

    return step


def make_eval_step(loss_fn: Callable) -> Callable:
    """Forward-only metrics over an eval batch (no compression — SURVEY.md
    §3.4). `batch` has no client axis; rng is for completeness (dropout off
    in eval loss_fns)."""

    def eval_step(params, net_state, batch, rng):
        _, aux = loss_fn(params, net_state, batch, rng)
        return aux["metrics"]

    return eval_step
