"""High-level federated API (SURVEY.md L5: `FedModel` / `FedOptimizer`).

`FederatedSession` is the TPU-native core: it owns the compiled round step,
the server state, per-client persistent state, and host-side client sampling
(SURVEY.md §7.3 "Client sampling + data indexing on host; everything else
compiled").  `FedModel` / `FedOptimizer` are thin reference-parity wrappers
over it so a training loop reads like the reference's
(`loss = model(...); opt.step()`) without any process/queue machinery behind
it — there are no workers to spawn, no shared memory to allocate.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import sys
import threading
from typing import Any, Callable

import numpy as np

import jax
import jax.numpy as jnp
from ..data.fed_dataset import FedDataset, prefetch_iter
from ..modes import modes
from ..modes.config import ModeConfig
from ..obs import registry as obreg
from ..obs import trace as obtrace
from ..parallel import mesh as meshlib
from ..resilience import retry as rtry
from ..sketch import csvec
from ..utils.comm import round_comm_mb
from . import engine


@dataclasses.dataclass(frozen=True)
class PreparedRound:
    """Host-side product of one round's preparation — client sampling, batch
    assembly, the device PRNG split — decoupled from the device dispatch so a
    prefetch thread can assemble round N+1's batch while the device computes
    round N (runner/). `snapshot` is the (host RNG, device key) state right
    AFTER this round's draws: committing the round publishes it as the
    session's round-boundary snapshot, so checkpoints stay replay-consistent
    even when the live streams have already been advanced by prefetch."""

    rnd: int
    ids: Any
    batch: dict
    sub: Any
    snapshot: tuple
    # cohort degradation bookkeeping: how many clients this round's validity
    # mask killed (failed loads / injected drops), and the re-queue state as
    # of this prepare — (depth for metrics, full queue snapshot so commit
    # can publish a checkpoint-consistent queue exactly like the RNG
    # snapshot: prepared-but-uncommitted rounds may already have served or
    # grown the LIVE queue)
    masked: int = 0
    requeue_depth: int = 0
    requeue: tuple = ()
    # (cid, enqueued_round) pairs matching `requeue` — the aged policy's
    # rounds-waiting bookkeeping rides the same committed-snapshot
    # discipline as the queue itself
    requeue_ages: tuple = ()
    # wire-payload serving (serve/, --serve_payload sketch): the round's
    # WIRE-DECODED per-client tables + arrival mask + the client program's
    # device-side aux (see FederatedSession.compute_client_tables). None =
    # a normal batch round; dispatch_round routes on it.
    payload: tuple | None = None
    # sketch-health cadence (--health_every): whether THIS round's batch
    # carries an armed `_health_on` flag — the host-side mirror of the
    # compiled cond's gate, so commit knows which rounds' health blocks
    # are real without reading device values
    health_on: bool = False


@dataclasses.dataclass
class InFlightRound:
    """A dispatched-but-uncommitted round (or fused block of rounds): the
    device-side result futures plus everything commit_round needs to publish
    it. `metrics` stays a DEVICE tree until commit, so the runner can defer
    the host sync to an eval/log boundary instead of blocking every
    dispatch."""

    new_state: Any
    new_client_state: Any
    metrics: Any
    lrs: list
    snapshot: tuple
    stacked: bool  # block dispatch: metrics leaves carry a leading [K] axis
    # per-round host-side degradation counters (aligned with lrs) + the
    # newest prep's re-queue snapshot, published at commit
    masked: list = dataclasses.field(default_factory=list)
    requeue_depths: list = dataclasses.field(default_factory=list)
    requeue: tuple = ()
    requeue_ages: tuple = ()
    # round-ledger / health bookkeeping (aligned with lrs): each round's
    # invited cohort ids and whether its health cadence was armed — the
    # host-side context commit hands to the obs sinks (ledger, monitor)
    cohorts: list = dataclasses.field(default_factory=list)
    health_on: list = dataclasses.field(default_factory=list)

    @property
    def num_rounds(self) -> int:
        return len(self.lrs)

    def release_state(self):
        """Drop the server-state references. The runner calls this once two
        NEWER dispatches exist: a batch commit publishes the state of the
        last dispatch it is given, which is the newest pending one (a full
        drain) or the one before it (a drain that keeps the newest queued),
        so holding every intermediate tree would pin up to max_inflight
        full copies of params+momentum+error in HBM with no reader (the
        metrics stay — they are the per-round scalars commit needs)."""
        self.new_state = None
        self.new_client_state = None


class FederatedSession:
    def __init__(
        self,
        train_loss_fn: Callable,
        eval_loss_fn: Callable,
        params: Any,
        net_state: Any,
        mode_cfg: ModeConfig,
        train_set: FedDataset,
        num_workers: int,
        local_batch_size: int,
        weight_decay: float = 0.0,
        seed: int = 0,
        mesh=None,
        dp_clip: float = 0.0,
        dp_noise: float = 0.0,
        client_dropout: float = 0.0,
        split_compile: bool = False,
        client_chunk: int = 0,
        on_nonfinite: str = "off",
        fault_plan=None,
        retry_policy: rtry.RetryPolicy | None = None,
        donate_state: bool = True,
        client_shards: int = 0,
        client_update_clip: float = 0.0,
        requeue_policy: str = "fifo",
        sketch_path: str = "ravel",
        quarantine_window: int = 1,
        wire_payloads: bool = False,
        merge_policy: str = "sum",
        merge_trim: int = 0,
        quarantine_scope: str = "cohort",
        stale_slots: int = 0,
        robust_residual: bool = False,
        health_every: int = 0,
        ledger_fingerprint: bool = False,
        serve_edges: int = 0,
    ):
        # client_shards: 0 = derive from the mesh (the default — on a >1-
        # device mesh with a mode in engine.supports_sharded_round's scope
        # the session compiles the SPMD sharded round, the sharded path
        # being the default whenever more than one device is visible);
        # > 1 without a mesh runs the SAME shard-structured program on one
        # device (the bit-parity reference the CPU-mesh tests pin against).
        if split_compile:
            # stub: benchmark/builders/common.py still passes the keyword
            raise ValueError(
                "split_compile was removed in PR 29: the fused round "
                "compiles and runs on the chip; drop the flag")
        if on_nonfinite not in ("off", "skip", "halt"):
            raise ValueError(
                f"on_nonfinite must be 'off', 'skip', or 'halt', got "
                f"{on_nonfinite!r}"
            )
        self.cfg = engine.EngineConfig(
            mode=mode_cfg, weight_decay=weight_decay, dp_clip=dp_clip,
            dp_noise=dp_noise, client_dropout=client_dropout,
            client_chunk=client_chunk,
            client_update_clip=client_update_clip,
            # sketch_path="layerwise": per-layer gradient blocks fold
            # straight into the Count-Sketch table (sketch/layerwise.py) —
            # the flat [d] gradient never materializes; pinned
            # bit-identical to the default ravel path
            sketch_path=sketch_path,
            # windowed quarantine baseline (1 = the pre-window running
            # median, bit-identically) and the wire-payload round shape
            # (per-client tables merged by ordered sum — serve/'s
            # --serve_payload sketch; see EngineConfig for both)
            quarantine_window=quarantine_window,
            wire_payloads=wire_payloads,
            # Byzantine-robust table merge (--merge_policy) + quarantine
            # screen granularity (--quarantine_scope) — see EngineConfig
            merge_policy=merge_policy,
            merge_trim=merge_trim,
            quarantine_scope=quarantine_scope,
            # buffered-async serving (--serve_async): slot count of the
            # stale-fold merge variant; 0 keeps the sync programs only.
            # With a robust merge_policy the stale slots join the order
            # statistics as weighted union-stack entries (the per-buffer
            # robust merge) instead of folding linearly
            stale_slots=stale_slots,
            # error-feedback-aware robust merges (--robust_residual): the
            # winsorized robust-vs-mean residual accumulates into Verror
            robust_residual=robust_residual,
            # two-tier edge-aggregation serving (--serve_edges >= 2,
            # serve/scale/): compiles the grouped-flat + partials-root
            # edge merge variants beside the plain program (linear merge
            # only; the robust policies run the tree in forward mode with
            # serve_edges=0 here — see EngineConfig)
            serve_edges=serve_edges,
            # sketch-health estimators (--health_every N > 0) and round-
            # ledger fingerprints (--ledger): in-program observability that
            # only READS round state — armed runs stay bit-identical to
            # unarmed ones (tests/test_sketch_health.py pins it)
            health=health_every > 0,
            ledger_fingerprint=ledger_fingerprint,
            # CLI "halt" is a host-side policy on top of the compiled "skip"
            # guard (state stays clean either way; the CLI decides to stop)
            on_nonfinite="skip" if on_nonfinite == "halt" else on_nonfinite,
        )
        if health_every < 0:
            raise ValueError(
                f"health_every must be >= 0, got {health_every}")
        self._health_every = max(health_every, 1)
        # obs sinks, attached by the CLIs (or tests) after construction:
        # commit_rounds hands every committed round to them in order —
        # monitor (health block -> registry/trace/history), slo (windowed
        # rules), ledger (the durable append). All default None = inert.
        self.health_monitor = None
        self.slo = None
        self.ledger = None
        # The per-client-TABLE round shape (engine.make_payload_round_steps)
        # serves three masters: a real wire (--serve_payload sketch), a
        # robust merge policy (order statistics need individual client
        # tables), and the adversarial attack faults (client_signflip /
        # client_scale / client_collude transform the per-client WIRE — the
        # object that only exists on the table round). Any of the three
        # routes the session through the two-program table round.
        adv_faults = (fault_plan is not None
                      and getattr(fault_plan, "has_adversarial",
                                  lambda: False)())
        if (fault_plan is not None
                and getattr(fault_plan, "has_normride", lambda: False)()
                and client_update_clip <= 0):
            raise ValueError(
                "client_normride rides just UNDER the quarantine screen "
                "(scale to ride * clip * running_median); with "
                "--client_update_clip at 0 there is no threshold to ride "
                "and the attack is undefined — arm the quarantine"
            )
        self._table_round = bool(
            engine.uses_table_round(self.cfg) or adv_faults)
        if self._table_round and not wire_payloads:
            why = ("merge_policy=" + repr(merge_policy)
                   if engine.robust_policy(self.cfg) is not None
                   else "adversarial fault kinds (client_signflip/"
                        "client_scale/client_collude)")
            if mode_cfg.mode != "sketch":
                raise ValueError(
                    f"{why} need(s) the per-client-table round, which "
                    f"requires mode='sketch'; got mode={mode_cfg.mode!r}"
                )
            if sketch_path != "ravel":
                raise ValueError(
                    f"{why} need(s) the per-client-table round "
                    "(sketch_path='ravel'); layerwise accumulation has no "
                    "per-client wire to screen or attack"
                )
        # cohort-degradation re-queue: client ids whose batch load failed (or
        # were fault-dropped) wait here and displace sampled ids in a later
        # round's cohort, so a dropped client's data is delayed, not lost.
        # `_requeue` is the LIVE queue (single producer: prepare_round);
        # `_requeue_committed` is the round-boundary snapshot checkpoints
        # write (same discipline as rng_snapshot — prefetch may have served
        # the live queue for rounds that never commit).
        # Serving order is `requeue_policy`: "fifo" (substitution order =
        # drop order) or "aged" (weighted choice by rounds-waiting from a
        # DEDICATED pinned RandomState — fairness at high drop rates without
        # perturbing the host-sampling stream). `_requeue_enqueued` maps a
        # queued cid to the round it was dropped; checkpoints persist the
        # committed (cid, enqueued_round) pairs (meta.json requeue_ages), so
        # a restored entry resumes its REAL rounds-waiting age.
        if requeue_policy not in ("fifo", "aged"):
            raise ValueError(
                f"requeue_policy must be 'fifo' or 'aged', got "
                f"{requeue_policy!r}"
            )
        self._requeue_policy = requeue_policy
        self._requeue_enqueued: dict[int, int] = {}
        self._requeue: collections.deque = collections.deque()
        self._requeue_committed: tuple = ()
        self._requeue_ages_committed: tuple = ()
        self._seed = seed
        # resilience hooks (resilience/): a seeded FaultPlan injects failures
        # at this session's named sites; the retry policy wraps data loading.
        # Both default to inert so existing callers see zero change.
        self.fault_plan = fault_plan
        self.retry_policy = retry_policy or rtry.RetryPolicy()
        # donate_state=False keeps the server state's device buffers alive
        # across the in-flight round (one extra copy of params+momentum+error
        # in HBM). Required for a WORKING mid-round emergency checkpoint on
        # real accelerators: with donation, self.state points at deleted
        # buffers for the whole round, so the watchdog's stage-3 save would
        # always fail with "Array has been deleted" exactly when a round is
        # wedged. CPU ignores donation, which is why tests can't catch it.
        self._donate_state = donate_state
        self.train_set = train_set
        self.num_workers = min(num_workers, train_set.num_clients)
        self.local_batch_size = local_batch_size
        if (client_shards >= 1 and mesh is not None
                and client_shards != meshlib.client_shards(mesh)):
            # any EXPLICIT shard count that disagrees with the mesh raises —
            # including client_shards=1 ("force unsharded"), which silently
            # compiling the mesh's S-way program would drop without notice
            raise ValueError(
                f"client_shards={client_shards} disagrees with the "
                f"{meshlib.client_shards(mesh)}-way client mesh; pass one or "
                "the other"
            )
        shards = (meshlib.client_shards(mesh) if mesh is not None
                  else max(client_shards, 1))
        if shards > 1 and self.num_workers % shards != 0:
            # The sampled-client axis must split evenly over the shards. The
            # old behavior (silently dropping to a single device) is a silent
            # n_devices-x slowdown on a pod — the exact failure class the
            # watchdog exists to catch. Instead, round the cohort to the
            # nearest viable multiple (documented, loud), and raise when no
            # multiple exists at all.
            up = -(-self.num_workers // shards) * shards
            adjusted = up if up <= train_set.num_clients else (
                train_set.num_clients // shards) * shards
            if adjusted <= 0:
                raise ValueError(
                    f"num_workers={self.num_workers} cannot be sharded over the "
                    f"{shards}-way client mesh: the dataset has only "
                    f"{train_set.num_clients} clients, fewer than one per shard. "
                    f"Reduce the mesh (--num_devices) or add clients."
                )
            print(
                f"note: num_workers={self.num_workers} not divisible by the "
                f"{shards}-way client mesh; rounding the cohort to {adjusted} "
                f"so the round stays sharded (pass a multiple of {shards} to "
                f"silence this)",
                flush=True,
            )
            self.num_workers = adjusted
        self.mesh = mesh
        # The SPMD sharded round (the default whenever the mesh splits the
        # client axis more than one way and the mode is in scope): each
        # device reduces + compresses its cohort shard locally and the
        # cross-device merge ships the compressed wire (the r x c sketch
        # table), never the dense [d] gradient. Out-of-scope modes keep the
        # GSPMD-annotation path unchanged.
        self._spmd = shards > 1 and engine.supports_sharded_round(mode_cfg)
        if client_shards > 1 and not self._spmd:
            # an EXPLICIT shard request for an out-of-scope mode must fail
            # loudly (the engine's _sharded_scope_check does): silently
            # running the plain round would hand a parity test a different
            # program. A mesh with an out-of-scope mode is fine — that's
            # the documented GSPMD fallback.
            raise ValueError(
                f"client_shards={client_shards} requires a mode in the "
                f"sharded-round scope (linear grad modes without client-"
                f"local state); mode={mode_cfg.mode!r} error_type="
                f"{mode_cfg.error_type!r} runs the GSPMD path — pass a mesh "
                "instead of client_shards"
            )
        if self._spmd:
            self.cfg = dataclasses.replace(self.cfg, client_shards=shards)
        # On the SPMD path client_chunk scans WITHIN each shard, so it must
        # divide the per-shard cohort, not the global one.
        chunk_cohort = (self.num_workers // shards if self._spmd
                        else self.num_workers)
        if client_chunk and chunk_cohort % client_chunk:
            # the cohort may have been clamped to num_clients or rounded/
            # sharded for the mesh above — a chunk that divided the REQUESTED
            # cohort may no longer divide; failing at the first jit trace
            # would be a far worse place to find out. Largest viable chunk.
            viable = next(
                c for c in range(min(client_chunk, chunk_cohort), 0, -1)
                if chunk_cohort % c == 0
            )
            print(
                f"note: client_chunk={client_chunk} does not divide the "
                f"{'per-shard ' if self._spmd else ''}cohort ({chunk_cohort})"
                f"; using client_chunk={viable}",
                flush=True,
            )
            self.cfg = dataclasses.replace(self.cfg, client_chunk=viable)
        self.rng = np.random.RandomState(seed)
        self._rng_key = jax.random.PRNGKey(seed)
        # round-boundary RNG snapshot (see _snapshot_rng): what checkpoint
        # writes, so a mid-round emergency save stays replay-consistent
        self._snapshot_rng()
        # guards the round-boundary publication of (state, round, snapshot,
        # comm totals) against a concurrent emergency checkpoint from the
        # watchdog's timer thread: ckpt.save captures all fields under this
        # lock, so it can never mix round N's params with round N-1's counter
        self.mutate_lock = threading.Lock()
        # pipelining head (runner/): the newest DISPATCHED state futures,
        # distinct from self.state (the newest COMMITTED state) so a chain of
        # uncommitted dispatches threads device-side while emergency
        # checkpoints keep reading a consistent committed view. Main-thread
        # only — dispatch and commit both run on the caller's thread.
        # _inflight counts dispatch UNITS (a fused block is one);
        # _inflight_rounds counts ROUNDS (a block is len(lrs)).
        self._inflight = 0
        self._inflight_rounds = 0
        self._head_state = None
        self._head_client_state = None

        self.state = engine.init_server_state(self.cfg, params, net_state)
        self.client_state = modes.init_client_state(mode_cfg, train_set.num_clients)

        self._train_loss_fn = train_loss_fn
        self._multi = None  # lazy: jitted by the first run_rounds block
        self._payload_client = None
        self._payload_merge = None
        self._payload_merge_stale = None
        self._payload_merge_edge_flat = None
        self._payload_merge_edge_root = None
        if self._table_round:
            # the per-client-table two-program round: client tables + table
            # merge (engine.make_payload_round_steps). The batch simulator
            # composes them (robust merge / adversarial chaos runs ride the
            # same shape without any wire); the serving layer calls them
            # separately with the wire round-trip in between
            # (compute_client_tables / dispatch_round on a payload-carrying
            # PreparedRound).
            client_p, merge_p = engine.make_payload_round_steps(
                train_loss_fn, self.cfg,
                self.mesh if self._spmd and self.mesh is not None else None,
                allow_batch_tables=True)
            self._payload_client = jax.jit(client_p)
            self._payload_merge = jax.jit(
                merge_p, donate_argnums=self._state_donation())
            if self.cfg.stale_slots > 0:
                # the buffered-async merge variant: the SAME merge with a
                # stale-fold slot stack appended. Kept beside — never
                # instead of — the plain program: a round with zero stale
                # entries dispatches the plain one, which is what pins
                # async-with-everyone-on-time bitwise == sync. jit is
                # lazy, so the variant costs nothing until the first
                # straggler actually folds (one extra compile then —
                # documented in MIGRATION.md).
                _, merge_s = engine.make_payload_round_steps(
                    train_loss_fn, self.cfg,
                    self.mesh if self._spmd and self.mesh is not None
                    else None,
                    allow_batch_tables=True,
                    stale_slots=self.cfg.stale_slots)
                self._payload_merge_stale = jax.jit(
                    merge_s, donate_argnums=self._state_donation())
            if self.cfg.serve_edges >= 2:
                # the two-tier edge-aggregation variants (serve/scale/):
                # the GROUPED flat program (full stack, per-edge scan
                # grouping — the flat-serving parity twin) and the
                # PARTIALS root program (edge-forwarded [E, r, c] stack).
                # jit is lazy, so they cost nothing until the serving
                # layer actually dispatches one.
                _, merge_ef = engine.make_payload_round_steps(
                    train_loss_fn, self.cfg,
                    self.mesh if self._spmd and self.mesh is not None
                    else None,
                    allow_batch_tables=True, edge_input="tables")
                _, merge_er = engine.make_payload_round_steps(
                    train_loss_fn, self.cfg,
                    self.mesh if self._spmd and self.mesh is not None
                    else None,
                    allow_batch_tables=True, edge_input="partials")
                self._payload_merge_edge_flat = jax.jit(
                    merge_ef, donate_argnums=self._state_donation())
                self._payload_merge_edge_root = jax.jit(
                    merge_er, donate_argnums=self._state_donation())
            self._step = engine.compose_payload(
                self._payload_client, self._payload_merge)
        elif self._spmd:
            self._step = jax.jit(
                engine.make_sharded_round_step(train_loss_fn, self.cfg,
                                               self.mesh),
                donate_argnums=self._state_donation())
        else:
            self._step = jax.jit(engine.make_round_step(train_loss_fn, self.cfg),
                                 donate_argnums=self._state_donation())
        # which client phase the round program above was built with: one
        # backward pass for the cohort, or one a client (the CLIs' start-up
        # line and the gauge say so; decided at trace time, so a gauge says
        # all a hit share could)
        fused = (not self._table_round
                 and engine.cohort_backward_fused(self.cfg))
        self.cohort_backward = "fused" if fused else "per-client"
        obreg.default().gauge("engine_cohort_backward_fused").set(int(fused))
        # likewise static: how many partial maxima the round's approximate
        # top-k over d picks its k from by selection (csvec.topk_abs); 0
        # with exact top-k, with no top-k, or where they are too few for
        # the selection to pay and lax.approx_max_k aggregates them itself
        mcfg = self.cfg.mode
        self.topk_partial_maxima = (
            csvec.approx_select_size(mcfg.d, mcfg.k, mcfg.topk_recall)
            if mcfg.topk_impl == "approx"
            and mcfg.mode in ("sketch", "true_topk", "local_topk") else 0)
        obreg.default().gauge("sketch_topk_partial_maxima").set(
            self.topk_partial_maxima)
        self._eval = jax.jit(engine.make_eval_step(eval_loss_fn))
        if self.client_state is not None:
            gather = lambda st, ids: jax.tree.map(lambda a: a[ids], st)  # noqa: E731
            scatter = lambda st, ids, rows: jax.tree.map(  # noqa: E731
                lambda a, r: a.at[ids].set(r), st, rows
            )
            if self.mesh is not None:
                # [num_clients, d] per-client state is the reference's memory
                # wall (SURVEY.md §3.3, §7 hard part (b)): shard its client
                # axis over the mesh so per-device residency is
                # num_clients/n_dev * d, and keep gather/scatter on-device
                # (XLA lowers the cross-shard row moves to collectives).
                ns = meshlib.client_sharding(self.mesh)
                nshards = meshlib.client_shards(self.mesh)
                pad = (-train_set.num_clients) % nshards
                if pad:  # pad rows are never indexed (ids < num_clients)
                    self.client_state = jax.tree.map(
                        lambda a: jnp.concatenate(
                            [a, jnp.zeros((pad,) + a.shape[1:], a.dtype)]
                        ),
                        self.client_state,
                    )
                self.client_state = jax.device_put(self.client_state, ns)
                # gathered rows ride the same client-axis sharding the batch
                # uses, so the vmapped per-client step stays fully sharded
                self._gather = jax.jit(gather, out_shardings=ns)
                # scatter donation follows the same gate as the round step:
                # an emergency save's device_get of client_state must not
                # race a donation that deletes the captured buffers
                self._scatter = jax.jit(scatter,
                                        donate_argnums=self._state_donation(),
                                        out_shardings=ns)
            else:
                self._gather = jax.jit(gather)
                self._scatter = jax.jit(scatter,
                                        donate_argnums=self._state_donation())
        self.round = 0
        # analytic wire-cost of one round (SURVEY.md §6 row 4 accounting)
        self.comm_per_round = round_comm_mb(mode_cfg, self.num_workers)
        # cumulative measured wire-cost since round 0. Summed from the
        # per-round figures (which scale with survivors under dropout and use
        # the measured down-link for local_topk), checkpointed, and restored —
        # deriving it as round * static-estimate overstates resumed runs.
        self.comm_mb_total = 0.0
        # cumulative cohort-degradation counters (the serving layer's
        # metrics endpoint reads them; RunStats keeps its own per-loop view)
        self.clients_dropped_total = 0
        self.clients_quarantined_total = 0

    def _mesh_ctx(self):
        """Context every round/eval program is called (hence traced) under
        when the session has a mesh: Pallas kernel calls at jit top level run
        replicated under a shard_map (a Mosaic call cannot be partitioned —
        this is what covers the GSPMD-annotated rounds, whose engine builders
        take no mesh), and jax.set_mesh when the mesh carries axes that ops
        resolve ambiently (ring attention's 'seq')."""
        if self.mesh is None:
            return contextlib.nullcontext()
        from ..sketch import pallas_kernels

        stack = contextlib.ExitStack()
        stack.enter_context(pallas_kernels.replicated_on(self.mesh))
        if meshlib.SEQ_AXIS in self.mesh.axis_names:
            stack.enter_context(jax.set_mesh(self.mesh))
        return stack

    def _state_donation(self) -> tuple:
        """donate_argnums for the round-step jits: (0,) normally, () when the
        caller needs the live server state readable mid-round (emergency
        checkpoints) — see the donate_state comment in __init__."""
        return (0,) if self._donate_state else ()

    def _snapshot_rng(self):
        """Capture (host sampling RNG, device PRNG key) as of the last
        COMPLETED round. The live streams advance at the start of the next
        round, before `self.round`/`self.state` reflect it — so an emergency
        checkpoint taken mid-round (the watchdog's timer thread) must write
        this snapshot, not the live streams, or the resumed run re-samples
        round N from a stream already advanced past its draws and trains a
        cohort no deterministic run of this seed would produce."""
        self.rng_snapshot = (self.rng.get_state(), self._rng_key)

    def _load_client_batch(self, ids, rnd: int | None = None):
        """Round-batch assembly behind the retry wrapper. The injection site
        fires BEFORE any host RNG is consumed, and a failed attempt restores
        the RNG snapshot, so a retried load replays the identical batch —
        recovery never perturbs the client sequence a resumed run must
        replay bit-for-bit. `rnd` is the GLOBAL round this batch feeds
        (defaults to the session counter; a prefetcher preparing ahead
        passes the future index so scheduled faults land on their round).

        Returns (batch, valid_or_None). A load that still fails after
        --max_retries DEGRADES instead of aborting the run: the round runs
        over an all-zero batch with every client's validity mask at 0 (the
        engine's fully-dropped-cohort semantics — momentum decays, state
        stays clean) and the cohort's ids are re-queued for a later round so
        their data is delayed, not lost. Loud on stderr; counted per round
        in metrics (clients_dropped). Note a degraded round consumes no
        batch-assembly RNG (the failed attempts restored it), so a run that
        hit a REAL exhausted flake no longer replays an uninterrupted run
        bit-for-bit — injected faults within the retry budget still do."""
        if rnd is None:
            rnd = self.round

        def attempt():
            rng_state = self.rng.get_state()
            try:
                if self.fault_plan is not None:
                    self.fault_plan.data_load(rnd)
                return self.train_set.client_batch(
                    self.rng, ids, self.local_batch_size,
                    self.cfg.mode.num_local_iters,
                )
            except Exception:
                self.rng.set_state(rng_state)
                raise

        try:
            return rtry.with_retries(
                attempt, site="data_load", policy=self.retry_policy,
                seed=rnd,
            ), None
        except Exception as e:  # noqa: BLE001 — degrade, don't abort
            print(
                f"ERROR: round {rnd} batch load failed after retries "
                f"({type(e).__name__}: {e}); degrading to a fully-masked "
                f"cohort and re-queuing its {len(ids)} client(s)",
                file=sys.stderr, flush=True,
            )
            queued = set(self._requeue)
            for i in ids:
                if int(i) not in queued:
                    self._requeue.append(int(i))
                    self._requeue_enqueued.setdefault(int(i), rnd)
            W = len(ids)
            return (
                self.train_set.empty_batch(
                    W, self.local_batch_size, self.cfg.mode.num_local_iters),
                np.zeros(W, np.float32),
            )

    # -- prepare / dispatch / commit (the runner/ pipeline surface) ----------
    def sample_cohort(self, rnd: int) -> np.ndarray:
        """The host-sampling half of a round's preparation: draw the cohort
        from the live sampling stream and substitute queued (previously
        dropped) clients in. Split out of prepare_round so a serving layer
        (serve/) can learn the round's INVITE list before any batch work —
        the stream draws are identical either way, which is what keeps a
        served round's cohort bit-identical to the batch simulator's."""
        ids = self.train_set.sample_clients(self.rng, self.num_workers)
        if self._requeue:
            # serve previously-dropped clients: substitute them into the
            # sampled cohort. The substitution consumes NO host RNG, so the
            # sampling stream is identical whether or not anything was
            # queued — only the cohort's membership changes (by design:
            # that IS the recovery).
            ids = self._serve_requeue(ids, rnd)
        return ids

    def prepare_round(self, rnd: int | None = None) -> PreparedRound:
        """Host-side half of a round: sample the cohort, assemble the batch
        (retry-wrapped, fault sites at `rnd`), split the device PRNG. Draws
        from the LIVE host streams in round order — the single producer
        (inline loop or the runner's prefetch thread) must call this
        sequentially. The returned snapshot captures the streams right after
        this round's draws; it becomes the session's round-boundary snapshot
        only when the round COMMITS, so an emergency checkpoint taken while
        later rounds are already prepared still resumes bit-identically."""
        if rnd is None:
            rnd = self.round + self._inflight_rounds
        return self._assemble_round(rnd, self.sample_cohort(rnd))

    def prepare_served_round(self, rnd: int, ids,
                             arrived) -> PreparedRound:
        """Round preparation from an EXTERNAL arrival stream (serve/): the
        cohort `ids` must be exactly what sample_cohort(rnd) returned (the
        service samples the invite list, announces it, and collects
        arrivals), and `arrived` is the [W] 0/1 float mask of invitees whose
        submission made the W-of-N close. No-shows and stragglers are
        handled EXACTLY like client_drop faults — rows zeroed, validity
        masked, client re-queued — so a served short cohort is bit-identical
        to the batch-simulator round that drops the same positions (the PR 4
        masking parity extends to the serving path by construction)."""
        # host-side by construction: the arrival mask comes from the
        # assembler's host bookkeeping, never a traced array
        arrived = np.asarray(arrived, np.float32)  # graftlint: disable=G001
        if len(arrived) != len(ids):
            raise ValueError(
                f"arrival mask covers {len(arrived)} clients but the round "
                f"invited {len(ids)}")
        return self._assemble_round(rnd, ids, arrived=arrived)

    def _assemble_round(self, rnd: int, ids,
                        arrived=None) -> PreparedRound:
        """Shared tail of round preparation: batch assembly (retry-wrapped,
        fault sites at `rnd`), no-show masking for served rounds, validity
        threading, the device PRNG split, and the post-draw snapshot.
        Traced on the `federated` track (this runs on the prefetch thread
        in async mode — the trace shows it overlapping device compute)."""
        with obtrace.span("federated", "prepare_round", round=rnd,
                          cohort=len(ids)):
            return self._assemble_round_traced(rnd, ids, arrived)

    def _assemble_round_traced(self, rnd: int, ids,
                               arrived=None) -> PreparedRound:
        batch, valid = self._load_client_batch(ids, rnd)
        if self.fault_plan is not None:
            # nonfinite burst rides the real gradient path (poison the
            # assembled batch); preempt stays a DISPATCH-time site so the
            # SIGTERM lands when the round runs, not when it is prefetched
            batch = self.fault_plan.poison(rnd, batch)
            batch, valid, dropped = self.fault_plan.client_faults(
                rnd, batch, valid, len(ids))
            for p in dropped:
                # check the LIVE queue per append: overlapping drop specs
                # can report the same position twice, and a double-queued
                # client would displace two sampled clients later
                cid = int(ids[p])
                if cid not in self._requeue:
                    self._requeue.append(cid)
                    self._requeue_enqueued.setdefault(cid, rnd)
        if arrived is not None and (arrived == 0.0).any():
            # served round closed short of the full invite list: no-shows
            # get the client_drop treatment (rows zeroed, mask 0, re-queued)
            # at the same point in the preparation the fault site uses, so
            # the two paths stay bit-identical
            no_show = [int(p) for p in np.flatnonzero(arrived == 0.0)]
            if valid is None:
                valid = np.ones(len(ids), np.float32)
            else:
                # host numpy by construction (loader validity mask)
                valid = np.array(valid, copy=True)  # graftlint: disable=G001
            batch = {k: (v if k.startswith("_")
                         # prep batches are host numpy (assembled on the
                         # host thread), so the copy is host work
                         else np.array(v, copy=True))  # graftlint: disable=G001
                     for k, v in batch.items()}
            for k, v in batch.items():
                if not k.startswith("_"):
                    v[no_show] = 0
            valid[no_show] = 0.0
            for p in no_show:
                cid = int(ids[p])
                if cid not in self._requeue:
                    self._requeue.append(cid)
                    self._requeue_enqueued.setdefault(cid, rnd)
        masked = int(len(ids) - valid.sum()) if valid is not None else 0
        if masked:
            obtrace.instant("federated", "cohort_degraded", round=rnd,
                            clients=masked)
        # the validity mask ALWAYS rides the batch (all-ones in the clean
        # case) so the compiled program never changes shape when the first
        # fault hits mid-run — a mid-run recompile on a TPU would stall the
        # exact round that is already degraded
        batch = dict(batch)
        batch[engine.VALID_KEY] = (
            valid if valid is not None
            else np.ones(len(ids), np.float32))
        if (self._table_round and self.fault_plan is not None
                and self.fault_plan.has_adversarial()):
            # adversarial wire transform (signflip / scale / collude): the
            # reserved leaves ride EVERY round of a plan that names the
            # kinds (identity defaults off-schedule) so the compiled table
            # round's shape is constant from round 0 — same discipline as
            # the validity mask above
            scale, src = self.fault_plan.adversarial_plan(rnd, len(ids))
            batch[engine.ADV_SCALE_KEY] = scale
            batch[engine.ADV_SRC_KEY] = src
            if self.fault_plan.has_normride():
                # the norm-riding fraction leaf (0 = honest) rides every
                # round of a plan that names the kind, like scale/src —
                # the compiled program's shape stays constant from round 0
                batch[engine.ADV_RIDE_KEY] = (
                    self.fault_plan.normride_plan(rnd, len(ids)))
        health_on = False
        if self.cfg.health:
            # the health-cadence flag rides the batch like `_valid` (shape-
            # constant from round 0 — the cadence is the VALUE, the program
            # never recompiles); [W]-shaped so it shards/stacks uniformly
            health_on = rnd % self._health_every == 0
            batch[engine.HEALTH_KEY] = np.full(
                len(ids), 1.0 if health_on else 0.0, np.float32)
        self._rng_key, sub = jax.random.split(self._rng_key)
        return PreparedRound(
            rnd, ids, batch, sub, (self.rng.get_state(), self._rng_key),
            masked=masked, requeue_depth=len(self._requeue),
            requeue=tuple(self._requeue),
            requeue_ages=tuple(self._requeue_enqueued.items()),
            health_on=health_on,
        )

    def _serve_requeue(self, ids, rnd: int = 0):
        """Substitute queued (previously dropped) client ids into a freshly
        sampled cohort in `requeue_policy` order, skipping ids the sample
        already contains. fifo consumes the queue front-first (bit-identical
        to the pre-policy behavior — pinned by the chaos tests); aged serves
        a weighted draw by rounds-waiting from `_aged_order`. Neither
        consumes host-sampling RNG."""
        # host-side by construction: sampled ids are host numpy, never a
        # traced array
        ids = np.array(ids, copy=True)  # graftlint: disable=G001
        in_cohort = {int(i) for i in ids}
        order = list(self._requeue)
        if self._requeue_policy == "aged" and len(order) > 1:
            order = self._aged_order(order, rnd)
        slot, served, leftover = 0, [], []
        for cid in order:
            if slot >= len(ids):
                leftover.append(cid)  # no slot left: stays queued
                continue
            if cid in in_cohort:
                # sampled naturally this round — already served
                self._requeue_enqueued.pop(cid, None)
                continue
            in_cohort.discard(int(ids[slot]))
            ids[slot] = cid
            in_cohort.add(cid)
            served.append(cid)
            self._requeue_enqueued.pop(cid, None)
            slot += 1
        self._requeue = collections.deque(leftover)
        if served:
            obtrace.instant("federated", "requeue_serve", round=rnd,
                            clients=[int(c) for c in served],
                            still_queued=len(self._requeue))
            # stderr, like the other cohort-degradation diagnostics: the
            # stdout metrics table must stay machine-parsable
            print(f"requeue: serving previously-dropped client(s) {served} "
                  f"({len(self._requeue)} still queued)",
                  file=sys.stderr, flush=True)
        return ids

    def _aged_order(self, queue: list, rnd: int) -> list:
        """Age-weighted serving order (requeue_policy="aged"):
        Efraimidis–Spirakis one-pass weighted sampling without replacement,
        weight = rounds-waiting + 1, drawn from a DEDICATED RandomState
        pinned to (session seed, round) — deterministic, replayable, and
        zero draws from the host-sampling stream (fifo-vs-aged never
        changes which clients the round SAMPLES, only which queued clients
        are served first)."""
        rs = np.random.RandomState((self._seed * 1_000_003 + rnd) % (2**32))
        # host ints by construction (queue bookkeeping), never traced
        ages = np.array(  # graftlint: disable=G001
            [rnd - self._requeue_enqueued.get(int(c), rnd) + 1
             for c in queue], np.float64)
        # larger age -> larger weight -> stochastically earlier: key
        # u^(1/w) with u ~ U(0,1) sorts weighted-without-replacement
        keys = rs.random_sample(len(queue)) ** (1.0 / ages)
        return [queue[i] for i in np.argsort(-keys, kind="stable")]

    # -- wire-payload serving (serve/, --serve_payload sketch) ---------------

    # graftlint: drain-point — payload rounds sync the client tables to the
    # host BY DESIGN: the tables are the wire objects the serving layer
    # serializes per client, so the round's host boundary moves here (the
    # payload path trades pipeline overlap for a real untrusted wire)
    def compute_client_tables(self, prep: PreparedRound):
        """Run the payload round's CLIENT program for a prepared cohort and
        fetch the per-client r x c tables to the host — the objects that
        cross the wire, one row per invitee. Returns (tables_np [W, r, c],
        aux); `aux` carries the device-side leftovers the merge dispatch
        needs (the exact state tree the client program read, per-client
        net-state/metric rows, the validity mask, the noise key)."""
        if self._payload_client is None:
            raise RuntimeError(
                "compute_client_tables needs a wire_payloads=True session "
                "(--serve_payload sketch)")
        batch = prep.batch
        if self.mesh is not None:
            batch = meshlib.shard_client_batch(self.mesh, batch)
        state = self._head_state if self._head_state is not None else self.state
        with self._mesh_ctx():
            (tables, nstates, mvals, part, noise_rng,
             lnorms) = self._payload_client(state, batch, prep.sub)
        tables_np = np.asarray(jax.device_get(tables))
        return tables_np, (state, nstates, mvals, part, noise_rng, lnorms)

    def quarantine_median_host(self) -> float:
        """Host copy of the CURRENT quarantine threshold baseline (0.0 with
        the quarantine off or unseeded) — the ingest validation gauntlet's
        sketch-space L2 screen reads this. Payload rounds sync per round
        anyway (compute_client_tables), so this fetch adds no new sync
        class.

        The scalar "median" key IS the table-space ring the payload merge
        advances (windowed when --quarantine_window > 1, co-resident with
        the per-leaf rings under --quarantine_scope layer), so the wire
        screen and the in-merge table-norm screen always read the same
        baseline: a payload the gauntlet rejects QUARANTINED is exactly a
        payload the merge would have quarantined — and either way the
        round is bitwise the round without that client (pinned in
        tests/test_byzantine.py). The per-leaf rings never reach the wire:
        the gauntlet sees only the table, which superimposes all layers."""
        if self.cfg.client_update_clip <= 0:
            return 0.0
        state = self._head_state if self._head_state is not None else self.state
        # host-side by design: read at the payload round's host boundary
        return float(jax.device_get(  # graftlint: disable=G001 — payload-boundary sync
            state["quarantine"]["median"]))

    def finish_served_payload(self, prep: PreparedRound, arrived,
                              wire_tables, aux,
                              stale=None, edge=None) -> PreparedRound:
        """Post-close bookkeeping of a served payload round: every invitee
        whose payload missed the merge (no-show, straggler, or a rejected
        frame) gets the client_drop treatment — counted as masked and
        re-queued for a later cohort — and the final PreparedRound carries
        the WIRE-DECODED table stack + arrival mask for dispatch_round. The
        RNG snapshot from assembly stays valid: nothing here consumes host
        RNG.

        `stale` (buffered-async serving): a ([stale_slots, r, c] table
        stack, [stale_slots] weight vector) host pair of LATE tables the
        service wants staleness-folded into THIS round's merge — requires
        a stale_slots > 0 session; None (and all sync paths) dispatches
        the plain merge program."""
        # host numpy by construction: the arrival mask comes from the
        # assembler, the validity mask from the loader/fault sites
        arrived = np.asarray(arrived, np.float32)  # graftlint: disable=G001
        _, valid = engine.split_valid(prep.batch)
        if valid is None:
            valid = np.ones(len(prep.ids), np.float32)
        eff = np.asarray(valid, np.float32) * arrived  # graftlint: disable=G001 — host mask
        for p in np.flatnonzero(eff == 0.0):
            cid = int(prep.ids[int(p)])
            if cid not in self._requeue:
                self._requeue.append(cid)
                self._requeue_enqueued.setdefault(cid, prep.rnd)
        masked = int(len(prep.ids) - eff.sum())
        if masked:
            obtrace.instant("federated", "cohort_degraded", round=prep.rnd,
                            clients=masked)
        if stale is not None and self._payload_merge_stale is None:
            raise ValueError(
                "finish_served_payload got a stale-fold stack but the "
                "session was built with stale_slots=0 — arm stale_slots "
                "(--serve_async wires it) or drop the stale entries")
        if edge is not None and self._payload_merge_edge_flat is None:
            raise ValueError(
                "finish_served_payload got an edge-tree block but the "
                "session was built with serve_edges=0 — arm serve_edges "
                "(--serve_edges wires it) or drop the edge routing")
        return dataclasses.replace(
            prep, masked=masked, requeue_depth=len(self._requeue),
            requeue=tuple(self._requeue),
            requeue_ages=tuple(self._requeue_enqueued.items()),
            # the gauntlet's validated table stack is host numpy already —
            # EXCEPT the fast path, whose ring uploader already shipped it
            # to device (a jax.Array passes through untouched; re-wrapping
            # would force a device->host->device bounce)
            payload=(wire_tables if isinstance(wire_tables, jax.Array)
                     else np.asarray(wire_tables, np.float32),  # graftlint: disable=G001
                     arrived, aux, stale, edge),
        )

    def _dispatch_payload_merge(self, prep: PreparedRound,
                                lr: float) -> InFlightRound:
        """Dispatch the payload round's MERGE program over the wire-decoded
        tables a served round collected (prep.payload). The merge consumes
        the SAME state tree the client program read (carried in aux), so
        the two programs see one consistent round. A prep carrying a
        stale-fold stack (buffered-async serving) dispatches the
        stale-slots merge variant; every other round — including every
        round of an async run where nobody was late — dispatches the plain
        program, the async==sync bit-identity's load-bearing routing."""
        payload = prep.payload
        if len(payload) < 5:
            payload = payload + (None,) * (5 - len(payload))
        wire_tables, arrived, aux, stale, edge = payload
        state, nstates, mvals, part, noise_rng, lnorms = aux
        merge, extra = self._payload_merge, ()
        kw = ({"health_on": jnp.float32(1.0 if prep.health_on else 0.0)}
              if self.cfg.health else {})
        if stale is not None:
            merge = self._payload_merge_stale
            extra = (jnp.asarray(stale[0], jnp.float32),
                     jnp.asarray(stale[1], jnp.float32))
        elif edge is not None:
            # the edge-tree round (serve/scale/edge.py): the root program
            # over forwarded [E, r, c] partials when the tree ran, the
            # grouped flat twin over the full stack otherwise — SAME
            # downstream arithmetic on the same inputs (the wire-formula
            # norms + hash assignment the serving layer computed), which
            # is the edge == flat bitwise pin
            if edge.get("partials") is not None:
                merge = self._payload_merge_edge_root
                wire_tables = edge["partials"]
            else:
                merge = self._payload_merge_edge_flat
            kw["norms_wire"] = jnp.asarray(edge["norms"], jnp.float32)
            kw["edge_assign"] = jnp.asarray(edge["assign"], jnp.int32)
        with self._mesh_ctx():
            new_state, metrics = merge(
                state, jnp.asarray(wire_tables), nstates, mvals, part,
                jnp.asarray(arrived, jnp.float32), jnp.float32(lr),
                noise_rng, lnorms, *extra, **kw)
        self._head_state = new_state
        self._inflight += 1
        self._inflight_rounds += 1
        return InFlightRound(new_state, None, metrics, [lr],
                             prep.snapshot, stacked=False,
                             masked=[prep.masked],
                             requeue_depths=[prep.requeue_depth],
                             requeue=prep.requeue,
                             requeue_ages=prep.requeue_ages,
                             cohorts=[prep.ids],
                             health_on=[prep.health_on])

    def dispatch_round(self, prep: PreparedRound, lr: float) -> InFlightRound:
        """Enqueue one round on the device WITHOUT a host sync. Chains on the
        newest dispatched state (not the committed one), so back-to-back
        dispatches queue on the device while metrics stay device arrays until
        commit_round. Caller must commit in dispatch order. A payload-
        carrying prep (served wire-payload round) dispatches the table-merge
        program over its wire-decoded tables instead."""
        if self.fault_plan is not None:
            # delivers a real SIGTERM that the runner's PreemptionHandler
            # turns into drain -> emergency checkpoint -> resumable exit
            self.fault_plan.preempt(prep.rnd)
        if prep.payload is not None:
            return self._dispatch_payload_merge(prep, lr)
        batch = prep.batch
        if self.mesh is not None:
            batch = meshlib.shard_client_batch(self.mesh, batch)
        state = self._head_state if self._head_state is not None else self.state
        cstate = (self._head_client_state
                  if self._head_client_state is not None else self.client_state)
        ids_dev = jnp.asarray(prep.ids)
        rows = self._gather(cstate, ids_dev) if cstate is not None else {}
        with self._mesh_ctx(), obtrace.span("session", "launch", round=prep.rnd):
            new_state, new_rows, metrics = self._step(
                state, batch, rows, jnp.float32(lr), prep.sub
            )
        new_cstate = None
        if cstate is not None:
            new_cstate = self._scatter(cstate, ids_dev, new_rows)
            self._head_client_state = new_cstate
        self._head_state = new_state
        self._inflight += 1
        self._inflight_rounds += 1
        return InFlightRound(new_state, new_cstate, metrics, [lr],
                             prep.snapshot, stacked=False,
                             masked=[prep.masked],
                             requeue_depths=[prep.requeue_depth],
                             requeue=prep.requeue,
                             requeue_ages=prep.requeue_ages,
                             cohorts=[prep.ids],
                             health_on=[prep.health_on])

    def dispatch_block(self, preps: list[PreparedRound], lrs) -> InFlightRound:
        """Enqueue a K-round fused block (ONE device dispatch, lax.scan over
        the round step) without a host sync. Stateless modes only — see
        supports_block_dispatch."""
        lrs = list(lrs)
        if self._multi is None:
            # make_multi_round_step routes to the SPMD sharded body itself
            # when the cfg/mesh say so — blocks stay data-parallel
            self._multi = jax.jit(
                engine.make_multi_round_step(self._train_loss_fn, self.cfg,
                                             self.mesh),
                donate_argnums=self._state_donation(),
            )
        # stack on the HOST: jnp.stack would commit the full [K, W, ...]
        # block to the default device before resharding — a K-round HBM
        # spike on one chip, defeating the memory story this feature and
        # client_chunk exist for. device transfer happens once, sharded.
        stacked = jax.tree.map(
            # prep batches are host numpy by construction (prepare_round
            # assembles them on the host thread), so this asarray is host
            # stacking, not a device sync
            lambda *xs: np.stack([np.asarray(x) for x in xs]),  # graftlint: disable=G001
            *[p.batch for p in preps],
        )
        if self.mesh is not None:
            stacked = meshlib.shard_stacked_client_batch(self.mesh, stacked)
        state = self._head_state if self._head_state is not None else self.state
        with self._mesh_ctx(), obtrace.span(
                "session", "launch", round_first=preps[0].rnd, rounds=len(lrs)):
            new_state, ms = self._multi(
                state, stacked, jnp.asarray(lrs, jnp.float32),
                jnp.stack([p.sub for p in preps]),
            )
        self._head_state = new_state
        self._inflight += 1
        self._inflight_rounds += len(lrs)
        return InFlightRound(new_state, None, ms, lrs,
                             preps[-1].snapshot, stacked=True,
                             masked=[p.masked for p in preps],
                             requeue_depths=[p.requeue_depth for p in preps],
                             requeue=preps[-1].requeue,
                             requeue_ages=preps[-1].requeue_ages,
                             cohorts=[p.ids for p in preps],
                             health_on=[p.health_on for p in preps])

    # graftlint: drain-point — commit IS the sanctioned per-round sync
    def commit_round(self, infl: InFlightRound, metrics_host=None) -> list[dict]:
        """Publish one dispatched round/block: sync its metrics (unless the
        caller already fetched them), assign the state futures, run the
        host-side bookkeeping, and install the round-boundary RNG snapshot —
        all atomically w.r.t. a concurrent emergency checkpoint."""
        if metrics_host is None:
            metrics_host = jax.device_get(infl.metrics)  # the round's sync
        return self.commit_rounds([infl], [metrics_host])

    def commit_rounds(self, infls: list[InFlightRound],
                      metrics_hosts: list) -> list[dict]:
        """Batch commit of the oldest in-flight dispatches, in dispatch
        order, under ONE mutate_lock hold: everything in flight (a full
        drain) or a prefix of it (the runner's depth-triggered drain leaves
        the newest dispatch queued on the device). Every round's
        metrics/comm/round-counter bookkeeping runs, but the server state,
        client state, RNG snapshot and re-queue are published ONCE — those
        of the LAST dispatch given (intermediate trees may already be
        released, see InFlightRound.release_state); the head of the
        dispatch chain stays while anything is left in flight. The single
        lock hold keeps the (state, round, snapshot) triple consistent for
        an emergency checkpoint: it observes the committed view of one
        round boundary, before this commit or after it, never a mix."""
        out = []
        obs_records = []
        with self.mutate_lock:
            for infl, mh in zip(infls, metrics_hosts):
                # the reserved obs prefixes never reach the metrics rows or
                # totals any logging consumer sees — popping them here is
                # half of the health/ledger bit-transparency contract (the
                # other half: the compiled estimators only read)
                mh = dict(mh)
                health = {k[len("health/"):]: mh.pop(k)
                          for k in [k for k in mh if k.startswith("health/")]}
                fp = {k[len("ledger/"):]: mh.pop(k)
                      for k in [k for k in mh if k.startswith("ledger/")]}
                if infl.stacked:
                    for i, lr in enumerate(infl.lrs):
                        m = self._finalize_metrics(
                            {k: v[i] for k, v in mh.items()}, lr,
                            masked=infl.masked[i],
                            requeue_depth=infl.requeue_depths[i])
                        out.append(m)
                        obs_records.append((
                            self.round - 1,
                            infl.cohorts[i] if infl.cohorts else None, m,
                            {k: v[i] for k, v in health.items()},
                            {k: v[i] for k, v in fp.items()},
                            infl.health_on[i] if infl.health_on else False))
                else:
                    m = self._finalize_metrics(
                        mh, infl.lrs[0], masked=infl.masked[0],
                        requeue_depth=infl.requeue_depths[0])
                    out.append(m)
                    obs_records.append((
                        self.round - 1,
                        infl.cohorts[0] if infl.cohorts else None, m,
                        health, fp,
                        infl.health_on[0] if infl.health_on else False))
                self._inflight -= 1
                self._inflight_rounds -= infl.num_rounds
            last = infls[-1]
            if last.new_state is None:
                raise RuntimeError(
                    "commit_rounds: the last dispatch of this commit has no "
                    "state reference (release_state must only be called on "
                    "entries with two newer dispatches behind them)"
                )
            self.state = last.new_state
            if last.new_client_state is not None:
                self.client_state = last.new_client_state
            self.rng_snapshot = last.snapshot
            self._requeue_committed = last.requeue
            self._requeue_ages_committed = last.requeue_ages
            if self._inflight == 0:
                self._head_state = None
                self._head_client_state = None
        # outside the mutate_lock: the sinks do host conversion + file IO —
        # an emergency checkpoint from the watchdog thread must never wait
        # on a ledger write
        if (self.health_monitor is not None or self.slo is not None
                or self.ledger is not None):
            self._publish_round_obs(obs_records)
        return out

    # graftlint: ledger-commit — THE one sanctioned ledger-append site
    # (rule G014): rounds reach the durable ledger HERE, at commit, and
    # nowhere else — which is the whole uncommitted-rounds-never-appear /
    # resume-without-duplicates discipline (obs/ledger.py).
    def _publish_round_obs(self, records):
        """Hand each just-committed round to the attached obs sinks, in
        dependency order: the health monitor first (its processed block
        feeds the others), then the SLO engine (windowed rules over the
        round series), then the durable ledger append. All values are host
        data already — the drain's one batched device_get carried them."""
        for rnd, ids, m, health, fp, health_on in records:
            block = None
            if (self.health_monitor is not None and health_on and health):
                block = self.health_monitor.on_round(rnd, health, m)
            if self.slo is not None:
                self.slo.on_round(rnd, m, block)
            if self.ledger is not None:
                self.ledger.append_round(
                    rnd, cohort=ids, metrics=m, health=block,
                    fingerprint=fp)

    # -- one federated round -------------------------------------------------
    def run_round(self, lr: float) -> dict:
        """Prepare + dispatch + commit, synchronously — bit-identical to the
        pre-pipeline implementation (the three phases are a pure refactor of
        the old inline body)."""
        prep = self.prepare_round(self.round)
        return self.commit_round(self.dispatch_round(prep, lr))[0]

    def _finalize_metrics(self, metrics_host: dict, lr: float,
                          masked: int = 0, requeue_depth: int = 0) -> dict:
        """Host-side per-round bookkeeping shared by run_round/run_rounds:
        comm accounting (survivor-scaled uplink, measured local_topk
        down-link), cohort-degradation counters, cumulative totals, and the
        round counter."""
        m = {k: float(v) for k, v in metrics_host.items()}
        m["lr"] = float(lr)
        # cohort degradation visible per round: how many clients the
        # validity mask killed, and how deep the re-queue of displaced
        # clients ran at this round's preparation
        m["clients_dropped"] = float(masked)
        m["requeue_depth"] = float(requeue_depth)
        self.clients_dropped_total += int(masked)
        self.clients_quarantined_total += int(m.get("clients_quarantined", 0))
        m.update(self.comm_per_round)
        # dropped/masked clients never transmit: charge uplink for the
        # clients that actually uploaded (the static comm_per_round assumes
        # all num_workers do). Quarantined clients DID upload — the server
        # rejected them after the fact — so they stay charged. The down-link
        # broadcast still reaches the whole next cohort.
        if (self.cfg.client_dropout > 0 or masked) and "participants" in m:
            uploaded = m["participants"] + m.get("clients_quarantined", 0.0)
            m["comm_up_mb"] *= uploaded / self.num_workers
            m["comm_total_mb"] = m["comm_up_mb"] + m["comm_down_mb"]
        if "down_support" in m:
            # local_topk: replace the static worst-case down-link estimate
            # with the round's measured broadcast support; past the sparse/
            # dense crossover a real server sends dense floats, so cap there
            from ..utils.comm import BYTES_F32, BYTES_PAIR

            per_client = min(
                m.pop("down_support") * BYTES_PAIR, self.cfg.mode.d * BYTES_F32
            )
            down = per_client * self.num_workers / 1e6
            m["comm_down_mb"] = down
            m["comm_total_mb"] = m["comm_up_mb"] + down
        self.comm_mb_total += m["comm_total_mb"]
        self.round += 1
        return m

    @property
    def supports_block_dispatch(self) -> bool:
        """Whether run_rounds can actually fuse a block into one dispatch:
        per-client-state modes need the host gather/scatter between rounds.
        An active fault plan also forces per-round dispatch: injection sites
        are scheduled by round, which a K-round fused block cannot honor."""
        return (self.client_state is None
                and self.fault_plan is None
                # table rounds (wire payloads / robust merge / adversarial
                # chaos) are per-round by construction: the wire crossing —
                # or its batch-simulated twin — is the round boundary
                and not self._table_round)

    # -- a block of rounds in one dispatch (SURVEY.md §7 hard part (d)) ------
    def run_rounds(self, lrs) -> list[dict]:
        """Run len(lrs) rounds with ONE device dispatch and ONE host sync —
        a lax.scan over the round step (engine.make_multi_round_step):
        blocks amortize the per-dispatch host work K-fold. Sampling and rng
        streams are IDENTICAL
        to sequential run_round calls (pinned by tests); per-client-state
        modes fall back to per-round dispatch."""
        lrs = list(lrs)
        if not self.supports_block_dispatch or len(lrs) <= 1:
            return [self.run_round(lr) for lr in lrs]
        # same prepare path as run_round (identical host RNG order, same
        # retry wrapper — a transient loader flake must not kill the block
        # path long stateless runs actually take), then one fused dispatch
        preps = [self.prepare_round(self.round + i) for i in range(len(lrs))]
        return self.commit_round(self.dispatch_block(preps, lrs))

    # -- evaluation (SURVEY.md §3.4: forward-only, no compression) -----------
    # graftlint: drain-point — eval runs only at a drained boundary (checked
    # below: raises if any dispatch is in flight), so its metric syncs are
    # the sanctioned kind
    def evaluate(self, dataset: FedDataset, batch_size: int = 512) -> dict:
        """Forward-only metrics over the whole eval set. On a mesh the batch
        axis shards over the client axes (eval has no client dimension — it's
        plain data parallelism over the same devices), so eval wall-clock
        scales with the mesh instead of running one-device while training
        runs n-way. eval_batches pads every batch to full shape with a
        0-mask tail, so metric sums are shard-count invariant
        (tests/test_engine.py::test_sharded_eval_matches_unsharded)."""
        if self._inflight:
            raise RuntimeError(
                f"evaluate() with {self._inflight} uncommitted in-flight "
                "dispatch(es): the runner must drain the pipeline before an "
                "eval boundary (self.state would be stale or donated)"
            )
        if self.fault_plan is not None:
            # eval-loader site: a scheduled eval_stall sleeps here once
            self.fault_plan.eval_load(self.round)
        totals: dict[str, float] = {}
        if self.mesh is not None:
            shards = meshlib.client_shards(self.mesh)
            batch_size = -(-batch_size // shards) * shards  # round up
        # double-buffer the host-side batch padding/assembly behind the
        # device's eval compute (values are identical; order is preserved)
        for batch in prefetch_iter(dataset.eval_batches(batch_size), depth=2):
            if self.mesh is not None:
                batch = meshlib.shard_client_batch(self.mesh, batch)
            with self._mesh_ctx():
                metrics = self._eval(
                    self.state["params"], self.state["net_state"], batch,
                    jax.random.PRNGKey(0),
                )
            for k, v in jax.device_get(metrics).items():
                totals[k] = totals.get(k, 0.0) + float(v)
        return totals


# ---------------------------------------------------------- reference parity


class FedModel:
    """Drop-in-ish wrapper (reference `FedModel(model, loss_fn, args)`):
    calling it runs one federated round and returns train metrics; `.eval()`
    runs the forward-only eval pass."""

    def __init__(self, session: FederatedSession):
        self.session = session

    def __call__(self, lr: float) -> dict:
        return self.session.run_round(lr)

    def eval(self, dataset: FedDataset, batch_size: int = 512) -> dict:
        return self.session.evaluate(dataset, batch_size)

    @property
    def params(self):
        return self.session.state["params"]


def plan_block(
    opt: "FedOptimizer", rnd: int, total_rounds: int, eval_every: int,
    checkpoint_every: int, rounds_per_dispatch: int,
) -> list[float]:
    """Per-round lrs for the next dispatch block, truncated at the run end
    and at any eval/checkpoint boundary so the logging/saving cadence is
    block-size-invariant. Advances the optimizer schedule. Shared by both
    training CLIs — the boundary arithmetic is subtle enough to live once."""
    block = min(
        max(rounds_per_dispatch, 1), total_rounds - rnd,
        eval_every - rnd % eval_every,
        *((checkpoint_every - rnd % checkpoint_every,)
          if checkpoint_every else ()),
    )
    lrs = []
    for _ in range(block):
        lrs.append(opt.lr)
        opt.step()
    return lrs


class FedOptimizer:
    """Reference `FedOptimizer(opt, args)` parity: owns the LR schedule; the
    server update itself (momentum + error feedback, Vvelocity/Verror) already
    ran inside the compiled round step, so `step()` only advances the
    schedule."""

    def __init__(self, schedule: Callable[[float], float], rounds_per_epoch: int):
        self.schedule = schedule
        self.rounds_per_epoch = max(rounds_per_epoch, 1)
        self._round = 0

    @property
    def round(self) -> int:
        """Schedule position; settable for checkpoint resume."""
        return self._round

    @round.setter
    def round(self, value: int):
        self._round = int(value)

    @property
    def lr(self) -> float:
        return float(self.schedule(self._round / self.rounds_per_epoch))

    def step(self):
        self._round += 1
