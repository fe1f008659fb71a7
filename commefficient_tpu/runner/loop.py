"""The shared run loop: block planning, overlap, and the operational wiring
(watchdog, preemption, non-finite halt, eval/checkpoint cadence) both CLIs
previously hand-rolled and copy-pasted.

Overlap model (async, the default):

    prefetch thread:  prepare N+1, N+2   (client sampling + batch assembly)
    main thread:      dispatch N, N+1, ...      (no per-dispatch host sync)
    device:           compute N, N+1, ...       (queued back-to-back)
    writer thread:    periodic checkpoint save  (staging + rename commit)
    main thread @ depth reached: device_get of every pending dispatch's
        metrics but the newest's, in dispatch order, a ready stamp after
        each -> commit in dispatch order; the newest stays queued, so the
        device has its next round while the host commits and dispatches
    main thread @ boundary: the same over every pending dispatch (a full
        drain) -> eval / log / checkpoint

What stays synchronous, deliberately:

- **Commit order**: rounds publish (state, round counter, comm totals, RNG
  snapshot) in dispatch order under the session's mutate_lock — an
  emergency checkpoint from the watchdog's timer thread always captures a
  consistent committed view.
- **Eval**: runs only at a fully drained boundary (the pipeline is empty,
  so `session.state` is the exact committed params — and, with buffer
  donation on, the only state guaranteed live). Between boundaries a drain
  that the depth triggers keeps the newest dispatch queued; every save,
  eval and exit drains that one too first.
- **Emergency + preemption + final saves**: the moments where "the save
  completed" must hold before the next action (abort, exit 75, process
  end). The async writer is DRAINED before the preemption save and before
  exit.
- **Non-finite halt**: evaluated from committed metrics at drain
  boundaries — the same block granularity the old loop had with
  `--rounds_per_dispatch > 1` (the compiled `skip` guard keeps state clean
  for any rounds dispatched past the poisoned one).

`--sync_loop` collapses all of it: inline preparation, one watchdog-wrapped
prepare->dispatch->sync per round (or per fused block), blocking saves —
the old loop, kept as the A/B baseline and escape hatch. Both paths drive
the identical compiled programs in the identical order with the identical
host RNG stream, which is why tests/test_runner.py can pin them
bit-identical.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import itertools
import os
import sys
import threading
import time

import jax

from ..federated.api import FederatedSession, FedOptimizer, plan_block
from ..federated.engine import ROUND_PHASES
from ..obs import registry as obreg
from ..obs import trace as obtrace
from ..obs.profiler import ProfileWindow
from ..resilience import EXIT_RESUMABLE, PreemptionHandler, preemption
from ..utils import checkpoint as ckpt
from ..utils.logging import Timer
from ..utils.watchdog import RoundWatchdog
from .prefetch import PreparedSource, RoundPrefetcher
from .writer import AsyncCheckpointWriter


DEFAULT_MAX_INFLIGHT = 4  # auto-tune's starting point until a round is timed
AUTO_INFLIGHT_LO, AUTO_INFLIGHT_HI = 3, 16


def _process_count() -> int:
    """Host count of the job — indirection point so tests can simulate a
    multi-host loop without lying to the rest of jax (orbax checkpointing
    also reads jax.process_count and would break under a global patch)."""
    return jax.process_count()


def _finished(infl) -> bool:
    """Whether a dispatch's round program has already run to its end, asked
    without waiting (its metrics are outputs of that one program)."""
    return jax.tree.leaves(infl.metrics)[0].is_ready()


# graftlint: drain-point — deliberate one-shot sync probe at loop start;
# the measured RTT is what the in-flight chain amortizes
def measure_rtt_ms(samples: int = 5) -> float:
    """Median host<->device round-trip of a trivial jitted op + device_get —
    the per-drain sync cost the in-flight chain exists to amortize. Cheap
    enough to run once at loop start."""
    import jax.numpy as jnp

    f = jax.jit(lambda x: x + 1.0)
    x = jnp.float32(0.0)
    jax.device_get(f(x))  # compile + warm
    ts = []
    for _ in range(samples):
        t0 = time.perf_counter()
        jax.device_get(f(x))
        ts.append((time.perf_counter() - t0) * 1e3)
    return sorted(ts)[len(ts) // 2]


def auto_inflight(rtt_ms: float, round_ms: float,
                  target_overhead: float = 0.1) -> int:
    """In-flight depth that keeps the per-drain host sync under
    ~target_overhead of the work it amortizes: each drain costs one RTT (the
    batched device_get), spread over the rounds committed in it, so depth
    >= rtt / (target * round) bounds the sync tax at ~target. Clamped to
    [3, 16]. 3 because a drain that the depth triggers keeps the newest
    dispatch queued (the device never waits for the host across it): at 3
    it still reads two dispatches, so the sync is spread over two rounds
    even on a zero-RTT local backend and both ready-stamp intervals (first
    and chained) keep getting observations; at 2 it would read one. 16
    bounds how much work a preemption's grace window must wait out (the
    same concern the fixed default had)."""
    if round_ms <= 0:
        return DEFAULT_MAX_INFLIGHT
    import math

    want = math.ceil(rtt_ms / (target_overhead * round_ms))
    return max(AUTO_INFLIGHT_LO, min(AUTO_INFLIGHT_HI, want))


@dataclasses.dataclass
class RunnerConfig:
    """Loop shape + operational policy (mirrors the CLI flag surface; build
    one with from_args in the CLIs, or directly in tests and in
    benchmark/harness.py)."""

    total_rounds: int
    eval_every: int
    checkpoint_every: int = 0
    checkpoint_dir: str = ""
    rounds_per_dispatch: int = 1
    sync_loop: bool = False
    # async only: drain when this many rounds are dispatched-uncommitted,
    # even between boundaries — bounds how much work a preemption's grace
    # window has to wait out, and how stale the halt check can run. Such a
    # drain commits all but the newest dispatch, which stays queued on the
    # device (a depth of 1 has nothing to keep and drains fully).
    # 0 (default) = auto-tune: measure the host<->device RTT once at loop
    # start, then re-derive the depth from the observed per-round time at
    # every drain (auto_inflight) — a slow host link gets a deep chain, a
    # local device stays shallow. > 0 is the manual override (--max_inflight).
    max_inflight: int = 0
    # round-prep lookahead; 0 = auto (double buffering, deepened to 4 when
    # the measured RTT says the host link is slow enough that batch assembly
    # may lag a drained burst of dispatches)
    prefetch_depth: int = 0
    on_nonfinite: str = "skip"  # the CLI-level halt policy ("halt" stops)
    watchdog_abort: bool = False
    no_emergency_checkpoint: bool = False
    # observability: a jax.profiler capture window around whole rounds
    # ("START:END"; empty = off) written into profile_dir — see
    # obs/profiler.py for the start/stop-at-round-boundary semantics
    profile_rounds: str = ""
    profile_dir: str = ""

    @classmethod
    def from_args(cls, args, total_rounds: int, eval_every: int):
        return cls(
            total_rounds=total_rounds,
            eval_every=eval_every,
            checkpoint_every=args.checkpoint_every,
            checkpoint_dir=args.checkpoint_dir,
            rounds_per_dispatch=args.rounds_per_dispatch,
            sync_loop=args.sync_loop,
            max_inflight=getattr(args, "max_inflight", 0),
            prefetch_depth=getattr(args, "prefetch_depth", 0),
            on_nonfinite=args.on_nonfinite,
            watchdog_abort=args.watchdog_abort,
            no_emergency_checkpoint=args.no_emergency_checkpoint,
            profile_rounds=getattr(args, "profile_rounds", ""),
            profile_dir=getattr(args, "profile_dir", ""),
        )


@dataclasses.dataclass
class RunStats:
    """What the loop did — the CLIs' final-metrics line, the chaos smokes
    and the tests read these.

    Since the obs/ layer landed these are a per-run VIEW over the
    process-wide metrics registry: run_loop increments named registry
    counters (runner_rounds_total, cohort_clients_dropped_total, ...) at
    the same points it always counted, takes a RegistryMark at loop start,
    and fills this dataclass from the deltas at loop end — so RunStats,
    serve's /metrics snapshot and benchmark/harness.py's window deltas all
    read the SAME numbers. (Concurrent run_loops in one process would
    cross-count; the loops in this repo — the CLIs, the benchmark's set-up
    and window — run sequentially.)"""

    rounds: int = 0
    wall_s: float = 0.0
    nonfinite_rounds: int = 0
    drains: int = 0
    # of those, the drains the depth triggered that left the newest
    # dispatch queued on the device (0 under --sync_loop or a depth of 1)
    drains_kept: int = 0
    evals: int = 0
    sync_checkpoints: int = 0
    async_checkpoints: int = 0
    # async loop introspection: the measured host<->device RTT and the
    # in-flight depth the loop ended on (auto-tuned unless --max_inflight)
    rtt_ms: float = 0.0
    max_inflight_used: int = 0
    # cohort degradation: clients masked out of rounds (failed loads /
    # injected drops), clients rejected by the sketch-space quarantine,
    # rounds that ran degraded at all, and how deep the dropped-client
    # re-queue got
    clients_dropped: int = 0
    clients_quarantined: int = 0
    degraded_rounds: int = 0
    requeue_depth_max: int = 0
    # Byzantine attacks the fault plan injected while this loop ran
    # (client_signflip / client_scale / client_collude firings — the
    # resilience_attack_*_total counters' per-run deltas summed): a chaos
    # run's stats say how much adversarial pressure the merge absorbed
    attacks_injected: int = 0
    # always-on serving acceptance: the mean/max wall gap between a drain's
    # commit and the NEXT dispatch — the server idle the pipelined serving
    # mode exists to close (a pipelined source has the next round prepared
    # when the drain ends, so the gap collapses to the dispatch call
    # itself). Also published as the `server_idle_ms` registry gauge (last
    # observed gap) + `runner_idle_ms` histogram.
    server_idle_ms: float = 0.0
    server_idle_ms_max: float = 0.0
    # SLO engine firings while this loop ran (--slo warn|halt; the
    # slo_violations_total registry counter's per-run delta) — a run that
    # finished "green" with violations > 0 finished on a warn posture, not
    # a healthy one
    slo_violations: int = 0


def make_save_ckpt(session: FederatedSession, checkpoint_dir: str):
    """The one shared save closure: serialized by its own lock (the
    watchdog's emergency save runs on a timer thread and must not race a
    scheduled/periodic save of the same round — both would target the same
    staging/final dirs), sharing the session's fault plan + retry policy so
    per-site injection counters stay coherent across the whole run.

    One writer per JOB, not per host: on a pod the checkpoint dir is shared
    storage and every host holds the same replicated state, so only process
    0 writes — two hosts saving the same round would build the identical
    staging dir name and clobber each other's half-written trees. Non-zero
    processes return None (callers treat it as 'nothing written here')."""
    lock = threading.Lock()

    # graftlint: drain-point — checkpoint writes ARE sanctioned blocking
    # work: sync-mode saves run on the dispatch thread at round boundaries
    # by design (the async writer moves the periodic ones off it)
    def save_ckpt():
        if jax.process_index() != 0:
            return None
        with lock:
            return ckpt.save(
                checkpoint_dir, session,
                fault_plan=session.fault_plan,
                retry_policy=session.retry_policy,
            )

    return save_ckpt


def run_loop(
    session: FederatedSession,
    opt: FedOptimizer,
    cfg: RunnerConfig,
    *,
    eval_fn=None,
    build_row=None,
    logger=None,
    save_ckpt=None,
    source=None,
    slo=None,
    postmortem=None,
) -> RunStats:
    """Run the training loop from session.round to cfg.total_rounds.

    eval_fn() -> metrics dict, called at every eval boundary (drained).
    build_row(rnd, m, totals, ev, time_s, nonfinite_total) -> row dict for
    the logger; `m` is the last round's metrics, `totals` the sum of every
    numeric metric key since the previous eval row. Either may be None (no
    eval / no logging — benchmark/harness.py). save_ckpt defaults to
    make_save_ckpt when cfg.checkpoint_dir is set.

    source: an external round source (next() -> PreparedRound in round
    order, stop()) — the serving layer (serve/ServedSource) passes one so
    the SERVICE drives the loop from its arrival stream instead of the loop
    pulling clients through the sampling prefetcher. When given, the loop
    neither wraps nor replaces it (the source owns its own overlap policy);
    default None builds the usual PreparedSource/RoundPrefetcher pair.

    slo: an obs.SloEngine the SESSION feeds at each commit (the CLIs wire
    both ends); the loop only checks its halt latch at drain boundaries
    and exits through the same clean shutdown/save path --on_nonfinite
    halt uses. postmortem: callable(reason) writing the crash bundle
    (obs.ledger.write_postmortem_bundle) — invoked on the watchdog-abort
    and preemption exit-75 paths, where the CLIs' exception handling
    never runs (os._exit) or runs too late to matter.

    Exits the process (not returns) on preemption (EXIT_RESUMABLE) and on
    --on_nonfinite halt, after the same drain/save sequence the CLIs used
    to inline.
    """
    stats = RunStats()
    t0 = time.perf_counter()
    eval_every = max(cfg.eval_every, 1)
    start_round = session.round
    # observability: every operational count goes through the process-wide
    # registry (obs/registry.py) and RunStats is carved out of it via this
    # mark's deltas at loop end; the tracer (obs/trace.py) is a no-op
    # unless the CLI armed it (--trace / --trace_events)
    reg = obreg.default()
    mark = reg.mark()
    tracer = obtrace.get()
    # device-phase span attribute: which sketch accumulation program the
    # session compiled (EngineConfig.sketch_path; "ravel" unless layerwise)
    sketch_path = getattr(session.cfg, "sketch_path", "ravel")
    phase_hist = {ph: reg.histogram(f"runner_phase_{ph}_ms")
                  for ph in obreg.RUNNER_PHASES}
    profile = ProfileWindow.parse(cfg.profile_rounds, cfg.profile_dir,
                                  phases=ROUND_PHASES)
    if profile is not None and profile.start >= cfg.total_rounds:
        # same contract as FaultPlan.validate_rounds: a window the run can
        # never reach must be loud at launch, not a silently-missing
        # capture discovered hours later
        profile.declare_unreachable(cfg.total_rounds)
        profile = None
    # (client_* fault schedules are validated against the FULL run length by
    # the CLIs — run_loop may legitimately cover a segment, e.g. the
    # benchmark's checked rounds and its window)
    # multi-host coordinated preemption: with > 1 process the LOCAL SIGTERM
    # flag must not short-circuit the SPMD schedule (the un-signalled hosts
    # would block in the next round's collectives) — every preemption
    # decision goes through the cross-host max-reduce at block boundaries,
    # where every host's collective call counts line up.
    process_count = _process_count()

    if save_ckpt is None and cfg.checkpoint_dir:
        save_ckpt = make_save_ckpt(session, cfg.checkpoint_dir)

    def _postmortem(reason: str):
        """Best-effort crash-bundle write: the exit it precedes is the
        point — a failing bundle must never mask it."""
        if postmortem is None:
            return
        try:
            postmortem(reason)
        except Exception as e:  # noqa: BLE001 — crash path
            print(f"runner: postmortem bundle failed ({type(e).__name__}: "
                  f"{e})", file=sys.stderr, flush=True)

    def _abort():
        # stage 4 of the watchdog ladder: flush the black box, THEN die
        # with the resumable status (os._exit skips every finally — this
        # is the one chance the bundle gets)
        _postmortem("watchdog_abort")
        os._exit(EXIT_RESUMABLE)

    # escalation ladder: warn -> stacks -> emergency ckpt -> (opt-in) abort
    # with the resumable status so a supervisor relaunches with --resume
    watchdog = RoundWatchdog(
        on_emergency=save_ckpt
        if save_ckpt and not cfg.no_emergency_checkpoint else None,
        on_abort=_abort if cfg.watchdog_abort and save_ckpt else None,
    )

    async_mode = not cfg.sync_loop
    # auto-tuned overlap depth (ROADMAP follow-up): measure the per-drain
    # host sync cost once, then keep re-deriving the in-flight depth from
    # the observed per-round time so the RTT tax stays ~10% of the round —
    # a slow host link converges to a deep chain, a local device to a
    # shallow one. --max_inflight / --prefetch_depth stay as manual overrides.
    rtt_ms = (
        measure_rtt_ms()
        if async_mode and (cfg.max_inflight <= 0 or cfg.prefetch_depth <= 0)
        else 0.0
    )
    eff_inflight = (cfg.max_inflight if cfg.max_inflight > 0
                    else DEFAULT_MAX_INFLIGHT)
    prefetch_depth = (
        cfg.prefetch_depth if cfg.prefetch_depth > 0
        else (4 if rtt_ms > 10.0 else 2)
    )
    ema_round_ms = 0.0
    stats.rtt_ms = rtt_ms
    writer = None
    if async_mode and save_ckpt and cfg.checkpoint_every:
        if session._donate_state:
            # an overlapped save reads session.state while later rounds
            # dispatch — with donation the committed buffers are already
            # dead. Keep the periodic saves, just blocking (the HBM-tight
            # --no_emergency_checkpoint trade-off extends to overlap).
            print(
                "runner: state-buffer donation is on "
                "(--no_emergency_checkpoint); periodic checkpoint writes "
                "stay synchronous — an overlapped save would read donated "
                "buffers",
                flush=True,
            )
        else:
            writer = AsyncCheckpointWriter(save_ckpt)
    src = source if source is not None else (
        RoundPrefetcher(session, start_round, depth=prefetch_depth)
        if async_mode else PreparedSource(session, start_round)
    )

    pending: collections.deque = collections.deque()  # in-flight dispatches
    pending_rounds = 0
    # serving-layer hook: a pipelined ServedSource gates the NEXT round's
    # payload client compute on the previous merge being dispatched (the
    # head-state chaining the bit-parity rests on) — resolved once so the
    # batch-simulator sources pay one getattr, not one per dispatch
    on_dispatched = getattr(src, "on_dispatched", None)
    # server-idle accounting (always-on serving acceptance): the gap from a
    # drain's commit to the next dispatch — ≈0 when the source has the next
    # round ready (pipelined), the whole invite/collect/close cycle when it
    # doesn't (serial served source)
    idle_hist = reg.histogram("runner_idle_ms")
    idle_gauge = reg.gauge("server_idle_ms")
    idle_mark: list = [None]  # [perf_counter at drain end] | [None]
    idle_acc = [0.0, 0, 0.0]  # sum_ms, n, max_ms
    # the round's own clock (always on, like the phase histograms): the
    # drain stamps each dispatch it reads as its metrics come back. A
    # dispatch read behind its predecessor gives the device's own time for
    # a round (chained); the first of a drain adds whatever the device
    # waited across the drain before (first: nothing, when that drain kept
    # a round queued); and bubble_host is the part of such a wait in which
    # the host had not yet handed the device its next round.
    # No stamp is carried across run_loop calls.
    chained_hist = reg.histogram("runner_round_interval_chained_ms")
    first_hist = reg.histogram("runner_round_interval_first_ms")
    bubble_host_hist = reg.histogram("runner_bubble_host_ms")
    ready_mark: list = [None]  # [perf_counter of the last ready stamp]
    # from a drain to the return of the next dispatch call: the time from
    # which the device is known to have had nothing queued. A full drain's
    # last ready stamp; after a kept drain None, unless the kept dispatch is
    # seen finished before that call is made (the host was late after all)
    after_drain = [False]
    host_mark: list = [None]

    def note_dispatched():
        """Called at each dispatch site once the dispatch has returned and
        its `dispatch` phase is observed: closes the host's part of the wait
        the last drain opened (first dispatch after a drain only; 0 when
        the device had the kept round to run all the while)."""
        if after_drain[0]:
            bubble_host_hist.observe(
                0.0 if host_mark[0] is None
                else (time.perf_counter() - host_mark[0]) * 1e3)
            after_drain[0] = False
            host_mark[0] = None

    def note_idle():
        """Called at each dispatch site BEFORE the dispatch: resolves the
        commit-to-dispatch gap the last drain opened (first dispatch after
        a drain only), and looks once, without waiting, whether the round
        that drain kept queued is already done."""
        if idle_mark[0] is None:
            return
        now = time.perf_counter()
        if host_mark[0] is None and pending and _finished(pending[-1]):
            host_mark[0] = now  # the kept round is done: the queue is empty
        ms = (now - idle_mark[0]) * 1e3
        idle_mark[0] = None
        idle_hist.observe(ms)
        idle_gauge.set(ms)
        idle_acc[0] += ms
        idle_acc[1] += 1
        idle_acc[2] = max(idle_acc[2], ms)
    # per-dispatch (trace timestamp, first round, round count), parallel to
    # `pending`: the deferred device-phase spans — written at the drain
    # that commits them, from its ready stamps, never by a mid-round sync
    # (the deferred-metrics discipline)
    dispatch_marks: collections.deque = collections.deque()
    totals: collections.defaultdict = collections.defaultdict(float)
    last_m: dict | None = None
    nonfinite_total = 0
    timer = Timer()

    last_drain_t = time.perf_counter()
    first_drain = True

    # graftlint: drain-point — THE drain point: device_get of the pending
    # dispatches' metrics, one dispatch at a time
    def drain(watch: bool = True, keep: int = 0):
        """Commit the pending dispatches, all but the newest `keep` (0 or 1):
        read each one's metrics back in dispatch order, stamping each as it
        arrives (the host is parked here for the device time of those
        rounds, so the last read returns when one batched read would), then
        in-order publication + metric folding. keep=1 is the drain the depth
        triggers: the device finds the kept round program queued when the
        last one read ends, and the commit, the next prepared round and its
        dispatch all pass while it runs. Everything that reads the committed
        state (save, eval, exit) drains with keep=0 first. In auto mode the
        wall time between drains (boundary work included — an overestimate
        only ever tunes the depth DOWN toward the safe floor) feeds the next
        in-flight depth; the FIRST interval is discarded — it carries the
        round step's jit compile (tens of seconds), which would seed the EMA
        ~1000x high and pin the depth at the floor for many drains."""
        nonlocal pending_rounds, last_m, nonfinite_total
        nonlocal eff_inflight, ema_round_ms, last_drain_t, first_drain
        if not pending:
            return
        if len(pending) == 1:
            keep = 0  # nothing older to commit: a depth of 1, or one block
        read = list(itertools.islice(pending, len(pending) - keep))
        committed = sum(fl.num_rounds for fl in read)
        first = session.round  # oldest uncommitted round index
        # the drain legitimately waits out every dispatch it reads, so the
        # watchdog threshold scales by their round count and the recorded
        # time is normalized back to a per-round figure (true median)
        t_drain0 = time.perf_counter()
        hosts, stamps = [], []
        with (watchdog.round(first, rounds=committed)
              if watch else contextlib.nullcontext()):
            with tracer.span("runner", "drain", round_first=first,
                             rounds=committed):
                for fl, (_, d_first, d_n) in zip(read, dispatch_marks):
                    hosts.append(jax.device_get(fl.metrics))
                    stamps.append(time.perf_counter())
                    # the stamp itself, on a running capture's clock
                    tracer.instant("runner", "ready", round_first=d_first,
                                   rounds=d_n)
        phase_hist["drain"].observe((stamps[-1] - t_drain0) * 1e3)
        # each dispatch recorded only a host timestamp; its device-phase
        # span runs from the later of that and the previous ready stamp (it
        # was queued behind that dispatch) to its own. sketch_path names
        # the compiled round variant (ravel | layerwise) so a trace shows
        # which accumulation program the device time belongs to when
        # A/B-ing the two arms.
        prev = ready_mark[0]
        for i, (stamp, (ts_us, d_first, d_n)) in enumerate(
                zip(stamps, dispatch_marks)):
            if prev is not None:
                (chained_hist if i else first_hist).observe(
                    (stamp - prev) * 1e3 / d_n)
            if tracer.enabled:
                if prev is not None:
                    ts_us = max(ts_us, tracer.us_at(prev))
                tracer.complete(
                    "device", f"rounds {d_first}..{d_first + d_n - 1}",
                    ts_us, tracer.us_at(stamp) - ts_us, round_first=d_first,
                    rounds=d_n, sketch_path=sketch_path)
            prev = stamp
        ready_mark[0] = stamps[-1]
        after_drain[0] = True
        host_mark[0] = None if keep else stamps[-1]
        t_commit0 = time.perf_counter()
        with tracer.span("runner", "commit", round_first=first,
                         rounds=committed):
            for i, m in enumerate(session.commit_rounds(read, hosts)):
                rnd_i = first + i
                last_m = m
                nf = int(m.get("nonfinite_rounds", 0))
                nonfinite_total += nf
                dropped = int(m.get("clients_dropped", 0))
                quarantined = int(m.get("clients_quarantined", 0))
                depth = int(m.get("requeue_depth", 0))
                reg.counter("runner_nonfinite_rounds_total").inc(nf)
                reg.counter("cohort_clients_dropped_total").inc(dropped)
                reg.counter("cohort_clients_quarantined_total").inc(
                    quarantined)
                if dropped or quarantined:
                    reg.counter("cohort_degraded_rounds_total").inc()
                reg.gauge("cohort_requeue_depth").set(depth)
                stats.requeue_depth_max = max(stats.requeue_depth_max, depth)
                tracer.instant("runner", "commit_round", round=rnd_i)
                if quarantined:
                    tracer.instant("resilience", "quarantine", round=rnd_i,
                                   clients=quarantined)
                for k, v in m.items():
                    if isinstance(v, (int, float)):
                        totals[k] += v
                if "moe_assignments" in m:
                    # what an expert model's clients counted (losses.make_lm_loss)
                    reg.counter("model_moe_assignments_total").inc(
                        int(m["moe_assignments"]))
                    reg.counter("model_moe_assignments_held_total").inc(
                        int(m["moe_assignments_held"]))
                    reg.gauge("model_moe_expert_load_max").set(
                        m["moe_load_max_sum"] / max(m["moe_load_max_count"], 1))
                if "moe_bias_flips" in m:
                    # tokens whose chosen experts the selection bias changed
                    reg.gauge("model_moe_bias_flips_share").set(
                        m["moe_bias_flips"] / max(m["moe_bias_tokens"], 1))
        phase_hist["commit"].observe((time.perf_counter() - t_commit0) * 1e3)
        for _ in read:
            pending.popleft()
            dispatch_marks.popleft()
        pending_rounds -= committed
        reg.counter("runner_rounds_total").inc(committed)
        reg.counter("runner_drains_total").inc()
        if keep:
            reg.counter("runner_drains_kept_total").inc()
        if profile is not None:
            profile.on_committed(session.round)
        on_committed = getattr(src, "on_committed", None)
        if on_committed is not None:
            # serving layer hook: submission-to-merge latencies resolve at
            # the commit that published their round's merged update
            on_committed(session.round)
        now = time.perf_counter()
        idle_mark[0] = now  # the idle window the next dispatch closes
        per_round = (now - last_drain_t) * 1e3 / max(committed, 1)
        last_drain_t = now
        if first_drain:
            first_drain = False  # compile-tainted interval: discard
        else:
            ema_round_ms = (per_round if ema_round_ms <= 0
                            else 0.5 * ema_round_ms + 0.5 * per_round)
            if async_mode and cfg.max_inflight <= 0:
                eff_inflight = auto_inflight(rtt_ms, ema_round_ms)

    def shutdown():
        """Exit-path teardown (preemption/halt): stop the prefetcher and
        drain the writer. A failed async save is reported but must NOT
        block the synchronous exit save that follows — that save is the
        corrective action (and carries its own retries)."""
        src.stop()
        if writer is not None:
            try:
                writer.drain()
            except Exception as e:  # noqa: BLE001 — exit save still runs
                print(
                    f"runner: async checkpoint failure at shutdown "
                    f"({type(e).__name__}: {e}); continuing to the "
                    "synchronous exit save", file=sys.stderr, flush=True,
                )
            writer.close()

    rnd = start_round
    try:
        with PreemptionHandler() as pre:
            while rnd < cfg.total_rounds:
                lrs = plan_block(opt, rnd, cfg.total_rounds, eval_every,
                                 cfg.checkpoint_every, cfg.rounds_per_dispatch)
                if len(lrs) > 1 and session.supports_block_dispatch:
                    # a fused block cannot split, so the capture window
                    # arms on OVERLAP (round-aligned superset); the
                    # per-round fallback below keeps per-round precision
                    if profile is not None:
                        profile.on_dispatch(rnd, rounds=len(lrs))
                    # one dispatch for the block; the watchdog times the
                    # block (prefetch pull included — a stalled loader is a
                    # stall the ladder should see). In async mode a dispatch
                    # returns without a host sync in ~ms, so it must not
                    # feed the learned round-time median (record=False) —
                    # the boundary drain records the true per-round time.
                    with watchdog.round(rnd, record=cfg.sync_loop):
                        t_p0 = time.perf_counter()
                        with tracer.span("runner", "prepare", round=rnd,
                                         rounds=len(lrs)):
                            preps = [src.next() for _ in lrs]
                        phase_hist["prepare"].observe(
                            (time.perf_counter() - t_p0) * 1e3)
                        note_idle()
                        t_d0 = time.perf_counter()
                        t_mark = tracer.now_us()
                        with tracer.span("runner", "dispatch", round=rnd,
                                         rounds=len(lrs)):
                            pending.append(session.dispatch_block(preps, lrs))
                        # marked only AFTER the dispatch succeeded: a
                        # raising dispatch must not leave a stale mark the
                        # next drain would resolve into a phantom span
                        dispatch_marks.append((t_mark, rnd, len(lrs)))
                        if on_dispatched is not None:
                            on_dispatched(rnd + len(lrs) - 1)
                        phase_hist["dispatch"].observe(
                            (time.perf_counter() - t_d0) * 1e3)
                        note_dispatched()
                        if len(pending) > 2:
                            pending[-3].release_state()  # ends no drain now
                        pending_rounds += len(lrs)
                        if cfg.sync_loop:
                            drain(watch=False)
                    rnd += len(lrs)
                else:
                    # per-round dispatch (stateful/split/fault-plan
                    # fallback): keep the watchdog per-round so a hang is
                    # detected at round, not block, granularity
                    for j, lr in enumerate(lrs):
                        if profile is not None:
                            profile.on_dispatch(rnd + j)
                        with watchdog.round(rnd + j, record=cfg.sync_loop):
                            t_p0 = time.perf_counter()
                            with tracer.span("runner", "prepare",
                                             round=rnd + j):
                                prep = src.next()
                            phase_hist["prepare"].observe(
                                (time.perf_counter() - t_p0) * 1e3)
                            note_idle()
                            t_d0 = time.perf_counter()
                            t_mark = tracer.now_us()
                            with tracer.span("runner", "dispatch",
                                             round=rnd + j):
                                pending.append(
                                    session.dispatch_round(prep, lr)
                                )
                            dispatch_marks.append((t_mark, rnd + j, 1))
                            if on_dispatched is not None:
                                on_dispatched(rnd + j)
                            phase_hist["dispatch"].observe(
                                (time.perf_counter() - t_d0) * 1e3)
                            note_dispatched()
                            if len(pending) > 2:
                                pending[-3].release_state()  # as above
                            pending_rounds += 1
                            if cfg.sync_loop:
                                drain(watch=False)
                        rnd += 1
                        if pre.triggered and process_count == 1:
                            break  # stop inside the block: the grace window
                            # is short. Multi-host: an early break would
                            # desync this host's dispatch count from its
                            # peers' (their collectives would hang), so the
                            # flag waits for the coordinated boundary check.
                # cross-host agreement on the preemption flag at the block
                # boundary: every host sees "any host was signalled" and
                # they all finish THIS round, checkpoint it, and exit 75
                # together (single process: just the local flag)
                preempt_now = (pre.triggered if process_count == 1
                               else preemption.coordinated(pre.triggered))
                # a boundary (something is about to read the committed
                # state, or the run ends) drains everything; the depth alone
                # drains all but the newest dispatch, which stays queued
                if (preempt_now
                        or rnd >= cfg.total_rounds
                        or rnd % eval_every == 0
                        or (cfg.checkpoint_every
                            and rnd % cfg.checkpoint_every == 0)):
                    drain()
                elif pending_rounds >= eff_inflight:
                    drain(keep=1)
                if preempt_now:
                    tracer.instant("resilience", "preempt_boundary",
                                   round=session.round)
                    shutdown()
                    if save_ckpt:
                        # make_save_ckpt already gates writes to process 0
                        # (one writer per job; None = not this host's write)
                        path = save_ckpt()
                        if path:
                            print(
                                f"preemption: emergency checkpoint at round "
                                f"{session.round}: {path}", flush=True,
                            )
                    _postmortem("preemption")
                    sys.exit(EXIT_RESUMABLE)
                if nonfinite_total and cfg.on_nonfinite == "halt":
                    drain()  # the round a kept drain left queued
                    shutdown()
                    if save_ckpt:
                        save_ckpt()
                    sys.exit(
                        f"halting at round {rnd}: non-finite update skipped "
                        "(--on_nonfinite halt; "
                        + ("state checkpointed clean)" if save_ckpt
                           else "no --checkpoint_dir, nothing saved)")
                    )
                if slo is not None and slo.halted:
                    # the session's commit hook fed the SLO engine at the
                    # drain above; a latched halt exits through the SAME
                    # clean sequence the non-finite halt uses — everything
                    # dispatched committed, committed state saved, writer
                    # drained, loud one-line verdict
                    drain()
                    shutdown()
                    if save_ckpt:
                        save_ckpt()
                    sys.exit(
                        f"halting at round {rnd}: SLO violation "
                        f"({slo.halted_reason}) (--slo halt; "
                        + ("state checkpointed clean)" if save_ckpt
                           else "no --checkpoint_dir, nothing saved)")
                    )
                if (cfg.checkpoint_every and save_ckpt
                        and rnd % cfg.checkpoint_every == 0):
                    if writer is not None:
                        writer.request()  # off the round path
                        reg.counter("runner_ckpt_async_total").inc()
                    else:
                        with tracer.span("runner", "checkpoint_sync",
                                         round=session.round):
                            save_ckpt()
                        reg.counter("runner_ckpt_sync_total").inc()
                if rnd % eval_every == 0 or rnd >= cfg.total_rounds:
                    with tracer.span("runner", "eval", round=session.round):
                        ev = eval_fn() if eval_fn is not None else {}
                    reg.counter("runner_evals_total").inc()
                    if build_row is not None and logger is not None:
                        logger.append(build_row(
                            rnd=rnd, m=last_m, totals=dict(totals), ev=ev,
                            time_s=timer(), nonfinite_total=nonfinite_total,
                        ))
                    totals.clear()
    finally:
        if profile is not None:
            profile.close()
        src.stop()
        # the prefetcher may have prepared (drawn host RNG / split the
        # device key for) rounds that were never dispatched; rewind the
        # LIVE streams to the committed round boundary so a caller reusing
        # the session (a second run_loop, run_round in a notebook) stays on
        # the bit-identical sequence the sync loop would produce. No-op
        # when the streams already sit at the boundary (sync mode, clean
        # exit).
        with session.mutate_lock:
            rng_state, rng_key = session.rng_snapshot
            session.rng.set_state(rng_state)
            session._rng_key = rng_key
            # same discipline for the dropped-client re-queue: uncommitted
            # prepares may have served (or grown) the live queue — restore
            # the ages WITH it, or the aged policy's weights would diverge
            # from the committed sequence on session reuse
            session._requeue = collections.deque(session._requeue_committed)
            session._requeue_enqueued = dict(session._requeue_ages_committed)
    # shutdown() tolerates a stored async-save failure: the final
    # synchronous save below is the corrective action (it carries its own
    # retries), and an hours-old transient write error must not block it
    shutdown()
    if save_ckpt:
        save_ckpt()  # final checkpoint, synchronous (durable before return)
        reg.counter("runner_ckpt_sync_total").inc()
    # RunStats = this run's registry deltas (see the dataclass docstring):
    # the registry is the single source of truth, RunStats its per-run view
    stats.rounds = session.round - start_round
    stats.nonfinite_rounds = int(mark.delta("runner_nonfinite_rounds_total"))
    stats.drains = int(mark.delta("runner_drains_total"))
    stats.drains_kept = int(mark.delta("runner_drains_kept_total"))
    stats.evals = int(mark.delta("runner_evals_total"))
    stats.sync_checkpoints = int(mark.delta("runner_ckpt_sync_total"))
    stats.async_checkpoints = int(mark.delta("runner_ckpt_async_total"))
    stats.clients_dropped = int(mark.delta("cohort_clients_dropped_total"))
    stats.clients_quarantined = int(
        mark.delta("cohort_clients_quarantined_total"))
    stats.degraded_rounds = int(mark.delta("cohort_degraded_rounds_total"))
    from ..resilience.faults import ADVERSARIAL_KINDS

    stats.attacks_injected = sum(
        int(mark.delta(
            f"resilience_attack_{kind[len('client_'):]}_total"))
        for kind in ADVERSARIAL_KINDS)
    stats.slo_violations = int(mark.delta("slo_violations_total"))
    stats.max_inflight_used = eff_inflight if async_mode else 0
    stats.server_idle_ms = idle_acc[0] / max(idle_acc[1], 1)
    stats.server_idle_ms_max = idle_acc[2]
    reg.gauge("runner_rtt_ms").set(rtt_ms)
    reg.gauge("runner_max_inflight").set(stats.max_inflight_used)
    stats.wall_s = time.perf_counter() - t0
    return stats
