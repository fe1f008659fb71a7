"""obs — unified observability: round tracing, metrics registry, profiler.

Three pieces, one contract (host-only, sync-free, bit-transparent):

- ``obs.trace``    — span/event tracer on named per-subsystem tracks
  (runner, device, writer, serve-ingest, assembler, federated,
  resilience), exported as Chrome-trace/Perfetto JSON (``--trace PATH``)
  and/or a line-buffered JSONL event stream (``--trace_events PATH``).
  Device-phase spans are DEFERRED: a dispatch records a host timestamp,
  and the runner's existing drain writes the span from its ready stamps
  (one per pending dispatch, taken as its metrics come back) — tracing
  never adds a host sync to the round path, and a traced run is pinned
  bit-identical to an untraced one. Inside a profiler capture the spans
  and the instants (the drain's ready stamps among them: `runner/ready`)
  are also `jax.profiler.TraceAnnotation`s, on the profiler's clock;
  `complete` and `instant_signal_safe` are not mirrored.
- ``obs.registry`` — process-wide counter/gauge/histogram/meter registry;
  the single source of truth RunStats, serve's /metrics snapshot, and
  bench's resilience/serve/obs blocks read from.
- ``obs.profiler`` — a ``jax.profiler`` capture window around whole rounds
  (``--profile_rounds START:END``), degrading to a loud no-op where the
  profiler is unavailable; after the capture, its summaries: device time
  by the round program's named phases and by the model's blocks, and every
  execution of the round program paired with the loop's own marks of that
  round (gauges and one stderr line each).

The contract is machine-enforced: graftlint G009 bans obs API calls inside
compiled scope (jit/shard_map bodies in the parity modules) — a span or a
counter.inc inside a traced function would either silently no-op per trace
or force a concretization; either way it lies.
"""

from __future__ import annotations

import dataclasses

from . import export, health, profiler, registry, slo, trace
from .health import HealthMonitor
from .profiler import ProfileWindow
from .registry import Registry
from .slo import SloEngine
from .trace import Tracer


def __getattr__(name):
    """Lazy obs.ledger access (PEP 562): the ledger module doubles as the
    `python -m commefficient_tpu.obs.ledger` CLI, and an eager package-
    level import would put it in sys.modules before runpy executes it as
    __main__ (the classic found-in-sys.modules RuntimeWarning)."""
    if name in ("ledger", "RoundLedger", "write_postmortem_bundle"):
        import importlib

        mod = importlib.import_module(".ledger", __name__)
        if name == "ledger":
            return mod
        return getattr(mod, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def configure_from_args(args) -> bool:
    """Arm (or disarm) the global tracer from the CLI flag surface; returns
    whether tracing is on. Called once per main() so back-to-back runs in
    one process (tests) each get a fresh event buffer."""
    trace_path = getattr(args, "trace", "") or None
    events_path = getattr(args, "trace_events", "") or None
    trace.configure(trace_path, events_path)
    return trace.get().enabled


def flush_trace() -> str | None:
    """Write the Chrome trace (if armed); note where it landed — on
    stderr, like every other diagnostic (the stdout metrics table must
    stay machine-parsable)."""
    import sys

    tracer = trace.get()
    n = tracer.event_count()
    path = trace.flush()
    if path:
        print(f"obs: trace written to {path} ({n} events)",
              file=sys.stderr, flush=True)
    return path


@dataclasses.dataclass
class ObsWiring:
    """What attach_from_args built + attached for one run: the sketch-
    health monitor, SLO engine, and round ledger (any may be None), plus
    the postmortem hook the runner calls on abort/exit-75 paths and the
    CLIs call on unhandled exceptions. `close()` in the run's finally."""

    monitor: object | None = None
    slo_engine: object | None = None
    round_ledger: object | None = None
    ledger_path: str | None = None
    postmortem_dir: str | None = None
    config: dict | None = None

    @property
    def postmortem(self):
        """The runner's postmortem hook (callable(reason) -> path), or
        None when no bundle dir is armed (no --ledger)."""
        if self.postmortem_dir is None:
            return None

        def write(reason: str) -> str:
            from .ledger import write_postmortem_bundle

            return write_postmortem_bundle(
                self.postmortem_dir, reason=reason,
                ledger_path=self.ledger_path, config=self.config)

        return write

    def close(self) -> None:
        if self.round_ledger is not None:
            self.round_ledger.close()


def attach_from_args(args, session) -> ObsWiring:
    """Build + ATTACH the observability the flag surface asks for:
    --health_every N arms the sketch-health monitor, --slo warn|halt the
    SLO engine (--slo_rules overrides the default rule set), --ledger PATH
    the durable round ledger (and, with it, the crash postmortem bundle at
    PATH.postmortem/). Call AFTER checkpoint restore — the ledger's
    resume truncation keys off the restored round, which is what makes a
    preempt -> resume run one gap-free, duplicate-free file."""
    wiring = ObsWiring(config={
        k: v for k, v in vars(args).items()
        if isinstance(v, (str, int, float, bool, type(None)))})
    if getattr(args, "health_every", 0):
        wiring.monitor = HealthMonitor(
            mode_cfg=session.cfg.mode, num_workers=session.num_workers,
            health_every=args.health_every)
        session.health_monitor = wiring.monitor
    if getattr(args, "slo", "off") != "off":
        wiring.slo_engine = SloEngine(
            slo.parse_rules(getattr(args, "slo_rules", "")), mode=args.slo)
        session.slo = wiring.slo_engine
    path = getattr(args, "ledger", "")
    if path:
        from .ledger import RoundLedger

        wiring.ledger_path = path
        wiring.postmortem_dir = path + ".postmortem"
        wiring.round_ledger = RoundLedger(
            path, resume_round=session.round,
            static={
                "mode": args.mode,
                "sketch": {"rows": args.num_rows, "cols": args.num_cols,
                           "k": args.k} if args.mode == "sketch" else None,
                "merge_policy": args.merge_policy,
                "merge_trim": args.merge_trim,
                "quarantine_scope": args.quarantine_scope,
                "quarantine_window": args.quarantine_window,
                "num_workers": session.num_workers,
                "seed": args.seed,
                "serve": getattr(args, "serve", "off"),
                "serve_payload": getattr(args, "serve_payload", "announce"),
                "health_every": getattr(args, "health_every", 0),
            })
        session.ledger = wiring.round_ledger
    return wiring


__all__ = [
    "HealthMonitor",
    "ObsWiring",
    "ProfileWindow",
    "Registry",
    "RoundLedger",
    "SloEngine",
    "Tracer",
    "attach_from_args",
    "configure_from_args",
    "export",
    "flush_trace",
    "health",
    "ledger",
    "profiler",
    "registry",
    "slo",
    "trace",
    "write_postmortem_bundle",
]
