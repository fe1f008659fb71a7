"""Streaming aggregation service: the batch simulator inverted.

FetchSGD's deployment story is millions of clients *pushing* sketch
updates at an always-on aggregator — the Count Sketch's linearity makes the
server-side merge of asynchronously-arriving updates cheap. This package is
that inversion over the existing engine/runner machinery:

- `ingest`    — bounded arrival queue with admission control (backpressure,
  duplicate / out-of-round rejection, early-push buffering, load shedding)
  plus the wire-payload validation gauntlet (`validate_payload` — THE
  sanctioned deserialization boundary for untrusted frame bytes,
  graftlint G011)
- `transport` — in-process (tests, parity pins) and loopback-socket
  (JSON-lines wire realism) submission fronts, hardened against a hostile
  peer: per-connection read deadlines, max-frame caps, force-closed
  connections on stop; client helpers with bounded jittered retries
- `assembler` — over-provisioned cohorts that close at W-of-N arrivals;
  stragglers and no-shows masked + re-queued via the PR 4 `_valid`/
  `_requeue` machinery, so a short cohort is bit-identical to the round
  over its survivors; payload rounds collect the validated table stack
- `clients`   — O(1)-per-participant client state: fold_in-derived per-
  client streams and device classes, no per-client table (10M-ID safe)
- `traffic`   — trace-driven generator: diurnal load, bursts, device
  classes with distinct straggle distributions (the test harness);
  payload rounds ship per-invitee tables with wire-fault injection at the
  transport seam
- `metrics`   — the ops surface: /metrics JSON endpoint (round, queue
  depth, arrival rate, quarantine/requeue/rejection/shed counters, stage
  histograms, the server_idle_ms always-on gauge)
- `pipeline`  — the ALWAYS-ON worker (`--serve_pipeline`): the serve
  cycle runs one-plus rounds ahead on a double-buffered thread, so round
  r+1's ingest overlaps round r's merge and the commit-to-dispatch gap
  collapses; bit-identical to the serial source by construction
- `service`   — `AggregationService` + `ServedSource`: the service drives
  `runner.run_loop(source=...)` instead of the loop pulling clients;
  `--serve_async` is the buffered FedBuff-shaped mode (buffer-size
  trigger closes, staleness-weighted folds of late tables)
- `scale`     — the C1M scale-out subsystem: `eventloop` (selectors
  reactor replacing thread-per-connection — `--serve_transport
  eventloop`), `shard` (N hash-routed ingest reactors over one admission
  queue — `--serve_shards`), `edge` (two-tier edge aggregation: shard-
  local ordered table sums forwarded as one r x c partial per edge,
  pinned bitwise == the flat merge — `--serve_edges`)

Both CLIs expose it as `--serve {inproc,socket}` (+ `--serve_quorum`,
`--serve_deadline`, `--serve_trace`, `--serve_metrics_port`,
`--serve_payload {announce,sketch}`, `--serve_shed_watermark`,
`--serve_pipeline`, `--serve_async` + `--serve_buffer` /
`--serve_staleness` / `--serve_stale_rounds`, `--serve_transport`,
`--serve_shards`, `--serve_edges`).
"""

# Lazy (PEP 562) re-exports: shard WORKER processes (serve/scale/procshard)
# import `commefficient_tpu.serve.<mod>` submodules, and an eager
# `from .service import ...` here would drag jax into every worker — the
# exact fork/spawn hazard graftlint G017 polices. Names resolve on first
# attribute access; the public surface is unchanged.
_EXPORTS = {
    "ClosedRound": "assembler",
    "CohortAssembler": "assembler",
    "IngestQueue": "ingest",
    "PayloadPolicy": "ingest",
    "Submission": "ingest",
    "validate_payload": "ingest",
    "MetricsServer": "metrics",
    "RoundPipeline": "pipeline",
    "AggregationService": "service",
    "ServeConfig": "service",
    "ServedSource": "service",
    "TraceConfig": "traffic",
    "TrafficGenerator": "traffic",
    "InProcessTransport": "transport",
    "SocketTransport": "transport",
    "abort_over_socket": "transport",
    "submit_over_socket": "transport",
    "submit_with_retries": "transport",
}


def __getattr__(name):
    mod = _EXPORTS.get(name)
    if mod is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    import importlib

    return getattr(importlib.import_module(f".{mod}", __name__), name)


def __dir__():
    return sorted(set(globals()) | set(_EXPORTS))


__all__ = [
    "AggregationService",
    "ClosedRound",
    "CohortAssembler",
    "IngestQueue",
    "InProcessTransport",
    "MetricsServer",
    "PayloadPolicy",
    "RoundPipeline",
    "ServeConfig",
    "ServedSource",
    "SocketTransport",
    "Submission",
    "TraceConfig",
    "TrafficGenerator",
    "abort_over_socket",
    "submit_over_socket",
    "submit_with_retries",
    "validate_payload",
]
