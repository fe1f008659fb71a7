"""Shared asynchronous run-loop harness for both training CLIs.

The harness overlaps the three host-side costs the old hand-rolled CLI loops
paid serially every round — client-batch assembly, metrics readback,
checkpoint writes — with device compute, and hoists the
watchdog/preemption/non-finite-halt/eval-cadence wiring that was copy-pasted
between `cv_train.py` and `gpt2_train.py` into one place so fixes land once.

- `prefetch.RoundPrefetcher` — double-buffered background preparation of
  client batches via `FederatedSession.prepare_round`, preserving the
  RNG-snapshot/retry semantics (a retried or replayed load is bit-identical).
- `writer.AsyncCheckpointWriter` — periodic checkpoint writes on a writer
  thread (safe to overlap: the staging-dir + rename-commit protocol means a
  torn write can never be mistaken for a checkpoint); emergency/preemption
  saves stay synchronous, and the writer is drained before exit 75.
- `loop.run_loop` — the loop itself: per-round device dispatch with metrics
  kept as DEVICE arrays until an eval/log/checkpoint boundary (JAX async
  dispatch queues rounds back-to-back; one batched `device_get` per
  boundary instead of one blocking sync per dispatch).

`--sync_loop` is the escape hatch: it reproduces the old serial loop
exactly (inline preparation, per-dispatch sync, blocking saves). The async
loop is pinned bit-identical to it — same host RNG order, same compiled
programs, same commit order — by tests/test_runner.py, including across a
checkpoint resume.
"""

from .loop import (
    RunnerConfig,
    RunStats,
    auto_inflight,
    measure_rtt_ms,
    run_loop,
)
from .prefetch import PreparedSource, RoundPrefetcher
from .writer import AsyncCheckpointWriter

__all__ = [
    "AsyncCheckpointWriter",
    "PreparedSource",
    "RoundPrefetcher",
    "RunStats",
    "RunnerConfig",
    "auto_inflight",
    "measure_rtt_ms",
    "run_loop",
]
