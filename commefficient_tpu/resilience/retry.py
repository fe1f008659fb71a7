"""Bounded retries with exponential backoff + deterministic jitter.

One generic wrapper for every host-side operation that can flake — checkpoint
IO, `jax.distributed` bootstrap, data loading. The policy is per-site (the
call sites pass their own `RetryPolicy`), the jitter is seeded so a retried
run replays the same delays, and exhaustion re-raises the LAST error so the
operator sees the real failure, not a retry-framework wrapper.

    with_retries(lambda: ckpt_write(...), site="ckpt_save",
                 policy=RetryPolicy(max_retries=3))

Retries are for TRANSIENT faults. Anything the caller knows is permanent
(bad config, assertion) should be excluded via `retry_on`. The three wired
sites (checkpoint IO, dist init, data loading) deliberately keep the
catch-all default: at those sites a transient flake and a permanent error
are indistinguishable by exception type (a coordinator-not-up-yet and a
typo'd address both time out identically), the retry cost is bounded
(max_retries attempts, seconds of backoff), and exhaustion re-raises the
REAL error — so a permanent failure is delayed, never masked.
"""

from __future__ import annotations

import dataclasses
import sys
import threading
import time
from typing import Callable

import numpy as np

from ..obs import registry as obreg

# process-wide per-site count of FAILED attempts (each one either backed off
# and retried, or exhausted the budget) — the countable footprint of a
# chaos run (`retry_counts()`): "the run recovered from N flakes" is a
# number, not a log-grep
_COUNTS_LOCK = threading.Lock()
_RETRY_COUNTS: dict[str, int] = {}


def _count_failure(site: str) -> None:
    with _COUNTS_LOCK:
        _RETRY_COUNTS[site] = _RETRY_COUNTS.get(site, 0) + 1
    obreg.default().counter("resilience_retries_total").inc()


def retry_counts() -> dict[str, int]:
    """Snapshot of {site: failed-attempt count} since process start (or the
    last reset). A site absent from the dict never failed."""
    with _COUNTS_LOCK:
        return dict(_RETRY_COUNTS)


def reset_retry_counts() -> None:
    with _COUNTS_LOCK:
        _RETRY_COUNTS.clear()


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """max_retries: extra attempts AFTER the first (so max_retries=3 means up
    to 4 calls). Delay before retry i (0-based) is
    base_delay_s * backoff**i, capped at max_delay_s, plus a uniform jitter
    of up to `jitter` of that delay (decorrelates a fleet of workers all
    retrying the same flaky endpoint)."""

    max_retries: int = 3
    base_delay_s: float = 0.1
    backoff: float = 2.0
    max_delay_s: float = 30.0
    jitter: float = 0.25
    retry_on: tuple = (Exception,)

    def __post_init__(self):
        if self.max_retries < 0:
            raise ValueError(f"max_retries must be >= 0, got {self.max_retries}")

    def delay_s(self, attempt: int, rng: np.random.RandomState) -> float:
        base = min(self.base_delay_s * self.backoff**attempt, self.max_delay_s)
        return base * (1.0 + self.jitter * float(rng.uniform()))


def with_retries(
    fn: Callable,
    *,
    site: str,
    policy: RetryPolicy | None = None,
    sleep: Callable[[float], None] = time.sleep,
    seed: int = 0,
    log: Callable[[str], None] | None = None,
):
    """Call `fn()` with up to `policy.max_retries` retries on `policy.retry_on`
    exceptions. Each failed attempt logs ONE loud line (site, attempt count,
    error, backoff) so a recovered flake is visible in the run log, then backs
    off. The final failure propagates unchanged."""
    policy = policy or RetryPolicy()
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    rng = np.random.RandomState(seed)
    for attempt in range(policy.max_retries + 1):
        try:
            return fn()
        except policy.retry_on as e:  # noqa: PERF203 — retry loop
            _count_failure(site)
            # trace instant per failed attempt; `round` is the caller's
            # jitter seed, which the wired sites key by global round (the
            # chaos trace smoke asserts retry instants land on the right
            # round; non-round sites pass 0)
            from ..obs import trace as obtrace

            obtrace.instant("resilience", f"retry:{site}",
                            attempt=attempt + 1, round=seed,
                            error=type(e).__name__)
            if attempt >= policy.max_retries:
                log(
                    f"retry[{site}]: attempt {attempt + 1}/"
                    f"{policy.max_retries + 1} failed ({type(e).__name__}: "
                    f"{e}); retries exhausted"
                )
                raise
            d = policy.delay_s(attempt, rng)
            log(
                f"retry[{site}]: attempt {attempt + 1}/"
                f"{policy.max_retries + 1} failed ({type(e).__name__}: {e}); "
                f"backing off {d:.2f}s"
            )
            sleep(d)
