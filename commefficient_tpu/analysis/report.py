"""graftlint output: human text and `--json` (CI / archival at the repo
root)."""

from __future__ import annotations

import collections
import json
from typing import IO

from .core import RunResult


def render_text(result: RunResult, out: IO[str]) -> None:
    for v in result.violations:
        out.write(v.format() + "\n")
    if result.stale_baseline:
        out.write(
            f"note: {len(result.stale_baseline)} stale baseline entr"
            f"{'y' if len(result.stale_baseline) == 1 else 'ies'} matched "
            "nothing (fixed or moved — prune with --write-baseline):\n")
        for e in result.stale_baseline:
            out.write(f"    {e['path']}: {e['code']}: {e['line']}\n")
    counts = collections.Counter(v.code for v in result.violations)
    summary = ", ".join(f"{c}={n}" for c, n in sorted(counts.items()))
    out.write(
        f"graftlint: {len(result.violations)} violation(s)"
        + (f" ({summary})" if summary else "")
        + f", {len(result.baselined)} baselined, {result.suppressed} "
        f"suppressed, {result.files_checked} file(s) checked\n")


def render_json(result: RunResult, out: IO[str]) -> None:
    counts: collections.Counter[str] = collections.Counter(
        v.code for v in result.violations)
    doc = {
        "version": 1,
        "ok": result.ok,
        "files_checked": result.files_checked,
        "counts": dict(sorted(counts.items())),
        "violations": [v.as_json() for v in result.violations],
        "baselined": [v.as_json() for v in result.baselined],
        "suppressed": result.suppressed,
        "stale_baseline": result.stale_baseline,
    }
    json.dump(doc, out, indent=2)
    out.write("\n")
