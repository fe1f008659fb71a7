"""CLI: `python -m commefficient_tpu.analysis [paths] [--json] ...`.

Exit status: 0 clean (after suppressions + baseline), 1 violations found,
2 usage/internal error. `--write-baseline` grandfathers the CURRENT
findings (G002/G003/G004 refuse grandfathering — those contracts admit
none) and exits 0.
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys

from . import ALL_RULES, RULE_CODES
from .baseline import DEFAULT_BASELINE, Baseline
from .core import Analyzer
from .report import render_json, render_text

# contracts that admit NO grandfathering: parity, reserved leaf, raw
# checkpoint writes — a violation is a bug today, not debt
NO_BASELINE_CODES = ("G002", "G003", "G004")


def _staged_files() -> list[str] | None:
    """Repo-relative paths staged for commit, or None outside git.
    ACMR: added/copied/modified/renamed — deletions have nothing to lint."""
    try:
        top = subprocess.run(
            ["git", "rev-parse", "--show-toplevel"],
            capture_output=True, text=True, check=True,
        ).stdout.strip()
        out = subprocess.run(
            ["git", "diff", "--cached", "--name-only", "--diff-filter=ACMR"],
            capture_output=True, text=True, check=True, cwd=top,
        ).stdout
    except (OSError, subprocess.CalledProcessError):
        return None
    os.chdir(top)
    return [ln for ln in out.splitlines() if ln]


def _lintable(rel: str) -> bool:
    return rel.endswith(".py") and (
        rel.startswith("commefficient_tpu/")
        or rel in ("cv_train.py", "gpt2_train.py", "chip_smoke.py")
    )


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m commefficient_tpu.analysis",
        description="graftlint: project-aware static analysis "
                    f"({', '.join(RULE_CODES)})",
    )
    p.add_argument("paths", nargs="*", default=None,
                   help="files/dirs to analyze (default: the "
                        "commefficient_tpu package)")
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="machine-readable report on stdout")
    p.add_argument("--baseline", default=DEFAULT_BASELINE,
                   help="baseline file (default: analysis/baseline.json)")
    p.add_argument("--no-baseline", action="store_true",
                   help="ignore the baseline (report grandfathered sites)")
    p.add_argument("--write-baseline", action="store_true",
                   help="grandfather current findings into --baseline "
                        "and exit 0 (G002/G003/G004 are never written)")
    p.add_argument("--select", default="",
                   help="comma-separated rule codes to run (default: all)")
    p.add_argument("--report-json", default="", metavar="PATH",
                   help="additionally write the JSON report to PATH (one "
                        "analysis run serves both the human text and the "
                        "archived report)")
    p.add_argument("--jobs", type=int, default=0, metavar="N",
                   help="analyze files across N worker processes "
                        "(default: CPU count; 1 forces serial; the report "
                        "is byte-identical either way)")
    p.add_argument("--changed-only", action="store_true",
                   help="analyze only the staged .py files (git diff "
                        "--cached); falls back to the whole package when "
                        "an analysis/ file itself is staged")
    args = p.parse_args(argv)

    if args.write_baseline and args.select:
        # a partial-rule rewrite would silently discard every OTHER rule's
        # grandfathered entries (Baseline.write replaces the whole file)
        print("--write-baseline cannot be combined with --select: the "
              "baseline is rewritten whole", file=sys.stderr)
        return 2

    rules = list(ALL_RULES)
    if args.select:
        wanted = {c.strip() for c in args.select.split(",") if c.strip()}
        unknown = wanted - set(RULE_CODES)
        if unknown:
            print(f"unknown rule code(s): {', '.join(sorted(unknown))} "
                  f"(valid: {', '.join(RULE_CODES)})", file=sys.stderr)
            return 2
        rules = [r for r in rules if r.code in wanted]

    if args.changed_only and args.paths:
        print("--changed-only derives its file list from the git index; "
              "explicit paths would be ignored — pass one or the other",
              file=sys.stderr)
        return 2

    paths = args.paths or None
    if not paths:
        paths = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

    if args.changed_only:
        staged = _staged_files()
        if staged is None:
            print("graftlint: --changed-only requires a git checkout",
                  file=sys.stderr)
            return 2
        if any(s.startswith("commefficient_tpu/analysis/") for s in staged):
            print("graftlint: an analysis/ file is staged — the rules "
                  "themselves changed, linting the whole package",
                  file=sys.stderr)
        else:
            lintable = [s for s in staged if _lintable(s)]
            if not lintable:
                print("graftlint: nothing staged to lint")
                return 0
            paths = [s for s in lintable if os.path.isfile(s)]
            if not paths:
                print("graftlint: nothing staged to lint")
                return 0

    jobs = args.jobs if args.jobs > 0 else (os.cpu_count() or 1)
    baseline = (Baseline.empty() if args.no_baseline or args.write_baseline
                else Baseline.load(args.baseline))
    try:
        result = Analyzer(rules=rules, baseline=baseline).run(paths,
                                                              jobs=jobs)
    except (OSError, ValueError) as e:
        print(f"graftlint: error: {e}", file=sys.stderr)
        return 2

    if args.write_baseline:
        keep = [v for v in result.violations
                if v.code not in NO_BASELINE_CODES and v.code != "G000"]
        refused = len(result.violations) - len(keep)
        Baseline.write(args.baseline, keep)
        print(f"graftlint: wrote {len(keep)} baseline entr"
              f"{'y' if len(keep) == 1 else 'ies'} to {args.baseline}"
              + (f" (refused {refused}: G000/G002/G003/G004 must be fixed, "
                 "not grandfathered)" if refused else ""))
        return 0

    if args.report_json:
        with open(args.report_json, "w", encoding="utf-8") as f:
            render_json(result, f)
    if args.as_json:
        render_json(result, sys.stdout)
    else:
        render_text(result, sys.stdout)
    return 0 if result.ok else 1


if __name__ == "__main__":
    sys.exit(main())
