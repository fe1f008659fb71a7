"""The documents name only what exists: every backticked `--flag` of the
README, the migration notes and the verify notes is an option of one of the
repo's own command lines, and every path in PERF.md's layer table is a file
or directory of the tree. No prose is checked."""

import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# scripts whose parser is built inside main(): their options are read off
# the source
SCRIPT_PARSERS = ("chip_smoke.py", "benchmark/run.py", "benchmark/calibrate.py",
                  "commefficient_tpu/analysis/__main__.py",
                  "commefficient_tpu/obs/ledger.py")

# flags of other tools that the documents quote
OTHER_TOOLS = {
    "--collect-only", "--dist",             # pytest, pytest-xdist
    "--cached",                             # git diff
    "--xla_force_host_platform_device_count",  # XLA_FLAGS
    "--xla_jf_dump_to", "--xla_jf_dump_llo_text",  # LIBTPU_INIT_ARGS
}

FLAG = re.compile(r"`[^`\n]*`")
OPTION = re.compile(r"(?<![\w-])--[a-zA-Z][\w-]*\*?")  # `--decode_*`: a prefix


def _repo_options() -> set[str]:
    from commefficient_tpu.utils.config import make_parser

    known = set(OTHER_TOOLS)
    for kind in ("cv", "gpt2"):
        for action in make_parser(kind)._actions:
            known.update(action.option_strings)
    for rel in SCRIPT_PARSERS:
        with open(os.path.join(REPO, rel)) as f:
            known.update(re.findall(r"add_argument\(\s*\"(--[\w-]+)\"", f.read()))
    return known


def _layer_table_paths() -> list[str]:
    with open(os.path.join(REPO, "PERF.md")) as f:
        text = f.read()
    section = text[text.index("## 3. Layers"):text.index("## 4. Cells")]
    rows = [ln for ln in section.splitlines() if ln.startswith("| ")][2:]
    assert rows, "PERF.md section 3 has no layer table"
    paths = []
    for row in rows:
        module = row.split("|")[2]
        for name in re.findall(r"`([\w./]+)`", module):
            if "/" in name or name.endswith(".py"):
                paths.append(name)
    return paths


@pytest.mark.parametrize("doc", ["README.md", "MIGRATION.md",
                                 ".claude/skills/verify/SKILL.md", "PERF.md"])
def test_documents_name_what_exists(doc):
    if doc == "PERF.md":
        paths = _layer_table_paths()
        assert len(paths) >= 10, paths
        missing = [p for p in paths if not (
            os.path.exists(os.path.join(REPO, p))
            or os.path.exists(os.path.join(REPO, "commefficient_tpu", p)))]
        assert not missing, f"PERF.md section 3 names paths that are gone: {missing}"
        return
    with open(os.path.join(REPO, doc)) as f:
        text = f.read()
    named = {opt for span in FLAG.findall(text) for opt in OPTION.findall(span)}
    assert len(named) >= 10, named
    known = _repo_options()
    unknown = sorted(
        opt for opt in named - known
        if not (opt.endswith("*") and any(k.startswith(opt[:-1]) for k in known)))
    assert not unknown, f"{doc} names flags no command line of the repo takes: {unknown}"
