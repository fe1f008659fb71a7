"""graftlint (commefficient_tpu/analysis/) — the static-analysis suite.

Three layers:

1. Fixture corpus: per rule code, a minimal VIOLATING snippet must fire
   (>= 1 finding of exactly that code) and its CONFORMING twin must stay
   silent for that code. Fixtures impersonate in-scope modules with a
   `# graftlint: module=` directive, so the scoped rules engage.
2. The real repo: `--json` over commefficient_tpu/ must exit 0 against the
   shipped baseline, and the shipped baseline must carry ZERO G002/G003/G004
   entries (those contracts admit no grandfathering).
3. Directive hygiene: `# graftlint: disable=` must name a valid rule code
   (a bad code is itself reported, G000, and is not suppressible).

Pure-host tests: the linter never imports the analyzed code, so none of
this touches jax.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from commefficient_tpu.analysis import ALL_RULES, RULE_CODES, Analyzer
from commefficient_tpu.analysis.baseline import DEFAULT_BASELINE, Baseline
from commefficient_tpu.analysis.rules_config import registered_flags

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures", "lint")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "commefficient_tpu")


def _codes(path: str) -> list[str]:
    result = Analyzer().run([path])
    return [v.code for v in result.violations]


# ------------------------------------------------------------------ fixtures


@pytest.mark.parametrize("code", RULE_CODES)
def test_rule_fires_on_violating_fixture(code):
    path = os.path.join(FIXTURES, f"{code.lower()}_bad.py")
    assert os.path.exists(path), f"missing violating fixture for {code}"
    found = _codes(path)
    assert code in found, (
        f"{code} did not fire on its violating fixture (found: {found})")


@pytest.mark.parametrize("code", RULE_CODES)
def test_rule_silent_on_conforming_fixture(code):
    path = os.path.join(FIXTURES, f"{code.lower()}_ok.py")
    assert os.path.exists(path), f"missing conforming fixture for {code}"
    found = _codes(path)
    assert code not in found, (
        f"{code} false-positived on its conforming twin (found: {found})")


def test_g007_fires_through_helper_import():
    """Package-level reachability: a time.sleep smuggled behind a helper
    IMPORT (run_loop -> other_module.wait_ready) must fire G007 — the case
    the old module-local call graph missed."""
    found = _codes(os.path.join(FIXTURES, "g007_import_bad.py"))
    assert "G007" in found, found


def test_g007_import_traversal_stops_at_drain_point():
    """The same import shape with the helper's wait DECLARED a drain point
    (in the helper's own module) must stay silent — that is how the serve/
    transports declare their sanctioned blocking points in code."""
    found = _codes(os.path.join(FIXTURES, "g007_import_ok.py"))
    assert "G007" not in found, found


def test_g010_sketch_boundary_declares_the_ravel_path():
    """The conforming twin's ravel site is legal ONLY because its def
    carries `# graftlint: sketch-boundary` — strip the directive and the
    same code must fire (the boundary is a declaration, not a loophole)."""
    with open(os.path.join(FIXTURES, "g010_ok.py")) as f:
        text = f.read()
    stripped = text.replace(
        "# graftlint: sketch-boundary — the ravel path IS the declared "
        "flat boundary\n", "")
    assert stripped != text, "fixture lost its sketch-boundary line"
    import tempfile

    with tempfile.NamedTemporaryFile(
            "w", suffix=".py", delete=False) as tmp:
        tmp.write(stripped)
        path = tmp.name
    try:
        assert "G010" in _codes(path)
    finally:
        os.unlink(path)


def test_g010_import_alone_is_silent():
    """`from jax.flatten_util import ravel_pytree` without a call moves no
    bytes — only the call that materializes the flat vector fires."""
    import tempfile

    src = ("# graftlint: module=commefficient_tpu/modes/modes.py\n"
           "from jax.flatten_util import ravel_pytree\n")
    with tempfile.NamedTemporaryFile(
            "w", suffix=".py", delete=False) as tmp:
        tmp.write(src)
        path = tmp.name
    try:
        assert "G010" not in _codes(path)
    finally:
        os.unlink(path)


def test_g012_robust_merge_is_a_declaration_not_a_loophole():
    """Strip the conforming twin's `# graftlint: robust-merge` marker and
    the same sorts must fire — the boundary is declared, never inferred."""
    with open(os.path.join(FIXTURES, "g012_ok.py")) as f:
        text = f.read()
    stripped = text.replace(
        "# graftlint: robust-merge — the declared order-statistics site\n",
        "")
    assert stripped != text, "fixture lost its robust-merge line"
    import tempfile

    with tempfile.NamedTemporaryFile(
            "w", suffix=".py", delete=False) as tmp:
        tmp.write(stripped)
        path = tmp.name
    try:
        assert "G012" in _codes(path)
    finally:
        os.unlink(path)


def test_g012_second_declared_boundary_fires():
    """THE robust-merge boundary is one function: a second declaration in
    parity scope is a second aggregation semantics hiding under the
    first's exemption, and must itself be a violation."""
    import tempfile

    src = (
        "# graftlint: module=commefficient_tpu/modes/modes.py\n"
        "import jax.numpy as jnp\n"
        "\n"
        "\n"
        "# graftlint: robust-merge\n"
        "def first(stacked):\n"
        "    return jnp.sort(stacked, axis=0)\n"
        "\n"
        "\n"
        "# graftlint: robust-merge\n"
        "def second(stacked):\n"
        "    return jnp.median(stacked, axis=0)\n"
    )
    with tempfile.NamedTemporaryFile(
            "w", suffix=".py", delete=False) as tmp:
        tmp.write(src)
        path = tmp.name
    try:
        found = _codes(path)
        assert found.count("G012") == 1, found  # the SECOND def, only
    finally:
        os.unlink(path)


def test_g012_boundary_outside_modes_fires_cross_file():
    """The boundary lives in ONE sanctioned file: declaring robust-merge in
    engine.py (also parity scope) must fire even for a lone declaration —
    that is how a cross-file second boundary is caught without cross-file
    rule state."""
    import tempfile

    src = (
        "# graftlint: module=commefficient_tpu/federated/engine.py\n"
        "import jax.numpy as jnp\n"
        "\n"
        "\n"
        "# graftlint: robust-merge\n"
        "def rogue(stacked):\n"
        "    return jnp.sort(stacked, axis=0)\n"
    )
    with tempfile.NamedTemporaryFile(
            "w", suffix=".py", delete=False) as tmp:
        tmp.write(src)
        path = tmp.name
    try:
        found = _codes(path)
        # the illegal declaration AND the unexempted sort both fire
        assert found.count("G012") == 2, found
    finally:
        os.unlink(path)


def test_g012_sketch_row_median_out_of_scope():
    """csvec's per-row median estimator (sketch/) sorts over the r hash-row
    axis — the Count-Sketch definition, not a client merge; the rule's
    scope deliberately excludes sketch/."""
    import tempfile

    src = ("# graftlint: module=commefficient_tpu/sketch/csvec.py\n"
           "import jax.numpy as jnp\n"
           "def estimate(per_row, r):\n"
           "    return jnp.sort(per_row, axis=0)[(r - 1) // 2]\n")
    with tempfile.NamedTemporaryFile(
            "w", suffix=".py", delete=False) as tmp:
        tmp.write(src)
        path = tmp.name
    try:
        assert "G012" not in _codes(path)
    finally:
        os.unlink(path)


def test_g013_second_declared_boundary_fires():
    """THE staleness-fold boundary is one function in engine.py: a second
    declaration is a second fold semantics hiding under the first's
    exemption, and must itself be a violation."""
    import tempfile

    src = (
        "# graftlint: module=commefficient_tpu/federated/engine.py\n"
        "import jax\n"
        "\n"
        "\n"
        "# graftlint: staleness-fold\n"
        "def first(table, live, stale_tables, stale_weights):\n"
        "    return table + (stale_weights[:, None, None]\n"
        "                    * stale_tables).sum(0)\n"
        "\n"
        "\n"
        "# graftlint: staleness-fold\n"
        "def second(table, live, stale_tables, stale_weights):\n"
        "    return table + (stale_tables * stale_weights[:, None,\n"
        "                    None]).sum(0)\n"
    )
    with tempfile.NamedTemporaryFile(
            "w", suffix=".py", delete=False) as tmp:
        tmp.write(src)
        path = tmp.name
    try:
        found = _codes(path)
        assert found.count("G013") == 1, found  # the SECOND def, only
    finally:
        os.unlink(path)


def test_g013_forwarding_is_legal_config_scalars_exempt():
    """The merge may FORWARD the stale stack to the boundary, and the
    stale_slots config scalar is not a wire value — neither fires; an
    inline multiply outside the boundary does."""
    import tempfile

    src = (
        "# graftlint: module=commefficient_tpu/federated/engine.py\n"
        "# graftlint: staleness-fold\n"
        "def _stale_fold(table, live, stale_tables, stale_weights):\n"
        "    return table + (stale_weights[:, None, None]\n"
        "                    * stale_tables).sum(0)\n"
        "\n"
        "\n"
        "def merge(table, live, stale_tables, stale_weights,\n"
        "          stale_slots=0):\n"
        "    if stale_slots:\n"
        "        return _stale_fold(table, live, stale_tables,\n"
        "                           stale_weights)\n"
        "    return table\n"
    )
    with tempfile.NamedTemporaryFile(
            "w", suffix=".py", delete=False) as tmp:
        tmp.write(src)
        path = tmp.name
    try:
        assert "G013" not in _codes(path)
    finally:
        os.unlink(path)
    bad = src + (
        "\n\ndef sneaky(table, stale_tables, stale_weights):\n"
        "    return table + (stale_weights[:, None, None]\n"
        "                    * stale_tables).sum(0)\n"
    )
    with tempfile.NamedTemporaryFile(
            "w", suffix=".py", delete=False) as tmp:
        tmp.write(bad)
        path = tmp.name
    try:
        assert "G013" in _codes(path)
    finally:
        os.unlink(path)


def test_g012_weighted_sort_smuggled_into_stale_fold_fires():
    """The weighted-order-statistics form (per-buffer robust merge): a
    sort/searchsorted smuggled INTO the declared staleness-fold boundary
    must fire G012 — the stale-fold declaration sanctions the LINEAR
    slot-ordered scan only, never order statistics (the wrong boundary's
    exemption buys nothing)."""
    found = _codes(os.path.join(FIXTURES, "g012_weighted_bad.py"))
    assert found.count("G012") >= 2, found  # sort + searchsorted at least
    assert "G013" not in found, found  # the stale arithmetic IS in-boundary


def test_g012_weighted_forwarding_to_robust_boundary_is_silent():
    """The conforming twin: the merge FORWARDS the stale union stacks to
    the robust-merge boundary through the attribute call
    (modes.merge_partial_wires) — no G012, and no G013 (keyword
    forwarding is the sanctioned shape)."""
    found = _codes(os.path.join(FIXTURES, "g012_weighted_ok.py"))
    assert "G012" not in found, found
    assert "G013" not in found, found


def test_g013_stale_arithmetic_inside_robust_merge_boundary_is_legal():
    """The async x robust composition: stale wire values joining the
    weighted order statistics INSIDE the declared robust-merge boundary
    (modes/modes.py) are sanctioned — that is the one other place their
    fold semantics are pinned; the same arithmetic outside it fires."""
    import tempfile

    src = (
        "# graftlint: module=commefficient_tpu/modes/modes.py\n"
        "import jax.numpy as jnp\n"
        "\n"
        "\n"
        "# graftlint: robust-merge\n"
        "def _robust_table_merge(stacked, live, policy, trim,\n"
        "                        stale_tables=None, stale_weights=None):\n"
        "    union = jnp.concatenate([stacked, stale_tables], axis=0)\n"
        "    w = jnp.concatenate([live, stale_weights])\n"
        "    order = jnp.argsort(union, axis=0, stable=True)\n"
        "    return union.sum(0), w.sum(), order\n"
    )
    with tempfile.NamedTemporaryFile(
            "w", suffix=".py", delete=False) as tmp:
        tmp.write(src)
        path = tmp.name
    try:
        found = _codes(path)
        assert "G013" not in found, found
        assert "G012" not in found, found
    finally:
        os.unlink(path)
    bad = src + (
        "\n\ndef outside(stale_tables, stale_weights):\n"
        "    return (stale_weights[:, None, None] * stale_tables).sum(0)\n"
    )
    with tempfile.NamedTemporaryFile(
            "w", suffix=".py", delete=False) as tmp:
        tmp.write(bad)
        path = tmp.name
    try:
        assert "G013" in _codes(path)
    finally:
        os.unlink(path)


def test_g013_generic_attribute_call_is_not_forwarding():
    """Attribute-call forwarding is sanctioned ONLY into the boundary
    entry points (merge_partial_wires / _robust_table_merge /
    _stale_fold): `jnp.average(stale_tables, weights=stale_weights)` is a
    smuggled weighted fold wearing a call's clothes — not an order
    statistic (G012 can't see it) and not forwarding — and must fire."""
    import tempfile

    src = (
        "# graftlint: module=commefficient_tpu/federated/engine.py\n"
        "import jax.numpy as jnp\n"
        "\n"
        "\n"
        "def sneaky(table, stale_tables, stale_weights):\n"
        "    return table + jnp.average(stale_tables, axis=0,\n"
        "                               weights=stale_weights)\n"
    )
    with tempfile.NamedTemporaryFile(
            "w", suffix=".py", delete=False) as tmp:
        tmp.write(src)
        path = tmp.name
    try:
        assert "G013" in _codes(path)
    finally:
        os.unlink(path)


def test_g014_second_declared_boundary_fires():
    """THE ledger-commit boundary is one function in federated/api.py: a
    second declaration is a second write path hiding under the first's
    exemption, and must itself be a violation."""
    import tempfile

    src = (
        "# graftlint: module=commefficient_tpu/federated/api.py\n"
        "\n"
        "\n"
        "# graftlint: ledger-commit\n"
        "def first(session, rnd, m):\n"
        "    session.ledger.append_round(rnd, metrics=m)\n"
        "\n"
        "\n"
        "# graftlint: ledger-commit\n"
        "def second(session, rnd, m):\n"
        "    session.ledger.append_round(rnd, metrics=m)\n"
    )
    with tempfile.NamedTemporaryFile(
            "w", suffix=".py", delete=False) as tmp:
        tmp.write(src)
        path = tmp.name
    try:
        found = _codes(path)
        assert found.count("G014") == 1, found  # the SECOND def, only
    finally:
        os.unlink(path)


def test_g014_runner_scope_and_construction_legal():
    """runner/ is in G014's scope (an exit path 'flushing' uncommitted
    rounds is the bug class), and constructing the writer stays legal —
    building a RoundLedger is wiring, appending is the policed verb."""
    import tempfile

    src = (
        "# graftlint: module=commefficient_tpu/runner/loop.py\n"
        "from commefficient_tpu.obs.ledger import RoundLedger\n"
        "\n"
        "\n"
        "def run_loop(session, pending):\n"
        "    ledger = RoundLedger('/tmp/run.jsonl')  # wiring: legal\n"
        "    for rnd in pending:\n"
        "        ledger.append_round(rnd)  # uncommitted flush: illegal\n"
    )
    with tempfile.NamedTemporaryFile(
            "w", suffix=".py", delete=False) as tmp:
        tmp.write(src)
        path = tmp.name
    try:
        found = _codes(path)
        assert found.count("G014") == 1, found  # the append, not the ctor
    finally:
        os.unlink(path)


def test_g016_ring_write_is_a_declaration_not_a_loophole():
    """The conforming twin's slot write is legal ONLY because its def
    carries `# graftlint: ring-write` — strip the directive and re-point
    the copy at a banned move, and the same module must fire (the
    boundary is a declaration, not a loophole)."""
    import tempfile

    src = (
        "# graftlint: module=commefficient_tpu/serve/ring.py\n"
        "import numpy as np\n"
        "\n"
        "\n"
        "def write_slot(block, index, raw):\n"
        "    # undeclared per-submission copy in fast-path scope\n"
        "    block.tables[index][...] = np.frombuffer(raw, '<f4').copy()\n"
    )
    with tempfile.NamedTemporaryFile(
            "w", suffix=".py", delete=False) as tmp:
        tmp.write(src)
        path = tmp.name
    try:
        assert "G016" in _codes(path)
    finally:
        os.unlink(path)


def test_g016_scope_is_fastpath_modules_only():
    """np.stack is the serve/ slow path's bread and butter — the rule must
    stay silent outside the declared fast-path modules (the assembler's
    stack copy is the slow path the fast path is compared against, not a
    bug)."""
    import tempfile

    src = (
        "# graftlint: module=commefficient_tpu/serve/assembler.py\n"
        "import numpy as np\n"
        "\n"
        "\n"
        "def collect(tables):\n"
        "    return np.stack(tables, axis=0)\n"
    )
    with tempfile.NamedTemporaryFile(
            "w", suffix=".py", delete=False) as tmp:
        tmp.write(src)
        path = tmp.name
    try:
        assert "G016" not in _codes(path)
    finally:
        os.unlink(path)


def test_g017_fires_direct_and_through_transitive_chain():
    """The violating fixture carries BOTH shapes: a direct module-level
    jax import in the worker-entry module, and one smuggled behind a
    same-directory helper import (the spawned worker executes both) —
    each must be its own finding."""
    found = _codes(os.path.join(FIXTURES, "g017_bad.py"))
    assert found.count("G017") >= 2, found


def test_g017_scope_is_worker_entry_modules_only():
    """A module-level jax import anywhere ELSE in the package is business
    as usual — the rule engages only on the declared worker-entry chain
    (service.py is the ROOT half; it imports jax by design)."""
    import tempfile

    src = (
        "# graftlint: module=commefficient_tpu/serve/service.py\n"
        "import jax.numpy as jnp\n"
        "\n"
        "\n"
        "def merge(stack):\n"
        "    return jnp.sum(stack, axis=0)\n"
    )
    with tempfile.NamedTemporaryFile(
            "w", suffix=".py", delete=False) as tmp:
        tmp.write(src)
        path = tmp.name
    try:
        assert "G017" not in _codes(path)
    finally:
        os.unlink(path)


def test_every_rule_has_fixture_pair():
    # adding a rule without fixtures should fail HERE, not in review
    for code in RULE_CODES:
        for suffix in ("bad", "ok"):
            assert os.path.exists(
                os.path.join(FIXTURES, f"{code.lower()}_{suffix}.py"))


def test_rule_codes_unique_and_well_formed():
    assert len(set(RULE_CODES)) == len(RULE_CODES)
    for rule in ALL_RULES:
        assert rule.code.startswith("G") and len(rule.code) == 4
        assert rule.name and rule.fixit


# ------------------------------------------------------------- the real repo


def test_repo_is_clean_under_shipped_baseline():
    """The acceptance gate: `python -m commefficient_tpu.analysis
    commefficient_tpu/ --json` exits 0 on the PR head."""
    out = subprocess.run(
        [sys.executable, "-m", "commefficient_tpu.analysis", PKG, "--json"],
        capture_output=True, text=True, cwd=REPO, timeout=120,
    )
    report = json.loads(out.stdout)
    assert out.returncode == 0, (
        f"graftlint found violations:\n"
        + "\n".join(f"{v['rel']}:{v['lineno']}: {v['code']} {v['message']}"
                    for v in report["violations"]))
    assert report["ok"] is True
    assert report["files_checked"] > 40


def test_shipped_baseline_has_no_parity_leaf_or_ckpt_entries():
    """G002/G003/G004 admit no grandfathering — the shipped baseline must
    end every PR empty of them."""
    baseline = Baseline.load(DEFAULT_BASELINE)
    banned = {e["code"] for e in baseline.entries} & {"G002", "G003", "G004"}
    assert not banned, f"baseline grandfathers banned codes: {banned}"


def test_clis_are_clean():
    paths = [os.path.join(REPO, f)
             for f in ("cv_train.py", "gpt2_train.py", "chip_smoke.py")]
    result = Analyzer().run(paths)
    assert result.ok, [v.format() for v in result.violations]


# ------------------------------------------------------------- directives


def test_disable_must_name_valid_rule_code(tmp_path):
    bad = tmp_path / "bad_directive.py"
    bad.write_text(
        "import jax\n"
        "x = 1  # graftlint: disable=G999\n"
        "y = 2  # graftlint: disable=frobnicate\n"
    )
    codes = _codes(str(bad))
    assert codes.count("G000") == 2, codes


def test_bad_directive_is_not_suppressible(tmp_path):
    f = tmp_path / "self_suppress.py"
    # disabling G000 on the same line must not silence the directive error
    f.write_text("x = 1  # graftlint: disable=G000\n")
    assert "G000" in _codes(str(f))


def test_valid_disable_suppresses(tmp_path):
    f = tmp_path / "suppressed.py"
    f.write_text(
        "# graftlint: module=commefficient_tpu/modes/fake.py\n"
        "from jax import lax\n"
        "def merge(t, ax):\n"
        "    return lax.psum(t, ax)  # graftlint: disable=G002 — test\n"
    )
    result = Analyzer().run([str(f)])
    assert result.ok
    assert result.suppressed == 1


def test_drain_point_exempts_whole_function(tmp_path):
    f = tmp_path / "drained.py"
    f.write_text(
        "# graftlint: module=commefficient_tpu/federated/fake.py\n"
        "import jax\n"
        "# graftlint: drain-point — test boundary\n"
        "def commit(pending):\n"
        "    return jax.device_get(pending)\n"
    )
    assert "G001" not in _codes(str(f))


def test_unknown_directive_verb_is_reported(tmp_path):
    f = tmp_path / "verb.py"
    f.write_text("x = 1  # graftlint: frobnicate=G001\n")
    assert "G000" in _codes(str(f))


# ------------------------------------------------------------- baseline


def test_baseline_matches_by_line_text_not_lineno(tmp_path):
    src = tmp_path / "grandfathered.py"
    src.write_text(
        "# graftlint: module=commefficient_tpu/runner/fake.py\n"
        "def from_args(args):\n"
        "    return args.not_a_flag\n"
    )
    result = Analyzer().run([str(src)])
    (v,) = result.violations
    bl = Baseline([{"path": v.rel, "code": v.code,
                    "line": v.line_text.strip()}])
    # shifting the site down two lines must not invalidate the entry
    src.write_text(
        "# graftlint: module=commefficient_tpu/runner/fake.py\n"
        "\n\n"
        "def from_args(args):\n"
        "    return args.not_a_flag\n"
    )
    result = Analyzer(baseline=bl).run([str(src)])
    assert result.ok and len(result.baselined) == 1


def test_stale_baseline_entries_are_reported(tmp_path):
    src = tmp_path / "fixed.py"
    src.write_text("x = 1\n")
    bl = Baseline([{"path": "fixed.py", "code": "G008",
                    "line": "return args.gone"}])
    result = Analyzer(baseline=bl).run([str(src)])
    assert result.ok
    assert len(result.stale_baseline) == 1


def test_write_baseline_refuses_banned_codes(tmp_path):
    src = tmp_path / "mixed.py"
    src.write_text(
        "# graftlint: module=commefficient_tpu/modes/fake.py\n"
        "from jax import lax\n"
        "def merge(t, ax):\n"
        "    return lax.psum(t, ax)\n"
    )
    bl_path = tmp_path / "baseline.json"
    out = subprocess.run(
        [sys.executable, "-m", "commefficient_tpu.analysis", str(src),
         "--baseline", str(bl_path), "--write-baseline"],
        capture_output=True, text=True, cwd=REPO, timeout=60,
    )
    assert out.returncode == 0, out.stderr
    written = json.loads(bl_path.read_text())
    assert written["entries"] == []  # G002 must be fixed, not grandfathered
    assert "refused" in out.stdout


# ------------------------------------------------------------- G008 plumbing


def test_registered_flags_extracted_from_config():
    flags = registered_flags()
    # a few load-bearing names from both task variants
    for name in ("checkpoint_every", "sync_loop", "max_inflight",
                 "fault_plan", "mesh", "model_parallel", "requeue_policy"):
        assert name in flags, name


def test_typoed_path_fails_loudly():
    # a gate that silently checks zero files is permanently green — a bad
    # path must exit 2, not 0
    out = subprocess.run(
        [sys.executable, "-m", "commefficient_tpu.analysis",
         "no_such_dir_xyz"],
        capture_output=True, text=True, cwd=REPO, timeout=60,
    )
    assert out.returncode == 2
    assert "no_such_dir_xyz" in out.stderr


def test_write_baseline_refuses_select():
    # a partial-rule rewrite would discard other rules' grandfathered
    # entries (the baseline file is rewritten whole)
    out = subprocess.run(
        [sys.executable, "-m", "commefficient_tpu.analysis", PKG,
         "--select", "G001", "--write-baseline"],
        capture_output=True, text=True, cwd=REPO, timeout=60,
    )
    assert out.returncode == 2
    assert "cannot be combined" in out.stderr


def test_report_json_flag_writes_archive(tmp_path):
    report = tmp_path / "report.json"
    out = subprocess.run(
        [sys.executable, "-m", "commefficient_tpu.analysis",
         os.path.join(FIXTURES, "g002_ok.py"), "--report-json", str(report)],
        capture_output=True, text=True, cwd=REPO, timeout=60,
    )
    assert out.returncode == 0
    assert json.loads(report.read_text())["ok"] is True
    assert "graftlint:" in out.stdout  # human text still on stdout


def test_json_report_shape(tmp_path):
    out = subprocess.run(
        [sys.executable, "-m", "commefficient_tpu.analysis",
         os.path.join(FIXTURES, "g002_bad.py"), "--json", "--no-baseline"],
        capture_output=True, text=True, cwd=REPO, timeout=60,
    )
    assert out.returncode == 1
    report = json.loads(out.stdout)
    assert report["counts"].get("G002") == 1
    (v,) = report["violations"]
    assert {"code", "rel", "lineno", "message", "fixit"} <= set(v)


# -- PR 20: concurrency rules (G018/G019/G020) + the G001 taint pass ---------


def test_g018_reports_both_directions_of_the_cycle():
    # fill_slot nests SLOT->RING lexically; flush_ring reaches RING->SLOT
    # through _grab_slot — BOTH edges of the inversion must be reported,
    # each at its own acquisition site
    vs = [v for v in Analyzer().run(
        [os.path.join(FIXTURES, "g018_bad.py")]).violations
        if v.code == "G018"]
    assert len(vs) == 2
    assert sorted(v.lineno for v in vs) == [17, 28]


def test_g018_edge_against_declared_order_fires(tmp_path):
    # no cycle at all — a SINGLE nesting that contradicts the declared
    # lock-order names is already a violation (the declaration is the
    # contract, not merely a cycle-breaking hint)
    f = tmp_path / "order_bad.py"
    f.write_text(
        "# graftlint: module=commefficient_tpu/serve/scale/order_demo.py\n"
        "import threading\n"
        "# graftlint: lock-order l1-ring\n"
        "_RING = threading.Lock()\n"
        "# graftlint: lock-order l0-slot\n"
        "_SLOT = threading.Lock()\n"
        "def go():\n"
        "    with _RING:\n"
        "        with _SLOT:\n"
        "            return 1\n")
    vs = [v for v in Analyzer().run([str(f)]).violations if v.code == "G018"]
    assert len(vs) == 1
    assert "declared lock order" in vs[0].message


def test_g018_declared_order_sanctions_the_nesting(tmp_path):
    f = tmp_path / "order_ok.py"
    f.write_text(
        "# graftlint: module=commefficient_tpu/serve/scale/order_demo2.py\n"
        "import threading\n"
        "# graftlint: lock-order l0-slot\n"
        "_SLOT = threading.Lock()\n"
        "# graftlint: lock-order l1-ring\n"
        "_RING = threading.Lock()\n"
        "def go():\n"
        "    with _SLOT:\n"
        "        with _RING:\n"
        "            return 1\n")
    assert "G018" not in _codes(str(f))


def test_g019_lockfree_directive_is_load_bearing(tmp_path):
    # strip the lockfree declaration from the conforming twin and the
    # tick counter becomes a finding — the directive is what sanctions it
    src = open(os.path.join(FIXTURES, "g019_ok.py"),
               encoding="utf-8").read()
    stripped = "\n".join(
        ln for ln in src.splitlines()
        if "lockfree" not in ln and "coarse progress" not in ln) + "\n"
    f = tmp_path / "g019_stripped.py"
    f.write_text(stripped)
    assert "G019" in _codes(str(f))
    assert "G019" not in _codes(os.path.join(FIXTURES, "g019_ok.py"))


def test_g019_lock_held_through_private_helper_counts(tmp_path):
    # must-hold: a private helper mutating shared state is safe when EVERY
    # call site holds the lock...
    common = (
        "# graftlint: module=commefficient_tpu/serve/scale/helper_demo{n}.py\n"
        "import threading\n"
        "class Pump:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._n = 0\n"
        "        self._t = None\n"
        "    def start(self):\n"
        "        self._t = threading.Thread(target=self._loop)\n"
        "        self._t.start()\n"
        "    def _bump(self):\n"
        "        self._n += 1\n"
        "    def submit(self):\n"
        "        {caller}\n"
        "    def _loop(self):\n"
        "        with self._lock:\n"
        "            self._bump()\n")
    ok = tmp_path / "helper_ok.py"
    ok.write_text(common.format(
        n=1, caller="with self._lock:\n            self._bump()"))
    assert "G019" not in _codes(str(ok))
    # ...and a finding when even one call site is bare
    bad = tmp_path / "helper_bad.py"
    bad.write_text(common.format(n=2, caller="self._bump()"))
    assert "G019" in _codes(str(bad))


def test_g020_jsonl_sink_call_fires(tmp_path):
    # the tracer's buffered emits take the ring lock internally — calling
    # them from signal context is the exact deadlock PR 7 carved
    # instant_signal_safe out to avoid
    f = tmp_path / "sink_bad.py"
    f.write_text(
        "import signal\n"
        "class _T:\n"
        "    def instant(self, *a, **k):\n"
        "        pass\n"
        "_TR = _T()\n"
        "def _h(signum, frame):\n"
        "    _TR.instant('term')\n"
        "def install():\n"
        "    signal.signal(signal.SIGTERM, _h)\n")
    vs = [v for v in Analyzer().run([str(f)]).violations if v.code == "G020"]
    assert len(vs) == 1
    assert "instant_signal_safe" in vs[0].message


def test_g020_rlock_is_exempt(tmp_path):
    # RLock is reentrant: re-acquiring from a handler that interrupted the
    # holder cannot self-deadlock, so it is not flagged
    f = tmp_path / "rlock_ok.py"
    f.write_text(
        "import signal\n"
        "import threading\n"
        "_RL = threading.RLock()\n"
        "def _h(signum, frame):\n"
        "    with _RL:\n"
        "        return signum\n"
        "def install():\n"
        "    signal.signal(signal.SIGTERM, _h)\n")
    assert "G020" not in _codes(str(f))


def test_g001_taint_catches_what_the_syntactic_scan_misses():
    # the acceptance regression pair: the PRE-taint rule (taint_pass
    # disabled) provably misses the helper-hidden float(); the shipped
    # rule catches it at the compiled-scope call site
    from commefficient_tpu.analysis.rules_sync import HostSyncInRoundPath

    class SyntacticOnly(HostSyncInRoundPath):
        taint_pass = False

    bad = os.path.join(FIXTURES, "g001_taint_bad.py")
    rules_without = [SyntacticOnly if r is HostSyncInRoundPath else r
                     for r in ALL_RULES]
    pre = [v.code for v in Analyzer(rules=rules_without).run([bad]).violations]
    assert "G001" not in pre  # the miss the taint pass exists to close
    vs = [v for v in Analyzer().run([bad]).violations if v.code == "G001"]
    assert len(vs) == 1
    assert vs[0].lineno == 13
    assert "coerce_scale" in vs[0].message


def test_g001_taint_metadata_is_laundered():
    # .shape and module constants are host-safe even on traced values —
    # the ok twin routes both through the same helper and stays silent
    assert "G001" not in _codes(os.path.join(FIXTURES, "g001_taint_ok.py"))


def test_lock_order_directive_needs_a_name(tmp_path):
    f = tmp_path / "noname.py"
    f.write_text("# graftlint: lock-order\nx = 1\n")
    assert "G000" in _codes(str(f))


def test_lockfree_directive_needs_a_justification(tmp_path):
    f = tmp_path / "nowhy.py"
    f.write_text("# graftlint: lockfree\nx = 1\n")
    assert "G000" in _codes(str(f))


def test_parallel_run_is_byte_deterministic():
    # jobs>1 fans files across processes; baseline matching and the final
    # sort happen in the parent, so the result must match serial exactly
    paths = [os.path.join(FIXTURES, n) for n in
             ("g018_bad.py", "g019_bad.py", "g020_bad.py",
              "g001_taint_bad.py", "g002_bad.py", "g007_import_bad.py")]
    serial = Analyzer().run(paths, jobs=1)
    par = Analyzer().run(paths, jobs=2)
    assert par.violations == serial.violations
    assert par.suppressed == serial.suppressed
    assert par.files_checked == serial.files_checked


def _git(repo, *args):
    subprocess.run(["git", *args], cwd=repo, check=True,
                   capture_output=True, text=True)


def _tmp_git_repo(tmp_path):
    _git(tmp_path, "init", "-q")
    _git(tmp_path, "config", "user.email", "t@t")
    _git(tmp_path, "config", "user.name", "t")
    return tmp_path


def test_changed_only_rejects_explicit_paths():
    out = subprocess.run(
        [sys.executable, "-m", "commefficient_tpu.analysis",
         "--changed-only", "commefficient_tpu"],
        capture_output=True, text=True, cwd=REPO, timeout=60,
    )
    assert out.returncode == 2
    assert "one or the other" in out.stderr


def test_changed_only_lints_exactly_the_staged_files(tmp_path):
    repo = _tmp_git_repo(tmp_path)
    env = dict(os.environ, PYTHONPATH=REPO)
    (repo / "commefficient_tpu").mkdir()
    demo = repo / "commefficient_tpu" / "tmp_demo.py"
    demo.write_text("x = 1\n")
    (repo / "unrelated.txt").write_text("hi\n")

    # nothing lintable staged -> clean exit, nothing analyzed
    _git(repo, "add", "unrelated.txt")
    out = subprocess.run(
        [sys.executable, "-m", "commefficient_tpu.analysis",
         "--changed-only"],
        capture_output=True, text=True, cwd=repo, env=env, timeout=60,
    )
    assert out.returncode == 0
    assert "nothing staged to lint" in out.stdout

    # a staged package file IS analyzed (and only it)
    _git(repo, "add", "commefficient_tpu/tmp_demo.py")
    out = subprocess.run(
        [sys.executable, "-m", "commefficient_tpu.analysis",
         "--changed-only"],
        capture_output=True, text=True, cwd=repo, env=env, timeout=60,
    )
    assert out.returncode == 0
    assert "1 file(s) checked" in out.stdout


def test_install_hooks_writes_changed_only_hook(tmp_path):
    repo = _tmp_git_repo(tmp_path)
    out = subprocess.run(
        ["bash", os.path.join(REPO, "scripts", "install_hooks.sh")],
        capture_output=True, text=True, cwd=repo, timeout=60,
    )
    assert out.returncode == 0, out.stderr
    hook = repo / ".git" / "hooks" / "pre-commit"
    assert hook.is_file()
    assert os.access(hook, os.X_OK)
    assert "--changed-only" in hook.read_text()
    # idempotent re-run over our own hook
    out = subprocess.run(
        ["bash", os.path.join(REPO, "scripts", "install_hooks.sh")],
        capture_output=True, text=True, cwd=repo, timeout=60,
    )
    assert out.returncode == 0

    # but a FOREIGN pre-commit hook is refused without FORCE=1
    hook.write_text("#!/bin/sh\necho custom\n")
    out = subprocess.run(
        ["bash", os.path.join(REPO, "scripts", "install_hooks.sh")],
        capture_output=True, text=True, cwd=repo, timeout=60,
    )
    assert out.returncode != 0
    assert "FORCE=1" in out.stderr
