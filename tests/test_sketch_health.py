"""Sketch-health observability (obs/health.py + obs/ledger.py + obs/slo.py).

The acceptance pins:

1. BIT-IDENTITY: a run with --health_every 1 and --ledger armed commits
   the exact params and metric rows of a run with both off — fused AND
   sharded (client_shards=2 reference) AND served (wire-payload round) —
   because the in-program estimators and fingerprints only READ round
   state, and the session pops the reserved "health/"/"ledger/" metric
   prefixes before any row consumer sees them.
2. The recall proxy (bracketed: naive same-rows upper / split-row cross
   lower, midpoint reported) tracks the dense-path truth within 0.05 on
   a dense-comparable geometry, and the bracket WIDENS under saturation.
3. The round ledger holds exactly the committed rounds — gap-free and
   duplicate-free across preempt -> resume on the real CLI (the resume
   truncation + commit-only appends), with the diff/replay-check CLI
   catching divergence and gaps.
4. The SLO engine fires on an injected quarantine spike, and --slo halt
   exits the runner cleanly through the checkpointed-halt path.
5. /metrics.prom renders # TYPE-annotated Prometheus text from the same
   registry the JSON endpoint reads.
6. The postmortem bundle carries trace + ledger tail + registry snapshot
   + config (the chaos `postmortem` mode drives the watchdog-abort path
   end to end; here the writer itself is pinned).
"""

from __future__ import annotations

import json
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree

import cv_train
from commefficient_tpu.data.fed_dataset import FedDataset, shard_iid
from commefficient_tpu.federated.api import FederatedSession, FedOptimizer
from commefficient_tpu.modes.config import ModeConfig
from commefficient_tpu.obs import health as obhealth
from commefficient_tpu.obs import ledger as obledger
from commefficient_tpu.obs import slo as obslo
from commefficient_tpu.obs import registry as obreg
from commefficient_tpu.resilience import EXIT_RESUMABLE
from commefficient_tpu.runner import RunnerConfig, run_loop
from commefficient_tpu.sketch import csvec
from conftest import run_queued

LR = 0.05


def _quad_loss(params, net_state, batch, rng):
    pred = batch["x"] @ params["w"] + params["b"]
    err = pred - jax.nn.one_hot(batch["y"], pred.shape[-1])
    mask = batch["mask"]
    per_ex = (err ** 2).sum(-1)
    return (per_ex * mask).sum() / jnp.maximum(mask.sum(), 1.0), {
        "net_state": net_state,
        "metrics": {"loss_sum": (per_ex * mask).sum(), "count": mask.sum()}}


def _session(health_every=0, shards=0, wire=False, ledger_fp=False,
             seed=0, rows=3, cols=8, k=4, **kw):
    rs = np.random.RandomState(0)
    x = rs.randn(96, 6).astype(np.float32)
    w_true = rs.randn(6, 3).astype(np.float32)
    y = (x @ w_true).argmax(-1).astype(np.int32)
    train = FedDataset(x, y, shard_iid(len(x), 12, np.random.RandomState(1)))
    params = {"w": jnp.asarray(rs.randn(6, 3).astype(np.float32) * 0.1),
              "b": jnp.zeros(3)}
    d = ravel_pytree(params)[0].size
    return FederatedSession(
        train_loss_fn=_quad_loss, eval_loss_fn=_quad_loss,
        params=params, net_state={},
        mode_cfg=ModeConfig(mode="sketch", d=d, k=k, num_rows=rows,
                            num_cols=cols, momentum=0.9,
                            momentum_type="virtual", error_type="virtual"),
        train_set=train, num_workers=4, local_batch_size=4, seed=seed,
        client_shards=shards, wire_payloads=wire,
        health_every=health_every, ledger_fingerprint=ledger_fp, **kw)


def _assert_params_equal(sa, sb):
    for a, b in zip(
        jax.tree.leaves(jax.device_get(sa.state["params"])),
        jax.tree.leaves(jax.device_get(sb.state["params"])),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class _CapturingMonitor(obhealth.HealthMonitor):
    def __init__(self, **kw):
        super().__init__(**kw)
        self.calls: list[tuple[int, dict]] = []

    def on_round(self, rnd, health, metrics):
        block = super().on_round(rnd, health, metrics)
        self.calls.append((rnd, block))
        return block


# ------------------------------------------------- THE bit-identity pins


@pytest.mark.parametrize(
    "shards,wire", [(0, False), (2, False), (0, True)],
    ids=["fused", "sharded", "served-payload"])
def test_health_and_ledger_bit_identity(shards, wire, tmp_path):
    """health_every=1 + ledger fingerprints vs both off: params and every
    committed metric row identical to the last bit on all three round
    shapes — the estimators only read, and the reserved prefixes are
    popped before any consumer."""
    a = _session(shards=shards, wire=wire)
    rows_a = [a.run_round(LR) for _ in range(4)]

    b = _session(health_every=1, shards=shards, wire=wire, ledger_fp=True)
    b.health_monitor = _CapturingMonitor(
        mode_cfg=b.cfg.mode, num_workers=b.num_workers, health_every=1)
    b.ledger = obledger.RoundLedger(str(tmp_path / "led.jsonl"))
    rows_b = [b.run_round(LR) for _ in range(4)]
    b.ledger.close()

    assert rows_a == rows_b
    _assert_params_equal(a, b)
    # and the instrumentation actually ran: 4 health blocks, 4 ledger rows
    assert [r for r, _ in b.health_monitor.calls] == [0, 1, 2, 3]
    recs = obledger.round_records(str(tmp_path / "led.jsonl"))
    assert [r["round"] for r in recs] == [0, 1, 2, 3]
    assert all(r["fingerprint"] for r in recs)
    assert all(r["health"] for r in recs)


def test_health_cadence_and_registry_gauges():
    """health_every=3 computes (and records) on rounds 0, 3 only; the
    monitor publishes health_* gauges and counts health rounds."""
    s = _session(health_every=3)
    mon = _CapturingMonitor(mode_cfg=s.cfg.mode, num_workers=s.num_workers,
                            health_every=3)
    s.health_monitor = mon
    before = obreg.default().counter("health_rounds_total").value
    for _ in range(5):
        s.run_round(LR)
    assert [r for r, _ in mon.calls] == [0, 3]
    assert obreg.default().counter("health_rounds_total").value \
        - before == 2
    _, block = mon.calls[-1]
    for key in ("grad_mass_est", "topk_mass_proxy", "row_mass_cv",
                "release_frac", "verror_ratio", "uplink_vs_dense"):
        assert isinstance(block[key], float), (key, block)
    assert obreg.default().gauge("health_topk_mass_proxy").value >= 0.0
    # dense-reference extras exist on the fused ravel path
    assert "topk_mass_true" in block and "leaf_norms" in block
    assert len(block["leaf_norms"]) == 2  # w + b leaves


def test_health_in_batch_commit_of_queued_rounds():
    """Four rounds queued and published by one commit_rounds: each round
    hands its own health block to the monitor at the commit, at the right
    cadence (rounds 0 and 2 of health_every=2), and the params equal an
    unmonitored session's."""
    s = _session(health_every=2)
    mon = _CapturingMonitor(mode_cfg=s.cfg.mode, num_workers=s.num_workers,
                            health_every=2)
    s.health_monitor = mon
    run_queued(s, [LR] * 4)
    assert [r for r, _ in mon.calls] == [0, 2]
    ref = _session()
    run_queued(ref, [LR] * 4)
    _assert_params_equal(s, ref)


def _dense_session_with_health():
    rs = np.random.RandomState(0)
    x = rs.randn(96, 6).astype(np.float32)
    y = (x @ rs.randn(6, 3).astype(np.float32)).argmax(-1).astype(np.int32)
    return FederatedSession(
        train_loss_fn=_quad_loss, eval_loss_fn=_quad_loss,
        params={"w": jnp.zeros((6, 3)), "b": jnp.zeros(3)}, net_state={},
        mode_cfg=ModeConfig(mode="uncompressed", d=21, momentum=0.0,
                            momentum_type="none", error_type="none"),
        train_set=FedDataset(
            x, y, shard_iid(96, 12, np.random.RandomState(1))),
        num_workers=4, local_batch_size=4, health_every=1)


def _cli_flags(*argv):
    from commefficient_tpu.utils.config import make_parser, resolve_defaults

    return resolve_defaults(make_parser("cv").parse_args(list(argv)))


@pytest.mark.parametrize("exc, match, build", [
    (ValueError, "health", lambda: _session(health_every=-1)),
    (ValueError, "sketch", _dense_session_with_health),
    (SystemExit, "must be >= 0",
     lambda: _cli_flags("--mode", "sketch", "--health_every", "-1")),
    (SystemExit, "no table to estimate from",
     lambda: _cli_flags("--mode", "uncompressed", "--health_every", "2")),
], ids=["session_negative", "session_dense_mode", "cli_negative",
        "cli_dense_mode"])
def test_health_validation_and_split_rejection(exc, match, build):
    """`health_every` is validated where it enters: the session's keyword
    and the trainers' flag (the `--split_compile` rejection that stood beside
    them went with the flag in PR 29)."""
    with pytest.raises(exc, match=match):
        build()


# --------------------------------------------- the recall-proxy bracket


def test_recall_proxy_brackets_truth_and_widens_under_saturation():
    """On a moderate geometry the bracketed proxy tracks the true top-k
    energy fraction within 0.05; cranking the compression (c/16) widens
    the bracket — the estimator reports its own degradation."""
    rs = np.random.RandomState(0)
    d = 50_000
    g = jnp.asarray(rs.standard_t(3.0, size=d).astype(np.float32))
    gsq = float(jnp.sum(g * g))

    def bracket(k, c):
        spec = ModeConfig(mode="sketch", d=d, k=k, num_rows=5, num_cols=c,
                          momentum=0.0, momentum_type="none",
                          error_type="virtual").sketch_spec
        tab = csvec.sketch_vec(spec, g)
        mass = float(obhealth.table_mass_estimate(tab))
        _, pv = csvec.unsketch_topk(spec, tab, k)
        naive = float(obhealth.topk_energy(pv)) / mass
        pess = float(obhealth.split_topk_energy_fraction(spec, tab, k, mass))
        tidx = csvec.topk_abs(g, k)
        true = float(jnp.sum(g[tidx] ** 2)) / gsq
        return naive, pess, 0.5 * (naive + pess), true

    naive, pess, proxy, true = bracket(512, 16_384)
    assert abs(proxy - true) <= 0.05, (proxy, true)
    assert naive >= pess  # the bracket's orientation
    width_ok = naive - pess
    naive2, pess2, _, _ = bracket(512, 1_024)  # saturated: k/c = 0.5
    assert naive2 - pess2 > width_ok, (
        "saturation did not widen the proxy bracket")


def test_split_estimator_chunked_path_matches_single_shot():
    """Past csvec's single-shot byte budget the split estimator scans the
    d axis with a running top-k carry instead of materializing [r, d] —
    the two paths must select the same coordinates and produce the same
    energy (the no-[d]-materialization discipline extends to health)."""
    rs = np.random.RandomState(0)
    d = 30_000
    g = jnp.asarray(rs.standard_t(3.0, size=d).astype(np.float32))
    spec = ModeConfig(mode="sketch", d=d, k=256, num_rows=5,
                      num_cols=4096, momentum=0.0, momentum_type="none",
                      error_type="virtual").sketch_spec
    tab = csvec.sketch_vec(spec, g)
    mass = float(obhealth.table_mass_estimate(tab))
    single = float(obhealth.split_topk_energy_fraction(spec, tab, 256, mass))
    orig = csvec.UNSKETCH_SINGLE_SHOT_BYTES
    try:
        csvec.UNSKETCH_SINGLE_SHOT_BYTES = 4 * spec.r * 4000  # force chunks
        chunked = float(
            obhealth.split_topk_energy_fraction(spec, tab, 256, mass))
    finally:
        csvec.UNSKETCH_SINGLE_SHOT_BYTES = orig
    assert abs(single - chunked) < 1e-4, (single, chunked)


def test_slo_shared_series_history_not_duplicated():
    """Two rules on ONE series must not double-append its history: the
    floor rule below needs a full 3-round window, so with correct
    bookkeeping it cannot fire before round 2 even with a second rule
    watching the same series."""
    eng = obslo.SloEngine(
        obslo.parse_rules("hi:loss_sum>100@3;lo:loss_sum<1@3"),
        mode="warn", alert=lambda m: None)
    fired = []
    for rnd in range(2):
        fired += eng.on_round(rnd, {"loss_sum": 0.5})
    assert not fired, fired  # 2 samples < window despite 2 rules
    fired += eng.on_round(2, {"loss_sum": 0.5})
    assert [e["rule"] for e in fired] == ["lo"]


def test_monitor_uplink_respects_zero_participants():
    mon = obhealth.HealthMonitor(mode_cfg=_session().cfg.mode,
                                 num_workers=4, health_every=1)
    block = mon.on_round(0, {"grad_mass_est": 1.0},
                         {"participants": 0.0})
    assert block["uplink_bytes"] == 0.0  # a fully-degraded round uploaded
    # nothing — 0.0 is a value, not a missing key


def test_table_mass_estimate_tracks_norm():
    rs = np.random.RandomState(1)
    d = 20_000
    g = jnp.asarray(rs.randn(d).astype(np.float32))
    spec = ModeConfig(mode="sketch", d=d, k=16, num_rows=5, num_cols=4096,
                      momentum=0.0, momentum_type="none",
                      error_type="virtual").sketch_spec
    tab = csvec.sketch_vec(spec, g)
    mass = float(obhealth.table_mass_estimate(tab))
    assert abs(mass - float(jnp.sum(g * g))) / float(jnp.sum(g * g)) < 0.1
    assert float(obhealth.row_mass_cv(tab)) < 0.2  # healthy sketch


# --------------------------------------------------------- round ledger


def test_ledger_appends_are_monotonic_and_replay_clean(tmp_path):
    path = str(tmp_path / "l.jsonl")
    led = obledger.RoundLedger(path, static={"merge_policy": "sum"})
    for r in range(3):
        led.append_round(r, cohort=[1, 2], metrics={"participants": 2.0,
                                                    "lr": 0.1})
    with pytest.raises(obledger.LedgerError, match="out of order"):
        led.append_round(2)
    led.close()
    assert obledger.replay_check(path) == []
    recs = obledger.read_records(path)
    assert recs[0]["kind"] == "header"
    assert recs[0]["static"]["merge_policy"] == "sum"


def test_ledger_replay_check_catches_gap_and_dup(tmp_path):
    path = str(tmp_path / "l.jsonl")
    rows = [{"schema": 1, "kind": "round", "round": r} for r in
            (0, 1, 3, 3)]
    with open(path, "w") as fh:
        for r in rows:
            fh.write(json.dumps(r) + "\n")
    problems = obledger.replay_check(path)
    assert any("gap" in p for p in problems), problems
    assert any("duplicate" in p for p in problems), problems
    assert obledger.main(["replay-check", path]) == 1
    # a torn FINAL line is the legal crash artifact
    with open(path, "a") as fh:
        fh.write('{"schema": 1, "kind": "round", "rou')
    assert len(obledger.read_records(path)) == 4


def test_ledger_diff_names_first_divergence(tmp_path):
    pa, pb = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
    for path, seed in ((pa, 0), (pb, 0)):
        s = _session(ledger_fp=True, seed=seed)
        s.ledger = obledger.RoundLedger(path)
        for _ in range(3):
            s.run_round(LR)
        s.ledger.close()
    assert obledger.diff(pa, pb)["equal"]
    assert obledger.main(["diff", pa, pb]) == 0
    pc = str(tmp_path / "c.jsonl")
    s = _session(ledger_fp=True, seed=7)  # different trajectory
    s.ledger = obledger.RoundLedger(pc)
    for _ in range(3):
        s.run_round(LR)
    s.ledger.close()
    res = obledger.diff(pa, pc)
    assert not res["equal"]
    assert res["first_divergence"]["round"] == 0
    assert obledger.main(["diff", pa, pc]) == 1


@pytest.fixture()
def tiny_cv(tmp_path, monkeypatch):
    import flax.linen as nn

    import commefficient_tpu.data.cifar as cifar_mod

    orig = cifar_mod.load_cifar_fed

    def tiny(*a, **kw):
        kw.update(synthetic_train=64, synthetic_test=32)
        return orig(*a, **kw)

    monkeypatch.setattr(cv_train, "load_cifar_fed", tiny)

    class _TinyNet(nn.Module):
        num_classes: int = 10
        dtype: str = "float32"

        @nn.compact
        def __call__(self, x, train=False):
            x = x.reshape((x.shape[0], -1))
            x = nn.relu(nn.Dense(16)(x))
            return nn.Dense(self.num_classes)(x)

    monkeypatch.setattr(cv_train, "ResNet9", _TinyNet)
    return tmp_path


@pytest.mark.chaos
def test_ledger_resume_continuation_is_gap_free(tiny_cv, tmp_path):
    """Preempt mid-run -> exit 75 -> --resume: ONE ledger file, every
    round exactly once (the resume truncation drops rounds committed
    after the checkpoint being resumed from; the resumed run re-commits
    and re-appends them), and the resumed records re-derive the SAME
    fingerprints an uninterrupted run writes (commit-only appends +
    bit-exact resume)."""
    led = str(tmp_path / "run.jsonl")
    base = [
        "--dataset", "cifar10", "--mode", "sketch", "--k", "32",
        "--num_rows", "3", "--num_cols", "128", "--num_clients", "8",
        "--num_workers", "2", "--local_batch_size", "4", "--lr_scale",
        "0.05", "--weight_decay", "0", "--data_root", "/nonexistent",
        "--num_rounds", "6", "--eval_every", "2",
        "--checkpoint_dir", str(tmp_path / "ck"),
        "--checkpoint_every", "2",
        "--ledger", led, "--health_every", "2",
    ]
    with pytest.raises(SystemExit) as ei:
        cv_train.main(base + ["--fault_plan", "preempt@3"])
    assert ei.value.code == EXIT_RESUMABLE
    session = cv_train.main(base + ["--resume"])
    assert session.round == 6
    assert obledger.replay_check(led) == [], obledger.replay_check(led)
    recs = obledger.round_records(led)
    assert [r["round"] for r in recs] == list(range(6))
    # the uninterrupted twin writes the identical round sequence
    led2 = str(tmp_path / "twin.jsonl")
    cv_train.main([a if a != led else led2 for a in base
                   if a not in ("--checkpoint_dir", str(tmp_path / "ck"))]
                  + ["--checkpoint_dir", str(tmp_path / "ck2")])
    twin = obledger.round_records(led2)
    assert [r["fingerprint"] for r in twin] \
        == [r["fingerprint"] for r in recs]


# ------------------------------------------------------------ SLO engine


def test_slo_rule_grammar():
    r = obslo.SloRule.parse("q:quarantine_rate>0.3@5")
    assert (r.name, r.series, r.op, r.threshold, r.window) == (
        "q", "quarantine_rate", ">", 0.3, 5)
    assert obslo.SloRule.parse("f:topk_mass_proxy<0.05").window == 5
    assert obslo.SloRule.parse("i:server_idle_ms^5@10").op == "^"
    for bad in ("noop", "x:series=1", "x:series>nan@0", "x:s>1@0"):
        with pytest.raises(ValueError):
            obslo.SloRule.parse(bad)
    with pytest.raises(ValueError, match="duplicate"):
        obslo.parse_rules("a:x>1;a:y>2")
    assert len(obslo.parse_rules("")) == len(obslo.DEFAULT_RULES)


def test_slo_spike_fires_edge_triggered_and_halt_latches():
    eng = obslo.SloEngine(obslo.parse_rules("q:quarantine_rate>0.3@3"),
                          mode="halt", alert=lambda m: None)
    before = obreg.default().counter("slo_violations_total").value
    clean = {"participants": 8.0, "clients_quarantined": 0.0}
    spike = {"participants": 4.0, "clients_quarantined": 4.0}
    fired = []
    for rnd in range(4):
        fired += eng.on_round(rnd, clean)
    assert not fired and not eng.halted
    for rnd in range(4, 8):
        fired += eng.on_round(rnd, spike)
    assert len(fired) == 1, fired  # edge-triggered: one episode, one event
    assert eng.halted and "quarantine_rate" in eng.halted_reason
    assert obreg.default().counter(
        "slo_violations_total").value - before == 1
    snap = eng.snapshot()
    assert snap["halted"] and snap["mode"] == "halt"


def test_slo_floor_rule_waits_for_window_and_reads_health():
    eng = obslo.SloEngine(obslo.parse_rules("r:topk_mass_proxy<0.5@3"),
                          mode="warn", alert=lambda m: None)
    ev = []
    for rnd in range(2):
        ev += eng.on_round(rnd, {}, {"topk_mass_proxy": 0.1})
    assert not ev  # floor rules can't fire before the window fills
    ev += eng.on_round(2, {}, {"topk_mass_proxy": 0.1})
    assert len(ev) == 1 and ev[0]["rule"] == "r"


def test_slo_halt_exits_run_loop_cleanly():
    """--slo halt: the engine latches at commit and the runner exits
    through the same clean path as --on_nonfinite halt, message naming
    the rule."""
    s = _session()
    eng = obslo.SloEngine(obslo.parse_rules("p:participants>0.5@2"),
                          mode="halt", alert=lambda m: None)
    s.slo = eng
    cfg = RunnerConfig(total_rounds=6, eval_every=6, sync_loop=True)
    with pytest.raises(SystemExit) as ei:
        run_loop(s, FedOptimizer(lambda _: LR, 1), cfg, slo=eng)
    assert "SLO violation" in str(ei.value.code)
    assert "p:" in str(ei.value.code) or "p" in eng.halted_reason


# ----------------------------------------------- Prometheus exposition


def test_prometheus_render_has_type_lines():
    from commefficient_tpu.serve.metrics import render_prometheus

    reg = obreg.Registry()
    reg.counter("runner_rounds_total").inc(3)
    reg.gauge("server_idle_ms").set(1.5)
    reg.histogram("runner_phase_drain_ms").observe(2.0)
    reg.meter("serve_arrival_rate").record(5)
    text = render_prometheus(reg)
    assert "# TYPE runner_rounds_total counter" in text
    assert "runner_rounds_total 3" in text
    assert "# TYPE server_idle_ms gauge" in text
    assert "server_idle_ms_max 1.5" in text
    assert "# TYPE runner_phase_drain_ms summary" in text
    assert 'runner_phase_drain_ms{quantile="0.5"} 2' in text
    assert "runner_phase_drain_ms_count 1" in text
    assert "# TYPE serve_arrival_rate_rate_per_s gauge" in text
    assert text.endswith("\n")


def test_prometheus_endpoint_serves_beside_json():
    from commefficient_tpu.serve.metrics import MetricsServer

    reg = obreg.Registry()
    reg.counter("slo_violations_total").inc()
    srv = MetricsServer(lambda: {"round": 1}, port=0, registry=reg)
    srv.start()
    try:
        host, port = srv.address
        with urllib.request.urlopen(
                f"http://{host}:{port}/metrics.prom", timeout=5) as r:
            assert r.status == 200
            assert "text/plain" in r.headers["Content-Type"]
            body = r.read().decode()
        assert "# TYPE slo_violations_total counter" in body
        with urllib.request.urlopen(
                f"http://{host}:{port}/metrics", timeout=5) as r:
            assert json.loads(r.read())["round"] == 1
        with pytest.raises(Exception):
            urllib.request.urlopen(
                f"http://{host}:{port}/nope", timeout=5)
    finally:
        srv.stop()


# ------------------------------------------------------------ postmortem


def test_postmortem_bundle_contents(tmp_path):
    led = str(tmp_path / "l.jsonl")
    s = _session(ledger_fp=True)
    s.ledger = obledger.RoundLedger(led)
    for _ in range(3):
        s.run_round(LR)
    s.ledger.close()
    out = obledger.write_postmortem_bundle(
        str(tmp_path / "bundle"), reason="test", ledger_path=led,
        last_k=2, config={"mode": "sketch", "fn": print})
    reason = json.load(open(f"{out}/reason.json"))
    assert reason["reason"] == "test"
    assert reason["artifact_failures"] is None
    assert "traceEvents" in json.load(open(f"{out}/trace.json"))
    tail = [json.loads(line) for line in open(f"{out}/ledger_tail.jsonl")]
    assert [r["round"] for r in tail if r.get("kind") == "round"] == [1, 2]
    assert isinstance(json.load(open(f"{out}/registry.json")), dict)
    cfg = json.load(open(f"{out}/config.json"))
    assert cfg["mode"] == "sketch"
    assert isinstance(cfg["fn"], str)  # non-JSON values stringified


def test_runstats_carries_slo_violations():
    s = _session()
    eng = obslo.SloEngine(obslo.parse_rules("p:participants>0.5@1"),
                          mode="warn", alert=lambda m: None)
    s.slo = eng
    cfg = RunnerConfig(total_rounds=3, eval_every=3, sync_loop=True)
    stats = run_loop(s, FedOptimizer(lambda _: LR, 1), cfg, slo=eng)
    assert stats.rounds == 3
    assert stats.slo_violations == 1  # one episode, edge-triggered
