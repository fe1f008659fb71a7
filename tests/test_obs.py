"""obs/ — unified tracing, metrics registry, and profiler capture.

The acceptance pins:

1. A traced run is BIT-IDENTICAL to an untraced run — params and every
   logged row — on the fused and the sharded (client_shards=2 reference)
   paths: the tracer only reads host clocks, never RNG or device state.
2. The exporter emits valid Chrome-trace JSON (ph/ts/dur/pid/tid fields,
   thread_name metadata naming the tracks).
3. A served run's trace shows LINKED submission->merge spans (same
   r<rnd>/c<cid> id as the admission instants) plus distinct prepare/
   dispatch/drain/commit phases per round.
4. The registry is thread-safe under the ingest path and is the single
   source RunStats is carved from (mark deltas).
5. The jax.profiler window starts/stops at the right round boundaries and
   degrades to a LOUD no-op where the profiler is unavailable.
6. TableLogger's JSONL sink survives a SIGKILLed process with only whole
   JSON lines on disk (crash-safe observability is table stakes).
"""

from __future__ import annotations

import json
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree

import cv_train
from commefficient_tpu.data.fed_dataset import FedDataset, shard_iid
from commefficient_tpu.federated.api import FederatedSession, FedOptimizer
from commefficient_tpu.modes.config import ModeConfig
from commefficient_tpu.obs import registry as obreg
from commefficient_tpu.obs import trace as obtrace
from commefficient_tpu.obs.profiler import ProfileWindow, parse_rounds_spec
from commefficient_tpu.runner import RunnerConfig, run_loop
from commefficient_tpu.serve import (
    AggregationService, IngestQueue, ServeConfig, Submission, TraceConfig,
    TrafficGenerator,
)

LR = 0.05


@pytest.fixture(autouse=True)
def _disarm_tracer():
    """Every test leaves the global tracer disarmed (configure() with no
    paths resets the buffer and disables emission)."""
    yield
    obtrace.configure()


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
            x = nn.relu(nn.Dense(32)(x))
            return nn.Dense(self.num_classes)(x)

    monkeypatch.setattr(cv_train, "ResNet9", _TinyNet)
    return tmp_path


def _argv(extra=()):
    return [
        "--dataset", "cifar10", "--mode", "uncompressed", "--num_clients", "8",
        "--num_workers", "2", "--local_batch_size", "4", "--lr_scale", "0.05",
        "--weight_decay", "0", "--data_root", "/nonexistent", *extra,
    ]


def _quad_loss(params, net_state, batch, rng):
    pred = batch["x"] @ params["w"] + params["b"]
    err = pred - jax.nn.one_hot(batch["y"], pred.shape[-1])
    mask = batch["mask"]
    count = jnp.maximum(mask.sum(), 1.0)
    per_ex = (err ** 2).sum(-1)
    return (per_ex * mask).sum() / count, {
        "net_state": net_state,
        "metrics": {"loss_sum": (per_ex * mask).sum(), "count": mask.sum()}}


def _tiny_session(shards=0, seed=0, num_clients=12, workers=4, din=6, dout=3):
    rs = np.random.RandomState(0)
    x = rs.randn(96, din).astype(np.float32)
    w_true = rs.randn(din, dout).astype(np.float32)
    y = (x @ w_true).argmax(-1).astype(np.int32)
    train = FedDataset(x, y, shard_iid(len(x), num_clients,
                                       np.random.RandomState(1)))
    params = {"w": jnp.asarray(rs.randn(din, dout).astype(np.float32) * 0.1),
              "b": jnp.zeros(dout)}
    d = ravel_pytree(params)[0].size
    return FederatedSession(
        train_loss_fn=_quad_loss, eval_loss_fn=_quad_loss,
        params=params, net_state={},
        mode_cfg=ModeConfig(mode="uncompressed", d=d, momentum=0.9,
                            momentum_type="virtual", error_type="none"),
        train_set=train, num_workers=workers, local_batch_size=4,
        seed=seed, client_shards=shards,
    )


def _assert_params_equal(sa, sb):
    for x, y in zip(
        jax.tree.leaves(jax.device_get(sa.state["params"])),
        jax.tree.leaves(jax.device_get(sb.state["params"])),
    ):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _rows(path):
    rows = [json.loads(line) for line in open(path)]
    for r in rows:
        r.pop("time_s")
    return rows


# ------------------------------------------------- THE bit-identity pins


@pytest.mark.parametrize("shards", [0, 2], ids=["fused", "sharded"])
def test_traced_rounds_bit_identical_to_untraced(shards, tmp_path):
    """Tracing reads host clocks only: round metrics and final params of a
    traced session must equal an untraced one's to the last bit — fused
    AND on the sharded single-device reference program."""
    a = _tiny_session(shards=shards)
    rows_a = [a.run_round(LR) for _ in range(3)]

    obtrace.configure(trace_path=str(tmp_path / "t.json"),
                      jsonl_path=str(tmp_path / "ev.jsonl"))
    b = _tiny_session(shards=shards)
    rows_b = [b.run_round(LR) for _ in range(3)]
    obtrace.configure()

    assert rows_a == rows_b
    _assert_params_equal(a, b)


@pytest.mark.chaos
def test_traced_cli_run_bit_identical_to_untraced(tiny_cv, tmp_path):
    """Full CLI run (async runner, eval cadence mid-run) with --trace +
    --trace_events vs without: params and every logged JSONL row must be
    bit-identical, and the trace must land with runner spans in it."""
    base = _argv(("--num_rounds", "4", "--eval_every", "2"))
    la, lb = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
    trace_path = str(tmp_path / "run_trace.json")
    sa = cv_train.main(base + ["--log_jsonl", la])
    sb = cv_train.main(base + ["--log_jsonl", lb, "--trace", trace_path,
                               "--trace_events",
                               str(tmp_path / "ev.jsonl")])
    assert sa.round == sb.round == 4
    _assert_params_equal(sa, sb)
    assert _rows(la) == _rows(lb)
    ev = json.load(open(trace_path))["traceEvents"]
    names = {e["name"] for e in ev if e["ph"] == "X"}
    assert {"prepare", "dispatch", "drain", "commit", "eval"} <= names
    # the federated prepare span ran on the prefetch thread and still landed
    assert "prepare_round" in names


# ----------------------------------------------------- exporter schema


def test_chrome_trace_export_schema(tmp_path):
    path = str(tmp_path / "t.json")
    obtrace.configure(trace_path=path)
    with obtrace.span("runner", "phase", round=0):
        pass
    obtrace.instant("resilience", "fault:test", round=1)
    obtrace.complete("device", "rounds 0..0", obtrace.now_us(), 123.0,
                     rounds=1)
    out = obtrace.flush()
    assert out == path
    doc = json.load(open(path))
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    assert {e["ph"] for e in evs} <= {"X", "i", "M"}
    for e in evs:
        assert {"ph", "pid", "tid", "name"} <= set(e), e
        if e["ph"] in ("X", "i"):
            assert isinstance(e["ts"], (int, float))
            assert "args" in e and "cat" in e
        if e["ph"] == "X":
            assert e["dur"] >= 0
    track_names = {e["args"]["name"] for e in evs
                   if e["ph"] == "M" and e["name"] == "thread_name"}
    assert {"runner", "device", "writer", "serve-ingest", "assembler",
            "federated", "resilience"} <= track_names
    # instants keep their args (the chaos smoke greps rounds out of these)
    inst = [e for e in evs if e["ph"] == "i"]
    assert inst and inst[0]["args"]["round"] == 1


def test_jsonl_event_sink_schema_and_whole_lines(tmp_path):
    path = tmp_path / "events.jsonl"
    obtrace.configure(jsonl_path=str(path))
    with obtrace.span("runner", "drain", rounds=2):
        pass
    obtrace.instant("federated", "requeue_serve", round=3, clients=[1])
    obtrace.configure()  # closes the sink
    lines = path.read_text().splitlines()
    assert len(lines) == 2
    for line in lines:
        ev = json.loads(line)
        assert ev["schema"] == obtrace.EVENT_SCHEMA_VERSION
        assert ev["track"] in ("runner", "federated")
        assert "ts" in ev and "name" in ev


def test_jsonl_stream_outlives_buffer_cap(tmp_path, capsys):
    """The bounded in-memory buffer caps the Chrome trace, not the on-disk
    JSONL stream: past max_events the stream keeps writing and the first
    drop is announced loudly (a --trace_events-only run never reaches
    flush()'s dropped-events note)."""
    path = tmp_path / "ev.jsonl"
    t = obtrace.Tracer(max_events=2)
    t.configure(trace_path=str(tmp_path / "t.json"), jsonl_path=str(path))
    for i in range(5):
        t.instant("runner", f"e{i}")
    assert t.event_count() == 2 and t.dropped_events == 3
    assert len(path.read_text().splitlines()) == 5
    assert "trace buffer full" in capsys.readouterr().err


def test_tracer_disabled_is_noop_and_bounded(tmp_path):
    t = obtrace.Tracer(max_events=3)
    with t.span("runner", "x"):
        pass
    t.instant("runner", "y")
    assert t.event_count() == 0  # disarmed: nothing buffered
    t.configure(trace_path=str(tmp_path / "t.json"))
    for i in range(10):
        t.instant("runner", f"e{i}")
    assert t.event_count() == 3  # bounded buffer
    assert t.dropped_events == 7
    doc = json.load(open(t.flush()))
    assert doc["otherData"]["dropped_events"] == 7


# ------------------------------------------- serve: linked merge spans


def test_serve_trace_links_submissions_and_shows_round_phases(tmp_path):
    """4-round served run through the REAL runner (sync loop => every
    round drains): the trace must show prepare/dispatch/drain/commit per
    round, submission->merge spans linked to their admission instants by
    the r<rnd>/c<cid> id, and the /metrics snapshot must surface the
    latency_ms / round_phase_ms histograms — the PR's acceptance shape."""
    obtrace.configure(trace_path=str(tmp_path / "serve.json"))
    sess = _tiny_session()
    svc = AggregationService(
        sess, ServeConfig(quorum=2, deadline_s=5.0),
        traffic=TrafficGenerator(
            TraceConfig(population=sess.train_set.num_clients, seed=5)),
    ).start()
    lat_before = svc._latency.count
    try:
        run_loop(sess, FedOptimizer(lambda _: LR, 1),
                 RunnerConfig(total_rounds=4, eval_every=4, sync_loop=True),
                 source=svc.source())
        assert sess.round == 4
        snap = svc.metrics_snapshot()
    finally:
        svc.close()
    evs = obtrace.get().events()
    spans = [e for e in evs if e["ph"] == "X"]
    inst = [e for e in evs if e["ph"] == "i"]
    sub_spans = [s for s in spans if s["name"].startswith("submission r")]
    for r in range(4):
        assert any(s["name"] == "prepare" and s["args"].get("round") == r
                   for s in spans), f"round {r}: no prepare span"
        assert any(s["name"] == "dispatch" and s["args"].get("round") == r
                   for s in spans), f"round {r}: no dispatch span"
        for phase in ("drain", "commit"):
            assert any(
                s["name"] == phase
                and s["args"]["round_first"] <= r
                < s["args"]["round_first"] + s["args"]["rounds"]
                for s in spans), f"round {r}: no {phase} span"
        assert any(i_["name"] == "commit_round"
                   and i_["args"]["round"] == r for i_ in inst)
        assert any(s["args"]["round"] == r for s in sub_spans), (
            f"round {r}: no submission->merge span")
    # linked: every merge span's submission id appeared as an ACCEPT
    accept_ids = {i_["args"]["submission"] for i_ in inst
                  if i_["name"] == "submit:ACCEPTED"}
    merge_ids = {s["args"]["submission"] for s in sub_spans}
    assert merge_ids and merge_ids <= accept_ids
    assert all(s["dur"] >= 0 for s in sub_spans)
    # the registry histogram counted exactly the merged submissions
    assert svc._latency.count - lat_before == len(sub_spans)
    # /metrics reads the same registry
    assert snap["latency_ms"]["count"] >= len(sub_spans)
    assert snap["latency_ms"]["p50"] is not None
    for phase in ("prepare", "dispatch", "drain", "commit"):
        assert snap["round_phase_ms"][phase]["p50"] is not None, phase


def test_fresh_service_does_not_claim_predecessor_merges():
    """The latency histogram is process-wide (single-source contract), but
    a NEW service's /metrics must report ITS merges, not a predecessor's:
    the count is baselined at construction."""
    first = _tiny_session()
    svc1 = AggregationService(
        first, ServeConfig(quorum=2, deadline_s=5.0),
        traffic=TrafficGenerator(
            TraceConfig(population=first.train_set.num_clients, seed=5)),
    ).start()
    try:
        src = svc1.source()
        first.commit_round(first.dispatch_round(src.next(), LR))
        src.on_committed(first.round)
        assert svc1.metrics_snapshot()["latency_ms"]["count"] >= 2
    finally:
        svc1.close()
    second = _tiny_session()
    svc2 = AggregationService(
        second, ServeConfig(quorum=2, deadline_s=5.0),
        traffic=TrafficGenerator(
            TraceConfig(population=second.train_set.num_clients, seed=5)),
    ).start()
    try:
        assert svc2.metrics_snapshot()["latency_ms"]["count"] == 0
    finally:
        svc2.close()


def test_instant_signal_safe_skips_jsonl_sink(tmp_path):
    """The SIGTERM handler's instant must land in the in-memory buffer but
    never the JSONL handle (the handler may have interrupted a write on
    that very handle — an interleaved write would tear a line)."""
    path = tmp_path / "ev.jsonl"
    t = obtrace.Tracer()
    t.configure(jsonl_path=str(path))
    t.instant("resilience", "normal")
    t.instant_signal_safe("resilience", "sigterm")
    assert t.event_count() == 2
    lines = [json.loads(line) for line in path.read_text().splitlines()]
    assert [ev["name"] for ev in lines] == ["normal"]


def test_served_source_on_committed_resolves_latencies():
    """Direct-driver path (bench's shape): record_merges resolves only
    COMMITTED rounds, and served-but-uncommitted rounds drop out on
    stop()."""
    sess = _tiny_session()
    svc = AggregationService(
        sess, ServeConfig(quorum=2, deadline_s=5.0),
        traffic=TrafficGenerator(
            TraceConfig(population=sess.train_set.num_clients, seed=5)),
    ).start()
    before = svc._latency.count
    try:
        src = svc.source()
        prep = src.next()
        assert svc.record_merges() == 0  # nothing committed yet
        sess.commit_round(sess.dispatch_round(prep, LR))
        src.on_committed(sess.round)
        n = svc._latency.count - before
        assert n >= 2  # at least the quorum's submissions merged
        src.next()  # served, never dispatched/committed
        src.stop()
        assert svc.record_merges() == 0  # uncommitted round was discarded
    finally:
        svc.close()


# --------------------------------------------------- registry contracts


def test_registry_kinds_marks_and_percentiles():
    reg = obreg.Registry()
    c = reg.counter("c")
    c.inc()
    c.inc(2)
    assert c.value == 3
    m = reg.mark()
    c.inc(5)
    assert m.delta("c") == 5
    assert m.delta("never_seen") == 0  # born after the mark: full value
    g = reg.gauge("g")
    g.set(2)
    g.set(1)
    assert g.value == 1 and g.max == 2
    h = reg.histogram("h")
    for i in range(100):
        h.observe(i)
    assert h.count == 100
    assert h.percentile(50) == 50
    s = h.summary()
    assert s["p50"] == 50 and s["p99"] == 99 and s["count"] == 100
    assert reg.histogram("h") is h  # get-or-create
    with pytest.raises(TypeError, match="one name, one kind"):
        reg.gauge("c")
    mt = reg.meter("m", window_s=10.0)
    mt.record(5)
    assert mt.rate() == 0.5
    snap = reg.snapshot()
    assert snap["c"] == 8.0 and snap["h"]["p50"] == 50


def test_histogram_window_bounds_memory():
    h = obreg.Histogram("h", window=64)
    for i in range(1000):
        h.observe(float(i))
    assert h.count == 1000  # cumulative count survives the window
    assert h.percentile(0) >= 936  # percentiles over the recent window


def test_registry_thread_safe_under_ingest_path():
    """8 transport threads hammering submit() with the accept hook wired
    to registry metrics (the live serve shape): every accept must count
    exactly once everywhere."""
    reg = obreg.Registry()
    accepted = reg.counter("accepted")
    rate = reg.meter("rate")
    lat = reg.histogram("lat")

    def hook(n):
        accepted.inc(n)
        rate.record(n)
        lat.observe(0.5)

    n_threads, per_thread = 8, 500
    q = IngestQueue(capacity=n_threads * per_thread + 1)
    q.on_accept = hook
    q.open_round(0, list(range(n_threads * per_thread)))

    def worker(k):
        for cid in range(k * per_thread, (k + 1) * per_thread):
            q.submit(Submission(client_id=cid, round=0))

    threads = [threading.Thread(target=worker, args=(k,))
               for k in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    total = n_threads * per_thread
    assert q.accepted == total
    assert int(accepted.value) == total
    assert lat.count == total


def test_runstats_is_a_registry_delta_view():
    """run_loop fills RunStats from registry mark deltas — the registry
    counters must advance by exactly what the stats report."""
    reg = obreg.default()
    before_rounds = reg.counter("runner_rounds_total").value
    before_drains = reg.counter("runner_drains_total").value
    s = _tiny_session()
    stats = run_loop(s, FedOptimizer(lambda _: LR, 1),
                     RunnerConfig(total_rounds=3, eval_every=3))
    assert stats.rounds == 3
    assert reg.counter("runner_rounds_total").value - before_rounds == 3
    assert (reg.counter("runner_drains_total").value - before_drains
            == stats.drains >= 1)
    assert stats.evals == 1
    # the phase histograms populated (the serve endpoint reads these)
    for phase in ("prepare", "dispatch", "drain", "commit"):
        assert reg.histogram(f"runner_phase_{phase}_ms").count > 0, phase


# ------------------------------------------------------- profiler window


def test_profile_rounds_spec_validation():
    assert parse_rounds_spec("") is None
    assert parse_rounds_spec("2:5") == (2, 5)
    for bad in ("5", "a:b", "3:1", "-1:2"):
        with pytest.raises(ValueError):
            parse_rounds_spec(bad)
    with pytest.raises(ValueError, match="profile_dir"):
        ProfileWindow(0, 1, "")


def test_profile_window_start_stop_at_round_boundaries(tmp_path, monkeypatch):
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d, **kw: calls.append(("start", d)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop",)))
    pw = ProfileWindow.parse("1:2", str(tmp_path))
    pw.on_dispatch(0)
    assert calls == []  # before the window
    pw.on_dispatch(1)
    assert calls == [("start", str(tmp_path))]
    pw.on_committed(2)  # round 1 committed; round 2 (END) still open
    assert len(calls) == 1
    pw.on_committed(3)  # round 2 committed -> stop
    assert calls[-1] == ("stop",)
    pw.on_dispatch(1)  # window is one-shot
    assert len(calls) == 2


def test_profile_window_block_overlap_and_resume_past(tmp_path, monkeypatch,
                                                      capsys):
    """A fused dispatch block OVERLAPPING the window starts the capture (a
    block cannot be split, so the capture is a round-aligned superset);
    a run that begins PAST the window declares it dead loudly instead of
    silently arming at the wrong rounds."""
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d, **kw: calls.append("start"))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append("stop"))
    pw = ProfileWindow.parse("5:6", str(tmp_path))
    pw.on_dispatch(0, rounds=4)  # block [0..3]: ends before the window
    assert calls == []
    pw.on_dispatch(4, rounds=4)  # block [4..7] contains round 5 -> start
    assert calls == ["start"]
    pw.on_committed(8)
    assert calls == ["start", "stop"]

    pw2 = ProfileWindow.parse("5:6", str(tmp_path))
    pw2.on_dispatch(8)  # resumed run already past the window
    assert calls == ["start", "stop"]  # no capture armed
    assert "behind the run" in capsys.readouterr().err
    pw2.on_dispatch(5)  # declared dead: stays dead
    assert calls == ["start", "stop"]


def test_profile_window_degrades_to_loud_noop(tmp_path, monkeypatch, capsys):
    def boom(d, **kw):
        raise RuntimeError("no profiler on this backend")

    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    pw = ProfileWindow.parse("0:1", str(tmp_path))
    pw.on_dispatch(0)  # must not raise
    err = capsys.readouterr().err
    assert "degrades to a no-op" in err
    pw.on_committed(5)
    pw.close()  # nothing active: both no-ops


# ------------------------------------ the round's own clock (ready stamps)


def _loop_cfg(total, **kw):
    return RunnerConfig(total_rounds=total, eval_every=1 << 30,
                        prefetch_depth=2, **kw)


def _run_logged(session, cfg):
    """run_loop with a row sink: (stats, rows less time_s)."""
    rows = []
    stats = run_loop(session, FedOptimizer(lambda _: LR, 1), cfg,
                     build_row=lambda **kw: kw, logger=rows)
    for r in rows:
        r.pop("time_s")
    return stats, rows


STAMP_HISTS = ("runner_round_interval_chained_ms",
               "runner_round_interval_first_ms", "runner_bubble_host_ms")


@pytest.mark.parametrize("depth,drains,kept", [
    (3, (2, 1), (1, 0)), (2, (3, 2), (2, 1)), (1, (4, 3), (0, 0))])
def test_ready_stamps_count_against_drains_and_rounds(depth, drains, kept):
    """The drain stamps each dispatch it reads as its metrics come back: a
    dispatch behind its predecessor in the drain observes `chained`, the
    first of a drain observes `first` and the first dispatch after a drain
    `bubble_host`, except in the first drain of a call, which has no stamp
    before it (none is carried across run_loop calls). A drain that the
    depth triggers leaves the newest dispatch for the next drain to read
    (depth 3: rounds 0-2 dispatched, 0 and 1 read, 2 read with 3 at the
    end of the call; depth 2 reads one at a time, so nothing is chained
    until the call's last, full drain), and counts in
    runner_drains_kept_total. Reading the metrics one dispatch at a time
    changes no result: state and rows equal the sync loop's."""
    reg = obreg.default()
    ref = _tiny_session()
    rows_ref = (_run_logged(ref, _loop_cfg(4, sync_loop=True))[1]
                + _run_logged(ref, _loop_cfg(7, sync_loop=True))[1])

    before = {n: reg.histogram(n).count for n in STAMP_HISTS}
    kept_before = reg.counter("runner_drains_kept_total").value
    s = _tiny_session()
    seg1, rows1 = _run_logged(s, _loop_cfg(4, max_inflight=depth))
    seg2, rows2 = _run_logged(s, _loop_cfg(7, max_inflight=depth))
    got = {n: reg.histogram(n).count - before[n] for n in STAMP_HISTS}

    assert (seg1.drains, seg2.drains) == drains
    assert (seg1.drains_kept, seg2.drains_kept) == kept
    assert (reg.counter("runner_drains_kept_total").value - kept_before
            == sum(kept))
    rounds, n_drains = 7, sum(drains)
    assert got["runner_round_interval_chained_ms"] == rounds - n_drains
    assert got["runner_round_interval_first_ms"] == n_drains - 2
    assert got["runner_bubble_host_ms"] == n_drains - 2
    for n in STAMP_HISTS:
        assert reg.histogram(n).count == 0 or reg.histogram(n).percentile(0) >= 0
    _assert_params_equal(ref, s)
    assert rows1 + rows2 == rows_ref and len(rows_ref) == 2


def test_bubble_host_is_zero_while_the_kept_round_runs(monkeypatch):
    """After a kept drain the host's part of the bubble is 0 unless the
    kept round is seen finished before the next dispatch call is made; then
    it runs from that look to the call's return. After a full drain it runs
    from the drain's last ready stamp, as it always did."""
    import commefficient_tpu.runner.loop as loop_mod

    hist = obreg.default().histogram("runner_bubble_host_ms")

    def observed(ready):
        monkeypatch.setattr(loop_mod, "_finished", lambda infl: ready)
        n0, sum0 = hist.count, hist.sum
        stats = run_loop(_tiny_session(), FedOptimizer(lambda _: LR, 1),
                         _loop_cfg(9, max_inflight=3))
        assert (stats.drains, stats.drains_kept) == (4, 3)
        assert hist.count - n0 == 3  # one after each kept drain
        return hist.sum - sum0

    assert observed(ready=False) == 0.0
    assert observed(ready=True) > 0.0


def test_device_track_spans_neither_overlap_nor_end_together(tmp_path):
    """The deferred device spans run from max(dispatch mark, previous ready
    stamp) to their own ready stamp: two rounds in flight read as two
    spans in a row, not two that end together at the drain."""
    obtrace.configure(trace_path=str(tmp_path / "t.json"))
    run_loop(_tiny_session(), FedOptimizer(lambda _: LR, 1),
             _loop_cfg(6, max_inflight=2))
    spans = sorted((e for e in obtrace.get().events()
                    if e["cat"] == "device"), key=lambda e: e["ts"])
    assert [e["args"]["round_first"] for e in spans] == list(range(6))
    assert all(e["args"]["sketch_path"] == "ravel" for e in spans)
    ends = [e["ts"] + e["dur"] for e in spans]
    assert len(set(ends)) == len(ends)
    for a_end, b in zip(ends, spans[1:]):
        assert a_end <= b["ts"] + 2e-3  # ts and dur are rounded to the ns


class _RecordingAnnotation:
    made: list = []
    log: list = []  # ("enter" | "exit", name) as the loop's thread did them

    def __init__(self, name, **kw):
        self.name = name
        self.made.append((name, kw))

    def __enter__(self):
        if threading.current_thread() is threading.main_thread():
            self.log.append(("enter", self.name))  # not the prefetch thread's
        return self

    def __exit__(self, *exc):
        if threading.current_thread() is threading.main_thread():
            self.log.append(("exit", self.name))
        return False


def test_loop_spans_are_mirrored_inside_a_profile_window(tmp_path,
                                                         monkeypatch):
    """While a ProfileWindow capture runs, every tracer span also enters a
    jax.profiler.TraceAnnotation("<track>/<name>", **args), with --trace
    off, and every instant enters one and leaves it at once; outside a
    capture nothing is constructed. The drain marks each dispatch it reads
    (`runner/ready`, the ready stamp itself), and the session's jit call is
    a span of its own inside the loop's dispatch."""
    made = _RecordingAnnotation.made = []
    log = _RecordingAnnotation.log = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _RecordingAnnotation)
    monkeypatch.setattr(jax.profiler, "start_trace", lambda d, **kw: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    opt = FedOptimizer(lambda _: LR, 1)

    run_loop(_tiny_session(), opt, _loop_cfg(4, max_inflight=2))
    assert made == [] and not obtrace.get().enabled

    run_loop(_tiny_session(), opt, _loop_cfg(
        6, max_inflight=2, profile_rounds="2:3", profile_dir=str(tmp_path)))
    assert obtrace.get().event_count() == 0  # the buffer stayed disarmed
    loop = [(n, kw) for n, kw in made if n.startswith("runner/")]
    # depth 2 reads one dispatch a drain and keeps the newest queued: the
    # capture opens at round 2's dispatch (round 1 still in flight) and
    # closes at the drain that commits round 3, after round 4's dispatch
    assert [(n, kw["round"]) for n, kw in loop if "round" in kw] == [
        ("runner/prepare", 2), ("runner/dispatch", 2),
        ("runner/commit_round", 1),
        ("runner/prepare", 3), ("runner/dispatch", 3),
        ("runner/commit_round", 2),
        ("runner/prepare", 4), ("runner/dispatch", 4),
        ("runner/commit_round", 3)]
    # one ready mark a dispatch read, inside the drain that read it
    assert [(n, kw["round_first"], kw["rounds"]) for n, kw in loop
            if "round_first" in kw] == [
        (f"runner/{what}", rnd, 1) for rnd in (1, 2, 3)
        for what in ("drain", "ready", "commit")]
    i = log.index(("enter", "runner/ready"))
    assert log[i - 1:i + 3] == [
        ("enter", "runner/drain"), ("enter", "runner/ready"),
        ("exit", "runner/ready"), ("exit", "runner/drain")]
    # the jit call alone, inside the loop's dispatch span, with its round
    assert [kw for n, kw in made if n == "session/launch"] == [
        {"round": 2}, {"round": 3}, {"round": 4}]
    i = log.index(("enter", "session/launch"))
    assert log[i - 1:i + 3] == [
        ("enter", "runner/dispatch"), ("enter", "session/launch"),
        ("exit", "session/launch"), ("exit", "runner/dispatch")]
    assert {n for n, _ in made} - {n for n, _ in loop} <= {
        "federated/prepare_round", "session/launch"}

    made.clear()  # the window closed: the mirror is off again
    with obtrace.span("runner", "prepare", round=9):
        pass
    obtrace.instant("runner", "ready", round_first=9, rounds=1)
    assert made == []


def test_block_dispatch_launch_span_names_its_rounds(tmp_path, monkeypatch):
    """A fused block's jit call is one `session/launch` with the block's
    first round and its size, and the drain reads it back as one ready
    mark of as many rounds."""
    made = _RecordingAnnotation.made = []
    _RecordingAnnotation.log = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _RecordingAnnotation)
    monkeypatch.setattr(jax.profiler, "start_trace", lambda d, **kw: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    run_loop(_tiny_session(), FedOptimizer(lambda _: LR, 1), _loop_cfg(
        8, max_inflight=4, rounds_per_dispatch=2, profile_rounds="2:5",
        profile_dir=str(tmp_path)))
    launches = [kw for n, kw in made if n == "session/launch"]
    assert launches and all(kw["rounds"] == 2 for kw in launches)
    assert [kw["round_first"] for kw in launches] == list(
        range(launches[0]["round_first"], launches[0]["round_first"]
              + 2 * len(launches), 2))
    readies = [kw for n, kw in made if n == "runner/ready"]
    assert readies and all(kw["rounds"] == 2 for kw in readies)
    assert {kw["round_first"] for kw in readies} <= {
        kw["round_first"] for kw in launches} | {0}


# ------------------------------------------- named phases, capture summary


def _lowered_scopes(mode, path):
    import re

    from commefficient_tpu.federated import engine

    params = {"w": jnp.ones((6, 3)), "b": jnp.zeros(3)}
    d = ravel_pytree(params)[0].size
    kw = {"sketch": dict(num_rows=3, num_cols=16, k=4),
          "true_topk": dict(k=4)}.get(mode, {})
    cfg = engine.EngineConfig(
        mode=ModeConfig(mode=mode, d=d, momentum=0.9,
                        momentum_type="virtual",
                        error_type="none" if mode == "uncompressed"
                        else "virtual", **kw),
        sketch_path=path)
    batch = {"x": jnp.ones((4, 2, 6)), "y": jnp.zeros((4, 2), jnp.int32),
             "mask": jnp.ones((4, 2))}
    text = jax.jit(engine.make_round_step(_quad_loss, cfg)).lower(
        engine.init_server_state(cfg, params, {}), batch, {},
        jnp.float32(LR), jax.random.PRNGKey(0)).as_text(debug_info=True)
    words = set()
    for loc in re.findall(r'loc\("([^"]*)"', text):
        words.update(re.findall(r"[A-Za-z_][A-Za-z0-9_]*", loc))
    return words & set(engine.ROUND_PHASES)


@pytest.mark.parametrize("mode,path,lacks", [
    ("sketch", "ravel", ()),
    ("sketch", "layerwise", ()),
    ("true_topk", "ravel", ("server_query",)),
    ("uncompressed", "ravel", ("server_query", "server_topk")),
])
def test_lowered_round_step_names_its_phases(mode, path, lacks):
    from commefficient_tpu.federated.engine import ROUND_PHASES

    assert _lowered_scopes(mode, path) == set(ROUND_PHASES) - set(lacks)


@pytest.mark.parametrize("extra,fused", [
    ((), True),
    (("--client_chunk", "1"), True),
    (("--client_update_clip", "4"), False),
    (("--dp_clip", "1.0"), False),
    (("--mode", "local_topk", "--k", "8", "--error_type", "none"), False),
], ids=["plain", "chunked", "quarantine", "dp_clip", "local_topk"])
def test_start_up_line_and_gauge_say_which_cohort_backward(tiny_cv, capsys,
                                                           extra, fused):
    """The choice between one backward pass for the cohort and one a client
    is made when the session builds its round program: the trainer's
    start-up line and the gauge `engine_cohort_backward_fused` say which."""
    from commefficient_tpu.utils.config import make_parser, resolve_defaults

    args = resolve_defaults(make_parser("cv").parse_args(_argv(extra)))
    session, _ = cv_train.build(args)
    word = "fused" if fused else "per-client"
    assert session.cohort_backward == word
    assert f"cohort backward: {word}\n" in capsys.readouterr().out
    assert obreg.default().gauge("engine_cohort_backward_fused").value == int(fused)


_APPROX_SKETCH = ("--mode", "sketch", "--k", "100", "--num_cols", "2048",
                  "--num_rows", "3", "--topk_impl", "approx",
                  "--topk_recall", "0.99")


@pytest.mark.parametrize("extra,min_n,selects", [
    ((), None, False),
    (_APPROX_SKETCH[:-4], None, False),
    (_APPROX_SKETCH, None, False),
    (_APPROX_SKETCH, 1000, True),
], ids=["no_topk", "exact", "approx_too_few_maxima", "approx_constant_down"])
def test_start_up_line_and_gauge_say_how_many_partial_maxima(
        tiny_cv, capsys, monkeypatch, extra, min_n, selects):
    """Whether the approximate top-k picks its k from the partial maxima by
    selection is decided from static shapes when the session builds its
    round program: the trainer's start-up line and the gauge
    `sketch_topk_partial_maxima` say how many there are, or 0."""
    from commefficient_tpu.sketch import csvec
    from commefficient_tpu.utils.config import make_parser, resolve_defaults

    if min_n is not None:
        monkeypatch.setattr(csvec, "TOPK_SELECT_MIN_N", min_n)
    args = resolve_defaults(make_parser("cv").parse_args(_argv(extra)))
    session, _ = cv_train.build(args)
    d, k = session.cfg.mode.d, session.cfg.mode.k
    want = csvec.approx_select_size(d, k, 0.99) if selects else 0
    assert want == 0 or 7 * k <= want < d
    assert (want > 0) == selects
    assert session.topk_partial_maxima == want
    assert (f"approx top-k partial maxima: {want}\n"
            in capsys.readouterr().out)
    assert obreg.default().gauge("sketch_topk_partial_maxima").value == want


def test_capture_summary_by_phase():
    """Hand-built device planes in the shape load_device_planes gives:
    nesting counts once (a while's body goes to its own phases, the rest of
    the while to the while's), an unknown scope goes to `other`, the phases
    sum to the busy union, and the rounds are the main module's runs."""
    from commefficient_tpu.obs import profiler

    US = 1000
    phases = ("client_grad", "cohort_reduce", "server_topk")
    ops = [
        ("%fusion.1", 0, 40 * US, "jit(step)/client_grad/vmap(jvp())/dot"),
        ("%concatenate", 40 * US, 10 * US,
         "jit(step)/client_grad/vmap(cohort_reduce)/concatenate"),
        ("%while", 60 * US, 30 * US, "jit(step)/server_algebra/while"),
        ("%sort", 65 * US, 20 * US,
         "jit(step)/server_algebra/while/body/server_topk/sort"),
        ("%copy", 70 * US, 5 * US, ""),  # inside the sort: the sort's phase
        ("%threefry", 100 * US, 10 * US, "jit(_threefry_split)/shift"),
        ("%not_a_phase", 110 * US, 10 * US, "jit(step)/my_client_grad_x/mul"),
    ]
    planes = [
        ("/device:TPU:0", [
            ("XLA Modules", [("jit_step(1)", 0, 90 * US, ""),
                             ("jit_step(1)", 100 * US, 1, ""),
                             ("jit__threefry_split(2)", 100 * US, 10 * US, "")]),
            ("XLA Ops", ops)])]
    got = profiler.summarize(planes, phases)
    assert got["traced_rounds"] == 2 and got["round_program"] == "jit_step(1)"
    ms = got["phase_device_ms"]
    assert ms["client_grad"] == pytest.approx(0.040 / 2)
    assert ms["cohort_reduce"] == pytest.approx(0.010 / 2)
    assert ms["server_topk"] == pytest.approx(0.020 / 2)
    assert ms["other"] == pytest.approx((0.010 + 0.010 + 0.010) / 2)
    assert sum(ms.values()) == pytest.approx(got["device_busy_ms"])
    assert got["device_busy_ms"] == pytest.approx(0.100 / 2)

    reg = obreg.Registry()
    profiler.publish(got, reg)
    shown = reg.snapshot()
    assert shown["profile_traced_rounds"]["value"] == 2
    assert shown["profile_phase_device_ms_server_topk"]["value"] == ms["server_topk"]
    assert shown["profile_device_busy_ms"]["value"] == got["device_busy_ms"]
    line = profiler.format_summary(got, phases)
    assert line.startswith("device ms/round: client_grad 0.0 | cohort_reduce")
    assert "other" in line and "2 rounds" in line

    with pytest.raises(ValueError):  # a capture with no device operations
        profiler.summarize([("/device:TPU:0", [("XLA Ops", [])])], phases)


def test_load_device_planes_reads_the_scope_stat_of_the_metadata(tmp_path):
    """The op_name stat sits on the event's metadata, which ProfileData does
    not show: an .xplane.pb built with the protobuf's own classes, with the
    scope once as a string and once as a reference to a stat metadata, an
    operation with no scope, a line that is not read and a host plane that
    must be ignored."""
    from commefficient_tpu.obs import profiler

    space = profiler._xplane_pb2().XSpace()
    host = space.planes.add(name="/host:CPU")
    host.stat_metadata[1].name = "tf_op"
    host.event_metadata[7].name = "host event"
    host.event_metadata[7].stats.add(metadata_id=1, str_value="jit(f)/apply/x")
    host.lines.add(name="XLA Ops").events.add(metadata_id=7, duration_ps=5)

    dev = space.planes.add(id=3, name="/device:TPU:0")
    for i, name in ((300, "tf_op"), (2, "flops"),
                    (9, "jit(step)/apply/scatter-add:")):
        dev.stat_metadata[i].name = name
    sort = dev.event_metadata[1]
    sort.name = "%sort = f32[8] sort(...)"
    sort.stats.add(metadata_id=2, double_value=3.5)
    sort.stats.add(metadata_id=300, str_value="jit(step)/server_topk/sort:")
    scatter = dev.event_metadata[2]
    scatter.name = "%scatter = f32[8] scatter(...)"
    scatter.stats.add(metadata_id=300, ref_value=9)
    dev.event_metadata[3].name = "%copy-start = ..."
    dev.event_metadata[3].stats.add(metadata_id=2, uint64_value=1 << 40)
    dev.event_metadata[4].name = "jit_step(1)"
    ops = dev.lines.add(name="XLA Ops", timestamp_ns=1000)
    ops.events.add(metadata_id=1, offset_ps=0, duration_ps=20_000_000)
    ops.events.add(metadata_id=2, offset_ps=20_000_000, duration_ps=5_000_000)
    ops.events.add(metadata_id=3, offset_ps=25_000_000, duration_ps=5_000_000)
    dev.lines.add(name="XLA Modules", timestamp_ns=1000).events.add(
        metadata_id=4, offset_ps=0, duration_ps=30_000_000)
    dev.lines.add(name="Scalar Unit").events.add(metadata_id=3, duration_ps=1)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())

    planes = profiler.load_device_planes(str(path))
    assert planes == [("/device:TPU:0", [
        ("XLA Ops", [
            ("%sort = f32[8] sort(...)", 1000.0, 20000.0,
             "jit(step)/server_topk/sort:"),
            ("%scatter = f32[8] scatter(...)", 21000.0, 5000.0,
             "jit(step)/apply/scatter-add:"),
            ("%copy-start = ...", 26000.0, 5000.0, "")]),
        ("XLA Modules", [("jit_step(1)", 1000.0, 30000.0, "")])])]
    ms = profiler.summarize(planes, ("server_topk", "apply"))["phase_device_ms"]
    assert ms == pytest.approx(
        {"server_topk": 0.020, "apply": 0.005, "other": 0.005})


def test_profile_window_never_shows_an_earlier_captures_summary(
        tmp_path, monkeypatch, capsys):
    """profile_traced_rounds is zeroed when a capture starts, so where its
    summary fails (here: no capture file; and a capture whose operations
    name no phase) the readers see no reading, not the last capture's."""
    from commefficient_tpu.obs import profiler

    monkeypatch.setattr(jax.profiler, "start_trace", lambda d, **kw: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    rounds = obreg.default().gauge("profile_traced_rounds")
    rounds.set(12)  # what an earlier capture of this process published
    pw = ProfileWindow.parse("0:1", str(tmp_path), phases=("apply",))
    pw.on_dispatch(0)
    assert rounds.value == 0
    pw.on_committed(2)
    assert "no summary of the capture (FileNotFoundError" in capsys.readouterr().err
    assert rounds.value == 0

    unscoped = [("/device:TPU:0", [
        ("XLA Modules", [("jit_step(1)", 0, 10, "")]),
        ("XLA Ops", [("%fusion", 0, 10, "")])])]
    with pytest.raises(ValueError, match="names a phase"):
        profiler.summarize(unscoped, ("apply",))


# ------------------------------------ the launch summary (one clock)

MS = 1e6  # the planes are in ns


def _launch_capture(rounds, step, device_step, *, head=0.1, busy=30.0,
                    tail=0.2, lag=0.5, late=(), undispatched=(), shift=None,
                    first_run_id=None):
    """Hand-built planes, host events and run ids of `rounds` executions of
    one round program. On the device line execution i starts at i *
    device_step (plus `shift[round]` for it and every later one) and runs
    head + busy + tail; the host stamps it ready `lag` after its module's
    end on a clock on which executions are `step` apart. The dispatch of a
    round returns 5 ms into its predecessor, or 1 ms after its predecessor's
    last operation for the rounds in `late`; rounds in `undispatched` have
    no dispatch span. With `first_run_id` the module events carry the
    runtime's ids, four apart, each dispatched round's launch holds the
    runtime's enqueue of its id (behind that of a small program's), and the
    host completes each execution 0.3 ms after its module's end."""
    modules, ops, host, run_ids = [], [], [], {}
    moved, last_op = 0.0, None
    for i, rnd in enumerate(rounds):
        moved += (shift or {}).get(rnd, 0.0)
        start = i * device_step + moved
        first = start + head
        modules.append(("jit_step(1)", start * MS, (head + busy + tail) * MS, ""))
        ops += [("%fusion.1", first * MS, busy / 2 * MS, ""),
                ("%fusion.2", (first + busy / 2) * MS, busy / 2 * MS, ""),
                ("%inner", (first + 1.0) * MS, 2.0 * MS, "")]  # nested: once
        ready = i * step + moved + head + busy + tail + lag
        host.append(("runner/ready", ready * MS, 0.001 * MS,
                     {"round_first": rnd, "rounds": 1}))
        if first_run_id is not None:
            run_ids[start * MS] = first_run_id + 4 * i
            # the three small programs behind it are done by then too
            host.append(("CompleteCallbacks", (ready - lag + 0.3) * MS,
                         0.2 * MS, {"run_id": first_run_id + 4 * i + 3}))
        if i and rnd not in undispatched:
            end = last_op + 1.0 if rnd in late else start - device_step + 5.0
            host.append(("runner/dispatch", (end - 3.0) * MS, 3.0 * MS,
                         {"round": rnd}))
            host.append(("session/launch", (end - 2.0) * MS, 1.5 * MS,
                         {"round": rnd}))
            if first_run_id is not None:
                host += [("DoEnqueueProgram", (end - 1.9) * MS, 0.03 * MS,
                          {"run_id": first_run_id + 4 * i - 1}),
                         ("DoEnqueueProgram", (end - 1.0) * MS, 0.03 * MS,
                          {"run_id": first_run_id + 4 * i})]
        last_op = first + busy
    planes = [("/device:TPU:0", [("XLA Modules", modules), ("XLA Ops", ops)])]
    return planes, host, run_ids


def test_launch_summary_pairs_executions_with_ready_marks():
    """Six executions 34 ms apart on both clocks, 30 ms busy each: the 4 ms
    a round are tail + between + head and no drift. The pair whose second
    dispatch came late does not count (it alone is 10 ms further apart), nor
    the one whose second dispatch is outside the capture; what the edges cut
    is counted: operations before the first module event, a small program's
    module beside the round program, the kept round's execution that no
    ready mark follows. Every hole between two executions is put down to
    the runtime's events in it, threads and nesting counted once."""
    from commefficient_tpu.obs import profiler

    planes, host, _ = _launch_capture(
        range(10, 16), 34.0, 34.0, late={13}, undispatched={11},
        shift={13: 10.0})
    mods, ops = planes[0][1][0][1], planes[0][1][1][1]
    ops += [("%fusion.2", -20.0 * MS, 15.0 * MS, ""),  # cut at the start
            ("%shift", 31.0 * MS, 0.1 * MS, "")]  # another program's
    mods += [("jit__threefry_split(2)", 31.0 * MS, 0.1 * MS, ""),
             ("jit_step(1)", 6 * 34.0 * MS + 10.0 * MS, 30.3 * MS, "")]
    ops.append(("%fusion.1", (6 * 34.0 + 10.1) * MS, 30.0 * MS, ""))
    host += [("PjitFunction(step)", 60.0 * MS, 2.0 * MS, {}),
             ("Linearize", 64.5 * MS, 3.0 * MS, {}),  # in (11, 12)'s hole
             ("Transpose", 65.0 * MS, 1.0 * MS, {}),  # inside it
             ("Transpose", 65.5 * MS, 1.5 * MS, {}),  # on another thread
             ("federated/prepare_round", 64.0 * MS, 3.9 * MS, {"round": 14})]
    got = profiler.summarize_launches(planes, host)
    assert got["round_program"] == "jit_step(1)" and got["by"] == "order"
    assert (got["executions"], got["paired"]) == (7, 6)
    assert (got["pairs"], got["starved"], got["unknown"]) == (3, 1, 1)
    assert got["dropped"] == {
        "ops_before_first_module": 1, "ops_after_last_module": 0,
        "modules_before_first_ready": 0, "modules_after_last_ready": 1}
    assert not got["first_cut"]
    want = {"gap": 4.0, "between": 3.7, "head": 0.1, "tail": 0.2,
            "inside": 0.0, "drift": 0.0, "ready_lag": 0.5, "call": 1.5,
            "busy": 30.0}
    for part, ms in want.items():
        assert got[f"{part}_ms"] == pytest.approx(ms, abs=1e-6), part
    # the hole between round 11's last operation (64.1) and round 12's
    # first (68.1) is one of five: the runtime's events, not the program's
    assert got["between_hosts"] == [("Linearize", pytest.approx(3.0 / 5)),
                                    ("Transpose", pytest.approx(2.0 / 5))]

    reg = obreg.Registry()
    profiler.publish_launches(got, reg)
    shown = reg.snapshot()
    assert shown["profile_launch_pairs"]["value"] == 3
    assert shown["profile_launch_gap_ms"]["value"] == pytest.approx(4.0)
    assert shown["profile_launch_busy_ms"]["value"] == pytest.approx(30.0)
    assert shown["profile_launch_drift_ms"]["value"] == pytest.approx(0.0, abs=1e-6)
    line = profiler.format_launches(got)
    assert line.startswith("launch ms/round: gap 4.000 = drift 0.000 + tail 0.200")
    assert "3 queued pairs (1 starved, 1 handed over before the capture)" in line
    assert "6 paired by order of 7 executions" in line and "NO READING" not in line
    assert "Linearize 0.600, Transpose 0.400" in line


def test_launch_summary_reads_a_compressed_device_line_as_drift():
    """The same rounds, 34 ms apart by the host's ready marks, on a device
    line that lays the executions end to end (30.3 ms apart): the gap is
    still 4 ms a round, and all of it but the head and the tail is drift,
    not a hole between the modules."""
    from commefficient_tpu.obs import profiler

    planes, host, _ = _launch_capture(range(20, 26), 34.0, 30.3)
    got = profiler.summarize_launches(planes, host)
    assert (got["pairs"], got["starved"], got["unknown"]) == (5, 0, 0)
    assert got["gap_ms"] == pytest.approx(4.0)
    assert got["between_ms"] == pytest.approx(0.0, abs=1e-6)
    assert got["drift_ms"] == pytest.approx(3.7)
    assert got["gap_ms"] == pytest.approx(sum(
        got[f"{p}_ms"] for p in ("drift", "tail", "between", "head", "inside")))


def test_launch_summary_takes_rounds_and_the_queue_from_the_runtimes_ids():
    """Where module events and the host's events carry the runtime's
    run_id, an execution's ready mark is the one that follows the host's
    completion of its id (the first execution was launched before the
    capture; its module event begins with the capture: cut), and a pair is
    queued by the ENQUEUE of its second program, not by the return of its
    dispatch: round 32's batch reached the device late, so its program was
    enqueued after round 31 had ended."""
    from commefficient_tpu.obs import profiler

    planes, host, run_ids = _launch_capture(
        range(30, 35), 34.0, 34.0, first_run_id=388, shift={32: 20.0})
    late = 2 * 34.0 + 20.0 - 0.5  # half a ms before its module starts
    host = [e for e in host if e[:1] + (e[3].get("run_id"),) != (
        "DoEnqueueProgram", 396)] + [
        ("DoEnqueueProgram", late * MS, 0.03 * MS, {"run_id": 396})]
    got = profiler.summarize_launches(planes, host, run_ids)
    assert got["by"] == "run_id" and got["first_cut"]
    assert (got["executions"], got["paired"]) == (5, 5)
    assert (got["pairs"], got["starved"], got["unknown"]) == (3, 1, 0)
    assert got["gap_ms"] == pytest.approx(4.0)
    assert "the first module event" in profiler.format_launches(got)
    # with no ids the dispatch's return decides, and the starved pair counts
    plain = profiler.summarize_launches(
        planes, [e for e in host if "run_id" not in e[3]])
    assert plain["by"] == "order" and (plain["pairs"], plain["starved"]) == (4, 0)
    assert plain["gap_ms"] == pytest.approx(4.0 + 20.0 / 4)


def test_launch_summary_under_three_pairs_is_no_reading(capsys):
    """Every second dispatch late: no queued pair, the line says so and the
    readers' threshold (3 pairs) is not met; a capture with no ready mark at
    all pairs nothing and drops every execution at the end edge."""
    from commefficient_tpu.obs import profiler

    planes, host, _ = _launch_capture(range(4), 40.0, 40.0, late={1, 2, 3})
    got = profiler.summarize_launches(planes, host)
    assert (got["pairs"], got["starved"]) == (0, 3)
    assert got["gap_ms"] == 0.0
    assert "NO READING" in profiler.format_launches(got)
    bare = profiler.summarize_launches(planes, [])
    assert (bare["paired"], bare["pairs"]) == (0, 0)
    assert bare["dropped"]["modules_after_last_ready"] == 4
    with pytest.raises(ValueError):
        profiler.summarize_launches([("/device:TPU:0", [("XLA Ops", [])])], [])


def test_load_capture_keeps_annotations_runtime_events_and_run_ids(tmp_path):
    """The host plane as summarize_launches takes it: the program's
    annotations with their round arguments, the runtime's events of 10 us or
    longer with their run_id, every thread's in one list; the Python
    tracer's frames are dropped by their metadata and a flood of
    microsecond events by its length. The device's module events give their
    run_id by their start."""
    from commefficient_tpu.obs import profiler

    space = profiler._xplane_pb2().XSpace()
    host = space.planes.add(name="/host:CPU")
    for i, name in ((1, "round_first"), (2, "rounds"), (3, "run_id"), (4, "size")):
        host.stat_metadata[i].name = name
    for i, name in ((1, "runner/ready"), (2, "$loop.py:527 drain"),
                    (3, "Transpose"), (4, "DoEnqueueProgram")):
        host.event_metadata[i].name = name
    main = host.lines.add(name="python", timestamp_ns=1000)
    ready = main.events.add(metadata_id=1, offset_ps=2_000_000, duration_ps=1000)
    ready.stats.add(metadata_id=1, int64_value=7)
    ready.stats.add(metadata_id=2, uint64_value=2)
    ready.stats.add(metadata_id=4, uint64_value=512)  # not a round argument
    main.events.add(metadata_id=2, offset_ps=0, duration_ps=9_000_000)
    task = host.lines.add(name="pjrt-tpu-tasks/1", timestamp_ns=2000)
    task.events.add(metadata_id=3, offset_ps=0, duration_ps=50_000_000)
    for i in range(5):  # the flood inside it: a microsecond each
        task.events.add(metadata_id=3, offset_ps=i * 2_000_000, duration_ps=1_000_000)
    enqueue = task.events.add(metadata_id=4, offset_ps=60_000_000, duration_ps=30_000_000)
    enqueue.stats.add(metadata_id=3, uint64_value=392)
    enqueue.stats.add(metadata_id=4, uint64_value=16384)
    dev = space.planes.add(name="/device:TPU:0")
    dev.stat_metadata[1].name = "run_id"
    dev.event_metadata[1].name = "jit_step(1)"
    dev.lines.add(name="XLA Modules", timestamp_ns=500).events.add(
        metadata_id=1, offset_ps=1_000_000, duration_ps=8_000_000).stats.add(
        metadata_id=1, uint64_value=392)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())
    want = [("runner/ready", 3000.0, 1.0, {"round_first": 7, "rounds": 2}),
            ("Transpose", 2000.0, 50000.0, {}),
            ("DoEnqueueProgram", 62000.0, 30000.0, {"run_id": 392})]
    planes, events, run_ids = profiler.load_capture(str(path))
    assert events == want and run_ids == {1500.0: 392}
    assert planes == [("/device:TPU:0", [
        ("XLA Modules", [("jit_step(1)", 1500.0, 8000.0, "")])])]
    assert planes == profiler.load_device_planes(str(path))


def test_profile_window_zeroes_the_launch_gauges_at_a_captures_start(
        tmp_path, monkeypatch):
    """profile_launch_pairs is zeroed when a capture starts, as
    profile_traced_rounds is: where this capture's launch summary fails, the
    readers see no reading, not the last capture's."""
    monkeypatch.setattr(jax.profiler, "start_trace", lambda d, **kw: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    pairs = obreg.default().gauge("profile_launch_pairs")
    pairs.set(9)  # what an earlier capture of this process published
    obreg.default().gauge("profile_launch_gap_ms").set(4.0)
    pw = ProfileWindow.parse("0:1", str(tmp_path))
    pw.on_dispatch(0)
    assert pairs.value == 0
    pw.on_committed(2)
    assert pairs.value == 0


def test_capture_options_leave_the_python_tracer_off(tmp_path, monkeypatch):
    """The window hands start_trace its options: the Python tracer's frames
    have no reader, so a capture does not record them."""
    from commefficient_tpu.obs import profiler

    seen = {}
    monkeypatch.setattr(
        jax.profiler, "start_trace",
        lambda d, profiler_options=None: seen.update(o=profiler_options))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    pw = ProfileWindow.parse("0:0", str(tmp_path))
    pw.on_dispatch(0)
    pw.close()
    assert seen["o"].python_tracer_level == profiler.PYTHON_TRACER_LEVEL == 0
    assert seen["o"].host_tracer_level == profiler.HOST_TRACER_LEVEL


# --------------------------------------------- crash-safe JSONL logging


def test_tablelogger_rows_carry_schema_version(tmp_path, capsys):
    from commefficient_tpu.utils.logging import (
        JSONL_SCHEMA_VERSION, TableLogger,
    )

    path = tmp_path / "rows.jsonl"
    t = TableLogger(str(path))
    t.append({"round": 0, "loss": 1.5})
    t.append({"round": 1, "loss": 1.25})
    t.close()
    rows = [json.loads(line) for line in path.read_text().splitlines()]
    assert [r["schema"] for r in rows] == [JSONL_SCHEMA_VERSION] * 2
    assert rows[1]["round"] == 1
    # the stdout table prints the CALLER's columns (no schema column)
    out = capsys.readouterr().out
    assert "schema" not in out


def test_tablelogger_killed_process_leaves_whole_lines(tmp_path):
    """SIGKILL a process mid-logging: every line already on disk must be a
    complete JSON object (line-buffered single-write append discipline)."""
    path = tmp_path / "rows.jsonl"
    child = (
        "import os, sys\n"
        "sys.stdout = open(os.devnull, 'w')\n"
        "from commefficient_tpu.utils.logging import TableLogger\n"
        f"t = TableLogger({str(path)!r})\n"
        "i = 0\n"
        "while True:\n"
        "    t.append({'round': i, 'loss': i * 0.5, 'pad': 'x' * 256})\n"
        "    i += 1\n"
    )
    p = subprocess.Popen([sys.executable, "-c", child])
    try:
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if path.exists() and path.stat().st_size > 8192:
                break
            time.sleep(0.02)
        else:
            pytest.fail("child never wrote enough rows")
    finally:
        p.kill()
        p.wait()
    lines = path.read_text().splitlines()
    assert len(lines) >= 10
    for i, line in enumerate(lines):
        row = json.loads(line)  # a torn line would raise here
        assert row["round"] == i
