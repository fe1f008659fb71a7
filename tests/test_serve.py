"""Streaming aggregation service (serve/) — ISSUE 6 tentpole.

Four layers:

1. Host-pure unit coverage of the ingest layer (admission control:
   backpressure, duplicate, out-of-round, early buffering), the W-of-N
   assembler, the O(1) fold_in client state, the traffic generator, and
   both transports (in-process + loopback socket).
2. THE acceptance pin: a served W-of-N round — same arrivals — is
   bit-identical (params + logged metrics) to the batch-simulator round
   that drops the same cohort positions via the fault plan, fused AND on
   the sharded single-device reference program.
3. Checkpoint discipline: requeue AGES and the pending arrival queue
   round-trip through meta.json; a preempted --serve run resumes
   bit-identical to the uninterrupted one through the real CLI.
4. The ops surface: /metrics endpoint fields over a live service.

The session-level tests use the same tiny-MLP/synthetic-data substitution
as tests/test_runner.py (serving logic is model-agnostic)."""

import json
import os
import tracemalloc
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree

import cv_train
from commefficient_tpu.data.fed_dataset import FedDataset, shard_iid
from commefficient_tpu.federated.api import FederatedSession
from commefficient_tpu.modes.config import ModeConfig
from commefficient_tpu.resilience import EXIT_RESUMABLE, FaultPlan
from commefficient_tpu.serve import (
    AggregationService,
    CohortAssembler,
    IngestQueue,
    ServeConfig,
    SocketTransport,
    Submission,
    TraceConfig,
    TrafficGenerator,
    submit_over_socket,
)
from commefficient_tpu.serve import clients as cl
from commefficient_tpu.serve.ingest import (
    ACCEPTED,
    BUFFERED,
    DUPLICATE,
    NOT_INVITED,
    OUT_OF_ROUND,
    QUEUE_FULL,
)
from commefficient_tpu.serve.metrics import MetricsServer
from commefficient_tpu.utils import checkpoint as ckpt
from commefficient_tpu.utils.config import make_parser, resolve_defaults

LR = 0.05


# ---------------------------------------------------------------- ingest layer


def _sub(cid, rnd=0, latency=0.1):
    return Submission(client_id=cid, round=rnd, latency_s=latency)


def test_ingest_accepts_invited_and_rejects_uninvited():
    q = IngestQueue(capacity=8)
    q.open_round(0, [1, 2, 3])
    assert q.submit(_sub(1)) == ACCEPTED
    assert q.submit(_sub(9)) == NOT_INVITED
    assert q.counters()["accepted"] == 1
    assert q.counters()["rejected_uninvited"] == 1


def test_ingest_rejects_duplicate_submission():
    q = IngestQueue(capacity=8)
    q.open_round(0, [1, 2])
    assert q.submit(_sub(1)) == ACCEPTED
    assert q.submit(_sub(1)) == DUPLICATE  # at-least-once transport retry
    assert q.counters()["rejected_dup"] == 1
    assert len(q.arrivals()) == 1  # the merge never double-counts


def test_ingest_backpressure_on_full_queue():
    q = IngestQueue(capacity=2)
    q.open_round(0, [1, 2, 3])
    assert q.submit(_sub(1)) == ACCEPTED
    assert q.submit(_sub(2)) == ACCEPTED
    assert q.submit(_sub(3)) == QUEUE_FULL  # the backpressure signal
    assert q.counters()["rejected_full"] == 1


def test_ingest_rejects_late_out_of_round():
    q = IngestQueue(capacity=8)
    q.open_round(3, [1, 2])
    assert q.submit(_sub(1, rnd=2)) == OUT_OF_ROUND  # already-closed round
    assert q.submit(_sub(1, rnd=9)) == OUT_OF_ROUND  # far-future round
    assert q.counters()["rejected_out_of_round"] == 2


def test_ingest_buffers_early_submission_for_next_round():
    """A push for round r+1 while r is open parks in the pending buffer and
    admits the moment r+1 opens — a pushing client never resubmits."""
    q = IngestQueue(capacity=8, pending_capacity=4)
    q.open_round(0, [1, 2])
    assert q.submit(_sub(5, rnd=1, latency=0.7)) == BUFFERED
    assert q.depth() == 1  # parked submissions count toward queue depth
    q.close_round()
    q.open_round(1, [5, 6])
    arr = q.arrivals()
    assert [a.client_id for a in arr] == [5]
    assert arr[0].latency_s == 0.7
    # a parked client NOT invited to round 1 stays parked
    q.close_round()
    q.open_round(2, [7])
    assert q.submit(_sub(9, rnd=3)) == BUFFERED
    q.close_round()
    assert q.pending_snapshot() == [(9, 0.1)]


def test_ingest_buffers_early_push_during_mid_merge_window():
    """The server is mid-merge between close_round(r) and open_round(r+1)
    (no round open): a push for r+1 must BUFFER, not bounce OUT_OF_ROUND —
    a pushing client never resubmits just because it raced the merge."""
    q = IngestQueue(capacity=8)
    q.open_round(0, [1, 2])
    q.close_round()  # mid-merge: nothing open
    assert q.submit(_sub(1, rnd=1, latency=0.2)) == BUFFERED
    assert q.submit(_sub(1, rnd=2)) == OUT_OF_ROUND  # beyond next: rejected
    q.open_round(1, [1, 9])
    assert [a.client_id for a in q.arrivals()] == [1]


def test_ingest_pending_buffer_is_bounded():
    q = IngestQueue(capacity=8, pending_capacity=1)
    q.open_round(0, [1])
    assert q.submit(_sub(5, rnd=1)) == BUFFERED
    assert q.submit(_sub(6, rnd=1)) == QUEUE_FULL
    assert q.submit(_sub(5, rnd=1)) == DUPLICATE


# ------------------------------------------------------------ W-of-N assembler


def _closed(latencies, quorum, deadline, invited=None):
    inv = list(invited or range(len(latencies)))
    q = IngestQueue(capacity=64)
    q.open_round(0, inv)
    for cid, lat in zip(inv, latencies):
        if np.isfinite(lat) and lat <= deadline:
            q.submit(Submission(client_id=cid, round=0, latency_s=lat))
    asm = CohortAssembler(q, quorum, deadline)
    return asm.close_virtual(0, inv), asm


def test_assembler_closes_at_quorum():
    """5 invited, quorum 3: the 3 fastest make the cut; the 4th (finite but
    slower than the close) is a straggler; inf is a no-show."""
    closed, asm = _closed([0.5, 0.1, 2.0, 0.3, np.inf], quorum=3, deadline=3.0)
    assert closed.closed_by == "quorum"
    np.testing.assert_array_equal(closed.arrived, [1, 1, 0, 1, 0])
    assert closed.close_latency_s == 0.5
    assert closed.stragglers == 1 and closed.no_shows == 1
    assert asm.counters()["closed_by_quorum"] == 1


def test_assembler_closes_at_deadline_when_short_of_quorum():
    closed, asm = _closed([0.5, np.inf, np.inf, 9.0], quorum=3, deadline=1.0)
    assert closed.closed_by == "deadline"
    np.testing.assert_array_equal(closed.arrived, [1, 0, 0, 0])
    assert closed.survivors == 1
    # 9.0 > deadline: the traffic layer never submitted it -> no-show
    assert closed.no_shows == 3
    assert asm.counters()["closed_by_deadline"] == 1


def test_assembler_wall_close_cuts_at_recv_order():
    q = IngestQueue(capacity=8)
    inv = [10, 11, 12]
    q.open_round(0, inv)
    q.submit(_sub(12, latency=0.9))
    q.submit(_sub(10, latency=0.1))
    asm = CohortAssembler(q, quorum=2, deadline_s=0.05)
    closed = asm.close_wall(0, inv)
    # recv order (12 then 10) decides, not the latency metadata
    np.testing.assert_array_equal(closed.arrived, [1.0, 0.0, 1.0])
    assert closed.closed_by == "quorum"


# ------------------------------------------------- O(1) fold_in client state


def test_fold_in_host_deterministic_and_vectorized():
    ids = np.array([0, 1, 2, 10_000_000 - 1], np.int64)
    a = cl.fold_in_host(42, ids)
    b = cl.fold_in_host(42, ids)
    np.testing.assert_array_equal(a, b)
    assert len(set(a.tolist())) == len(ids)  # no trivial collisions
    assert cl.fold_in_host(42, 1) != cl.fold_in_host(43, 1)  # seed folds in
    # scalar == vectorized element
    assert cl.fold_in_host(42, 2) == a[2]


def test_device_class_stable_and_weighted():
    ids = np.arange(20_000)
    idx = cl.device_class_index(7, ids)
    np.testing.assert_array_equal(idx, cl.device_class_index(7, ids))
    frac = np.bincount(idx, minlength=3) / len(ids)
    want = np.array([c.weight for c in cl.DEFAULT_CLASSES])
    np.testing.assert_allclose(frac, want / want.sum(), atol=0.02)


def test_response_latency_mixes_classes_and_no_shows():
    ids = np.arange(10_000)
    lat = cl.response_latency_s(3, ids, rnd=5)
    assert np.isinf(lat).any() and np.isfinite(lat).any()
    assert (lat[np.isfinite(lat)] > 0).all()
    # round folds in: a different round redraws
    lat2 = cl.response_latency_s(3, ids, rnd=6)
    assert not np.array_equal(lat, lat2)
    np.testing.assert_array_equal(lat, cl.response_latency_s(3, ids, rnd=5))


def test_client_state_is_o1_at_10m_population():
    """The 10M-ID acceptance check in unit form: deriving latencies for
    invite batches drawn from a 10M-ID universe allocates memory
    proportional to the BATCH, never the population (no table anywhere)."""
    def peak(population):
        rs = np.random.RandomState(0)
        tracemalloc.start()
        for rnd in range(8):
            ids = rs.randint(0, population, size=2048)
            cl.response_latency_s(11, ids, rnd)
        _, p = tracemalloc.get_traced_memory()
        tracemalloc.stop()
        return p

    small, big = peak(10_000), peak(10_000_000)
    assert big <= 2 * small, (small, big)
    assert big < 32 << 20  # and absolutely tiny vs any 10M-row table


# ------------------------------------------------------------------- traffic


def test_trace_config_parse_and_rejects_unknown_keys():
    t = TraceConfig.parse("population=500,base_rate=9.5,burst_rate=0.25")
    assert (t.population, t.base_rate, t.burst_rate) == (500, 9.5, 0.25)
    assert TraceConfig.parse("") == TraceConfig()
    with pytest.raises(ValueError, match="unknown key"):
        TraceConfig.parse("populaton=5")
    with pytest.raises(ValueError, match="bad value"):
        TraceConfig.parse("population=lots")


def test_diurnal_rate_shape():
    g = TrafficGenerator(TraceConfig(base_rate=100, diurnal_amplitude=0.5,
                                     diurnal_period_s=86400))
    trough, peak = g.rate_at(0.0), g.rate_at(43200.0)
    assert trough == pytest.approx(50.0) and peak == pytest.approx(150.0)


def test_arrival_events_deterministic_and_window_independent():
    g = TrafficGenerator(TraceConfig(population=1000, base_rate=50, seed=9))
    a = [(t, ids.tolist()) for t, ids in g.arrival_events(0.0, 10.0)]
    b = [(t, ids.tolist()) for t, ids in g.arrival_events(0.0, 10.0)]
    assert a == b and a
    assert all(0 <= i < 1000 for _, ids in a for i in ids)


def test_respond_to_invites_submits_in_latency_order_within_deadline():
    g = TrafficGenerator(TraceConfig(population=100, seed=1))
    got = []
    sent = g.respond_to_invites(0, np.arange(40), lambda s: got.append(s),
                                deadline_s=2.0)
    assert sent == len(got) > 0
    lats = [s.latency_s for s in got]
    assert lats == sorted(lats)
    assert all(lat <= 2.0 for lat in lats)
    expected = g.invite_latencies(0, np.arange(40))
    assert sent == int((expected[np.isfinite(expected)] <= 2.0).sum())


# ---------------------------------------------------------- socket transport


def test_socket_transport_round_trips_admission_decisions():
    q = IngestQueue(capacity=4)
    q.open_round(2, [7, 8])
    t = SocketTransport(q)
    t.start()
    try:
        addr = t.address
        assert submit_over_socket(
            addr, Submission(client_id=7, round=2, latency_s=0.3)) == ACCEPTED
        assert t.submit(
            Submission(client_id=7, round=2)) == DUPLICATE
        assert submit_over_socket(
            addr, Submission(client_id=7, round=0)) == OUT_OF_ROUND
        assert submit_over_socket(
            addr, Submission(client_id=99, round=2)) == NOT_INVITED
    finally:
        t.stop()
    arr = q.arrivals()
    assert [a.client_id for a in arr] == [7]
    assert arr[0].latency_s == 0.3


# --------------------------------------------------- session-level fixtures


def _quad_loss(params, net_state, batch, rng):
    pred = batch["x"] @ params["w"] + params["b"]
    err = pred - jax.nn.one_hot(batch["y"], pred.shape[-1])
    mask = batch["mask"]
    count = jnp.maximum(mask.sum(), 1.0)
    per_ex = (err ** 2).sum(-1)
    return (per_ex * mask).sum() / count, {
        "net_state": net_state,
        "metrics": {"loss_sum": (per_ex * mask).sum(), "count": mask.sum()}}


def _tiny_session(shards=0, seed=0, fault_plan=None, requeue_policy="fifo",
                  num_clients=12, workers=4, din=6, dout=3):
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
        seed=seed, client_shards=shards, fault_plan=fault_plan,
        requeue_policy=requeue_policy,
    )


def _serve_rounds(session, n, quorum=2, deadline=1.0, trace_seed=5):
    """Run n served rounds; returns (metrics rows, per-round dropped
    positions)."""
    svc = AggregationService(
        session, ServeConfig(quorum=quorum, deadline_s=deadline),
        traffic=TrafficGenerator(
            TraceConfig(population=session.train_set.num_clients,
                        seed=trace_seed)),
    ).start()
    src = svc.source()
    rows, drops = [], []
    try:
        for _ in range(n):
            prep = src.next()
            drops.append(sorted(
                int(p) for p in
                np.flatnonzero(np.asarray(prep.batch["_valid"]) == 0.0)))
            rows.append(session.commit_round(
                session.dispatch_round(prep, LR))[0])
    finally:
        svc.close()
    return rows, drops


def _drop_plan(drops):
    return ";".join(
        f"client_drop@{r}:clients=" + "+".join(map(str, pos))
        for r, pos in enumerate(drops) if pos)


def _assert_params_equal(sa, sb):
    for x, y in zip(
        jax.tree.leaves(jax.device_get(sa.state["params"])),
        jax.tree.leaves(jax.device_get(sb.state["params"])),
    ):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# --------------------------------------------------- THE parity acceptance pin


@pytest.mark.parametrize("shards", [0, 2], ids=["fused", "sharded"])
def test_served_round_bit_identical_to_batch_simulator(shards):
    """A served W-of-N round — quorum close, stragglers/no-shows masked and
    re-queued — is bit-identical (params + every logged metric) to the
    batch-simulator round that drops the SAME positions via the fault plan,
    on the fused path and on the sharded single-device reference program."""
    a = _tiny_session(shards=shards)
    rows_a, drops = _serve_rounds(a, 3, quorum=2, deadline=1.0)
    assert any(drops), "trace produced no casualties; pin would be vacuous"

    plan = FaultPlan.parse(_drop_plan(drops))
    b = _tiny_session(shards=shards, fault_plan=plan)
    rows_b = [b.run_round(LR) for _ in range(3)]

    for ra, rb in zip(rows_a, rows_b):
        assert set(ra) == set(rb)
        for k in ra:
            assert ra[k] == rb[k], (k, ra[k], rb[k])
    _assert_params_equal(a, b)
    # the re-queues evolved identically too (served no-shows == faulted drops)
    assert list(a._requeue) == list(b._requeue)
    assert a._requeue_enqueued == b._requeue_enqueued


def test_full_arrival_round_is_bit_identical_to_plain_round():
    """When every invitee arrives inside the quorum window the served round
    must be EXACTLY the batch-simulator round: same cohort, same batch,
    same key chain — the serving layer is a pure re-plumbing."""
    a = _tiny_session()
    svc = AggregationService(
        a, ServeConfig(quorum=a.num_workers, deadline_s=1e9),
        traffic=TrafficGenerator(
            TraceConfig(population=a.train_set.num_clients, seed=5)),
    ).start()
    try:
        src = svc.source()
        rows_a = [a.commit_round(a.dispatch_round(src.next(), LR))[0]
                  for _ in range(2)]
    finally:
        svc.close()
    b = _tiny_session()
    rows_b = [b.run_round(LR) for _ in range(2)]
    for ra, rb in zip(rows_a, rows_b):
        for k in ra:
            assert ra[k] == rb[k], k
    _assert_params_equal(a, b)


# ----------------------------------------------- checkpoint: ages + pending


def test_requeue_ages_persist_through_checkpoint(tmp_path):
    """Satellite: --requeue_policy aged ages resume their REAL rounds-waiting
    from meta.json instead of restarting at 1 — the aged serving order after
    resume matches the uninterrupted session's exactly."""
    plan = FaultPlan.parse("client_drop@0:clients=0+1;client_drop@1:clients=2")
    a = _tiny_session(fault_plan=plan, requeue_policy="aged", workers=3)
    a.run_round(LR)
    a.run_round(LR)
    assert a._requeue_enqueued  # queued casualties carry their drop rounds
    path = ckpt.save(str(tmp_path), a)

    b = _tiny_session(requeue_policy="aged", workers=3)
    ckpt.restore(path, b)
    assert b._requeue_enqueued == a._requeue_enqueued
    assert list(b._requeue) == list(a._requeue)
    # behavioral pin: the aged weighted order (a function of the AGES) now
    # serves identically on both sessions for the rounds that follow
    for _ in range(3):
        ma, mb = a.run_round(LR), b.run_round(LR)
        assert ma["loss_sum"] == mb["loss_sum"]
    assert list(a._requeue) == list(b._requeue)
    _assert_params_equal(a, b)


def test_pending_arrival_queue_persists_through_checkpoint(tmp_path):
    """The early-submission buffer rides meta.json: a service rebuilt on a
    restored session sees the parked pushes again."""
    a = _tiny_session()
    svc = AggregationService(
        a, ServeConfig(quorum=2, deadline_s=1.0),
        traffic=TrafficGenerator(
            TraceConfig(population=a.train_set.num_clients, seed=5)),
    ).start()
    try:
        src = svc.source()
        prep = src.next()
        # park an early push for the NEXT round while round 1 is not open
        a.commit_round(a.dispatch_round(prep, LR))
        svc.queue.open_round(1, [])  # open so round-2 pushes are "early"
        assert svc.queue.submit(
            Submission(client_id=3, round=2, latency_s=0.4)) == BUFFERED
        svc._record_boundary(1)
        path = ckpt.save(str(tmp_path), a)
    finally:
        svc.close()

    b = _tiny_session()
    ckpt.restore(path, b)
    assert b.restored_serve_meta["pending"] == [[3, 0.4]]
    svc_b = AggregationService(
        b, ServeConfig(quorum=2, deadline_s=1.0),
        traffic=TrafficGenerator(
            TraceConfig(population=b.train_set.num_clients, seed=5)))
    try:
        assert svc_b.queue.pending_snapshot() == [(3, 0.4)]
    finally:
        svc_b.close()


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


@pytest.mark.chaos
def test_cli_serve_preempt_resume_bit_identical(tiny_cv, tmp_path):
    """The served CLI run (W-of-N, requeue, trace traffic) preempted
    mid-run resumes BIT-IDENTICAL to the uninterrupted served run — the
    arrival stream, requeue ages, and pending queue all restore from
    meta.json (acceptance criterion 3's checkpoint half)."""
    serve_flags = ("--serve", "inproc", "--serve_quorum", "5",
                   "--serve_deadline", "2.0", "--num_rounds", "4")
    sa = cv_train.main(_argv(serve_flags))  # uninterrupted reference

    ckdir = str(tmp_path / "ck")
    chaos = ["--checkpoint_dir", ckdir, "--checkpoint_every", "2",
             "--fault_plan", "preempt@2"]
    with pytest.raises(SystemExit) as ei:
        cv_train.main(_argv(serve_flags) + chaos)
    assert ei.value.code == EXIT_RESUMABLE
    sc = cv_train.main(_argv(serve_flags) + chaos + ["--resume"])
    assert sc.round == 4
    _assert_params_equal(sa, sc)
    assert list(sa._requeue) == list(sc._requeue)
    assert sa._requeue_enqueued == sc._requeue_enqueued


@pytest.mark.chaos
def test_cli_serve_end_to_end_with_aged_requeue(tiny_cv):
    """--serve inproc + --requeue_policy aged through the real CLI: the run
    finishes every round with finite params and no leaked service threads."""
    import threading

    before = {t.name for t in threading.enumerate()}
    s = cv_train.main(_argv(("--serve", "inproc", "--serve_quorum", "5",
                             "--serve_deadline", "2.0", "--num_rounds", "4",
                             "--requeue_policy", "aged")))
    assert s.round == 4
    flat = np.asarray(ravel_pytree(jax.device_get(s.state["params"]))[0])
    assert np.isfinite(flat).all()
    leaked = {t.name for t in threading.enumerate()} - before
    assert not {n for n in leaked if n.startswith("serve-")}, leaked


# --------------------------------------------------------------- ops surface


def test_metrics_endpoint_serves_service_snapshot():
    a = _tiny_session()
    svc = AggregationService(
        a, ServeConfig(quorum=2, deadline_s=1.0, metrics_port=0),
        traffic=TrafficGenerator(
            TraceConfig(population=a.train_set.num_clients, seed=5)),
    ).start()
    try:
        src = svc.source()
        a.commit_round(a.dispatch_round(src.next(), LR))
        host, port = svc.metrics_server.address
        with urllib.request.urlopen(
                f"http://{host}:{port}/metrics", timeout=5) as resp:
            m = json.loads(resp.read())
        for field in ("round", "queue_depth", "arrival_rate_per_s",
                      "submissions", "rounds", "requeue_depth",
                      "clients_dropped", "clients_quarantined", "quorum"):
            assert field in m, field
        assert m["round"] == 1
        assert m["rounds"]["rounds_closed"] == 1
        assert m["submissions"]["accepted"] >= 2
        # non-metrics paths 404
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"http://{host}:{port}/other", timeout=5)
    finally:
        svc.close()


def test_service_refuses_bad_configs():
    a = _tiny_session()
    with pytest.raises(ValueError, match="quorum"):
        AggregationService(a, ServeConfig(quorum=99),
                           traffic=TrafficGenerator(TraceConfig()))
    with pytest.raises(ValueError, match="traffic"):
        AggregationService(a, ServeConfig(quorum=2))
    with pytest.raises(ValueError, match="transport"):
        AggregationService(a, ServeConfig(quorum=2, transport="carrier-pigeon"),
                           traffic=TrafficGenerator(TraceConfig()))


# ------------------------------------------------------------- untrusted wire
# ISSUE 9: client-computed sketch payloads, the server-side validation
# gauntlet, transport chaos, and overload shedding.

from commefficient_tpu.resilience.faults import FaultPlan as _FP  # noqa: E402
from commefficient_tpu.serve import abort_over_socket  # noqa: E402
from commefficient_tpu.serve import submit_with_retries  # noqa: E402
from commefficient_tpu.serve.clients import DeviceClass  # noqa: E402
from commefficient_tpu.serve.ingest import (  # noqa: E402
    MALFORMED,
    QUARANTINED,
    SHEDDING,
    STALE_SCHEMA,
    PayloadPolicy,
    validate_payload,
)
from commefficient_tpu.sketch.payload import (  # noqa: E402
    SCHEMA_VERSION,
    encode_frame,
)

# a device-class mix with no organic no-shows/straggle, so wire-chaos tests
# target exactly the clients the fault plan names
RELIABLE_CLASSES = (
    DeviceClass("lab", weight=1.0, latency_median_s=0.1,
                latency_sigma=0.1, no_show_prob=0.0),
)

_PAYLOAD_SHAPE = (3, 8)  # (num_rows, num_cols) of the tiny sketch sessions


def _sketch_session(shards=0, seed=0, fault_plan=None, clip=0.0, window=1,
                    num_clients=12, workers=4, din=6, dout=3):
    """_tiny_session's sketch-mode twin: wire_payloads=True, so the round is
    the two-program payload shape (client tables + table merge)."""
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
        mode_cfg=ModeConfig(mode="sketch", d=d, k=4,
                            num_rows=_PAYLOAD_SHAPE[0],
                            num_cols=_PAYLOAD_SHAPE[1],
                            momentum=0.9, momentum_type="virtual",
                            error_type="virtual"),
        train_set=train, num_workers=workers, local_batch_size=4,
        seed=seed, client_shards=shards, fault_plan=fault_plan,
        wire_payloads=True, client_update_clip=clip,
        quarantine_window=window,
    )


def _serve_payload_rounds(session, n, transport="inproc", quorum=2,
                          deadline=5.0, trace_seed=5,
                          classes=RELIABLE_CLASSES, fastpath=False):
    """Run n served wire-payload rounds; returns (service, per-round dropped
    positions). The service is closed before returning."""
    svc = AggregationService(
        session,
        ServeConfig(quorum=quorum, deadline_s=deadline, transport=transport,
                    payload="sketch", fastpath=fastpath),
        traffic=TrafficGenerator(
            TraceConfig(population=session.train_set.num_clients,
                        seed=trace_seed), classes=classes),
    ).start()
    src = svc.source()
    drops = []
    try:
        for _ in range(n):
            prep = src.next()
            arrived = prep.payload[1]
            drops.append(sorted(
                int(p) for p in np.flatnonzero(arrived == 0.0)))
            session.commit_round(session.dispatch_round(prep, LR))
    finally:
        svc.close()
    return svc, drops


def _policy(clip=0.0, median=None):
    return PayloadPolicy(rows=_PAYLOAD_SHAPE[0], cols=_PAYLOAD_SHAPE[1],
                         clip_multiple=clip,
                         quarantine_median=(None if median is None
                                            else (lambda: median)))


def _table(fill=0.5):
    return np.full(_PAYLOAD_SHAPE, fill, np.float32)


# ---------------------------------------------------- the validation gauntlet


def test_validate_payload_accepts_clean_frame_and_raw_array():
    t = _table()
    for payload in (encode_frame(t), t):
        out, decision, detail = validate_payload(payload, _policy())
        assert decision == ACCEPTED, (decision, detail)
        np.testing.assert_array_equal(out, t)
        assert out.dtype == np.float32


def test_validate_payload_rejects_checksum_flip():
    frame = _FP.corrupt_frame(encode_frame(_table()))
    out, decision, detail = validate_payload(frame, _policy())
    assert (out, decision) == (None, MALFORMED)
    assert "checksum" in detail


def test_validate_payload_rejects_truncation_by_length_prefix():
    frame = _FP.truncate_frame(encode_frame(_table()))
    out, decision, detail = validate_payload(frame, _policy())
    assert (out, decision) == (None, MALFORMED)
    assert "length prefix" in detail or "decoded" in detail


def test_validate_payload_rejects_stale_schema():
    frame = encode_frame(_table(), schema=SCHEMA_VERSION + 1)
    out, decision, detail = validate_payload(frame, _policy())
    assert (out, decision) == (None, STALE_SCHEMA)


def test_validate_payload_rejects_shape_dtype_and_garbage():
    good = encode_frame(_table())
    cases = [
        None,                                    # no payload at all
        "zzz",                                   # not a frame
        {**good, "shape": [4, 8]},               # shape vs the SERVER's spec
        {**good, "dtype": "<f8"},                # wrong wire dtype
        {**good, "nbytes": 12},                  # lying length prefix
        {**good, "data": "!!!notbase64!!!"},     # undecodable data
        {k: v for k, v in good.items() if k != "schema"},  # missing field
        np.zeros((4, 4), np.float32),            # raw array, wrong shape
        np.zeros(_PAYLOAD_SHAPE, np.float64),    # raw array, wrong dtype
    ]
    for payload in cases:
        out, decision, _ = validate_payload(payload, _policy())
        assert (out, decision) == (None, MALFORMED), payload


def test_validate_payload_quarantines_nonfinite_and_oversized():
    bad = _table()
    bad[1, 2] = np.nan
    out, decision, detail = validate_payload(encode_frame(bad), _policy())
    assert (out, decision) == (None, QUARANTINED)
    assert "non-finite" in detail
    # sketch-space L2 screen against the running median, at the wire
    out, decision, detail = validate_payload(
        encode_frame(_table(100.0)), _policy(clip=2.0, median=1.0))
    assert (out, decision) == (None, QUARANTINED)
    assert "median" in detail
    # same table under a healthy median passes
    out, decision, _ = validate_payload(
        encode_frame(_table(100.0)), _policy(clip=2.0, median=1e3))
    assert decision == ACCEPTED


def test_payload_queue_runs_gauntlet_and_counts_rejections():
    q = IngestQueue(capacity=8, payload_policy=_policy())
    q.open_round(0, [1, 2, 3, 4])
    ok = encode_frame(_table())
    assert q.submit(Submission(1, 0, 0.1, payload=ok)) == ACCEPTED
    assert q.submit(Submission(
        2, 0, 0.1, payload=_FP.corrupt_frame(ok))) == MALFORMED
    assert q.submit(Submission(
        3, 0, 0.1, payload=encode_frame(_table(), schema=99))) == STALE_SCHEMA
    assert q.submit(Submission(4, 0, 0.1, payload=None)) == MALFORMED
    c = q.counters()
    assert c["rejected_malformed"] == 2
    assert c["rejected_stale_schema"] == 1
    # a rejected client may retry with a GOOD frame: rejection != admission
    assert q.submit(Submission(2, 0, 0.2, payload=ok)) == ACCEPTED
    arr = q.arrivals()
    assert sorted(a.client_id for a in arr) == [1, 2]
    for a in arr:
        np.testing.assert_array_equal(a.table, _table())


def test_payload_round_rejects_early_push():
    """A sketch payload is a function of the OPEN round's params — a table
    'for the next round' cannot exist yet, so the pending buffer is closed
    on the payload path."""
    q = IngestQueue(capacity=8, payload_policy=_policy())
    q.open_round(0, [1])
    assert q.submit(Submission(
        5, 1, 0.1, payload=encode_frame(_table()))) == OUT_OF_ROUND
    assert q.counters()["buffered"] == 0


# ------------------------------------------------------------- load shedding


def test_shedding_turns_overload_away_before_other_work():
    q = IngestQueue(capacity=4, pending_capacity=0, shed_watermark=0.5,
                    shed_retry_after_s=2.5)
    q.open_round(0, [1, 2, 3, 4, 5])
    assert q.submit(_sub(1)) == ACCEPTED
    assert q.submit(_sub(2)) == ACCEPTED  # depth 2 = watermark (0.5 * 4)
    # sheds before the expensive work (invite lookup, payload decode) —
    # a fresh or uninvited client costs only the depth comparison plus one
    # O(1) set probe under a flood
    assert q.submit(_sub(3)) == SHEDDING
    assert q.submit(_sub(99)) == SHEDDING
    # ...but a retry of an ALREADY-ADMITTED submission hears DUPLICATE
    # (== success: the reply was lost, the merge will count it) — shedding
    # must not make an at-least-once client burn its retry budget on a
    # submission the server already took
    assert q.submit(_sub(1)) == DUPLICATE
    assert q.counters()["shed"] == 2
    assert q.counters()["rejected_dup"] == 1
    assert q.shed_retry_after_s == 2.5
    assert q.depth() == 2  # bounded: nothing queued past the watermark


def test_shedding_off_by_default_keeps_queue_full_semantics():
    q = IngestQueue(capacity=2)
    q.open_round(0, [1, 2, 3])
    assert q.submit(_sub(1)) == ACCEPTED
    assert q.submit(_sub(2)) == ACCEPTED
    assert q.submit(_sub(3)) == QUEUE_FULL
    assert q.counters()["shed"] == 0


def test_socket_shed_reply_carries_retry_after_hint():
    q = IngestQueue(capacity=4, pending_capacity=0, shed_watermark=0.25,
                    shed_retry_after_s=1.5)
    q.open_round(0, [1, 2])
    t = SocketTransport(q)
    t.start()
    try:
        assert submit_over_socket(t.address, _sub(1)) == ACCEPTED
        from commefficient_tpu.serve.transport import _roundtrip

        reply = _roundtrip(t.address, _sub(2))
        assert reply["status"] == SHEDDING
        assert reply["retry_after_s"] == 1.5
    finally:
        t.stop()


# -------------------------------------------------------- client-side retries


def test_submit_with_retries_backs_off_on_shedding_with_hint_floor():
    from commefficient_tpu.serve import transport as tmod

    replies = [{"status": SHEDDING, "retry_after_s": 0.8},
               {"status": SHEDDING, "retry_after_s": 0.8},
               {"status": ACCEPTED}]
    calls, sleeps = [], []

    def fake_roundtrip(addr, sub, timeout_s=5.0):
        calls.append(sub)
        return replies[len(calls) - 1]

    orig = tmod._roundtrip
    tmod._roundtrip = fake_roundtrip
    try:
        status = submit_with_retries(
            ("h", 1), _sub(7), max_retries=3, base_backoff_s=0.05,
            sleep=sleeps.append)
    finally:
        tmod._roundtrip = orig
    assert status == ACCEPTED
    assert len(calls) == 3
    # every backoff is floored at the server's hint
    assert all(s >= 0.8 for s in sleeps)


def test_submit_with_retries_duplicate_is_success_and_returns_immediately():
    from commefficient_tpu.serve import transport as tmod

    def fake_roundtrip(addr, sub, timeout_s=5.0):
        return {"status": DUPLICATE}

    sleeps = []
    orig = tmod._roundtrip
    tmod._roundtrip = fake_roundtrip
    try:
        status = submit_with_retries(("h", 1), _sub(7), sleep=sleeps.append)
    finally:
        tmod._roundtrip = orig
    # at-least-once: the first attempt's admission survived a lost reply —
    # a DUPLICATE on retry IS success, and no backoff is spent on it
    assert status == DUPLICATE
    assert sleeps == []


def test_submit_with_retries_bounded_budget_and_deterministic_jitter():
    from commefficient_tpu.serve import transport as tmod

    def fake_roundtrip(addr, sub, timeout_s=5.0):
        raise ConnectionRefusedError("down")

    schedules = []
    for _ in range(2):
        sleeps = []
        orig = tmod._roundtrip
        tmod._roundtrip = fake_roundtrip
        try:
            status = submit_with_retries(
                ("h", 1), _sub(7, rnd=3), max_retries=3,
                base_backoff_s=0.05, max_backoff_s=0.4, sleep=sleeps.append)
        finally:
            tmod._roundtrip = orig
        assert status == "CONN_FAILED"
        assert len(sleeps) == 3  # bounded: exactly max_retries backoffs
        schedules.append(tuple(sleeps))
    # jitter is a pure function of (client, round, attempt): replayable
    assert schedules[0] == schedules[1]
    # exponential growth with jitter in [0.5, 1.5)x, capped
    assert all(0.5 * 0.05 * 2**i <= s <= 1.5 * min(0.05 * 2**i, 0.4)
               for i, s in enumerate(schedules[0]))


# -------------------------------------------- payload parity (acceptance pin)


@pytest.mark.parametrize("shards", [0, 2], ids=["fused", "sharded"])
def test_served_payload_round_bit_identical_to_batch_round(shards):
    """THE wire acceptance pin: a served round whose submissions carry REAL
    client-computed sketch tables — with wire_corrupt + wire_dup +
    client_poison injected at the transport seam — commits params
    BIT-identical to the batch wire-payload round that drops the same
    casualties, fused AND sharded. Every rejection class fired as an
    admission counter."""
    plan = _FP.parse(
        "wire_corrupt@1:clients=0;wire_dup@1:clients=1;"
        "client_poison@2:clients=3,value=nan")
    a = _sketch_session(shards=shards, fault_plan=plan, clip=3.0)
    svc, drops = _serve_payload_rounds(a, 3, quorum=4, deadline=30.0)
    c = svc.queue.counters()
    assert c["rejected_malformed"] >= 1, c     # corrupt -> checksum
    assert c["rejected_dup"] >= 1, c           # dup -> dedup, single-count
    assert c["rejected_quarantined"] >= 1, c   # poison -> wire screen
    assert drops[1] and drops[2], drops

    pl = ";".join(f"client_drop@{r}:clients=" + "+".join(map(str, pos))
                  for r, pos in enumerate(drops) if pos)
    b = _sketch_session(shards=shards, fault_plan=_FP.parse(pl), clip=3.0)
    for _ in range(3):
        b.run_round(LR)
    _assert_params_equal(a, b)
    assert list(a._requeue) == list(b._requeue)


def test_served_payload_round_over_socket_matches_inproc():
    """The loopback socket (real frame serialization, checksums, concurrent
    connections) and the in-process transport commit IDENTICAL params for
    the same trace — float32 framing is exact, so the wire adds no
    arithmetic."""
    a = _sketch_session()
    _serve_payload_rounds(a, 2, transport="inproc", quorum=4, deadline=30.0)
    b = _sketch_session()
    _serve_payload_rounds(b, 2, transport="socket", quorum=4, deadline=30.0)
    _assert_params_equal(a, b)


def test_serve_payload_mode_requires_wire_payload_session():
    a = _tiny_session()  # announce-shaped session (wire_payloads off)
    with pytest.raises(ValueError, match="wire_payloads"):
        AggregationService(
            a, ServeConfig(quorum=2, payload="sketch"),
            traffic=TrafficGenerator(TraceConfig(population=12)))


# ------------------------------------------ zero-copy fast path (bitwise pin)


@pytest.mark.parametrize("transport", ["inproc", "socket"])
@pytest.mark.parametrize("shards", [0, 2], ids=["fused", "sharded"])
def test_fastpath_served_round_bit_identical_to_slow_path(shards, transport):
    """THE fast-path acceptance pin: --serve_fastpath (pinned ring +
    batched gauntlet + chunked ingest/H2D overlap) commits params BITWISE
    identical to the slow path over the same trace and the same injected
    chaos — fused and sharded, inproc and socket. The ring is a layout
    change, never an order change."""
    plan = "wire_corrupt@1:clients=0;client_poison@2:clients=3,value=nan"
    a = _sketch_session(shards=shards, fault_plan=_FP.parse(plan), clip=3.0)
    svc_a, drops_a = _serve_payload_rounds(
        a, 3, transport=transport, quorum=4, deadline=30.0, fastpath=True)
    b = _sketch_session(shards=shards, fault_plan=_FP.parse(plan), clip=3.0)
    svc_b, drops_b = _serve_payload_rounds(
        b, 3, transport=transport, quorum=4, deadline=30.0, fastpath=False)
    assert drops_a == drops_b
    _assert_params_equal(a, b)
    assert list(a._requeue) == list(b._requeue)
    # the chaos actually went through the fast-path gauntlet
    ca = svc_a.queue.counters()
    assert ca["rejected_malformed"] >= 1, ca
    assert ca["rejected_quarantined"] >= 1, ca
    if transport == "socket":
        # and the socket run really batched: the gauntlet histogram saw
        # blocks, and the ring saw occupancy
        assert svc_a.registry.histogram("serve_gauntlet_batch_ms").count > 0
    assert svc_a.registry.histogram("serve_ring_occupancy").count > 0


def test_fastpath_touches_fewer_bytes_than_slow_path_over_socket():
    """The perf claim the lint rule guards, as a counter: over the socket
    the slow path touches each accepted table's bytes twice (decode copy +
    assembler stack copy), the fast path once (the ring-slot write)."""
    a = _sketch_session()
    svc_a, _ = _serve_payload_rounds(
        a, 2, transport="socket", quorum=4, deadline=30.0, fastpath=True)
    fast = svc_a.registry.counter("serve_table_bytes_copied_total").value
    b = _sketch_session()
    svc_b, _ = _serve_payload_rounds(
        b, 2, transport="socket", quorum=4, deadline=30.0, fastpath=False)
    slow = svc_b.registry.counter("serve_table_bytes_copied_total").value
    assert 0 < fast < slow, (fast, slow)
    _assert_params_equal(a, b)  # fewer copies, same bytes served


def test_fastpath_requires_sketch_payload_and_no_edges():
    a = _sketch_session()
    with pytest.raises(ValueError, match="serve_edges"):
        AggregationService(
            a, ServeConfig(quorum=2, payload="sketch", fastpath=True,
                           transport="socket", edges=2),
            traffic=TrafficGenerator(TraceConfig(population=12)))
    b = _tiny_session()
    with pytest.raises(ValueError, match="fastpath"):
        AggregationService(
            b, ServeConfig(quorum=2, payload="announce", fastpath=True),
            traffic=TrafficGenerator(TraceConfig(population=12)))


# ------------------------------------- single-damaged-frame property (bitwise)


def _one_payload_round(session, mutate=None, target=2):
    """One served-style payload round driven at queue level: every invitee
    submits its real table, `mutate(frame)` damages the target position's
    frame (None = clean). Returns committed params (flat)."""
    ids = session.sample_cohort(0)
    prep0 = session.prepare_served_round(
        0, ids, np.ones(len(ids), np.float32))
    tables, aux = session.compute_client_tables(prep0)
    q = IngestQueue(capacity=16, payload_policy=_policy())
    q.open_round(0, ids)
    asm = CohortAssembler(q, quorum=len(ids), deadline_s=10.0,
                          payload_shape=_PAYLOAD_SHAPE)
    for i, cid in enumerate(ids):
        payload = encode_frame(tables[i])
        if i == target and mutate is not None:
            sent = mutate(payload)
            for p in sent if isinstance(sent, list) else [sent]:
                if p is not None:
                    q.submit(Submission(int(cid), 0, 0.1, payload=p))
        else:
            q.submit(Submission(int(cid), 0, 0.1, payload=payload))
    closed = asm.close_virtual(0, ids)
    prep = session.finish_served_payload(
        prep0, closed.arrived, closed.tables, aux)
    session.commit_round(session.dispatch_round(prep, LR))
    return np.asarray(
        ravel_pytree(jax.device_get(session.state["params"]))[0])


DAMAGE = {
    "corrupt": lambda f: _FP.corrupt_frame(f),
    "truncate": lambda f: _FP.truncate_frame(f),
    "stale_schema": lambda f: {**f, "schema": SCHEMA_VERSION + 7},
    "wrong_shape": lambda f: {**f, "shape": [1, 1]},
    "garbage": lambda f: "not a frame at all",
    "dropped_mid_send": lambda f: None,  # the send never completes
}


@pytest.mark.parametrize("kind", sorted(DAMAGE))
def test_single_damaged_frame_never_changes_committed_params(kind):
    """The robustness property: ANY single corrupted / truncated / stale /
    garbled / half-sent frame changes NOTHING about the committed params
    relative to the round where that client simply never submitted —
    rejection == drop, bitwise. (A duplicated frame is the other half:
    == the round where it submitted once.)"""
    damaged = _one_payload_round(_sketch_session(), mutate=DAMAGE[kind])
    # the reference: the target client never submits at all
    reference = _one_payload_round(
        _sketch_session(), mutate=lambda f: None)
    np.testing.assert_array_equal(damaged, reference)


def test_duplicated_frame_is_counted_once_bitwise():
    duplicated = _one_payload_round(
        _sketch_session(), mutate=lambda f: [f, f])
    clean = _one_payload_round(_sketch_session(), mutate=None)
    np.testing.assert_array_equal(duplicated, clean)


def _one_payload_round_batched(session, mutate=None, target=2):
    """_one_payload_round's batched-gauntlet twin: every submission goes
    through ONE submit_block call (the worker-pool entry point), so the
    damaged frame sits INSIDE a vectorized validation block surrounded by
    clean neighbors. Returns committed params (flat)."""
    ids = session.sample_cohort(0)
    prep0 = session.prepare_served_round(
        0, ids, np.ones(len(ids), np.float32))
    tables, aux = session.compute_client_tables(prep0)
    q = IngestQueue(capacity=16, payload_policy=_policy())
    q.open_round(0, ids)
    asm = CohortAssembler(q, quorum=len(ids), deadline_s=10.0,
                          payload_shape=_PAYLOAD_SHAPE)
    subs = []
    for i, cid in enumerate(ids):
        payload = encode_frame(tables[i])
        if i == target and mutate is not None:
            sent = mutate(payload)
            for p in sent if isinstance(sent, list) else [sent]:
                if p is not None:
                    subs.append(Submission(int(cid), 0, 0.1, payload=p))
        else:
            subs.append(Submission(int(cid), 0, 0.1, payload=payload))
    statuses = q.submit_block(subs)
    assert len(statuses) == len(subs)
    closed = asm.close_virtual(0, ids)
    prep = session.finish_served_payload(
        prep0, closed.arrived, closed.tables, aux)
    session.commit_round(session.dispatch_round(prep, LR))
    return np.asarray(
        ravel_pytree(jax.device_get(session.state["params"]))[0])


@pytest.mark.parametrize("kind", sorted(DAMAGE))
def test_damaged_frame_inside_batched_block_rejects_only_itself(kind):
    """The batched gauntlet inherits the per-frame robustness property: a
    corrupted / truncated / stale / garbled / half-sent frame inside a
    validation BLOCK rejects only that submission — committed params are
    bitwise the round where that client never submitted, and its clean
    block-mates all land."""
    damaged = _one_payload_round_batched(
        _sketch_session(), mutate=DAMAGE[kind])
    reference = _one_payload_round_batched(
        _sketch_session(), mutate=lambda f: None)
    np.testing.assert_array_equal(damaged, reference)
    # and the batched path is bitwise the scalar path, damage and all
    scalar = _one_payload_round(_sketch_session(), mutate=DAMAGE[kind])
    np.testing.assert_array_equal(damaged, scalar)


def test_duplicated_frame_inside_batched_block_is_counted_once():
    duplicated = _one_payload_round_batched(
        _sketch_session(), mutate=lambda f: [f, f])
    clean = _one_payload_round_batched(_sketch_session(), mutate=None)
    np.testing.assert_array_equal(duplicated, clean)


def test_batched_block_screens_poison_against_quarantine_median():
    """The vectorized L2 screen reproduces the scalar quarantine verdict:
    a NaN table and an outlier-norm table inside one block both reject,
    their clean neighbors accept, with the same detail discipline."""
    q = IngestQueue(capacity=16, payload_policy=_policy(clip=2.0, median=1.0))
    q.open_round(0, [1, 2, 3, 4])
    nan_t = _table()
    nan_t[0, 0] = np.nan
    subs = [
        Submission(1, 0, 0.1, payload=encode_frame(_table(0.1))),
        Submission(2, 0, 0.1, payload=encode_frame(nan_t)),
        Submission(3, 0, 0.1, payload=encode_frame(_table(100.0))),
        Submission(4, 0, 0.1, payload=encode_frame(_table(0.2))),
    ]
    statuses = q.submit_block(subs)
    assert statuses == [ACCEPTED, QUARANTINED, QUARANTINED, ACCEPTED]
    c = q.counters()
    assert c["rejected_quarantined"] == 2
    assert c["accepted"] == 2


# --------------------------------------------- close_wall under concurrency


def test_close_wall_cut_excludes_arrivals_racing_the_drain():
    """Recv-order wall-clock cut: submissions ADMITTED between the wait's
    satisfaction and close_round's drain are stragglers, not survivors —
    the cut is decided on the snapshot the wait returned."""
    q = IngestQueue(capacity=8)
    q.open_round(0, [1, 2, 3, 4])
    asm = CohortAssembler(q, quorum=2, deadline_s=0.05)
    orig_wait = q.wait_for

    def racy_wait(count, timeout_s, rnd=None):
        q.submit(_sub(1))
        q.submit(_sub(2))
        snap = orig_wait(count, 0.0)
        # these land AFTER the wall-clock cut, BEFORE the drain
        q.submit(_sub(3))
        q.submit(_sub(4))
        return snap

    q.wait_for = racy_wait
    closed = asm.close_wall(0, [1, 2, 3, 4])
    assert closed.closed_by == "quorum"
    assert closed.arrived.tolist() == [1.0, 1.0, 0.0, 0.0]
    assert closed.stragglers == 2  # submitted, admitted, but past the cut


def test_close_wall_deadline_verdict_survives_racing_arrivals():
    """A deadline-expired wait must stay closed_by='deadline' even when
    late arrivals pile in during the wait->drain gap — they cannot
    retroactively make the round a quorum close."""
    q = IngestQueue(capacity=8)
    q.open_round(0, [1, 2, 3])
    asm = CohortAssembler(q, quorum=3, deadline_s=0.01)
    orig_wait = q.wait_for

    def racy_wait(count, timeout_s, rnd=None):
        q.submit(_sub(1))
        snap = orig_wait(count, 0.01)  # times out short of quorum
        q.submit(_sub(2))
        q.submit(_sub(3))
        return snap

    q.wait_for = racy_wait
    closed = asm.close_wall(0, [1, 2, 3])
    assert closed.closed_by == "deadline"
    assert closed.arrived.tolist() == [1.0, 0.0, 0.0]


def test_close_wall_under_socket_load_with_stragglers():
    """Satellite: the recv-order wall-clock cut under REAL concurrent
    socket connections carrying payload frames, with injected stragglers.
    Exactly the first `quorum` admitted clients survive, every survivor's
    validated table rides into the close, and the slow group never makes
    the cut."""
    import threading as th

    ids = list(range(12))
    q = IngestQueue(capacity=64, payload_policy=_policy())
    q.open_round(0, ids)
    t = SocketTransport(q)
    t.start()
    asm = CohortAssembler(q, quorum=6, deadline_s=10.0,
                          payload_shape=_PAYLOAD_SHAPE)
    import time as _time
    fast, slow = set(range(8)), set(range(8, 12))

    def client(cid):
        _time.sleep(0.02 if cid in fast else 1.2)  # injected stragglers
        try:
            submit_over_socket(t.address, Submission(
                cid, 0, latency_s=0.02, payload=_table(float(cid + 1))))
        except OSError:
            pass

    threads = [th.Thread(target=client, args=(cid,)) for cid in ids]
    try:
        for x in threads:
            x.start()
        closed = asm.close_wall(0, ids)
    finally:
        for x in threads:
            x.join()
        t.stop()
    assert closed.closed_by == "quorum"
    assert closed.survivors == 6
    survivors = {int(c) for c, a in zip(closed.invited, closed.arrived)
                 if a == 1.0}
    assert survivors <= fast, survivors  # recv order == the fast group
    # every survivor's VALIDATED table (and nobody else's) is in the stack
    for pos, cid in enumerate(closed.invited):
        expect = (_table(float(cid + 1)) if closed.arrived[pos] == 1.0
                  else np.zeros(_PAYLOAD_SHAPE, np.float32))
        np.testing.assert_array_equal(closed.tables[pos], expect)


# ------------------------------------------------------- transport hardening


def test_socket_read_deadline_disconnects_silent_peer():
    """Slow-loris defense: a peer that connects and never sends is
    disconnected when the read deadline lapses — its handler thread exits
    on its own, before any stop()."""
    import socket as sk
    import threading as th
    import time as _time

    q = IngestQueue(capacity=4)
    q.open_round(0, [1])
    t = SocketTransport(q, read_deadline_s=0.2)
    t.start()
    try:
        conn = sk.create_connection(t.address)
        deadline = _time.monotonic() + 3.0
        while _time.monotonic() < deadline:
            if not any(x.name == "serve-conn" and x.is_alive()
                       for x in th.enumerate()):
                break
            _time.sleep(0.05)
        else:
            raise AssertionError("silent peer's thread outlived the "
                                 "read deadline")
        conn.close()
    finally:
        t.stop()


def test_socket_max_frame_rejects_newline_less_flood():
    """Memory-bomb defense: a newline-less byte flood is cut off at the
    frame cap with a MALFORMED reply and a disconnect — per-connection
    memory stays bounded no matter what the peer sends."""
    import socket as sk

    q = IngestQueue(capacity=4)
    q.open_round(0, [1])
    t = SocketTransport(q, max_frame_bytes=2048)
    t.start()
    try:
        with sk.create_connection(t.address) as conn:
            conn.sendall(b"x" * 8192)  # no newline ever
            conn.settimeout(5.0)
            reply = b""
            while b"\n" not in reply:
                chunk = conn.recv(4096)
                if not chunk:
                    break
                reply += chunk
            assert b"MALFORMED" in reply, reply
            assert conn.recv(4096) == b""  # server hung up
    finally:
        t.stop()
    assert q.counters()["accepted"] == 0
    # a transport-decided MALFORMED still shows in the queue's counters —
    # the /metrics submissions block must see a byte-flood happening
    assert q.counters()["rejected_malformed"] == 1


def test_socket_stop_joins_half_open_and_mid_frame_connections():
    """Thread hygiene satellite: stop() force-closes live connections and
    joins EVERY per-connection thread within its deadline — including
    threads parked on abandoned half-open peers and mid-frame senders."""
    import socket as sk
    import threading as th

    q = IngestQueue(capacity=8)
    q.open_round(0, [1, 2])
    t = SocketTransport(q, read_deadline_s=30.0)  # deadline will NOT help
    t.start()
    conns = []
    try:
        for _ in range(3):
            conns.append(sk.create_connection(t.address))  # half-open
        conns[0].sendall(b'{"client_id": 1, ')  # mid-frame, never finished
        # a completed submission keeps one healthy connection around too
        assert submit_over_socket(
            t.address, Submission(2, 0, latency_s=0.1)) == ACCEPTED
    finally:
        t.stop(join_deadline_s=5.0)
        leaked = [x.name for x in th.enumerate()
                  if x.name.startswith("serve-") and x.is_alive()]
        assert not leaked, leaked
        for c in conns:
            c.close()


def test_abort_over_socket_is_a_no_show():
    """conn_drop realism: a connection that dies mid-send admits NOTHING —
    the partial frame never parses and the handler thread moves on."""
    q = IngestQueue(capacity=4, payload_policy=_policy())
    q.open_round(0, [1])
    t = SocketTransport(q)
    t.start()
    try:
        abort_over_socket(t.address, Submission(
            1, 0, latency_s=0.1, payload=_table()))
        assert q.counters()["accepted"] == 0
        # the same client can still submit for real afterwards
        assert submit_over_socket(t.address, Submission(
            1, 0, latency_s=0.2, payload=encode_frame(_table()))) == ACCEPTED
    finally:
        t.stop()


# ------------------------------------------- checkpoint resume (payload path)


@pytest.mark.chaos
def test_cli_serve_payload_preempt_resume_bit_identical(tiny_cv, tmp_path):
    """Checkpoint -> resume MID-SERVED-ROUND on the payload path: the
    --serve_payload sketch CLI run preempted by an injected SIGTERM resumes
    BIT-identical to the uninterrupted run — cohort stream, payload tables,
    requeue state and all."""
    flags = ("--serve", "inproc", "--serve_payload", "sketch",
             "--mode", "sketch", "--k", "16", "--num_cols", "256",
             "--num_rows", "3", "--serve_deadline", "2.0",
             "--num_rounds", "4")
    argv = [
        "--dataset", "cifar10", "--num_clients", "8", "--num_workers", "2",
        "--local_batch_size", "4", "--lr_scale", "0.05",
        "--weight_decay", "0", "--data_root", "/nonexistent", *flags,
    ]
    sa = cv_train.main(list(argv))  # uninterrupted reference

    ckdir = str(tmp_path / "ck")
    chaos = ["--checkpoint_dir", ckdir, "--checkpoint_every", "2",
             "--fault_plan", "preempt@2"]
    with pytest.raises(SystemExit) as ei:
        cv_train.main(list(argv) + chaos)
    assert ei.value.code == EXIT_RESUMABLE
    sc = cv_train.main(list(argv) + chaos + ["--resume"])
    assert sc.round == 4
    _assert_params_equal(sa, sc)
    assert list(sa._requeue) == list(sc._requeue)
    assert sa._requeue_enqueued == sc._requeue_enqueued
