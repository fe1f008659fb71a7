"""Async run-loop harness (runner/): the acceptance pin is that the
overlapped loop — background batch prefetch, deferred device_get of
metrics, checkpoint writes on a writer thread — produces BIT-IDENTICAL
final params and logged metrics to `--sync_loop` (the old serial loop),
including across an emergency-checkpoint resume, because both drive the
identical compiled programs in the identical order with the identical host
RNG stream.

Same tiny-MLP + synthetic-CIFAR substitution as tests/test_resilience.py
(the loop logic is model-agnostic; ResNet-9 compiles for minutes on this
1-core box)."""

import json
import os
import threading
import time

import numpy as np
import pytest

import jax

import cv_train
from commefficient_tpu.resilience import EXIT_RESUMABLE
from commefficient_tpu.runner import AsyncCheckpointWriter, RoundPrefetcher
from commefficient_tpu.utils import checkpoint as ckpt
from commefficient_tpu.utils.config import make_parser, resolve_defaults

LR = 0.05


def _argv(extra=()):
    return [
        "--dataset", "cifar10", "--mode", "uncompressed", "--num_clients", "8",
        "--num_workers", "2", "--local_batch_size", "4", "--lr_scale", "0.05",
        "--weight_decay", "0", "--data_root", "/nonexistent", *extra,
    ]


def _args(extra=()):
    return resolve_defaults(make_parser("cv").parse_args(_argv(extra)))


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


def _rows(path):
    """Logged JSONL rows minus wall-clock (the one legitimately
    loop-dependent field)."""
    rows = [json.loads(line) for line in open(path)]
    for r in rows:
        r.pop("time_s")
    return rows


def _assert_params_equal(sa, sb):
    for x, y in zip(
        jax.tree.leaves(jax.device_get(sa.state["params"])),
        jax.tree.leaves(jax.device_get(sb.state["params"])),
    ):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ------------------------------------------------- the acceptance headline


@pytest.mark.chaos
def test_async_loop_bit_identical_to_sync(tiny_cv, tmp_path):
    """Multi-round run through the REAL CLI, eval cadence mid-run, mixed
    drain boundaries (--checkpoint_every 2 against --eval_every 3: the
    async loop drains after rounds 2, 3, 4 and 6): its final params and
    every logged metric row must be bit-identical to --sync_loop's."""
    base = _argv(("--num_rounds", "6", "--eval_every", "3",
                  "--checkpoint_every", "2"))
    la, lb = str(tmp_path / "sync.jsonl"), str(tmp_path / "async.jsonl")
    sa = cv_train.main(base + ["--sync_loop", "--log_jsonl", la])
    sb = cv_train.main(base + ["--log_jsonl", lb])
    assert sa.round == sb.round == 6
    _assert_params_equal(sa, sb)
    rows_a, rows_b = _rows(la), _rows(lb)
    assert rows_a and rows_a == rows_b


@pytest.mark.chaos
def test_async_loop_preempt_resume_bit_identical(tiny_cv, tmp_path):
    """SIGTERM mid-block under the async loop (prefetcher ahead, rounds in
    flight, periodic saves on the writer thread): drain -> emergency
    checkpoint -> exit 75; the relaunched --resume run must finish with
    params bit-identical to an uninterrupted --sync_loop run. This is the
    'checkpoint+resume mid-run + SIGTERM mid-block' acceptance case."""
    base = _argv(("--num_rounds", "6"))
    sa = cv_train.main(base + ["--sync_loop"])  # uninterrupted reference

    ckdir = str(tmp_path / "ck")
    chaos = ["--checkpoint_dir", ckdir, "--checkpoint_every", "2",
             "--fault_plan", "preempt@2"]
    with pytest.raises(SystemExit) as ei:
        cv_train.main(base + chaos)
    assert ei.value.code == EXIT_RESUMABLE
    # the SIGTERM fired as round 2 dispatched; the drain let it commit, so
    # the emergency checkpoint is a verified round-3 boundary
    names = sorted(d for d in os.listdir(ckdir) if d.startswith("round_"))
    assert names[-1] == "round_00000003"
    assert ckpt.verify(os.path.join(ckdir, names[-1])) is True

    sc = cv_train.main(base + chaos + ["--resume"])
    assert sc.round == 6
    _assert_params_equal(sa, sc)


@pytest.mark.chaos
def test_prefetcher_deterministic_under_injected_data_fault(tiny_cv):
    """A data load failing transiently ON THE PREFETCH THREAD must recover
    via the retry wrapper's RNG-snapshot restore and still serve the
    bit-identical round sequence — prefetch never perturbs the client
    stream."""
    a, _ = cv_train.build(_args())
    ms_a = [a.run_round(LR) for _ in range(4)]

    b, _ = cv_train.build(_args(("--fault_plan", "data_fail@1:times=2")))
    src = RoundPrefetcher(b, b.round, depth=2)
    try:
        ms_b = [b.commit_round(b.dispatch_round(src.next(), LR))[0]
                for _ in range(4)]
    finally:
        src.stop()
    assert [m["loss_sum"] for m in ms_a] == [m["loss_sum"] for m in ms_b]
    _assert_params_equal(a, b)


@pytest.mark.chaos
def test_async_periodic_checkpoints_land_verified(tiny_cv, tmp_path):
    """Periodic saves ride the writer thread in the async loop; by process
    end every committed checkpoint must verify and include the final
    round's synchronous save."""
    ckdir = str(tmp_path / "ck")
    s = cv_train.main(_argv(("--num_rounds", "6", "--checkpoint_dir", ckdir,
                             "--checkpoint_every", "2")))
    assert s.round == 6
    names = sorted(d for d in os.listdir(ckdir) if d.startswith("round_"))
    assert names and names[-1] == "round_00000006"
    for name in names:
        assert ckpt.verify(os.path.join(ckdir, name)) is True
    # no staging dirs leaked by the overlapped writes
    assert not [d for d in os.listdir(ckdir) if d.startswith(".tmp_round_")]


# ----------------------------------------------------- prefetcher contract


def test_prefetcher_serves_rounds_in_order(tiny_cv):
    """The prefetched sequence must equal inline prepare_round calls on an
    identically-seeded session: same cohorts, same batches, same snapshot
    chain (the double buffer only changes WHEN host work runs)."""
    a, _ = cv_train.build(_args())
    b, _ = cv_train.build(_args())
    inline = [a.prepare_round(i) for i in range(3)]
    src = RoundPrefetcher(b, 0, depth=2)
    try:
        fetched = [src.next() for _ in range(3)]
    finally:
        src.stop()
    for pa, pb in zip(inline, fetched):
        assert pa.rnd == pb.rnd
        np.testing.assert_array_equal(pa.ids, pb.ids)
        for k in pa.batch:
            np.testing.assert_array_equal(pa.batch[k], pb.batch[k])
        np.testing.assert_array_equal(np.asarray(pa.sub), np.asarray(pb.sub))


def test_prefetcher_degrades_exhausted_loader_to_masked_cohort(tiny_cv):
    """Retry exhaustion no longer kills the run (cohort fault tolerance):
    the prepared round comes back fully masked (validity all zero, zero
    batch) with every cohort id re-queued for a later round — on the
    prefetch thread exactly as inline."""
    from commefficient_tpu.federated import engine

    b, _ = cv_train.build(
        _args(("--fault_plan", "data_fail@0:times=99", "--max_retries", "1"))
    )
    src = RoundPrefetcher(b, 0, depth=2)
    try:
        prep = src.next()
    finally:
        src.stop()
    assert prep.masked == b.num_workers
    np.testing.assert_array_equal(
        np.asarray(prep.batch[engine.VALID_KEY]),
        np.zeros(b.num_workers, np.float32))
    assert prep.requeue_depth == b.num_workers
    assert sorted(prep.requeue) == sorted(int(i) for i in prep.ids)
    # the degraded round still runs: fully-dropped-cohort semantics
    m = b.commit_round(b.dispatch_round(prep, 0.05))[0]
    assert m["participants"] == 0.0 and m["clients_dropped"] == b.num_workers


def test_prefetcher_stop_unblocks_producer(tiny_cv):
    """stop() must join a producer blocked on a full queue (the preemption
    exit path cannot afford to leak a thread mid-assembly)."""
    b, _ = cv_train.build(_args())
    src = RoundPrefetcher(b, 0, depth=1)
    src.next()  # ensure the thread is live and refilling
    time.sleep(0.05)  # let it block on the full queue
    src.stop()
    assert not src._pf._thread.is_alive()


# --------------------------------------------------------- writer contract


def test_writer_coalesces_requests():
    gate = threading.Event()
    calls = []

    def save():
        gate.wait(5)
        calls.append(1)
        return f"p{len(calls)}"

    w = AsyncCheckpointWriter(save)
    w.request()
    deadline = time.monotonic() + 5
    while not w._busy and time.monotonic() < deadline:
        time.sleep(0.005)  # wait until the first save is IN flight
    for _ in range(4):
        w.request()  # all four coalesce into ONE follow-up save
    gate.set()
    w.drain()
    w.close()
    # four requests landed while a save was in flight: ONE follow-up save
    # ran (capturing the newest state), all four counted as coalesced
    assert len(calls) == 2
    assert w.saves_completed == 2 and w.saves_coalesced == 4
    assert w.last_path == "p2"


def test_writer_reraises_failure_at_drain():
    def bad():
        raise OSError("disk gone")

    w = AsyncCheckpointWriter(bad, alert=lambda m: None)
    w.request()
    with pytest.raises(OSError, match="disk gone"):
        w.drain()
    w.drain()  # error surfaced once; the writer stays usable
    w.close()


def test_writer_close_finishes_outstanding_work():
    calls = []
    w = AsyncCheckpointWriter(lambda: calls.append(1) or "p")
    w.request()
    w.close()
    assert calls == [1]
    with pytest.raises(RuntimeError, match="closed"):
        w.request()


def test_superseded_inflight_releases_state_batch_commit_exact(tiny_cv):
    """The HBM contract of the async pipeline: once a newer dispatch
    supersedes an in-flight round, its server-state tree is released (only
    the newest is ever published at a batch commit) — and the batch commit
    still produces the exact per-round metrics and final params of the
    synchronous loop."""
    s, _ = cv_train.build(_args())
    i1 = s.dispatch_round(s.prepare_round(0), LR)
    i2 = s.dispatch_round(s.prepare_round(1), LR)
    i1.release_state()
    assert i1.new_state is None  # nothing pins the intermediate tree
    out = s.commit_rounds([i1, i2], jax.device_get([i1.metrics, i2.metrics]))
    assert len(out) == 2 and s.round == 2

    b, _ = cv_train.build(_args())
    mb = [b.run_round(LR) for _ in range(2)]
    assert [m["loss_sum"] for m in out] == [m["loss_sum"] for m in mb]
    _assert_params_equal(s, b)
    # releasing the NEWEST entry is a contract violation, loudly
    i3 = s.dispatch_round(s.prepare_round(2), LR)
    i3.release_state()
    with pytest.raises(RuntimeError, match="release_state"):
        s.commit_rounds([i3], [jax.device_get(i3.metrics)])


def test_async_writer_failure_does_not_block_final_save(tiny_cv, tmp_path):
    """A periodic save failing on the writer thread hours into a run must
    not block the FINAL synchronous save at normal completion — that save
    is the corrective action."""
    from commefficient_tpu.federated.api import FedOptimizer
    from commefficient_tpu.runner import RunnerConfig, run_loop

    # checkpoint_dir arms emergency saves -> donation off -> writer eligible
    s, _ = cv_train.build(_args(("--checkpoint_dir", str(tmp_path / "ck"))))
    calls = []

    def flaky_save():
        calls.append(1)
        if len(calls) == 1:
            raise OSError("transient ENOSPC")
        return "saved"

    stats = run_loop(
        s, FedOptimizer(lambda _: LR, 1),
        RunnerConfig(total_rounds=4, eval_every=4, checkpoint_every=2,
                     checkpoint_dir=str(tmp_path / "ck")),
        save_ckpt=flaky_save,
    )
    assert s.round == 4
    assert stats.async_checkpoints >= 1  # the periodic save rode the writer
    assert len(calls) >= 2  # failed periodic + successful final


def test_session_reusable_after_async_loop(tiny_cv):
    """run_loop's exit path rewinds the live host RNG / device key to the
    committed boundary (the prefetcher prepared — and drew RNG for — rounds
    that were never dispatched), so continuing to drive the session stays on
    the bit-identical sequence the sync loop would produce."""
    from commefficient_tpu.federated.api import FedOptimizer
    from commefficient_tpu.runner import RunnerConfig, run_loop

    a, _ = cv_train.build(_args())
    b, _ = cv_train.build(_args())
    run_loop(a, FedOptimizer(lambda _: LR, 1),
             RunnerConfig(total_rounds=3, eval_every=3))  # async
    run_loop(b, FedOptimizer(lambda _: LR, 1),
             RunnerConfig(total_rounds=3, eval_every=3, sync_loop=True))
    _assert_params_equal(a, b)
    ma, mb = a.run_round(LR), b.run_round(LR)  # continue past the loop
    assert ma["loss_sum"] == mb["loss_sum"]
    _assert_params_equal(a, b)


# ------------------------------------------------------- session invariant


def test_evaluate_refuses_inflight_pipeline(tiny_cv):
    """Eval must only run at a drained boundary (the committed state is the
    only consistent — and, under donation, the only live — view)."""
    s, test_set = cv_train.build(_args())
    prep = s.prepare_round(0)
    infl = s.dispatch_round(prep, LR)
    with pytest.raises(RuntimeError, match="in-flight"):
        s.evaluate(test_set, 32)
    s.commit_round(infl)
    s.evaluate(test_set, 32)  # drained: fine


# ------------------------------- the drain that keeps one round queued


def _spy_commits(session, log, read_state=False):
    """Record (round after the commit, dispatches still in flight[, the
    committed params on the host]) at every commit_rounds call."""
    inner = session.commit_rounds

    def commit_rounds(infls, hosts):
        out = inner(infls, hosts)
        entry = (session.round, session._inflight)
        if read_state:
            entry += (jax.device_get(session.state["params"]),)
        log.append(entry)
        return out

    session.commit_rounds = commit_rounds


def _drive(session, test_set, cfgs):
    """run_loop once a config, with an eval and a row sink: (stats of each
    call, rows less time_s)."""
    from commefficient_tpu.federated.api import FedOptimizer
    from commefficient_tpu.runner import run_loop

    rows, stats = [], []
    for cfg in cfgs:
        stats.append(run_loop(
            session, FedOptimizer(lambda _: LR, 1), cfg,
            eval_fn=lambda: session.evaluate(test_set, 32),
            build_row=lambda **kw: kw, logger=rows))
    for r in rows:
        r.pop("time_s")
    return stats, rows


@pytest.mark.parametrize("depth", [3, 2, 1])
def test_kept_drain_loop_bit_identical_to_sync(tiny_cv, tmp_path, depth):
    """Two consecutive run_loop calls over an eval boundary (every 5) and
    a checkpoint boundary (every 7): a drain the depth triggers commits all
    but the newest dispatch and leaves exactly that one in flight, every
    boundary drain leaves none, and the state published at EVERY commit —
    a kept drain's too, read back here with donation off — the rows and
    the final state equal the --sync_loop run's bit for bit."""
    from commefficient_tpu.obs import registry as obreg
    from commefficient_tpu.runner import RunnerConfig

    def cfgs(ckdir, **kw):
        return [RunnerConfig(total_rounds=t, eval_every=5, checkpoint_every=7,
                             checkpoint_dir=str(tmp_path / ckdir), **kw)
                for t in (9, 16)]

    # --checkpoint_dir arms emergency saves, so the state is not donated
    ref, test_set = cv_train.build(_args(("--checkpoint_dir", "x")))
    ref_log = []
    _spy_commits(ref, ref_log, read_state=True)
    _, rows_ref = _drive(ref, test_set, cfgs("ref", sync_loop=True))
    by_round = {rnd: params for rnd, _, params in ref_log}
    assert sorted(by_round) == list(range(1, 17))

    reg = obreg.default()
    mark = reg.mark()
    s, _ = cv_train.build(_args(("--checkpoint_dir", "x")))
    log = []
    _spy_commits(s, log, read_state=True)
    stats, rows = _drive(s, test_set, cfgs("ck", max_inflight=depth))

    kept = [e for e in log if e[1] == 1]
    full = [e for e in log if e[1] == 0]
    assert len(kept) + len(full) == len(log)  # never more than one left
    assert sum(st.drains for st in stats) == len(log) == int(
        mark.delta("runner_drains_total"))
    assert sum(st.drains_kept for st in stats) == len(kept) == int(
        mark.delta("runner_drains_kept_total"))
    # boundaries at 5, 7, 9 (end of call 1), 10, 14, 15, 16: fully drained
    assert [e[0] for e in full if e[0] in (5, 7, 9, 10, 14, 15, 16)] == [
        5, 7, 9, 10, 14, 15, 16]
    if depth == 1:
        assert not kept and len(full) == 16
    else:
        assert kept and not set(e[0] for e in full) - {5, 7, 9, 10, 14, 15, 16}
    for rnd, _, params in log:
        for x, y in zip(jax.tree.leaves(params),
                        jax.tree.leaves(by_round[rnd])):
            np.testing.assert_array_equal(x, y)
    assert rows == rows_ref and [r["rnd"] for r in rows] == [5, 9, 10, 15, 16]
    _assert_params_equal(ref, s)
    assert s._inflight == 0 and s._head_state is None


@pytest.mark.chaos
@pytest.mark.parametrize("fault,on_nonfinite,exit_code,rounds_done", [
    ("preempt@3", "skip", EXIT_RESUMABLE, 4),
    ("nonfinite@1", "halt", None, 3),
], ids=["preempt", "nonfinite_halt"])
def test_exit_while_a_round_is_kept_queued_saves_drained_state(
        tiny_cv, fault, on_nonfinite, exit_code, rounds_done):
    """Depth 3, no boundary before round 6: rounds 0-2 are dispatched, a
    kept drain commits 0 and 1 and leaves 2 queued. A SIGTERM at round 3's
    dispatch, or the non-finite round 1 that this drain commits under
    --on_nonfinite halt, must commit the kept round (and whatever followed
    it) before the exit save: the save sees nothing in flight, and its
    state is the sync loop's after as many rounds."""
    from commefficient_tpu.federated.api import FedOptimizer
    from commefficient_tpu.runner import RunnerConfig, run_loop

    s, _ = cv_train.build(_args((
        "--fault_plan", fault, "--on_nonfinite", on_nonfinite,
        "--checkpoint_dir", "x")))
    log, seen = [], []
    _spy_commits(s, log)

    def save_ckpt():
        seen.append(((s.round, s._inflight),
                     jax.device_get(s.state["params"])))
        return "saved"

    with pytest.raises(SystemExit) as ei:
        run_loop(s, FedOptimizer(lambda _: LR, 1),
                 RunnerConfig(total_rounds=6, eval_every=6, max_inflight=3,
                              on_nonfinite=on_nonfinite),
                 save_ckpt=save_ckpt)
    if exit_code is None:
        assert "non-finite" in str(ei.value.code)
    else:
        assert ei.value.code == exit_code
    assert log[0] == (2, 1)  # the kept drain came first, one round queued
    assert log[-1] == (rounds_done, 0)
    assert [view for view, _ in seen] == [(rounds_done, 0)]

    # the reference never sees the signal; a poisoned round it does see
    ref, _ = cv_train.build(_args((
        *(() if "preempt" in fault else ("--fault_plan", fault)),
        "--on_nonfinite", on_nonfinite)))
    for _ in range(rounds_done):
        ref.run_round(LR)
    for x, y in zip(jax.tree.leaves(seen[0][1]),
                    jax.tree.leaves(jax.device_get(ref.state["params"]))):
        np.testing.assert_array_equal(x, y)


def test_commit_rounds_publishes_the_prefix_it_is_given(tiny_cv):
    """commit_rounds over a prefix of what is in flight publishes the
    prefix's LAST state, RNG snapshot and re-queue, keeps the head of the
    dispatch chain for the dispatch left in flight, and with donation off
    that published state is a live copy; committing the rest leaves the
    session equal to one that was always fully drained."""
    flags = ("--fault_plan", "client_drop@1:clients=0+1",
             "--checkpoint_dir", "x")  # a drop fills the re-queue; no donation
    s, _ = cv_train.build(_args(flags))
    i1, i2, i3 = (s.dispatch_round(s.prepare_round(r), LR) for r in range(3))
    i1.release_state()  # two newer dispatches: what the loop releases
    assert i2.requeue and i2.requeue != i1.requeue
    out = s.commit_rounds([i1, i2], jax.device_get([i1.metrics, i2.metrics]))
    assert len(out) == 2 and s.round == 2 and s._inflight == 1
    assert s.state is i2.new_state and s._head_state is i3.new_state
    assert s.rng_snapshot is i2.snapshot
    assert s._requeue_committed == i2.requeue
    assert s._requeue_ages_committed == i2.requeue_ages

    b, _ = cv_train.build(_args(flags))
    mb = [b.run_round(LR) for _ in range(2)]
    assert [m["loss_sum"] for m in out] == [m["loss_sum"] for m in mb]
    _assert_params_equal(s, b)  # readable while round 2 is still in flight
    assert b._requeue_committed == s._requeue_committed

    out += s.commit_rounds([i3], [jax.device_get(i3.metrics)])
    mb.append(b.run_round(LR))
    assert s.round == 3 and s._inflight == 0 and s._head_state is None
    assert [m["loss_sum"] for m in out] == [m["loss_sum"] for m in mb]
    _assert_params_equal(s, b)
    assert s._requeue_committed == b._requeue_committed
    np.testing.assert_array_equal(s.rng_snapshot[0][1], b.rng_snapshot[0][1])
