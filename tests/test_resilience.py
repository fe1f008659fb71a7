"""Fault-injection + failure-recovery tests (resilience/ and the hardening it
proves out: atomic+checksummed checkpoints, the non-finite round guard, retry
wrappers, preemption handling).

The `chaos`-marked tests drive the REAL cv_train path (build/main) on a tiny
MLP (the checkpoint/recovery logic is model-agnostic; ResNet-9 compiles for
minutes on this 1-core box). Everything is seeded — FaultPlan, data, init —
so a failure here reproduces, it doesn't flake. scripts/chaos_smoke.sh runs
exactly this marker."""

import os
import signal
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp  # noqa: F401 — chaos fixtures build jax models

import cv_train
from commefficient_tpu.resilience import (
    EXIT_RESUMABLE, FaultPlan, InjectedTransientError, PreemptionHandler,
    RetryPolicy, with_retries,
)
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
    """cv_train with a synthetic 64-image CIFAR shard and a 2-layer MLP in
    place of ResNet-9 (same trick as test_checkpoint: recovery logic is
    model-agnostic; the real model's CLI path is covered by
    test_determinism/test_golden)."""
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


# ------------------------------------------------------------- faults.py unit


def test_fault_plan_parse():
    plan = FaultPlan.parse(
        "preempt@3;nonfinite@4:value=inf;data_fail@1,2:times=2;seed=9"
    )
    assert plan.seed == 9
    assert plan.spec("preempt", 3).rounds == (3,)
    assert plan.spec("preempt", 4) is None
    assert plan.spec("nonfinite", 4).params == {"value": "inf"}
    assert plan.spec("data_fail", 2).params["times"] == 2  # coerced at parse
    # round-less spec matches any round (e.g. dist_init has no round)
    assert FaultPlan.parse("dist_init:times=2").spec("dist_init") is not None
    # off-by-default contract
    assert FaultPlan.parse("") is None and FaultPlan.parse(None) is None
    with pytest.raises(ValueError):
        FaultPlan.parse("explode@1")
    with pytest.raises(ValueError):
        FaultPlan.parse("stall@1:secs")
    # a typo'd param key must fail parse, not silently under-inject
    with pytest.raises(ValueError, match="unknown param"):
        FaultPlan.parse("data_fail@1:time=5")
    # a bad param VALUE must reject the plan at launch, not crash at the
    # scheduled round hours into the run
    with pytest.raises(ValueError, match="bad value"):
        FaultPlan.parse("data_fail@1:times=two")
    with pytest.raises(ValueError, match="bad value"):
        FaultPlan.parse("nonfinite@1:value=infinity")
    # dist_init fires at bootstrap (rnd=None): a round schedule would
    # silently never inject, so it must not parse
    with pytest.raises(ValueError, match="bootstrap"):
        FaultPlan.parse("dist_init@0:times=2")


def test_fire_transient_budget_is_per_round_site():
    plan = FaultPlan.parse("data_fail@1:times=2")
    plan.fire_transient("data_fail", 0)  # not scheduled for round 0
    for _ in range(2):
        with pytest.raises(InjectedTransientError):
            plan.fire_transient("data_fail", 1)
    plan.fire_transient("data_fail", 1)  # budget spent -> succeeds


def test_stall_site_sleeps_once():
    plan = FaultPlan.parse("stall@0:secs=0.05")
    t0 = time.monotonic()
    plan.data_load(0)
    first = time.monotonic() - t0
    t0 = time.monotonic()
    plan.data_load(0)  # one-shot: a retried/repeated hit must not re-stall
    again = time.monotonic() - t0
    assert first >= 0.05 and again < 0.05


def test_eval_stall_site_sleeps_once_on_scheduled_round():
    plan = FaultPlan.parse("eval_stall@2:secs=0.05")
    t0 = time.monotonic()
    plan.eval_load(0)  # not scheduled for round 0
    assert time.monotonic() - t0 < 0.05
    t0 = time.monotonic()
    plan.eval_load(2)
    assert time.monotonic() - t0 >= 0.05
    t0 = time.monotonic()
    plan.eval_load(2)  # one-shot per round
    assert time.monotonic() - t0 < 0.05
    # the training-loader `stall` spec must NOT leak into the eval site
    assert FaultPlan.parse("stall@2:secs=9").spec("eval_stall", 2) is None


def test_retry_counts_surface_failed_attempts():
    """Chaos runs are countable: every failed attempt bumps the per-site
    process counter that `retry_counts()` returns."""
    from commefficient_tpu.resilience import reset_retry_counts, retry_counts

    reset_retry_counts()
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise OSError("flake")
        return "ok"

    with_retries(flaky, site="countme",
                 policy=RetryPolicy(max_retries=3, base_delay_s=0.0),
                 sleep=lambda d: None, log=lambda m: None)
    assert retry_counts()["countme"] == 2
    assert "neverfailed" not in retry_counts()
    reset_retry_counts()
    assert retry_counts() == {}


# -------------------------------------------------------------- retry.py unit


def test_with_retries_recovers_then_exhausts():
    calls, logs = [], []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise OSError("transient flake")
        return "ok"

    out = with_retries(
        flaky, site="t", policy=RetryPolicy(max_retries=3, base_delay_s=0.0),
        sleep=lambda d: None, log=logs.append,
    )
    assert out == "ok" and len(calls) == 3
    assert len(logs) == 2 and all("retry[t]" in line for line in logs)

    attempts = []

    def always_fails():
        attempts.append(1)
        raise OSError("permanent")

    with pytest.raises(OSError, match="permanent"):
        with_retries(
            always_fails, site="t",
            policy=RetryPolicy(max_retries=2, base_delay_s=0.0),
            sleep=lambda d: None, log=logs.append,
        )
    assert len(attempts) == 3  # 1 try + 2 retries, last error re-raised


def test_dist_init_retry_tears_down_half_initialized_client(monkeypatch):
    """Regression: jax assigns its global distributed client BEFORE
    connect(), so a failed first attempt used to make every retry raise
    'initialize should only be called once' — masking the real connectivity
    error and guaranteeing exhaustion. The join must shutdown() between
    attempts so each retry is genuine."""
    import jax

    from commefficient_tpu.parallel import distributed

    calls = {"init": 0, "shutdown": 0}
    client_assigned = {"v": False}

    def fake_initialize(**kw):
        if client_assigned["v"]:
            raise RuntimeError("initialize should only be called once")
        client_assigned["v"] = True  # assigned before connect, like real jax
        calls["init"] += 1
        if calls["init"] < 3:
            raise OSError("coordinator not listening yet")

    def fake_shutdown():
        calls["shutdown"] += 1
        client_assigned["v"] = False

    monkeypatch.setattr(jax.distributed, "initialize", fake_initialize)
    monkeypatch.setattr(jax.distributed, "shutdown", fake_shutdown)
    monkeypatch.setattr(distributed, "_INITIALIZED", False)
    monkeypatch.setattr(
        "commefficient_tpu.utils.hermetic.backends_initialized", lambda: False
    )
    assert distributed.initialize(
        force=True, retry_policy=RetryPolicy(max_retries=3, base_delay_s=0.0)
    )
    assert calls["init"] == 3  # two real failures, then a genuine success
    assert calls["shutdown"] == 2  # teardown between every failed attempt


def test_retry_jitter_is_seeded():
    pol = RetryPolicy(max_retries=3, base_delay_s=0.1)
    a = [pol.delay_s(i, np.random.RandomState(5)) for i in range(3)]
    b = [pol.delay_s(i, np.random.RandomState(5)) for i in range(3)]
    assert a == b
    assert a[1] > a[0]  # exponential backoff grows


# -------------------------------------------------------- preemption.py unit


def test_preemption_handler_sets_flag_and_restores_previous():
    seen = []
    prev = signal.signal(signal.SIGTERM, lambda s, f: seen.append(s))
    try:
        with PreemptionHandler() as pre:
            assert not pre.triggered
            os.kill(os.getpid(), signal.SIGTERM)
            assert pre.triggered  # flag only — no exit, no exception
        # the previous handler is back in place after exit
        os.kill(os.getpid(), signal.SIGTERM)
        assert seen == [signal.SIGTERM]
    finally:
        signal.signal(signal.SIGTERM, prev)
    assert EXIT_RESUMABLE == 75  # EX_TEMPFAIL: the supervisor contract


# ----------------------------------------------------- chaos: engine recovery


@pytest.mark.chaos
def test_data_load_retry_replays_identical_round(tiny_cv):
    """A transiently-failing data load must recover AND yield the exact batch
    the clean run sees: the injection site fires before any host RNG is
    consumed and a failed attempt restores the RNG snapshot."""
    a, _ = cv_train.build(_args())
    ma = a.run_round(LR)
    b, _ = cv_train.build(_args(("--fault_plan", "data_fail@0:times=2")))
    mb = b.run_round(LR)
    assert ma["loss_sum"] == mb["loss_sum"]
    for x, y in zip(
        jax.tree.leaves(jax.device_get(a.state["params"])),
        jax.tree.leaves(jax.device_get(b.state["params"])),
    ):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.chaos
def test_eval_stall_fires_in_real_eval_path(tiny_cv):
    """The eval_stall site is wired into FederatedSession.evaluate (the eval
    loader the round-5 FEMNIST stall actually lived in): scheduled round
    stalls once, and eval results are unaffected."""
    s, test_set = cv_train.build(
        _args(("--fault_plan", "eval_stall@1:secs=0.3"))
    )
    ev0 = s.evaluate(test_set, 32)  # round 0: no stall; compiles eval
    s.run_round(LR)  # -> round 1
    t0 = time.monotonic()
    ev1 = s.evaluate(test_set, 32)
    stalled = time.monotonic() - t0
    t0 = time.monotonic()
    ev2 = s.evaluate(test_set, 32)  # one-shot: same round, no re-stall
    clean = time.monotonic() - t0
    assert stalled >= 0.3 and stalled - clean >= 0.25
    assert ev1 == ev2 and ev0.keys() == ev1.keys()


def _snap(session):
    st = jax.device_get(session.state)
    from jax.flatten_util import ravel_pytree

    return (
        np.asarray(ravel_pytree(st["params"])[0]),
        np.asarray(st["mode_state"]["Vvelocity"]),
        np.asarray(st["mode_state"]["Verror"]),
    )


@pytest.mark.chaos
def test_nonfinite_round_skipped_keeps_state_clean(tiny_cv):
    """An injected NaN burst through the real gradient path is skipped like a
    fully-dropped cohort: momentum decays (V2 = rho*V1), error feedback and
    params never absorb the poison — pinned against the clean run's state —
    and the skip is visible in metrics."""
    a, _ = cv_train.build(_args())
    for _ in range(2):
        a.run_round(LR)
    p1, v1, e1 = _snap(a)

    b, _ = cv_train.build(_args(("--fault_plan", "nonfinite@2")))
    ms = [b.run_round(LR) for _ in range(3)]
    assert [m["nonfinite_rounds"] for m in ms] == [0.0, 0.0, 1.0]
    p2, v2, e2 = _snap(b)
    # clean prefix: rounds 0-1 bit-identical to the un-faulted run
    rho = np.float32(0.9)
    np.testing.assert_allclose(v2, rho * v1, rtol=1e-6)
    np.testing.assert_array_equal(e2, e1)
    np.testing.assert_allclose(p2, p1 - np.float32(LR) * v2, rtol=1e-6, atol=1e-7)
    assert np.isfinite(p2).all() and np.isfinite(v2).all()
    # the session keeps training normally after the skipped round
    m = b.run_round(LR)
    assert m["nonfinite_rounds"] == 0.0
    assert np.isfinite(_snap(b)[0]).all()

    # and the guard is load-bearing: --on_nonfinite off lets the poison in
    c, _ = cv_train.build(
        _args(("--fault_plan", "nonfinite@2", "--on_nonfinite", "off"))
    )
    for _ in range(3):
        c.run_round(LR)
    assert not np.isfinite(_snap(c)[0]).all()


@pytest.mark.chaos
def test_donate_state_off_is_bit_transparent(tiny_cv, tmp_path):
    """--checkpoint_dir disables state-buffer donation (so the watchdog's
    mid-round emergency save can read the live state on real accelerators);
    donation only changes buffer reuse, never numerics — pin that."""
    a, _ = cv_train.build(_args())
    assert a._donate_state
    b, _ = cv_train.build(_args(("--checkpoint_dir", str(tmp_path / "ck"))))
    assert not b._donate_state
    # the HBM opt-out keeps donation (and gives up the mid-round save)
    opt, _ = cv_train.build(_args(("--checkpoint_dir", str(tmp_path / "ck"),
                                   "--no_emergency_checkpoint")))
    assert opt._donate_state
    for _ in range(2):
        a.run_round(LR)
        b.run_round(LR)
    for x, y in zip(
        jax.tree.leaves(jax.device_get(a.state["params"])),
        jax.tree.leaves(jax.device_get(b.state["params"])),
    ):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.chaos
def test_nonfinite_round_with_dp_releases_no_noise(tiny_cv):
    """A skipped round transmits nothing, so it must release nothing: with
    central DP on, the poisoned round's update must be EXACTLY the momentum
    decay (V2 = rho*V1, p2 = p1 - lr*V2) — any leaked DP noise on the zeroed
    aggregate would shift both and feed pure noise into the params."""
    ex = ("--dp_clip", "1.0", "--dp_noise", "0.5",
          "--fault_plan", "nonfinite@2")
    b, _ = cv_train.build(_args(ex))
    for _ in range(2):
        b.run_round(LR)
    p1, v1, _ = _snap(b)
    m = b.run_round(LR)
    assert m["nonfinite_rounds"] == 1.0
    p2, v2, _ = _snap(b)
    rho = np.float32(0.9)
    np.testing.assert_allclose(v2, rho * v1, rtol=1e-6)
    np.testing.assert_allclose(p2, p1 - np.float32(LR) * v2, rtol=1e-6,
                               atol=1e-7)


# --------------------------------------------- chaos: checkpoint IO recovery


@pytest.mark.chaos
def test_checkpoint_write_retries_recover(tiny_cv, tmp_path):
    s, _ = cv_train.build(_args())
    s.run_round(LR)  # session.round -> 1
    path = ckpt.save(
        str(tmp_path / "ck"), s, fault_plan=FaultPlan.parse("ckpt_fail@1:times=2"),
        retry_policy=RetryPolicy(max_retries=3, base_delay_s=0.001),
    )
    assert ckpt.verify(path) is True  # recovered write is complete + clean
    with pytest.raises(InjectedTransientError):
        ckpt.save(
            str(tmp_path / "ck2"), s,
            fault_plan=FaultPlan.parse("ckpt_fail@1:times=5"),
            retry_policy=RetryPolicy(max_retries=1, base_delay_s=0.001),
        )
    # the failed save left no committed round_* dir behind
    ck2 = tmp_path / "ck2"
    assert not ck2.is_dir() or not any(
        d.startswith("round_") for d in os.listdir(ck2)
    )


@pytest.mark.chaos
def test_same_round_resave_overwrites_cleanly(tiny_cv, tmp_path):
    """An emergency save of a round that already has a committed checkpoint
    (watchdog stage 3 after a scheduled save) replaces it via rename-aside —
    the result verifies and no displaced .old copy lingers."""
    s, _ = cv_train.build(_args())
    s.run_round(LR)
    ckdir = str(tmp_path / "ck")
    p1 = ckpt.save(ckdir, s)
    p2 = ckpt.save(ckdir, s)
    assert p1 == p2 and ckpt.verify(p2) is True
    assert not [d for d in os.listdir(ckdir) if d.endswith(".displaced")]
    # crash window between the two renames: only the displaced copy exists,
    # and restore_latest must recover the round from it
    os.rename(p2, p2 + ".displaced")
    s2, _ = cv_train.build(_args())
    restored = ckpt.restore_latest(ckdir, s2)
    assert restored.endswith(".displaced") and s2.round == 1


@pytest.mark.chaos
def test_corrupt_and_truncated_checkpoints_fall_back(tiny_cv, tmp_path, capsys):
    """The headline recovery guarantee of the manifest: a damaged latest
    checkpoint costs one checkpoint interval, not the run."""
    ckdir = str(tmp_path / "ck")
    s, _ = cv_train.build(_args())
    for _ in range(3):
        s.run_round(LR)
        ckpt.save(ckdir, s)
    names = sorted(d for d in os.listdir(ckdir) if d.startswith("round_"))
    assert len(names) == 3
    # newest: simulated partial write (truncation); middle: bit-flip
    t = FaultPlan._largest_data_file(os.path.join(ckdir, names[-1]))
    with open(t, "r+b") as f:
        f.truncate(os.path.getsize(t) // 2)
    c = FaultPlan._largest_data_file(os.path.join(ckdir, names[-2]))
    with open(c, "r+b") as f:
        f.seek(os.path.getsize(c) // 2)
        b = f.read(1)
        f.seek(-1, os.SEEK_CUR)
        f.write(bytes([b[0] ^ 0xFF]))

    s2, _ = cv_train.build(_args())
    restored = ckpt.restore_latest(ckdir, s2)
    err = capsys.readouterr().err
    assert restored.endswith(names[0]) and s2.round == 1
    assert err.count("FAILED integrity") == 2
    assert "recovered" in err and "skipping 2 damaged" in err


@pytest.mark.chaos
def test_fault_plan_corrupts_committed_checkpoint(tiny_cv, tmp_path):
    """ckpt_corrupt lands AFTER the atomic commit + manifest, so verification
    (not luck) catches it; with every candidate damaged, restore_latest
    refuses to silently restart from round 0."""
    s, _ = cv_train.build(_args(("--fault_plan", "ckpt_corrupt@1")))
    s.run_round(LR)
    path = ckpt.save(str(tmp_path / "ck"), s, fault_plan=s.fault_plan)
    assert ckpt.verify(path) is False
    s2, _ = cv_train.build(_args())
    with pytest.raises(RuntimeError, match="no restorable checkpoint"):
        ckpt.restore_latest(str(tmp_path / "ck"), s2)
    # an empty/missing dir is a fresh run, not an error
    assert ckpt.restore_latest(str(tmp_path / "fresh"), s2) is None


@pytest.mark.chaos
def test_resume_replays_dropout_masks(tiny_cv, tmp_path):
    """The device-side PRNG stream (participation masks) is checkpointed, so
    a resumed run under client dropout replays the uninterrupted run's
    cohorts bit-for-bit — not just the host-side client sampling."""
    ex = ("--client_dropout", "0.5")
    a, _ = cv_train.build(_args(ex))
    parts_a = [a.run_round(LR)["participants"] for _ in range(6)]
    # the seed produces at least one non-full cohort (note: the 8-way CPU
    # mesh rounds num_workers up to 8, so "full" is a.num_workers, not 2)
    assert min(parts_a) < a.num_workers

    b, _ = cv_train.build(_args(ex))
    for _ in range(3):
        b.run_round(LR)
    path = ckpt.save(str(tmp_path / "ckd"), b)
    c, _ = cv_train.build(_args(ex))
    ckpt.restore(path, c)
    parts_c = [c.run_round(LR)["participants"] for _ in range(3)]
    assert parts_c == parts_a[3:]
    for x, y in zip(
        jax.tree.leaves(jax.device_get(a.state["params"])),
        jax.tree.leaves(jax.device_get(c.state["params"])),
    ):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.chaos
def test_emergency_save_mid_round_keeps_rng_consistent(tiny_cv, tmp_path):
    """A watchdog emergency checkpoint fires from the timer thread while the
    in-flight round has already advanced the host sampling RNG. save() must
    write the round-boundary snapshot, not the live stream — otherwise the
    resumed run re-samples that round from a stream advanced past its draws
    and trains a cohort no deterministic run of this seed produces."""
    a, _ = cv_train.build(_args())
    for _ in range(2):
        a.run_round(LR)
    # the stuck round 2 has already consumed the host RNG for its sampling
    a.train_set.sample_clients(a.rng, a.num_workers)
    path = ckpt.save(str(tmp_path / "ck"), a)

    b, _ = cv_train.build(_args())
    ckpt.restore(path, b)
    c, _ = cv_train.build(_args())  # clean reference: RNG never torn
    for _ in range(2):
        c.run_round(LR)
    mb, mc = b.run_round(LR), c.run_round(LR)
    assert mb["loss_sum"] == mc["loss_sum"]
    for x, y in zip(
        jax.tree.leaves(jax.device_get(b.state["params"])),
        jax.tree.leaves(jax.device_get(c.state["params"])),
    ):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# --------------------------------------- chaos: cohort-level fault tolerance


@pytest.mark.chaos
def test_client_drop_degrades_round_and_requeues(tiny_cv):
    """An injected client_drop degrades ONE round (participants down by the
    dropped count, clients_dropped counted, requeue depth visible) and the
    dropped client is served back into the next cohort instead of losing its
    data; training continues normally."""
    s, _ = cv_train.build(
        _args(("--fault_plan", "client_drop@1:clients=0")))
    W = s.num_workers  # the 8-way CPU mesh rounds the cohort up to 8
    m0 = s.run_round(LR)
    assert m0["participants"] == W and m0["clients_dropped"] == 0.0
    m1 = s.run_round(LR)
    assert m1["clients_dropped"] == 1.0
    assert m1["participants"] == W - 1
    assert m1["requeue_depth"] == 1.0
    assert len(s._requeue) == 1
    m2 = s.run_round(LR)  # the queued client is substituted into round 2
    assert m2["requeue_depth"] == 0.0 and len(s._requeue) == 0
    assert m2["participants"] == W
    assert np.isfinite(_snap(s)[0]).all()


@pytest.mark.chaos
def test_overlapping_drop_specs_requeue_each_client_once(tiny_cv):
    """Two client_drop specs naming the same position in the same round must
    queue that client ONCE — a double-queued id would displace two sampled
    clients in later rounds and train the same shard twice."""
    s, _ = cv_train.build(_args((
        "--fault_plan", "client_drop@1:clients=0;client_drop@1:clients=0+2")))
    s.run_round(LR)
    m = s.run_round(LR)
    assert m["clients_dropped"] == 2.0
    assert len(s._requeue) == len(set(s._requeue)) == 2


@pytest.mark.chaos
def test_requeue_policy_fifo_is_bit_unchanged(tiny_cv):
    """The --requeue_policy knob's compatibility pin: the default (fifo)
    serves the queue in exactly the pre-knob order and the whole run —
    params, metrics, queue state — is bit-identical to a session built
    without the kwarg at all. Drops in two consecutive rounds build a
    2-deep queue so the ORDER of substitution is actually exercised."""
    plan = ("client_drop@1:clients=0;client_drop@2:clients=1",)

    def run(extra=()):
        s, _ = cv_train.build(_args(("--fault_plan",) + plan + extra))
        rows = [s.run_round(LR) for _ in range(5)]
        return s, rows

    s_default, rows_default = run()
    s_fifo, rows_fifo = run(("--requeue_policy", "fifo"))
    assert s_default._requeue_policy == "fifo"  # the default IS fifo
    for a, b in zip(rows_default, rows_fifo):
        assert a == b
    np.testing.assert_array_equal(*map(lambda s: _snap(s)[0],
                                       (s_default, s_fifo)))
    assert list(s_default._requeue) == list(s_fifo._requeue)


@pytest.mark.chaos
def test_requeue_policy_aged_is_deterministic_and_serves_all(tiny_cv):
    """The aged stub: weighted-by-rounds-waiting serving order from a
    pinned dedicated seed — two identical sessions agree bit-for-bit
    (deterministic), every dropped client is eventually served (no
    starvation in the drained case), and the SAMPLED cohort stream is
    policy-invariant (the dedicated RandomState consumes no host-sampling
    RNG: a later clean round samples the same cohort under both policies)."""
    plan = ("--fault_plan", "client_drop@1:clients=0+1", "--num_workers", "2")

    def run(policy):
        s, _ = cv_train.build(_args(plan + ("--requeue_policy", policy)))
        rows = [s.run_round(LR) for _ in range(6)]
        return s, rows

    s_a, rows_a = run("aged")
    s_b, rows_b = run("aged")
    for a, b in zip(rows_a, rows_b):
        assert a == b  # pinned seed: deterministic replay
    np.testing.assert_array_equal(_snap(s_a)[0], _snap(s_b)[0])
    assert not s_a._requeue  # both dropped clients were served back
    # policy-invariant sampling: the host RNG state after the run is the
    # same under fifo — the aged draw came from the dedicated stream
    s_f, _ = run("fifo")[0], None
    assert s_f.rng.get_state()[1].tolist() == s_a.rng.get_state()[1].tolist()

    # the weighted order itself: with strongly unequal ages the older
    # client wins the front slot for this pinned seed deterministically
    s_a._requeue.extend([3, 4])
    s_a._requeue_enqueued.update({3: 0, 4: s_a.round - 1})
    order1 = s_a._aged_order(list(s_a._requeue), s_a.round)
    order2 = s_a._aged_order(list(s_a._requeue), s_a.round)
    assert order1 == order2 and set(order1) == {3, 4}


@pytest.mark.chaos
def test_periodic_saves_gated_to_process_zero(tiny_cv, tmp_path, monkeypatch):
    """make_save_ckpt is the one-writer-per-job gate for EVERY save the
    runner schedules (periodic, halt, final, emergency — not just the
    preemption path): a non-zero process writes nothing and returns None."""
    from commefficient_tpu.runner.loop import make_save_ckpt

    s, _ = cv_train.build(_args())
    s.run_round(LR)
    ckdir = str(tmp_path / "ck")
    save = make_save_ckpt(s, ckdir)
    monkeypatch.setattr(jax, "process_index", lambda: 1)
    assert save() is None and not os.path.isdir(ckdir)
    monkeypatch.undo()
    path = save()  # process 0: the real write
    assert path and ckpt.verify(path) is True


@pytest.mark.chaos
def test_cli_rejects_unreachable_client_fault_schedule(tiny_cv):
    """A client_* site scheduled past the run's end fails at LAUNCH (the CLI
    validates against the full run length), not silently never-fires."""
    with pytest.raises(ValueError, match="can never fire"):
        cv_train.main(_argv(
            ("--num_rounds", "3", "--fault_plan", "client_drop@5:clients=0")))


@pytest.mark.chaos
def test_client_straggle_is_slow_but_bit_transparent(tiny_cv):
    """A straggling client stalls its round's preparation (watchdog/overlap
    fodder) but changes no bits: the run equals the un-faulted run exactly."""
    a, _ = cv_train.build(_args())
    b, _ = cv_train.build(
        _args(("--fault_plan", "client_straggle@1:clients=0,secs=0.3")))
    for _ in range(2):
        a.run_round(LR)
    t0 = time.monotonic()
    for _ in range(2):
        b.run_round(LR)
    assert time.monotonic() - t0 >= 0.3
    for x, y in zip(
        jax.tree.leaves(jax.device_get(a.state["params"])),
        jax.tree.leaves(jax.device_get(b.state["params"])),
    ):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.chaos
def test_client_poison_quarantined_like_a_drop(tiny_cv):
    """The quarantine acceptance pin through the real CLI path: a
    client_poison update (adversarially large, through the real gradients)
    is rejected with params bit-equal to the run where that client is
    DROPPED instead — and the identical clean run quarantines nothing."""
    clip = ("--client_update_clip", "10")
    a, _ = cv_train.build(_args((
        *clip, "--fault_plan", "client_poison@1:clients=1,value=big")))
    ma = [a.run_round(LR) for _ in range(2)]
    assert [m["clients_quarantined"] for m in ma] == [0.0, 1.0]
    assert ma[1]["participants"] == a.num_workers - 1
    assert np.isfinite(_snap(a)[0]).all()

    b, _ = cv_train.build(_args((
        *clip, "--fault_plan", "client_drop@1:clients=1")))
    for _ in range(2):
        b.run_round(LR)
    for x, y in zip(
        jax.tree.leaves(jax.device_get(a.state["params"])),
        jax.tree.leaves(jax.device_get(b.state["params"])),
    ):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    c, _ = cv_train.build(_args(clip))  # clean run, quarantine armed
    mc = [c.run_round(LR) for _ in range(2)]
    assert all(m["clients_quarantined"] == 0.0 for m in mc)
    assert all(m["participants"] == c.num_workers for m in mc)


@pytest.mark.chaos
def test_client_drop_resume_mid_degraded_run_bit_identical(tiny_cv, tmp_path):
    """Checkpoint + resume MID-degraded-run: preempted in the same round the
    drop fired, the re-queue state rides the checkpoint (meta.json), so the
    resumed run serves the dropped client at the same later round the
    uninterrupted run does — final params bit-identical."""
    base = _argv(("--num_rounds", "6"))
    fault = "client_drop@2:clients=0"
    sa = cv_train.main(base + ["--fault_plan", fault])
    assert sa.round == 6
    params_a = jax.device_get(sa.state["params"])

    ckdir = str(tmp_path / "ck")
    chaos = ["--checkpoint_dir", ckdir,
             "--fault_plan", f"{fault};preempt@2"]
    with pytest.raises(SystemExit) as ei:
        cv_train.main(base + chaos)
    assert ei.value.code == EXIT_RESUMABLE
    # the emergency checkpoint carries the un-served re-queue
    import json

    latest = sorted(d for d in os.listdir(ckdir)
                    if d.startswith("round_") and "." not in d)[-1]
    with open(os.path.join(ckdir, latest, "meta.json")) as f:
        assert len(json.load(f)["requeued"]) == 1

    sc = cv_train.main(base + chaos + ["--resume"])
    assert sc.round == 6
    for x, y in zip(
        jax.tree.leaves(params_a),
        jax.tree.leaves(jax.device_get(sc.state["params"])),
    ):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.chaos
def test_host_preempt_fires_only_on_matching_host(tiny_cv, tmp_path):
    """host_preempt targets ONE simulated host by jax.process_index(): host=0
    preempts this (single-process, index 0) run through the coordinated
    path; host=1 does not exist in a single-process job and is rejected at
    LAUNCH (an unfireable site = a vacuous chaos run), as is a round past
    the run's end."""
    base = _argv(("--num_rounds", "4"))
    ck = ["--checkpoint_dir", str(tmp_path / "ck")]
    with pytest.raises(SystemExit) as ei:
        cv_train.main(base + ck + ["--fault_plan", "host_preempt@1:host=0"])
    assert ei.value.code == EXIT_RESUMABLE
    with pytest.raises(ValueError, match="can never fire"):
        cv_train.main(base + ["--fault_plan", "host_preempt@1:host=1"])
    with pytest.raises(ValueError, match="can never fire"):
        cv_train.main(base + ["--fault_plan", "host_preempt@9:host=0"])


@pytest.mark.chaos
def test_coordinated_preemption_stops_unsignalled_host(tiny_cv, tmp_path,
                                                       monkeypatch):
    """The multi-host acceptance pin, simulated: this 'host' receives NO
    SIGTERM, but the cross-host max-reduce reports a peer was signalled —
    the loop must still drain, checkpoint the agreed round, and exit 75
    (without agreement this host would run to completion while the
    signalled peer exited, desyncing the job)."""
    from commefficient_tpu.parallel import distributed
    from commefficient_tpu.runner import loop as rloop

    calls = {"n": 0}

    def fake_all_hosts_max(v):
        calls["n"] += 1
        return 1 if calls["n"] >= 3 else int(v)

    monkeypatch.setattr(rloop, "_process_count", lambda: 2)
    monkeypatch.setattr(distributed, "all_hosts_max", fake_all_hosts_max)
    ckdir = str(tmp_path / "ck")
    with pytest.raises(SystemExit) as ei:
        cv_train.main(_argv(("--num_rounds", "8", "--checkpoint_dir", ckdir)))
    assert ei.value.code == EXIT_RESUMABLE
    assert calls["n"] >= 3  # the agreement ran at round boundaries
    names = sorted(d for d in os.listdir(ckdir) if d.startswith("round_"))
    assert names and names[-1] == "round_00000003"  # the agreed round
    assert ckpt.verify(os.path.join(ckdir, names[-1])) is True


# ------------------------------------------- chaos: damaged-checkpoint GC


@pytest.mark.chaos
def test_damaged_checkpoints_set_aside_and_garbage_collected(
        tiny_cv, tmp_path, capsys):
    """restore_latest renames failed candidates to *.damaged (they stop
    being restore/prune candidates) and bounds the graveyard to the newest
    KEEP_DAMAGED, counting deletions — chaos ckpt_corrupt runs no longer
    accumulate damaged trees unboundedly."""
    ckdir = str(tmp_path / "ck")
    s, _ = cv_train.build(_args())
    for _ in range(3):
        s.run_round(LR)
        ckpt.save(ckdir, s)
    names = sorted(d for d in os.listdir(ckdir) if d.startswith("round_"))
    for name in names[-2:]:  # damage the newest two
        t = FaultPlan._largest_data_file(os.path.join(ckdir, name))
        with open(t, "r+b") as f:
            f.truncate(os.path.getsize(t) // 2)

    s2, _ = cv_train.build(_args())
    restored = ckpt.restore_latest(ckdir, s2)
    assert restored.endswith(names[0]) and s2.round == 1
    damaged = sorted(d for d in os.listdir(ckdir) if d.endswith(".damaged"))
    assert damaged == [f"{names[-2]}.damaged", f"{names[-1]}.damaged"]
    # damaged trees are no longer candidates: latest() sees only the good one
    assert ckpt.latest(ckdir) == os.path.abspath(os.path.join(ckdir, names[0]))

    # a third damaged checkpoint pushes past KEEP_DAMAGED=2: GC deletes the
    # oldest, loudly
    for _ in range(3):
        s2.run_round(LR)
    p4 = ckpt.save(ckdir, s2)  # round_00000004
    t = FaultPlan._largest_data_file(p4)
    with open(t, "r+b") as f:
        f.truncate(os.path.getsize(t) // 2)
    s3, _ = cv_train.build(_args())
    ckpt.restore_latest(ckdir, s3)
    err = capsys.readouterr().err
    assert "checkpoint GC: deleted 1 damaged" in err
    damaged = sorted(d for d in os.listdir(ckdir) if d.endswith(".damaged"))
    assert len(damaged) == 2 and f"{names[-2]}.damaged" not in damaged


@pytest.mark.chaos
def test_all_damaged_dir_refuses_fresh_restart(tiny_cv, tmp_path):
    """A directory whose every checkpoint was set aside as damaged is NOT a
    fresh run: a later resume must refuse to silently restart from round 0."""
    ckdir = str(tmp_path / "ck")
    s, _ = cv_train.build(_args(("--fault_plan", "ckpt_corrupt@1")))
    s.run_round(LR)
    ckpt.save(ckdir, s, fault_plan=s.fault_plan)
    s2, _ = cv_train.build(_args())
    with pytest.raises(RuntimeError, match="no restorable checkpoint"):
        ckpt.restore_latest(ckdir, s2)  # renames the only candidate aside
    with pytest.raises(RuntimeError, match="only damaged"):
        ckpt.restore_latest(ckdir, s2)  # second resume: still not "fresh"


# ------------------------------------- chaos: the headline preempt -> resume


@pytest.mark.chaos
def test_preempt_resume_bit_identical(tiny_cv, tmp_path):
    """The acceptance headline: a run SIGTERM'd mid-round by the fault plan
    takes an emergency checkpoint, exits EXIT_RESUMABLE, and the relaunched
    --resume run (same argv, as a supervisor would issue) finishes with
    params bit-identical to the uninterrupted run."""
    base = _argv(("--num_rounds", "6"))
    sa = cv_train.main(base)
    assert sa.round == 6
    params_a = jax.device_get(sa.state["params"])

    ckdir = str(tmp_path / "ck")
    chaos = ["--checkpoint_dir", ckdir, "--fault_plan", "preempt@3"]
    with pytest.raises(SystemExit) as ei:
        cv_train.main(base + chaos)
    assert ei.value.code == EXIT_RESUMABLE
    # SIGTERM fired as round 3 ran; the handler let it finish, then saved
    names = sorted(d for d in os.listdir(ckdir) if d.startswith("round_"))
    assert names[-1] == "round_00000004"
    assert ckpt.verify(os.path.join(ckdir, names[-1])) is True

    # relaunch with identical argv + --resume: preempt@3 must NOT re-fire
    # (round-indexed schedule; the resumed run starts at round 4)
    sc = cv_train.main(base + chaos + ["--resume"])
    assert sc.round == 6
    for x, y in zip(
        jax.tree.leaves(params_a),
        jax.tree.leaves(jax.device_get(sc.state["params"])),
    ):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
