"""bench.py timing discipline: the class of bug that invalidated rounds 2-3
(async-dispatch illusions, chains shorter than the sync RTT clamping to 0)
now has unit pins. Runs bench helpers in-process on the CPU mesh."""

import math
import time

import jax
import jax.numpy as jnp
import pytest


def _import_bench(monkeypatch, **env):
    """Fresh bench import under `env`, with teardown that restores
    COMMEFFICIENT_NO_PALLAS: importing bench mutates it process-wide
    (bench.py's engine-routing knob: oracle mode SETS =1, the round-5
    default auto mode POPS it); without restore, every later in-process
    test sees the pallas library force-toggled — test_pallas's routing
    assertions fail by test ORDER, not by code (observed: 187/188 with
    this fixture first, in the oracle-default era)."""
    import importlib
    import os
    import sys

    for k, v in env.items():
        monkeypatch.setenv(k, v)
    prior = os.environ.get("COMMEFFICIENT_NO_PALLAS")
    sys.modules.pop("bench", None)
    mod = importlib.import_module("bench")

    def teardown():
        sys.modules.pop("bench", None)
        if prior is None:
            os.environ.pop("COMMEFFICIENT_NO_PALLAS", None)
        else:
            os.environ["COMMEFFICIENT_NO_PALLAS"] = prior

    return mod, teardown


@pytest.fixture()
def bench_mod(monkeypatch):
    mod, teardown = _import_bench(monkeypatch, BENCH_MODEL="resnet9")
    yield mod
    teardown()


def test_time_adaptive_measures_real_compute(bench_mod):
    """A chain whose cost is ~linear in n: the per-iteration estimate must be
    positive, finite, and flagged trustworthy (not rtt_dominated) when the
    chain dwarfs the claimed round-trip."""

    def fn_of_n(n):
        def run(x):
            def body(c, _):
                # real work XLA cannot elide: the carry feeds itself
                return c @ c / jnp.maximum(jnp.abs(c).max(), 1.0), ()

            y, _ = jax.lax.scan(body, x, None, length=n)
            return y[0, 0]

        return run

    x = jnp.eye(256) * 1.1
    per, n, rtt_dominated = bench_mod._time_adaptive(fn_of_n, (x,), 4, rt_ms=0.0)
    assert per > 0 and n >= 4
    assert not rtt_dominated


def test_time_adaptive_flags_rtt_dominated(bench_mod):
    """An ultra-cheap chain against a huge claimed RTT must come back flagged
    rtt_dominated — round 3's 0.504 ms kernel 'measurement' was exactly this
    case silently passing as a number."""

    def fn_of_n(n):
        def run(x):
            def body(c, _):
                return c + 1.0, ()

            y, _ = jax.lax.scan(body, x, None, length=n)
            return y

        return run

    per, n, rtt_dominated = bench_mod._time_adaptive(
        fn_of_n, (jnp.float32(0.0),), 2, rt_ms=60_000.0, cap=8)
    assert rtt_dominated  # the cap bites long before 4x a 60 s RTT
    assert per >= 0.0 and math.isfinite(per)  # clamped, never negative


def test_time_adaptive_grows_chain_toward_target(bench_mod):
    """When the first chain is too short for the 4x-RTT target, the helper
    must retry with a longer chain (growth is the fix for the clamp bug)."""
    calls = []

    def fn_of_n(n):
        calls.append(n)

        def run(x):
            def body(c, _):
                return c + 1.0, ()

            y, _ = jax.lax.scan(body, x, None, length=n)
            return y

        return run

    bench_mod._time_adaptive(fn_of_n, (jnp.float32(0.0),), 2, rt_ms=50.0, cap=64)
    assert len(calls) == 2 and calls[1] > calls[0]  # grew once, toward cap
    assert calls[1] <= 64


def test_server_split_reports_all_ops(bench_mod, monkeypatch):
    """_server_split at tiny dims returns every attribution key with finite
    values and no error (the GPT-2 wall attribution path)."""
    from commefficient_tpu.modes.config import ModeConfig

    monkeypatch.setattr(bench_mod, "PHASE_CHAIN", 2)
    cfg = ModeConfig(mode="sketch", d=4096, k=64, num_rows=3, num_cols=1024,
                     momentum_type="virtual", error_type="virtual")
    out = bench_mod._server_split(cfg, rt_ms=0.0)
    assert "error" not in out, out
    for key in ("accumulate_ms", "estimates_ms", "topk_exact_ms",
                "topk_approx_ms", "topk_oversample_ms", "algebra_sketch_ms",
                "delta_apply_sparse_ms", "delta_apply_dense_ms",
                "ravel_unravel_ms"):
        assert key in out and out[key] >= 0.0, (key, out)
    assert out["d"] == 4096 and out["k"] == 64


def test_server_split_topk_runs_at_engine_recall(bench_mod, monkeypatch):
    """ADVICE r5: the isolated topk_approx/oversample chains must run at the
    recall the ENGINE actually runs (mode_cfg.topk_recall), not topk_abs's
    default 0.95 — approx_max_k's cost depends on recall_target, so the
    attribution would otherwise measure a different op."""
    from commefficient_tpu.modes.config import ModeConfig
    from commefficient_tpu.sketch import csvec

    calls = []
    real = csvec.topk_abs

    def spy(x, k, approx=False, recall=0.95, impl=None):
        calls.append((impl, recall))
        return real(x, k, approx=approx, recall=recall, impl=impl)

    monkeypatch.setattr(csvec, "topk_abs", spy)
    monkeypatch.setattr(bench_mod, "PHASE_CHAIN", 2)
    cfg = ModeConfig(mode="sketch", d=4096, k=64, num_rows=3, num_cols=1024,
                     momentum_type="virtual", error_type="virtual",
                     topk_recall=0.7)
    out = bench_mod._server_split(cfg, rt_ms=0.0)
    assert "error" not in out, out
    assert out["topk_recall"] == 0.7
    recalls = {r for impl, r in calls if impl in ("approx", "oversample")}
    assert recalls == {0.7}, calls


def test_run_loop_bench_measures_both_arms(monkeypatch):
    """bench's run_loop section must drive a real FederatedSession through
    the shared harness in BOTH loop modes and report the acceptance pair
    (wall_clock_updates_per_sec, host_overhead_ms) per arm, plus fold an
    injected fault's footprint into nonfinite_rounds."""
    bench, teardown = _import_bench(
        monkeypatch, BENCH_MODEL="resnet9", BENCH_WORKERS="2",
        BENCH_LOCAL_BATCH="2", BENCH_COLS="512", BENCH_TOPK="32",
        BENCH_BLOCKS="1", BENCH_DTYPE="float32",
        BENCH_RUN_LOOP_ROUNDS="3",
        # nonfinite@3 lands inside the timed sync arm (rounds 2-4 after the
        # 2-round warmup); preempt@4 must be STRIPPED, not SIGTERM the bench
        BENCH_FAULT_PLAN="nonfinite@3;preempt@4",
    )
    try:
        import flax.linen as nn

        from commefficient_tpu.models.losses import make_classification_loss

        class _TinyNet(nn.Module):
            num_classes: int = 10
            dtype: str = "float32"

            @nn.compact
            def __call__(self, x, train=False):
                x = x.reshape((x.shape[0], -1))
                return nn.Dense(self.num_classes)(x)

        def tiny_workload():
            model = _TinyNet()
            x0 = jnp.zeros((1, 32, 32, 3), jnp.float32)
            params = model.init(jax.random.PRNGKey(0), x0, train=False)["params"]
            loss_fn = make_classification_loss(model, train=True)
            sketch_kw = dict(k=32, num_rows=3, num_cols=512, num_blocks=1)
            return params, {}, None, loss_fn, "tiny", sketch_kw, 2

        monkeypatch.setattr(bench, "_resnet9_workload", tiny_workload)
        out = bench._run_loop_bench(round_ms=0.0)
        assert "error" not in out, out
        for arm in ("sync", "async"):
            assert out[arm]["wall_clock_updates_per_sec"] > 0
            assert "host_overhead_ms" in out[arm]
            assert out[arm]["drains"] >= 1
        assert out["async_speedup_vs_sync"] > 0
        assert out["nonfinite_rounds"] == 1  # the injected burst, counted
        assert "stripped" in out["fault_plan_note"]
    finally:
        teardown()


def test_flops_chunked_matches_unchunked(monkeypatch):
    """XLA cost analysis counts a lax.scan body ONCE, so the chunked client
    step (BENCH_CLIENT_CHUNK > 0) undercounts flops by the trip count (W=64's
    flops at W=256, an MFU understated 4x). _flops_per_round's chunk_trips rescaling must bring the
    chunked estimate back to the unchunked one (same W, same dims)."""
    bench, teardown = _import_bench(
        monkeypatch, BENCH_MODEL="resnet9", BENCH_WORKERS="4",
        BENCH_LOCAL_BATCH="1", BENCH_COLS="256", BENCH_TOPK="32",
        BENCH_BLOCKS="1", BENCH_DTYPE="float32",
    )
    try:
        from jax.flatten_util import ravel_pytree

        params, net_state, batch, loss_fn, _, sketch_kw, workers = (
            bench._resnet9_workload())
        d = ravel_pytree(params)[0].size

        def build(chunk):
            monkeypatch.setenv("BENCH_CLIENT_CHUNK", str(chunk))
            eng, mode_cfg, cfg, step = bench._make_step(loss_fn, sketch_kw, d)
            state = eng.init_server_state(
                cfg, jax.tree.map(jnp.copy, params),
                jax.tree.map(jnp.copy, net_state))
            return cfg, step, state

        _, step0, state0 = build(0)
        f0, note0 = bench._flops_per_round(step0, state0, batch, 1)
        cfg1, step1, state1 = build(2)
        trips = workers // cfg1.client_chunk
        assert trips == 2
        f1, note1 = bench._flops_per_round(step1, state1, batch, trips)
        assert note0 is None and note1 is not None
        assert f0 and f1
        # scan plumbing adds epsilon; the convs dominate, so within 10%
        assert abs(f1 - f0) / f0 < 0.10, (f0, f1)
    finally:
        teardown()


def test_gpt2_chunk_default_divides_any_cohort(monkeypatch):
    """The gpt2 client_chunk default must divide W for ANY BENCH_WORKERS a
    smoke run might set (the engine raises on non-divisors): gcd(8, W)
    degrades gracefully — 8 for the W=64 default, 2 for a W=6 smoke."""
    monkeypatch.delenv("BENCH_CLIENT_CHUNK", raising=False)
    for w, expect in (("64", 8), ("6", 2), ("3", 1), ("16", 8)):
        bench, teardown = _import_bench(
            monkeypatch, BENCH_MODEL="gpt2", BENCH_GPT2_SIZE="tiny",
            BENCH_WORKERS=w, BENCH_COLS="1024", BENCH_TOPK="16",
            BENCH_BLOCKS="1", BENCH_SEQ="16")
        try:
            def dummy_loss(params, net_state, batch, rng):
                raise AssertionError("never traced at build time")
            _, _, cfg, _ = bench._make_step(
                dummy_loss, dict(k=16, num_rows=3, num_cols=1024,
                                 num_blocks=1), d=4096)
            assert cfg.client_chunk == expect, (w, cfg.client_chunk)
            assert int(w) % cfg.client_chunk == 0
        finally:
            teardown()
