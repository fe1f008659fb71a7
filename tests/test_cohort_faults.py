"""Cohort-level fault tolerance (ISSUE 4 tentpole): the per-client validity
mask and the sketch-space quarantine, at engine level.

The acceptance contract under test: a round with k masked clients is
bit-identical (params + metrics) to a reference round over just the W-k
surviving clients — on the fused path and on the sharded (mesh ==
single-device) path — and a poisoned client is rejected by the quarantine
exactly as if it had been externally masked, while an identical clean run is
untouched. conftest forces the 8-device CPU mesh, so this file is part of the
forced-8-device tier-1 slice (scripts/tier1_8dev.sh).

Bit-identity mechanics: with client_chunk=1 the weighted reduce is a scan
accumulating one client at a time, so a masked client contributes an exact
`acc + 0.0` — the partial-sum sequence over the survivors is literally the
same float operations the surviving-cohort round performs (the losses here
consume no per-client rng, so survivor gradients are identical too).
"""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree

from commefficient_tpu.federated import engine
from commefficient_tpu.modes.config import ModeConfig
from commefficient_tpu.parallel import mesh as meshlib
from commefficient_tpu.resilience import FaultPlan

SKETCH_KW = dict(mode="sketch", k=16, num_rows=3, num_cols=1024,
                 hash_family="rotation", momentum_type="virtual",
                 error_type="virtual")


def quad_params(key, din=10, dout=4):
    k1, k2 = jax.random.split(key)
    return {"w": jax.random.normal(k1, (din, dout)) * 0.1,
            "b": jnp.zeros(dout)}


def quad_loss(params, net_state, batch, rng):
    """Least-squares head: the gradient scales LINEARLY with the input, so a
    client whose rows are scaled 1e3 produces an update ~1e6 x the cohort
    median — exactly what the quarantine's magnitude screen must catch (a
    tanh MLP would saturate the poison away)."""
    pred = batch["x"] @ params["w"] + params["b"]
    err = pred - jax.nn.one_hot(batch["y"], pred.shape[-1])
    mask = batch["mask"]
    count = jnp.maximum(mask.sum(), 1.0)
    per_ex = (err ** 2).sum(-1)
    loss = (per_ex * mask).sum() / count
    return loss, {"net_state": net_state,
                  "metrics": {"loss_sum": (per_ex * mask).sum(),
                              "count": mask.sum()}}


def _data(key, n, din=10, dout=4):
    kx, kw = jax.random.split(key)
    x = jax.random.normal(kx, (n, din))
    w_true = jax.random.normal(kw, (din, dout))
    return {"x": x, "y": (x @ w_true).argmax(-1), "mask": jnp.ones(n)}


def _batch(key, W, B=4):
    data = _data(key, W * B)
    return jax.tree.map(lambda a: a.reshape((W, B) + a.shape[1:]), data)


def _cfg(shards=1, **eng_kw):
    params = quad_params(jax.random.PRNGKey(0))
    d = ravel_pytree(params)[0].size
    mcfg = ModeConfig(**{**SKETCH_KW, "d": d})
    return params, engine.EngineConfig(mode=mcfg, weight_decay=5e-4,
                                       client_shards=shards, **eng_kw)


def _flat(state):
    return np.asarray(ravel_pytree(state["params"])[0])


def _with_valid(batch, valid):
    out = dict(batch)
    out[engine.VALID_KEY] = jnp.asarray(valid, jnp.float32)
    return out


# ------------------------------------------------- masked == surviving cohort


def test_masked_round_bit_identical_to_surviving_cohort_fused():
    """THE acceptance pin, fused path: kill clients {2, 5} of an 8-cohort via
    the validity mask -> params, server state AND every metric bit-equal to
    the round sampled with just the 6 survivors, when that round carries an
    all-ones mask of its own.

    Against the 6 survivors with NO mask, metrics and counts stay bitwise and
    params are pinned to last-bit tolerance, because the two sides are then
    different programs by construction: with no mask and no dropout the
    participation vector is a compile-time constant, so the survivor count is
    the literal 6.0 and XLA:CPU turns `wsum / 6.0` into a multiply by the
    rounded reciprocal, where a masked round divides by a count it reads at
    run time. The cohort reduce itself is bit-equal at vmap widths 8 and 6.
    Measured gap on this toolchain (jax 0.9.0): 3 of 44 params, 7.5e-9 abs,
    1.1e-7 rel; 21 of 3072 Vvelocity entries, 3.0e-8 abs."""
    W, dead = 8, [2, 5]
    params, cfg = _cfg(client_chunk=1)
    batch = _batch(jax.random.PRNGKey(1), W)
    valid = np.ones(W, np.float32)
    valid[dead] = 0.0
    lr, rng = jnp.float32(0.1), jax.random.PRNGKey(7)

    step = jax.jit(engine.make_round_step(quad_loss, cfg))

    def one_round(b):
        s = engine.init_server_state(cfg, jax.tree.map(jnp.copy, params), {})
        s, _, m = step(s, b, {}, lr, rng)
        return s, m

    s_m, m_m = one_round(_with_valid(batch, valid))
    surv = np.flatnonzero(valid)
    ref_batch = jax.tree.map(lambda a: a[surv], batch)
    s_r, m_r = one_round(_with_valid(ref_batch, np.ones(len(surv))))
    s_u, m_u = one_round(ref_batch)

    np.testing.assert_array_equal(_flat(s_m), _flat(s_r))
    for a, b in zip(jax.tree.leaves(s_m["mode_state"]),
                    jax.tree.leaves(s_r["mode_state"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_allclose(_flat(s_m), _flat(s_u), rtol=2e-7, atol=1e-8)
    assert set(m_m) == set(m_r) == set(m_u)
    for k in m_r:
        np.testing.assert_array_equal(np.asarray(m_m[k]), np.asarray(m_r[k]),
                                      err_msg=k)
        np.testing.assert_array_equal(np.asarray(m_m[k]), np.asarray(m_u[k]),
                                      err_msg=k)
    assert float(m_m["participants"]) == float(len(surv))


def test_masked_round_bit_identical_to_surviving_cohort_sharded():
    """Same pin on the sharded round (single-device reference program): one
    client masked in EVERY shard (W=8 over S=4 -> survivors W-k=4 over the
    same 4 shards), so the per-shard partial sums and the ordered table
    merge are the identical float sequence in both runs."""
    W, S = 8, 4
    dead = [1, 3, 5, 7]  # position 1 of each wl=2 shard
    params, cfg = _cfg(shards=S, client_chunk=1)
    batch = _batch(jax.random.PRNGKey(2), W)
    valid = np.ones(W, np.float32)
    valid[dead] = 0.0
    lr, rng = jnp.float32(0.1), jax.random.PRNGKey(9)

    step = jax.jit(engine.make_sharded_round_step(quad_loss, cfg))
    s_m = engine.init_server_state(cfg, jax.tree.map(jnp.copy, params), {})
    s_m, _, m_m = step(s_m, _with_valid(batch, valid), {}, lr, rng)

    surv = np.flatnonzero(valid)
    ref_batch = jax.tree.map(lambda a: a[surv], batch)
    s_r = engine.init_server_state(cfg, jax.tree.map(jnp.copy, params), {})
    s_r, _, m_r = step(s_r, ref_batch, {}, lr, rng)

    np.testing.assert_array_equal(_flat(s_m), _flat(s_r))
    for k in m_r:
        np.testing.assert_array_equal(np.asarray(m_m[k]), np.asarray(m_r[k]),
                                      err_msg=k)
    assert float(m_m["participants"]) == 4.0


def test_masked_round_mesh_bit_identical_to_single_device():
    """The mask rides the batch pytree, so the 8-device shard_map round with
    a degraded cohort stays bit-identical to the single-device reference —
    params and every metric (the ISSUE's mesh-path acceptance)."""
    mesh = meshlib.make_mesh(8)
    W = 16
    params, cfg = _cfg(shards=8, client_update_clip=4.0)
    batch = _batch(jax.random.PRNGKey(3), W)
    valid = np.ones(W, np.float32)
    valid[[1, 9, 14]] = 0.0
    bm = _with_valid(batch, valid)
    lr = jnp.float32(0.1)

    ref = jax.jit(engine.make_sharded_round_step(quad_loss, cfg))
    msh = jax.jit(engine.make_sharded_round_step(quad_loss, cfg, mesh))
    s_r = engine.init_server_state(cfg, jax.tree.map(jnp.copy, params), {})
    s_m = engine.init_server_state(cfg, jax.tree.map(jnp.copy, params), {})
    bm_sharded = meshlib.shard_client_batch(mesh, bm)
    for i in range(3):
        rng = jax.random.PRNGKey(100 + i)
        s_r, _, m_r = ref(s_r, bm, {}, lr, rng)
        s_m, _, m_m = msh(s_m, bm_sharded, {}, lr, rng)
        assert set(m_r) == set(m_m)
        for k in m_r:
            np.testing.assert_array_equal(np.asarray(m_r[k]),
                                          np.asarray(m_m[k]), err_msg=k)
    np.testing.assert_array_equal(_flat(s_r), _flat(s_m))
    for a, b in zip(jax.tree.leaves(s_r["mode_state"]),
                    jax.tree.leaves(s_m["mode_state"])):
        # same last-bit tolerance as test_sharded_round (XLA:CPU value-
        # dependent vectorization between lax.map and shard_map bodies)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-7, atol=1e-8)
    np.testing.assert_allclose(
        float(s_r["quarantine"]["median"]), float(s_m["quarantine"]["median"]),
        rtol=2e-7)


@pytest.mark.parametrize("clip", [4.0, 0.0], ids=["per_client", "fused"])
def test_masked_client_garbage_is_inert(clip):
    """A dead client's batch content must not matter — NaN rows behind a zero
    validity mask produce the identical round a zeroed batch does, and the
    round its own rows behind that mask do (the degrade path's contract:
    failed loads hand the engine zeros, but nothing may depend on that).
    On both client phases: with the quarantine armed (per-client gradients,
    NaN-safe by the mask-and-sum) and with it off (one backward pass for the
    cohort, where a zero cotangent would not stop a NaN: the masked rows
    are zeroed before the forward pass)."""
    W = 8
    params, cfg = _cfg(client_update_clip=clip)
    assert engine.cohort_backward_fused(cfg) == (clip == 0.0)
    batch = _batch(jax.random.PRNGKey(4), W)
    valid = np.ones(W, np.float32)
    valid[3] = 0.0
    lr, rng = jnp.float32(0.1), jax.random.PRNGKey(11)
    step = jax.jit(engine.make_round_step(quad_loss, cfg))

    def round_with(fill):
        rows = {k: np.array(v, copy=True) for k, v in
                jax.tree.map(np.asarray, batch).items()}
        if fill is not None:
            rows["x"][3] = fill
        state = engine.init_server_state(cfg, jax.tree.map(jnp.copy, params), {})
        state, _, m = step(
            state, _with_valid({k: jnp.asarray(v) for k, v in rows.items()},
                               valid), {}, lr, rng)
        return state, m

    s_a, m_a = round_with(None)
    assert np.isfinite(_flat(s_a)).all()
    for fill in (np.nan, 0.0):
        s_b, m_b = round_with(fill)
        np.testing.assert_array_equal(_flat(s_a), _flat(s_b))
        for k in m_a:
            np.testing.assert_array_equal(np.asarray(m_a[k]), np.asarray(m_b[k]),
                                          err_msg=k)


# ----------------------------------------------------------------- quarantine


def _poison_rows(batch, pos, scale):
    out = {k: np.array(np.asarray(v), copy=True) for k, v in batch.items()}
    out["x"][pos] = out["x"][pos] * scale
    return {k: jnp.asarray(v) for k, v in out.items()}


@pytest.mark.parametrize("poison", ["big", "nan"])
def test_quarantine_rejects_poisoned_client_like_a_mask(poison):
    """An adversarially large (or non-finite) update is rejected by the
    quarantine EXACTLY as if the client had been externally masked: params
    bit-equal to the run whose validity mask kills that client, and the
    rejection is counted. Round 0 runs clean to seed the running median."""
    W, bad = 8, 5
    params, cfg = _cfg(client_update_clip=10.0)
    b0 = _batch(jax.random.PRNGKey(5), W)
    b1 = _batch(jax.random.PRNGKey(6), W)
    b1_poisoned = (_poison_rows(b1, bad, 1e3) if poison == "big"
                   else _poison_rows(b1, bad, np.nan))
    lr = jnp.float32(0.1)

    step = jax.jit(engine.make_round_step(quad_loss, cfg))
    s_q = engine.init_server_state(cfg, jax.tree.map(jnp.copy, params), {})
    s_q, _, m0 = step(s_q, b0, {}, lr, jax.random.PRNGKey(20))
    assert float(m0["clients_quarantined"]) == 0.0
    assert float(s_q["quarantine"]["median"]) > 0.0
    s_q, _, m1 = step(s_q, b1_poisoned, {}, lr, jax.random.PRNGKey(21))
    assert float(m1["clients_quarantined"]) == 1.0
    assert float(m1["participants"]) == W - 1
    assert np.isfinite(_flat(s_q)).all()

    # reference: same rounds, clean data, client `bad` externally masked
    valid = np.ones(W, np.float32)
    valid[bad] = 0.0
    s_m = engine.init_server_state(cfg, jax.tree.map(jnp.copy, params), {})
    s_m, _, _ = step(s_m, b0, {}, lr, jax.random.PRNGKey(20))
    s_m, _, mm = step(s_m, _with_valid(b1, valid), {}, lr,
                      jax.random.PRNGKey(21))
    np.testing.assert_array_equal(_flat(s_q), _flat(s_m))
    np.testing.assert_array_equal(np.asarray(m1["loss_sum"]),
                                  np.asarray(mm["loss_sum"]))


def test_quarantine_clean_run_untouched():
    """With no poison, the armed quarantine rejects NOTHING and the run
    matches the clip=0 run to last-bit tolerance over chained rounds (the
    two compile as different XLA programs — the NaN-safe select weighting
    refuses some reduce fusions — so this is a cross-program comparison:
    tight allclose, with the counts exact)."""
    W = 8
    params, cfg_off = _cfg()
    _, cfg_on = _cfg(client_update_clip=3.0)
    lr = jnp.float32(0.1)
    step_off = jax.jit(engine.make_round_step(quad_loss, cfg_off))
    step_on = jax.jit(engine.make_round_step(quad_loss, cfg_on))
    s_off = engine.init_server_state(cfg_off, jax.tree.map(jnp.copy, params), {})
    s_on = engine.init_server_state(cfg_on, jax.tree.map(jnp.copy, params), {})
    for i in range(3):
        b = _batch(jax.random.PRNGKey(30 + i), W)
        rng = jax.random.PRNGKey(60 + i)
        s_off, _, m_off = step_off(s_off, b, {}, lr, rng)
        s_on, _, m_on = step_on(s_on, b, {}, lr, rng)
        assert float(m_on["clients_quarantined"]) == 0.0
        assert float(m_off["participants"]) == float(m_on["participants"])
        for k in m_off:
            np.testing.assert_allclose(np.asarray(m_off[k]),
                                       np.asarray(m_on[k]), rtol=1e-6,
                                       err_msg=k)
    np.testing.assert_allclose(_flat(s_off), _flat(s_on), rtol=1e-6,
                               atol=1e-7)


def test_quarantine_sharded_mesh_matches_reference():
    """Per-client quarantine inside the per-shard local reduce: the poisoned
    client is rejected before the table merge (no densified cross-device
    traffic), and mesh == single-device holds with the screen armed."""
    mesh = meshlib.make_mesh(8)
    W, bad = 16, 6
    params, cfg = _cfg(shards=8, client_update_clip=10.0)
    b0 = _batch(jax.random.PRNGKey(12), W)
    b1 = _poison_rows(_batch(jax.random.PRNGKey(13), W), bad, 1e3)
    lr = jnp.float32(0.1)

    ref = jax.jit(engine.make_sharded_round_step(quad_loss, cfg))
    msh = jax.jit(engine.make_sharded_round_step(quad_loss, cfg, mesh))
    s_r = engine.init_server_state(cfg, jax.tree.map(jnp.copy, params), {})
    s_m = engine.init_server_state(cfg, jax.tree.map(jnp.copy, params), {})
    for b, seed in ((b0, 50), (b1, 51)):
        rng = jax.random.PRNGKey(seed)
        s_r, _, m_r = ref(s_r, b, {}, lr, rng)
        s_m, _, m_m = msh(s_m, meshlib.shard_client_batch(mesh, b), {}, lr,
                          rng)
        for k in m_r:
            np.testing.assert_array_equal(np.asarray(m_r[k]),
                                          np.asarray(m_m[k]), err_msg=k)
    assert float(m_r["clients_quarantined"]) == 1.0
    assert float(m_r["participants"]) == W - 1
    np.testing.assert_array_equal(_flat(s_r), _flat(s_m))


def test_quarantine_local_state_mode_keeps_rows_clean():
    """Per-client-wire path (local_topk with local error): a quarantined
    client's error row keeps its pre-round value — the poison never enters
    its persistent state."""
    params = quad_params(jax.random.PRNGKey(0))
    d = ravel_pytree(params)[0].size
    mcfg = ModeConfig(mode="local_topk", d=d, k=8, momentum_type="none",
                      error_type="local", num_clients=8)
    cfg = engine.EngineConfig(mode=mcfg, client_update_clip=10.0)
    from commefficient_tpu.modes import modes as modelib

    rows = jax.vmap(lambda _: modelib.empty_client_row(mcfg))(jnp.arange(8))
    step = jax.jit(engine.make_round_step(quad_loss, cfg))
    st = engine.init_server_state(cfg, jax.tree.map(jnp.copy, params), {})
    b0 = _batch(jax.random.PRNGKey(14), 8)
    st, rows, _ = step(st, b0, rows, jnp.float32(0.1), jax.random.PRNGKey(0))
    before = np.asarray(rows["error"][4])
    b1 = _poison_rows(_batch(jax.random.PRNGKey(15), 8), 4, np.nan)
    st, rows, m = step(st, b1, rows, jnp.float32(0.1), jax.random.PRNGKey(1))
    assert float(m["clients_quarantined"]) == 1.0
    np.testing.assert_array_equal(np.asarray(rows["error"][4]), before)
    assert np.isfinite(np.asarray(rows["error"])).all()
    assert np.isfinite(_flat(st)).all()


# --------------------------------------------------------- fault-plan surface


def test_client_fault_kinds_parse_and_coerce():
    plan = FaultPlan.parse(
        "client_drop@2:clients=0+3;client_poison@2:clients=1,value=big;"
        "client_straggle@1:clients=2,secs=0.01;host_preempt@3:host=1"
    )
    assert plan.spec("client_drop", 2).params["clients"] == (0, 3)
    assert plan.spec("client_poison", 2).params["value"] == "big"
    assert plan.spec("client_straggle", 1).params["secs"] == 0.01
    assert plan.spec("host_preempt", 3).params["host"] == 1
    # coerce-and-error discipline, same as the existing sites
    with pytest.raises(ValueError, match="bad value"):
        FaultPlan.parse("client_drop@1:clients=a+b")
    with pytest.raises(ValueError, match="bad value"):
        FaultPlan.parse("client_poison@1:value=huge")
    with pytest.raises(ValueError, match="bad value"):
        FaultPlan.parse("host_preempt@1:host=zero")
    with pytest.raises(ValueError, match="unknown param"):
        FaultPlan.parse("client_drop@1:client=0")
    # "big" is poison-only: nonfinite keeps its nan/inf contract
    with pytest.raises(ValueError, match="bad value"):
        FaultPlan.parse("nonfinite@1:value=big")


def test_validate_rounds_rejects_unreachable_client_sites():
    plan = FaultPlan.parse("client_drop@7:clients=0;preempt@9")
    with pytest.raises(ValueError, match="can never fire"):
        plan.validate_rounds(6)
    plan.validate_rounds(8)  # client_drop@7 in range; preempt not a client site
    FaultPlan.parse("client_poison:clients=0").validate_rounds(1)  # unscheduled


def test_validate_wire_context_rejects_wire_kinds_without_payload_path():
    # wire_* kinds inject at the serving payload seam only: a plan naming
    # them on a run without --serve_payload sketch would pass vacuously
    # (zero injections, chaos run green) — reject it at launch instead
    plan = FaultPlan.parse("wire_corrupt@1:clients=0;conn_drop@2:clients=1")
    with pytest.raises(ValueError, match="can never fire"):
        plan.validate_wire_context(False)
    plan.validate_wire_context(True)  # payload path armed: fine
    # a plan with no wire kinds never cares about the payload path
    FaultPlan.parse("client_drop@1:clients=0").validate_wire_context(False)


def test_client_faults_apply_and_requeue_positions():
    plan = FaultPlan.parse(
        "client_drop@2:clients=0+3;client_poison@2:clients=1,value=nan")
    W = 4
    batch = {"x": np.ones((W, 2, 3), np.float32),
             "y": np.ones((W, 2), np.int32),
             "mask": np.ones((W, 2), np.float32),
             "_valid": np.ones(W, np.float32)}
    out, valid, dropped = plan.client_faults(2, batch, None, W)
    assert sorted(dropped) == [0, 3]
    np.testing.assert_array_equal(valid, [0.0, 1.0, 1.0, 0.0])
    assert (out["x"][0] == 0).all() and (out["y"][3] == 0).all()
    assert np.isnan(out["x"][1]).all() and np.isnan(out["mask"][1]).all()
    assert (out["x"][2] == 1).all()  # untouched client
    # reserved control rows are never poisoned or zeroed
    np.testing.assert_array_equal(out["_valid"], np.ones(W, np.float32))
    # wrong round: everything passes through untouched
    b2, v2, d2 = plan.client_faults(1, batch, None, W)
    assert d2 == [] and v2 is None and b2 is batch
    # out-of-range positions fail the chaos run loudly
    with pytest.raises(ValueError, match="out of range"):
        FaultPlan.parse("client_drop@0:clients=9").client_faults(
            0, batch, None, W)


def test_client_straggle_sleeps_once():
    plan = FaultPlan.parse("client_straggle@1:clients=0,secs=0.05")
    batch = {"x": np.ones((2, 2), np.float32)}
    t0 = time.monotonic()
    plan.client_faults(1, batch, None, 2)
    stalled = time.monotonic() - t0
    t0 = time.monotonic()
    plan.client_faults(1, batch, None, 2)  # one-shot per round
    again = time.monotonic() - t0
    assert stalled >= 0.05 and again < 0.05


def test_coordinated_preemption_max_reduces_across_hosts(monkeypatch):
    """resilience.coordinated = max over hosts of the local flag: a host
    WITHOUT a local SIGTERM must still see True when any peer flags (the
    one-host-preempted pod case), and single-process stays the identity
    without touching a collective."""
    from commefficient_tpu.parallel import distributed
    from commefficient_tpu.resilience import coordinated

    assert coordinated(False) is False and coordinated(True) is True
    monkeypatch.setattr(distributed, "all_hosts_max", lambda v: 1)
    assert coordinated(False) is True  # a peer host was signalled


# ------------------------------------------------- windowed quarantine median


def test_quarantine_window_default_keeps_state_tree_and_threshold():
    """quarantine_window=1 (the default) is the pre-window behavior: the
    server state carries ONLY {"median"} (so existing checkpoints stay
    shape-compatible) and the active threshold after each round is exactly
    that round's live-cohort median — which a window=K run must also agree
    with while its ring is what the window median reduces to."""
    W, K = 8, 4
    params, cfg1 = _cfg(client_update_clip=10.0)
    _, cfgK = _cfg(client_update_clip=10.0, quarantine_window=K)
    lr = jnp.float32(0.1)
    step1 = jax.jit(engine.make_round_step(quad_loss, cfg1))
    stepK = jax.jit(engine.make_round_step(quad_loss, cfgK))
    s1 = engine.init_server_state(cfg1, jax.tree.map(jnp.copy, params), {})
    sK = engine.init_server_state(cfgK, jax.tree.map(jnp.copy, params), {})
    assert set(s1["quarantine"]) == {"median"}
    assert set(sK["quarantine"]) == {"median", "window", "count"}
    assert sK["quarantine"]["window"].shape == (K,)

    meds = []  # per-round live-cohort medians (window=1 active threshold)
    for r in range(3):
        b = _batch(jax.random.PRNGKey(40 + r), W)
        s1, _, m1 = step1(s1, b, {}, lr, jax.random.PRNGKey(60 + r))
        sK, _, mK = stepK(sK, b, {}, lr, jax.random.PRNGKey(60 + r))
        meds.append(float(m1["quarantine_median"]))
        # clean data: neither run quarantines, so the cohorts (and the
        # per-round medians feeding both baselines) stay identical
        assert float(m1["clients_quarantined"]) == 0.0
        assert float(mK["clients_quarantined"]) == 0.0
        # the window=K active threshold is the median over the filled ring
        # slots — the window=1 run's per-round medians, reduced
        np.testing.assert_allclose(
            float(mK["quarantine_median"]), float(np.median(meds[-K:])),
            rtol=1e-6)
        assert int(sK["quarantine"]["count"]) == min(r + 1, K)
    # params identical too: the window only changes the THRESHOLD, and the
    # clean run never trips it
    np.testing.assert_array_equal(_flat(s1), _flat(sK))


def test_quarantine_window_tolerates_one_collapsed_round():
    """The drift scenario the window exists for: one round whose cohort
    update norms COLLAPSE (near-converged batch, lr pivot) drags the
    window=1 threshold down with it, so the NEXT round's healthy clients
    all screen as 'adversarially large' and quarantine; a window=4 baseline
    moves at window speed — one outlier round perturbs one slot — and the
    healthy cohort passes."""
    W, K = 8, 4
    params, cfg1 = _cfg(client_update_clip=10.0)
    _, cfgK = _cfg(client_update_clip=10.0, quarantine_window=K)
    lr = jnp.float32(0.1)
    b_normal = [_batch(jax.random.PRNGKey(70 + r), W) for r in range(4)]
    # the collapsed round: example masks scaled 1e-4 scale the whole loss
    # (count floors at 1.0), so every client's update norm collapses with
    # them — small but finite, the shape of a near-converged / lr-pivot
    # round
    b_tiny = {k: (v * 1e-4 if k == "mask" else v)
              for k, v in _batch(jax.random.PRNGKey(80), W).items()}
    schedule = [b_normal[0], b_normal[1], b_tiny, b_normal[2]]

    for cfg, expect_quarantined in ((cfg1, W), (cfgK, 0)):
        step = jax.jit(engine.make_round_step(quad_loss, cfg))
        s = engine.init_server_state(cfg, jax.tree.map(jnp.copy, params), {})
        last = None
        for r, b in enumerate(schedule):
            s, _, last = step(s, b, {}, lr, jax.random.PRNGKey(90 + r))
        assert float(last["clients_quarantined"]) == expect_quarantined, (
            cfg.quarantine_window, float(last["clients_quarantined"]))
