"""Byzantine-robust sketch aggregation (PR 10).

Pins, per the acceptance bar:

- `--merge_policy trimmed` with trim=0 BIT-identical to `sum` (params +
  every logged row) on the fused announce path AND the payload round —
  and it must not silently reroute the session through the table round.
- the robust table merge against a numpy reference (live-mask exclusion,
  client-index tie-breaks), and its mesh-/shard-shape invariance.
- the adversarial suite: each new attack kind degrades the linear sum
  measurably while trimmed/median recover final loss to within a stated
  eps of the clean run, same seed, same (table) round shape.
- per-layer quarantine: single-leaf window=1 bitwise equal to the scalar
  screen; the per-leaf screen catches a one-layer attack the diluted flat
  norm misses; per-leaf rings advance exactly like L scalar rings.
- `--quarantine_window` on the sharded and payload paths: the windowed
  threshold equals the rolling median of the per-round medians.
- the satellite fix: a wire (gauntlet) rejection and an in-round merge
  quarantine of the same client are bitwise-equivalent rounds.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree

from commefficient_tpu.data.fed_dataset import FedDataset, shard_iid
from commefficient_tpu.federated import engine
from commefficient_tpu.federated.api import FederatedSession
from commefficient_tpu.modes import modes
from commefficient_tpu.modes.config import ModeConfig
from commefficient_tpu.resilience import FaultPlan
from commefficient_tpu.serve.ingest import (
    ACCEPTED,
    QUARANTINED,
    PayloadPolicy,
    validate_payload,
)


def quad_loss(params, net_state, batch, rng):
    pred = batch["x"] @ params["w"] + params["b"]
    err = pred - jax.nn.one_hot(batch["y"], pred.shape[-1])
    mask = batch["mask"]
    per_ex = (err ** 2).sum(-1)
    return (per_ex * mask).sum() / jnp.maximum(mask.sum(), 1.0), {
        "net_state": net_state,
        "metrics": {"loss_sum": (per_ex * mask).sum(), "count": mask.sum()}}


def single_leaf_loss(params, net_state, batch, rng):
    pred = batch["x"] @ params["w"]
    err = pred - jax.nn.one_hot(batch["y"], pred.shape[-1])
    mask = batch["mask"]
    per_ex = (err ** 2).sum(-1)
    return (per_ex * mask).sum() / jnp.maximum(mask.sum(), 1.0), {
        "net_state": net_state,
        "metrics": {"loss_sum": (per_ex * mask).sum(), "count": mask.sum()}}


def make_session(loss_fn=quad_loss, single_leaf=False, num_workers=4,
                 seed=0, **kw):
    rs = np.random.RandomState(0)
    x = rs.randn(96, 6).astype(np.float32)
    w_true = rs.randn(6, 3).astype(np.float32)
    y = (x @ w_true).argmax(-1).astype(np.int32)
    train = FedDataset(x, y, shard_iid(len(x), 12, np.random.RandomState(1)))
    params = {"w": jnp.asarray(rs.randn(6, 3).astype(np.float32) * 0.1)}
    if not single_leaf:
        params["b"] = jnp.zeros(3)
    d = ravel_pytree(params)[0].size
    return FederatedSession(
        train_loss_fn=loss_fn, eval_loss_fn=loss_fn,
        params=params, net_state={},
        mode_cfg=ModeConfig(mode="sketch", d=d, k=4, num_rows=3, num_cols=8,
                            momentum=0.9, momentum_type="virtual",
                            error_type="virtual"),
        train_set=train, num_workers=num_workers, local_batch_size=4,
        seed=seed, **kw)


def flat_params(session) -> np.ndarray:
    return np.asarray(
        ravel_pytree(jax.device_get(session.state["params"]))[0])


def run(session, n=4, lr=0.05):
    return [session.run_round(lr) for _ in range(n)]


# ------------------------------------------------- robust merge, unit level


def _np_trimmed_mean(tables, live, trim):
    """Per-coordinate numpy reference: drop the trim lowest/highest LIVE
    values (ties by client index) and average the survivors."""
    W = tables.shape[0]
    out = np.zeros(tables.shape[1:], np.float32)
    flat = tables.reshape(W, -1)
    n = int(live.sum())
    res = np.zeros(flat.shape[1], np.float32)
    for c in range(flat.shape[1]):
        rows = [(flat[i, c], i) for i in range(W) if live[i] > 0]
        rows.sort()  # value, then client index — the stable tie-break
        kept = rows[trim:n - trim]
        res[c] = (sum(v for v, _ in kept) / max(n - 2 * trim, 1)
                  if kept else 0.0)
    return res.reshape(out.shape)


def _np_median(tables, live):
    W = tables.shape[0]
    flat = tables.reshape(W, -1)
    n = int(live.sum())
    res = np.zeros(flat.shape[1], np.float32)
    for c in range(flat.shape[1]):
        vals = sorted(flat[i, c] for i in range(W) if live[i] > 0)
        if not vals:
            continue
        lo, hi = (n - 1) // 2, n // 2
        res[c] = 0.5 * (vals[lo] + vals[hi])
    return res.reshape(tables.shape[1:])


@pytest.mark.parametrize("live_mask", [
    np.ones(6, np.float32),
    np.array([1, 0, 1, 1, 0, 1], np.float32),
])
def test_robust_merge_matches_numpy_reference(live_mask):
    rs = np.random.RandomState(3)
    tables = rs.randn(6, 3, 5).astype(np.float32)
    live = jnp.asarray(live_mask)
    got_med = np.asarray(modes._robust_table_merge(
        jnp.asarray(tables), live, "median", 0))
    np.testing.assert_allclose(got_med, _np_median(tables, live_mask),
                               rtol=1e-6)
    got_tr = np.asarray(modes._robust_table_merge(
        jnp.asarray(tables), live, "trimmed", 1))
    np.testing.assert_allclose(got_tr, _np_trimmed_mean(tables, live_mask, 1),
                               rtol=1e-6)


def test_trimmed_tie_break_is_by_client_index():
    """Duplicate values: the stable argsort ranks ties by client index, so
    the kept set — and therefore the fp sum — is deterministic."""
    tables = jnp.asarray(np.array(
        [[[1.0]], [[1.0]], [[1.0]], [[5.0]]], np.float32))
    live = jnp.ones(4)
    # trim=1 drops rank 0 (client 0, the first 1.0) and rank 3 (the 5.0):
    # survivors are clients 1 and 2 -> mean exactly 1.0
    got = np.asarray(modes._robust_table_merge(tables, live, "trimmed", 1))
    np.testing.assert_array_equal(got, np.array([[1.0]], np.float32))


def test_robust_merge_excludes_dead_rows_from_order_stats():
    """A dead client's value must not shift the median — dead rows are
    excluded, not treated as zero-valued contributions."""
    tables = jnp.asarray(np.array(
        [[[10.0]], [[-100.0]], [[12.0]], [[14.0]]], np.float32))
    live = jnp.asarray(np.array([1, 0, 1, 1], np.float32))
    got = np.asarray(modes._robust_table_merge(tables, live, "median", 0))
    np.testing.assert_array_equal(got, np.array([[12.0]], np.float32))


def test_robust_merge_excludes_nonfinite_live_rows():
    """A live NaN/Inf row is excluded like a dead one — from the order
    statistics AND the live count — so it can neither poison the estimate
    nor burn a slot of the trim budget (a NaN client + trim oversized
    clients must not smuggle an outlier past the trimmed window)."""
    tables = jnp.asarray(np.array(
        [[[np.nan]], [[1.0]], [[2.0]], [[3.0]], [[100.0]]], np.float32))
    live = jnp.ones(5)
    # trim=1 over the 4 FINITE rows: drop 1.0 and 100.0 -> mean(2, 3)
    got = np.asarray(modes._robust_table_merge(tables, live, "trimmed", 1))
    np.testing.assert_array_equal(got, np.array([[2.5]], np.float32))
    got_med = np.asarray(modes._robust_table_merge(tables, live, "median", 0))
    np.testing.assert_array_equal(got_med, np.array([[2.5]], np.float32))


def test_robust_round_masks_nonfinite_client_without_quarantine():
    """A NaN table under a robust policy with the quarantine UNARMED must
    leave the round like a dropped client — masked out of the survivor
    count, the rescale, and the metric folds — never a committed round
    rescaled by the wrong live count (the sum policy skips such a round
    via the non-finite guard; the robust policies degrade it instead)."""
    s = make_session(merge_policy="median",
                     fault_plan=FaultPlan.parse(
                         "client_poison@1:clients=2,value=nan"))
    ms = run(s, 3)
    assert ms[1]["participants"] == 3.0, ms[1]  # the NaN client masked
    assert all(np.isfinite(m["loss_sum"]) for m in ms), ms
    assert np.isfinite(flat_params(s)).all()


def test_robust_merge_degraded_below_trim_is_zero():
    tables = jnp.asarray(np.ones((4, 2, 2), np.float32))
    live = jnp.asarray(np.array([1, 0, 0, 0], np.float32))
    got = np.asarray(modes._robust_table_merge(tables, live, "trimmed", 1))
    np.testing.assert_array_equal(got, np.zeros((2, 2), np.float32))


def test_merge_partial_wires_rejects_bad_robust_calls():
    cfg = ModeConfig(mode="uncompressed", d=4, momentum_type="none",
                     error_type="none")
    with pytest.raises(ValueError, match="no table wire"):
        modes.merge_partial_wires(cfg, {"dense": jnp.zeros((2, 4))},
                                  policy="median", live=jnp.ones(2))
    scfg = ModeConfig(mode="sketch", d=4, k=2, num_rows=2, num_cols=4)
    with pytest.raises(ValueError, match="live-client mask"):
        modes.merge_partial_wires(scfg, {"table": jnp.zeros((2, 2, 4))},
                                  policy="median")
    with pytest.raises(ValueError, match="trim the whole cohort"):
        modes.merge_partial_wires(scfg, {"table": jnp.zeros((2, 2, 4))},
                                  policy="trimmed", live=jnp.ones(2), trim=1)


# --------------------------------------------------- trim=0 == sum, pinned


def test_trimmed_zero_is_sum_bitwise_fused():
    """trimmed@0 on the announce path: params + EVERY logged row bitwise,
    and no silent reroute through the table round."""
    a, b = make_session(), make_session(merge_policy="trimmed", merge_trim=0)
    ra, rb = run(a), run(b)
    assert ra == rb
    np.testing.assert_array_equal(flat_params(a), flat_params(b))
    assert b._payload_client is None
    assert not b._table_round


def test_trimmed_zero_is_sum_bitwise_payload():
    """trimmed@0 on the wire-payload round compiles the exact sum merge."""
    a = make_session(wire_payloads=True)
    b = make_session(wire_payloads=True, merge_policy="trimmed",
                     merge_trim=0)
    ra, rb = run(a), run(b)
    assert ra == rb
    np.testing.assert_array_equal(flat_params(a), flat_params(b))


_SKETCH_8 = dict(mode="sketch", d=8, k=2, num_rows=2, num_cols=4)


@pytest.mark.parametrize("match, build", [
    ("mode='sketch'", lambda: engine.EngineConfig(
        mode=ModeConfig(mode="uncompressed", d=8, momentum_type="none",
                        error_type="none"),
        merge_policy="median")),
    ("merge_trim", lambda: engine.EngineConfig(
        mode=ModeConfig(**_SKETCH_8), merge_policy="median", merge_trim=1)),
    ("ravel", lambda: make_session(merge_policy="median",
                                   sketch_path="layerwise")),
    # the linear builders refuse a robust cfg outright
    ("make_payload_round_steps", lambda: engine.make_round_step(
        quad_loss, engine.EngineConfig(
            mode=ModeConfig(**_SKETCH_8), merge_policy="trimmed",
            merge_trim=1))),
], ids=["non_sketch_mode", "median_with_trim", "layerwise_path",
        "linear_builder"])
def test_robust_policy_validation(match, build):
    with pytest.raises(ValueError, match=match):
        build()


def test_robust_session_falls_back_to_per_round_blocks():
    """run_rounds on a robust session must fall back to per-round dispatch
    (the table round has no fused multi-round program) and still equal the
    sequential rounds bitwise."""
    a = make_session(merge_policy="median")
    assert not a.supports_block_dispatch
    b = make_session(merge_policy="median")
    ra = a.run_rounds([0.05, 0.05, 0.05])
    rb = [b.run_round(0.05) for _ in range(3)]
    assert ra == rb
    np.testing.assert_array_equal(flat_params(a), flat_params(b))


@pytest.mark.parametrize("policy,kw", [
    ("median", {}), ("trimmed", {"merge_trim": 1})])
def test_robust_merge_shard_invariant(policy, kw):
    """Per-client tables make the robust statistic shard-count-invariant:
    client_shards=2 bitwise equals the unsharded table round."""
    a = make_session(merge_policy=policy, **kw)
    b = make_session(merge_policy=policy, client_shards=2, **kw)
    ra, rb = run(a), run(b)
    assert ra == rb
    np.testing.assert_array_equal(flat_params(a), flat_params(b))


def test_robust_merge_mesh_matches_single_device():
    """The robust table round on a four-device mesh (one client a device)
    equals the same shard-structured program on one device
    (`client_shards=4`) bit for bit: params, server state and every metric
    row. Against the UNSHARDED one-device session it is pinned to last-bit
    tolerance, because those two are different programs by construction:
    the per-client gradients and tables are computed under a vmap of width 1
    on each device there and of width 4 here, and XLA:CPU vectorises the
    same per-client subgraph differently at the two widths (the class
    `test_sharded_round.py::test_sharded_mesh_bit_identical_to_single_device`
    names; width 2 against 4 happens to come out bitwise, see
    `test_robust_merge_shard_invariant`). It shows from round 0 with or
    without the attack. Measured gap on this toolchain (jax 0.9.0): 3 of 21
    params after three rounds, 1.5e-8 abs, 2.6e-7 rel; `loss_sum` of round 0
    differs by one ulp (13.859041 / 13.859042), every count is equal."""
    from commefficient_tpu.parallel import mesh as meshlib

    if jax.device_count() < 4:
        pytest.skip("needs the forced multi-device CPU mesh")
    mesh = meshlib.make_mesh_from_spec("clients=4")
    plan = "client_collude@1:frac=0.5"
    a = make_session(merge_policy="median",
                     fault_plan=FaultPlan.parse(plan))
    r = make_session(merge_policy="median", client_shards=4,
                     fault_plan=FaultPlan.parse(plan))
    b = make_session(merge_policy="median", mesh=mesh,
                     fault_plan=FaultPlan.parse(plan))
    ma, mr, mb = run(a, 3), run(r, 3), run(b, 3)
    assert mr == mb
    np.testing.assert_array_equal(flat_params(r), flat_params(b))
    for x, y in zip(jax.tree.leaves(jax.device_get(r.state["mode_state"])),
                    jax.tree.leaves(jax.device_get(b.state["mode_state"]))):
        np.testing.assert_array_equal(x, y)
    np.testing.assert_allclose(flat_params(a), flat_params(b),
                               rtol=2e-7, atol=1e-8)
    for row_a, row_b in zip(ma, mb):
        assert set(row_a) == set(row_b)
        for k in row_a:
            if k == "loss_sum":
                np.testing.assert_allclose(row_a[k], row_b[k], rtol=2e-7)
            else:
                assert row_a[k] == row_b[k], k


# ------------------------------------------------------- adversarial suite


def _final_loss(ms):
    """Last round's train loss from a metrics-row list (probe helper)."""
    return ms[-1]["loss_sum"] / max(ms[-1]["count"], 1.0)


# the acceptance A/B harness: W=12, concentrated per-client gradients
# (local_batch 16 of a 20-example shard), no momentum (the attack's effect
# isn't laundered through the momentum EMA), and the metric is the EXACT
# eval loss over the whole dataset — batch noise out of the measurement.
_AB_ROUNDS = 6
_AB_ALL = ",".join(str(r) for r in range(_AB_ROUNDS))
ATTACKS = {
    "client_signflip": f"client_signflip@{_AB_ALL}:clients=0+1",
    "client_scale": f"client_scale@{_AB_ALL}:clients=0+1,factor=25",
    "client_collude": f"client_collude@{_AB_ALL}:frac=0.15",
}

_AB_RS = np.random.RandomState(0)
_AB_X = _AB_RS.randn(240, 6).astype(np.float32)
_AB_Y = (_AB_X @ _AB_RS.randn(6, 3).astype(np.float32)
         ).argmax(-1).astype(np.int32)


def _ab_session(**kw):
    train = FedDataset(_AB_X, _AB_Y,
                       shard_iid(len(_AB_X), 12, np.random.RandomState(1)))
    params = {"w": jnp.full((6, 3), 0.1, jnp.float32), "b": jnp.zeros(3)}
    d = ravel_pytree(params)[0].size
    return FederatedSession(
        train_loss_fn=quad_loss, eval_loss_fn=quad_loss,
        params=params, net_state={},
        mode_cfg=ModeConfig(mode="sketch", d=d, k=8, num_rows=3,
                            num_cols=16, momentum=0.0, momentum_type="none",
                            error_type="virtual"),
        train_set=train, num_workers=12, local_batch_size=16, seed=0, **kw)


def _ab_eval_loss(s) -> float:
    ds = FedDataset(_AB_X, _AB_Y,
                    shard_iid(len(_AB_X), 12, np.random.RandomState(1)))
    ev = s.evaluate(ds, batch_size=64)
    return ev["loss_sum"] / max(ev["count"], 1)


def _ab_arm(policy_kw, plan_text=None) -> float:
    s = _ab_session(
        fault_plan=FaultPlan.parse(plan_text) if plan_text else None,
        **policy_kw)
    for _ in range(_AB_ROUNDS):
        s.run_round(0.05)
    return _ab_eval_loss(s)


_AB_POLICIES = {
    # "sum" as the table round (trimmed@0 IS the sum program), so every arm
    # shares one round shape and damage is attack-caused, not shape-caused
    "sum": {"merge_policy": "trimmed", "merge_trim": 0,
            "wire_payloads": True},
    "trimmed": {"merge_policy": "trimmed", "merge_trim": 3},
    "median": {"merge_policy": "median"},
}


@pytest.mark.parametrize("kind", list(ATTACKS))
def test_attack_degrades_sum_robust_recovers(kind):
    """THE acceptance A/B, fully seeded: the attacked linear sum ends
    measurably worse than its clean run, while trimmed AND median stay
    within the stated eps — 0.75 x the sum's damage, one-sided (a robust
    arm may end BETTER than clean; what it must never do is carry the
    attack) — of their OWN clean runs, and strictly beat the attacked
    sum. Comparing each policy against its own clean baseline is the
    honest frame: robust estimators pay a small clean-accuracy tax (the
    README trade-off), and the defense claim is attack-INVARIANCE."""
    clean = {p: _ab_arm(dict(kw)) for p, kw in _AB_POLICIES.items()}
    plan = ATTACKS[kind]

    def attacked_arm(p):
        kw = dict(_AB_POLICIES[p])
        kw.pop("wire_payloads", None)  # adversarial kinds force the shape
        return _ab_arm(kw, plan)

    att = {p: attacked_arm(p) for p in _AB_POLICIES}
    deg = att["sum"] - clean["sum"]
    assert deg > 0.05, (
        f"{kind} under the linear sum should degrade the eval loss "
        f"measurably (clean {clean['sum']:.4f}, attacked {att['sum']:.4f})")
    eps = 0.75 * deg  # the stated recovery bar
    for policy in ("trimmed", "median"):
        gap = att[policy] - clean[policy]
        assert gap < eps, (
            f"{kind} under {policy}: attacked {att[policy]:.4f} vs own "
            f"clean {clean[policy]:.4f} — gap {gap:.4f} exceeds "
            f"eps={eps:.4f} (sum degraded by {deg:.4f})")
        assert att[policy] < att["sum"], (
            f"{kind}: {policy} ({att[policy]:.4f}) should strictly beat "
            f"the attacked sum ({att['sum']:.4f})")


def test_scale_attack_quarantined_params_equal_drop():
    """A model-replacement scaler caught by the sketch-space screen is —
    in params — the round without that client (the quarantine's original
    contract, extended to the attack kinds)."""
    plan = "client_scale@2:clients=1,factor=100"
    # both sessions run the SAME table-round program (wire_payloads), so
    # the only difference is quarantine-in-merge vs dropped-at-prepare;
    # compare THROUGH the attacked round (a dropped client is additionally
    # re-queued into a later cohort — recovery the quarantine deliberately
    # does not grant an attacker, so later rounds diverge by design)
    a = make_session(client_update_clip=3.0, wire_payloads=True,
                     fault_plan=FaultPlan.parse(plan))
    ms = run(a, 3)
    assert sum(m["clients_quarantined"] for m in ms) == 1
    b = make_session(client_update_clip=3.0, wire_payloads=True,
                     fault_plan=FaultPlan.parse("client_drop@2:clients=1"))
    run(b, 3)
    np.testing.assert_array_equal(flat_params(a), flat_params(b))


def test_adversarial_plan_is_seeded_and_deterministic():
    p1 = FaultPlan.parse("seed=7;client_collude@3:frac=0.5")
    p2 = FaultPlan.parse("seed=7;client_collude@3:frac=0.5")
    s1 = p1.adversarial_plan(3, 8)
    s2 = p2.adversarial_plan(3, 8)
    np.testing.assert_array_equal(s1[0], s2[0])
    np.testing.assert_array_equal(s1[1], s2[1])
    # a different seed picks different colluders (with overwhelming prob.)
    p3 = FaultPlan.parse("seed=8;client_collude@3:frac=0.5")
    s3 = p3.adversarial_plan(3, 8)
    assert not (np.array_equal(s1[1], s3[1])
                and np.array_equal(s1[0], s3[0]))
    # off-schedule rounds return the identity transform and fire nothing
    s_off = p1.adversarial_plan(4, 8)
    np.testing.assert_array_equal(s_off[0], np.ones(8, np.float32))
    np.testing.assert_array_equal(s_off[1], np.arange(8))


def test_collude_source_excludes_co_attacked_positions():
    """With a signflip co-scheduled on the lowest indices, the collusion's
    clone source must skip them — colluders clone an HONEST table, never
    an already-attacked wire (which would amplify the other attack
    instead of staging the documented one)."""
    plan = FaultPlan.parse(
        "seed=7;client_signflip@3:clients=0+1;client_collude@3:frac=0.25")
    scale, src = plan.adversarial_plan(3, 8)
    colluders = [p for p in range(8) if src[p] != p]
    assert colluders, "collusion never fired"
    sources = {int(src[p]) for p in colluders}
    assert len(sources) == 1
    source = sources.pop()
    assert source not in (0, 1), f"clone source {source} is an attacked client"
    assert scale[source] == 1.0 and src[source] == source


def test_collude_single_worker_is_loud_noop():
    """num_workers=1 leaves no honest source: the injection must be a loud
    no-op (identity transform), never an unhandled crash at round prep."""
    plan = FaultPlan.parse("client_collude@1:frac=0.5")
    scale, src = plan.adversarial_plan(1, 1)
    np.testing.assert_array_equal(scale, np.ones(1, np.float32))
    np.testing.assert_array_equal(src, np.arange(1))


def test_adversarial_parse_validation():
    with pytest.raises(ValueError, match="finite nonzero"):
        FaultPlan.parse("client_scale@1:clients=0,factor=0")
    with pytest.raises(ValueError, match="majority"):
        FaultPlan.parse("client_collude@1:frac=0.9")
    with pytest.raises(ValueError, match="unknown param"):
        FaultPlan.parse("client_signflip@1:factor=2")
    # dead schedule rejected at launch like every client_* kind
    plan = FaultPlan.parse("client_signflip@9:clients=0")
    with pytest.raises(ValueError, match="can never fire"):
        plan.validate_rounds(5)


def test_adversarial_kinds_need_table_round():
    with pytest.raises(ValueError, match="mode='sketch'"):
        rs = np.random.RandomState(0)
        x = rs.randn(32, 4).astype(np.float32)
        y = rs.randint(0, 3, 32).astype(np.int32)
        FederatedSession(
            train_loss_fn=quad_loss, eval_loss_fn=quad_loss,
            params={"w": jnp.zeros((4, 3)), "b": jnp.zeros(3)},
            net_state={},
            mode_cfg=ModeConfig(mode="uncompressed", d=15,
                                momentum_type="none", error_type="none"),
            train_set=FedDataset(x, y, shard_iid(32, 8,
                                                 np.random.RandomState(1))),
            num_workers=4, local_batch_size=4, seed=0,
            fault_plan=FaultPlan.parse("client_signflip@1:clients=0"))


# ------------------------------------------------------ per-layer quarantine


def test_layer_scope_single_leaf_bitwise_equals_cohort():
    """Single-leaf model, window=1: the per-leaf norm IS the flat norm, so
    layer scope is bit-identical to the scalar screen — params + rows."""
    plan = "client_poison@2:clients=1,value=big"
    a = make_session(loss_fn=single_leaf_loss, single_leaf=True,
                     client_update_clip=3.0,
                     fault_plan=FaultPlan.parse(plan))
    b = make_session(loss_fn=single_leaf_loss, single_leaf=True,
                     client_update_clip=3.0, quarantine_scope="layer",
                     fault_plan=FaultPlan.parse(plan))
    ra, rb = run(a), run(b)
    assert ra == rb
    assert sum(m["clients_quarantined"] for m in rb) == 1
    np.testing.assert_array_equal(flat_params(a), flat_params(b))


def test_layer_mask_catches_what_flat_norm_dilutes():
    """A client hiding a one-leaf attack inside an in-bounds flat norm: the
    scalar screen passes it, the per-leaf screen trips it."""
    cfg = engine.EngineConfig(
        mode=ModeConfig(mode="sketch", d=1000, k=4, num_rows=2, num_cols=16),
        client_update_clip=2.0, quarantine_scope="layer")
    # leaf medians: a big first leaf, a tiny second leaf
    lmed = jnp.asarray([10.0, 0.1])
    qmed = jnp.asarray(10.0)  # flat norms dominated by leaf 0
    # client 1 moved ALL its mass into leaf 1 (20x that leaf's median)
    # while its flat norm stays ~10 — inside the scalar screen
    norms = jnp.asarray([10.0, 10.2])
    lnorms = jnp.asarray([[10.0, 0.1], [10.0, 2.0]])
    scalar_bad = engine._quarantine_mask(cfg, norms, qmed)
    layer_bad = engine._quarantine_layer_mask(cfg, lnorms, lmed)
    assert not bool(scalar_bad[1]), "scalar screen should miss the attack"
    assert bool(layer_bad[1]), "per-leaf screen should catch it"
    assert not bool(layer_bad[0])


def test_layer_rings_advance_like_L_scalar_rings():
    cfg = engine.EngineConfig(
        mode=ModeConfig(mode="sketch", d=100, k=4, num_rows=2, num_cols=16),
        client_update_clip=2.0, quarantine_scope="layer",
        quarantine_window=3)
    L, W, K = 3, 5, 3
    rs = np.random.RandomState(0)
    lnorms = jnp.asarray(rs.rand(W, L).astype(np.float32) + 0.5)
    part = jnp.asarray(np.array([1, 1, 0, 1, 1], np.float32))
    qstate = {
        "layer_median": jnp.zeros(L), "layer_window": jnp.zeros((L, K)),
        "layer_count": jnp.zeros(L, jnp.int32),
    }
    got = engine._advance_quarantine_layers(cfg, qstate, lnorms, part)
    for leaf in range(L):
        ref = engine._advance_quarantine(
            cfg, {"median": qstate["layer_median"][leaf],
                  "window": qstate["layer_window"][leaf],
                  "count": qstate["layer_count"][leaf]},
            lnorms[:, leaf], part)
        np.testing.assert_array_equal(
            np.asarray(got["layer_median"])[leaf], np.asarray(ref["median"]))
        np.testing.assert_array_equal(
            np.asarray(got["layer_window"])[leaf], np.asarray(ref["window"]))


def test_layer_scope_quarantines_poison_on_payload_and_sharded_paths():
    plan = "client_poison@2:clients=1,value=big"
    for kw in ({"wire_payloads": True}, {"client_shards": 2}):
        s = make_session(client_update_clip=3.0, quarantine_scope="layer",
                         fault_plan=FaultPlan.parse(plan), **kw)
        ms = run(s, 4)
        assert sum(m["clients_quarantined"] for m in ms) == 1, kw
        assert np.isfinite(flat_params(s)).all()
        q = jax.device_get(s.state["quarantine"])
        assert q["layer_median"].shape == (2,)  # w and b leaves


def test_layer_scope_validation():
    with pytest.raises(ValueError, match="client_update_clip"):
        make_session(quarantine_scope="layer")


# --------------------------------------- quarantine window, sharded/payload


def _rolling_median(vals, k):
    out = []
    for i in range(len(vals)):
        w = vals[max(0, i - k + 1):i + 1]
        out.append(float(np.median(w)))
    return out


@pytest.mark.parametrize("kw", [{"client_shards": 2},
                                {"wire_payloads": True}])
def test_quarantine_window_on_sharded_and_payload_paths(kw):
    """window=K on the sharded and payload paths: on a clean run (nothing
    quarantined, so thresholds never feed back) the windowed threshold
    metric equals the rolling median of the window=1 per-round medians."""
    base = make_session(client_update_clip=50.0, **kw)
    m1 = run(base, 5)
    per_round = [m["quarantine_median"] for m in m1]
    assert not any(m["clients_quarantined"] for m in m1)
    win = make_session(client_update_clip=50.0, quarantine_window=3, **kw)
    m3 = run(win, 5)
    got = [m["quarantine_median"] for m in m3]
    np.testing.assert_allclose(got, _rolling_median(per_round, 3), rtol=1e-6)


# ------------------------------- wire rejection == merge quarantine, bitwise


def test_wire_rejection_equals_merge_quarantine_bitwise():
    """The satellite fix's regression: the SAME attacked payload, once
    rejected at the wire (gauntlet QUARANTINED -> arrived=0, zero table)
    and once admitted but quarantined in the merge (table screen), must
    produce bitwise-identical committed params — and the gauntlet screens
    against the exact scalar ring the merge advances."""

    def served_round(reject_at_wire: bool):
        s = make_session(wire_payloads=True, client_update_clip=3.0,
                         quarantine_window=2)
        # round 0: clean, seeds the table-space median ring
        run(s, 1)
        rnd = s.round
        ids = s.sample_cohort(rnd)
        prep = s.prepare_served_round(rnd, ids,
                                      np.ones(len(ids), np.float32))
        tables, aux = s.compute_client_tables(prep)
        attacked = np.array(tables, copy=True)
        attacked[1] *= 100.0  # model replacement on position 1
        qmed = s.quarantine_median_host()
        assert qmed > 0.0, "ring must be seeded after the clean round"
        policy = PayloadPolicy(
            rows=s.cfg.mode.num_rows, cols=s.cfg.mode.num_cols,
            clip_multiple=3.0, quarantine_median=lambda: qmed)
        arrived = np.ones(len(ids), np.float32)
        wire_tables = np.array(attacked, copy=True)
        if reject_at_wire:
            t, decision, _ = validate_payload(attacked[1], policy)
            assert decision == QUARANTINED
            arrived[1] = 0.0
            wire_tables[1] = 0.0  # a rejected frame never reaches the merge
        else:
            # wire screen disarmed: the merge's table screen must catch it
            t, decision, _ = validate_payload(
                attacked[1],
                PayloadPolicy(rows=policy.rows, cols=policy.cols))
            assert decision == ACCEPTED
        prep = s.finish_served_payload(prep, arrived, wire_tables, aux)
        m = s.commit_round(s.dispatch_round(prep, 0.05))[0]
        return s, m

    a, ma = served_round(reject_at_wire=True)
    b, mb = served_round(reject_at_wire=False)
    assert ma["clients_quarantined"] == 0.0  # never arrived
    assert mb["clients_quarantined"] == 1.0  # caught in-merge
    assert ma["participants"] == mb["participants"]
    np.testing.assert_array_equal(flat_params(a), flat_params(b))
