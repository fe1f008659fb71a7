"""Round-engine tests (SURVEY.md §4 integration list): `uncompressed` matches
plain SGD bit-for-bit (the reference's control mode); fedavg with 1 local iter
matches SGD; sharded-over-8-CPU-devices result matches unsharded; loss falls
under every mode on a tiny synthetic problem."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.flatten_util import ravel_pytree
from jax.sharding import NamedSharding, PartitionSpec as P

from commefficient_tpu.federated import engine
from commefficient_tpu.modes import modes
from commefficient_tpu.modes.config import ModeConfig
from commefficient_tpu.parallel import mesh as meshlib
from conftest import run_queued


def init_mlp(key, din=10, dh=16, dout=4):
    k1, k2 = jax.random.split(key)
    return {
        "w1": jax.random.normal(k1, (din, dh)) * 0.1,
        "b1": jnp.zeros(dh),
        "w2": jax.random.normal(k2, (dh, dout)) * 0.1,
        "b2": jnp.zeros(dout),
    }


def mlp_loss(params, net_state, batch, rng):
    h = jnp.tanh(batch["x"] @ params["w1"] + params["b1"])
    logits = h @ params["w2"] + params["b2"]
    logp = jax.nn.log_softmax(logits)
    per_ex = -jnp.take_along_axis(logp, batch["y"][:, None], axis=1)[:, 0]
    mask = batch["mask"]
    count = jnp.maximum(mask.sum(), 1.0)
    loss = (per_ex * mask).sum() / count
    correct = ((logits.argmax(-1) == batch["y"]) * mask).sum()
    return loss, {
        "net_state": net_state,
        "metrics": {"loss_sum": (per_ex * mask).sum(), "count": mask.sum(), "correct": correct},
    }


def _data(key, n, din=10, dout=4):
    kx, kw = jax.random.split(key)
    x = jax.random.normal(kx, (n, din))
    w_true = jax.random.normal(kw, (din, dout))
    y = (x @ w_true).argmax(-1)
    return {"x": x, "y": y, "mask": jnp.ones(n)}


def _ucfg(**kw):
    base = dict(mode="uncompressed", d=0, momentum_type="none", error_type="none")
    base.update(kw)
    return base


def _make(cfg_kw, wd=0.0, **eng_kw):
    params = init_mlp(jax.random.PRNGKey(0))
    d = ravel_pytree(params)[0].size
    mcfg = ModeConfig(**{**cfg_kw, "d": d})
    cfg = engine.EngineConfig(mode=mcfg, weight_decay=wd, **eng_kw)
    state = engine.init_server_state(cfg, params, {})
    step = jax.jit(engine.make_round_step(mlp_loss, cfg))
    return cfg, state, step


def test_uncompressed_matches_plain_sgd():
    data = _data(jax.random.PRNGKey(1), 16)
    batch = jax.tree.map(lambda a: a[None], data)  # W=1
    cfg, state, step = _make(_ucfg())
    lr = jnp.float32(0.2)

    # manual SGD on the same loss
    params = init_mlp(jax.random.PRNGKey(0))
    for i in range(5):
        state, _, metrics = step(state, batch, {}, lr, jax.random.PRNGKey(i))
        g = jax.grad(lambda p: mlp_loss(p, {}, data, None)[0])(params)
        params = jax.tree.map(lambda p, gg: p - lr * gg, params, g)
    for a, b in zip(jax.tree.leaves(state["params"]), jax.tree.leaves(params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


def test_uncompressed_momentum_weight_decay_matches_manual():
    data = _data(jax.random.PRNGKey(2), 16)
    batch = jax.tree.map(lambda a: a[None], data)
    cfg, state, step = _make(_ucfg(momentum_type="virtual", momentum=0.9), wd=0.01)
    lr = jnp.float32(0.1)

    params = init_mlp(jax.random.PRNGKey(0))
    vel = jax.tree.map(jnp.zeros_like, params)
    for i in range(4):
        state, _, _ = step(state, batch, {}, lr, jax.random.PRNGKey(i))
        g = jax.grad(lambda p: mlp_loss(p, {}, data, None)[0])(params)
        g = jax.tree.map(lambda gg, p: gg + 0.01 * p, g, params)
        vel = jax.tree.map(lambda v, gg: 0.9 * v + gg, vel, g)
        params = jax.tree.map(lambda p, v: p - lr * v, params, vel)
    for a, b in zip(jax.tree.leaves(state["params"]), jax.tree.leaves(params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


def test_fedavg_single_local_iter_matches_sgd():
    data = _data(jax.random.PRNGKey(3), 8)
    batch = jax.tree.map(lambda a: a[None, None], data)  # W=1, L=1
    cfg, state, step = _make(
        dict(mode="fedavg", momentum_type="none", error_type="none", num_local_iters=1)
    )
    lr = jnp.float32(0.2)
    state, _, _ = step(state, batch, {}, lr, jax.random.PRNGKey(0))

    params = init_mlp(jax.random.PRNGKey(0))
    g = jax.grad(lambda p: mlp_loss(p, {}, data, None)[0])(params)
    params = jax.tree.map(lambda p, gg: p - lr * gg, params, g)
    for a, b in zip(jax.tree.leaves(state["params"]), jax.tree.leaves(params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


def test_multi_client_mean_equals_big_batch():
    """W clients with equal shards == one client with the union (uniform
    client weighting; shards equal-sized so the means coincide)."""
    data = _data(jax.random.PRNGKey(4), 32)
    w4 = jax.tree.map(lambda a: a.reshape((4,) + (8,) + a.shape[1:]), data)
    one = jax.tree.map(lambda a: a[None], data)
    lr = jnp.float32(0.1)
    cfg, state4, step = _make(_ucfg())
    _, state1, _ = _make(_ucfg())
    s4, _, m4 = step(state4, w4, {}, lr, jax.random.PRNGKey(0))
    s1, _, m1 = step(state1, one, {}, lr, jax.random.PRNGKey(0))
    for a, b in zip(jax.tree.leaves(s4["params"]), jax.tree.leaves(s1["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)
    assert float(m4["count"]) == float(m1["count"]) == 32.0


def test_sharded_equals_unsharded():
    """The same step over an 8-device CPU mesh (client axis sharded) produces
    the same new params — 'distributed without a cluster' (SURVEY.md §4)."""
    mesh = meshlib.make_mesh(8)
    data = _data(jax.random.PRNGKey(5), 64)
    w8 = jax.tree.map(lambda a: a.reshape((8,) + (8,) + a.shape[1:]), data)
    lr = jnp.float32(0.1)
    cfg, state, step = _make(_ucfg())
    ref, _, _ = step(state, w8, {}, lr, jax.random.PRNGKey(0))

    _, state2, _ = _make(_ucfg())
    sharded_batch = meshlib.shard_client_batch(mesh, w8)
    got, _, _ = step(state2, sharded_batch, {}, lr, jax.random.PRNGKey(0))
    for a, b in zip(jax.tree.leaves(got["params"]), jax.tree.leaves(ref["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


def test_fedavg_local_momentum_matches_manual():
    """momentum_type='local': heavy-ball momentum inside the local-SGD loop.
    One client, 3 local iters — compare against a hand-rolled momentum SGD."""
    data = _data(jax.random.PRNGKey(9), 12)
    micro = jax.tree.map(lambda a: a.reshape((1, 3, 4) + a.shape[1:]), data)
    lr, mu = 0.1, 0.5
    cfg, state, step = _make(
        dict(mode="fedavg", d=0, momentum_type="local", momentum=mu,
             error_type="none", num_local_iters=3)
    )
    new_state, _, _ = step(state, micro, {}, jnp.float32(lr), jax.random.PRNGKey(0))

    # manual: p_{t+1} = p_t - lr * m_t,  m_t = mu m_{t-1} + g_t
    params = init_mlp(jax.random.PRNGKey(0))
    pflat, unravel = ravel_pytree(params)
    m = np.zeros_like(pflat)
    p = np.asarray(pflat)
    for i in range(3):
        mb = jax.tree.map(lambda a: a[0, i], micro)
        g = ravel_pytree(jax.grad(lambda pp: mlp_loss(pp, {}, mb, None)[0])(unravel(jnp.asarray(p))))[0]
        m = mu * m + np.asarray(g)
        p = p - lr * m
    # server applies the averaged delta at server_lr = 1
    np.testing.assert_allclose(
        np.asarray(ravel_pytree(new_state["params"])[0]), p, rtol=1e-5, atol=1e-6
    )


def test_fedavg_server_lr_scales_delta():
    data = _data(jax.random.PRNGKey(10), 16)
    batch = jax.tree.map(lambda a: a.reshape((2, 2, 4) + a.shape[1:]), data)
    base = dict(mode="fedavg", d=0, momentum_type="none", error_type="none",
                num_local_iters=2)
    _, s1, step1 = _make(base)
    _, s2, step2 = _make({**base, "server_lr": 0.5})
    n1, _, _ = step1(s1, batch, {}, jnp.float32(0.1), jax.random.PRNGKey(0))
    n2, _, _ = step2(s2, batch, {}, jnp.float32(0.1), jax.random.PRNGKey(0))
    d1 = _flat_delta(s1, n1)
    d2 = _flat_delta(s2, n2)
    np.testing.assert_allclose(d2, 0.5 * d1, rtol=1e-5, atol=1e-7)


# ------------------------------------------------- differential privacy

def _flat_delta(state_before, state_after):
    a = ravel_pytree(state_before["params"])[0]
    b = ravel_pytree(state_after["params"])[0]
    return np.asarray(a - b)


def test_dp_clip_bounds_update_norm():
    """With a tiny clip, the server delta norm is ≤ lr·clip (uncompressed mode,
    W clipped client updates averaged then scaled by lr)."""
    data = _data(jax.random.PRNGKey(7), 32)
    batch = jax.tree.map(lambda a: a.reshape((4, 8) + a.shape[1:]), data)
    lr = 0.5
    clip = 1e-3
    cfg, state, step = _make(_ucfg(), dp_clip=clip)
    new_state, _, _ = step(state, batch, {}, jnp.float32(lr), jax.random.PRNGKey(0))
    delta = _flat_delta(state, new_state)
    assert np.linalg.norm(delta) <= lr * clip * 1.001
    # and with a huge clip the step matches the unclipped engine exactly
    cfg2, state2, step2 = _make(_ucfg(), dp_clip=1e9)
    cfg3, state3, step3 = _make(_ucfg())
    s2, _, _ = step2(state2, batch, {}, jnp.float32(lr), jax.random.PRNGKey(0))
    s3, _, _ = step3(state3, batch, {}, jnp.float32(lr), jax.random.PRNGKey(0))
    np.testing.assert_allclose(
        ravel_pytree(s2["params"])[0], ravel_pytree(s3["params"])[0], rtol=1e-6
    )


def test_dp_noise_perturbs_deterministically():
    """Same rng ⇒ identical noised step; different rng ⇒ different params;
    noise magnitude scales with the multiplier."""
    data = _data(jax.random.PRNGKey(8), 16)
    batch = jax.tree.map(lambda a: a.reshape((2, 8) + a.shape[1:]), data)
    lr = jnp.float32(0.1)

    def run(noise, key):
        cfg, state, step = _make(_ucfg(), dp_clip=1.0, dp_noise=noise)
        new_state, _, _ = step(state, batch, {}, lr, key)
        return ravel_pytree(new_state["params"])[0]

    p_a = run(0.5, jax.random.PRNGKey(0))
    p_b = run(0.5, jax.random.PRNGKey(0))
    p_c = run(0.5, jax.random.PRNGKey(1))
    np.testing.assert_array_equal(np.asarray(p_a), np.asarray(p_b))
    assert not np.allclose(np.asarray(p_a), np.asarray(p_c))
    # true_topk's dense wire is also a sound noise surface
    tcfg = dict(mode="true_topk", k=20, momentum_type="virtual", error_type="virtual")
    cfg, state, step = _make(tcfg, dp_clip=1.0, dp_noise=0.1)
    new_state, _, m = step(state, batch, {}, lr, jax.random.PRNGKey(0))
    assert np.isfinite(_flat_delta(state, new_state)).all()


def test_dp_noise_key_independent_of_client_keys():
    """The DP noise stream must never coincide with any client's rng: in
    threefry, fold_in(key, i) == split(key, n)[i], so deriving noise via
    fold_in from the same rng the client keys are split from collides at
    cohort sizes >= the folded constant (advisor finding, round 1). The
    engine splits a dedicated stream first; mirror that derivation here and
    assert no collision at a large cohort."""
    rng = jax.random.PRNGKey(123)
    num_sampled = 2048
    crng, noise_rng = jax.random.split(rng)
    client_keys = np.asarray(jax.random.split(crng, num_sampled))
    noise_keys = np.asarray(
        [jax.random.fold_in(noise_rng, i) for i in range(4)] + [noise_rng]
    )
    for nk in noise_keys:
        assert not (client_keys == nk[None, :]).all(axis=1).any()
    # and the old, broken derivation really does collide — the test's reason
    old_nkey = np.asarray(jax.random.fold_in(rng, 0x0D9))
    old_clients = np.asarray(jax.random.split(rng, num_sampled))
    assert (old_clients == old_nkey[None, :]).all(axis=1).any()


def test_dp_noise_rejects_unsound_surfaces():
    """Sketch tables (l1-scale worst-case sensitivity) and mutable model
    collections (BN stats bypass the mechanism) must be rejected."""
    with pytest.raises(ValueError):
        _make(
            dict(mode="sketch", k=20, num_rows=3, num_cols=100,
                 momentum_type="virtual", error_type="virtual"),
            dp_clip=1.0,
            dp_noise=0.1,
        )
    params = init_mlp(jax.random.PRNGKey(0))
    d = ravel_pytree(params)[0].size
    cfg = engine.EngineConfig(
        mode=ModeConfig(**_ucfg(d=d)), dp_clip=1.0, dp_noise=0.1
    )
    with pytest.raises(ValueError):
        engine.init_server_state(cfg, params, {"batch_stats": {"m": jnp.zeros(3)}})


def test_dp_noise_requires_clip():
    with pytest.raises(ValueError):
        _make(_ucfg(), dp_noise=1.0)


def test_dp_noise_rejects_client_local_state():
    """topk(error_accumulator + update) has unbounded norm across rounds, so
    dp_clip cannot bound sensitivity — must be rejected, not silently unsound."""
    with pytest.raises(ValueError):
        _make(
            dict(mode="local_topk", k=50, momentum_type="none", error_type="local",
                 num_clients=4),
            dp_clip=1.0,
            dp_noise=0.5,
        )


@pytest.mark.parametrize(
    "cfg_kw",
    [
        _ucfg(),
        _ucfg(momentum_type="virtual"),
        dict(mode="sketch", k=50, num_rows=3, num_cols=200, momentum_type="virtual",
             error_type="virtual"),
        dict(mode="true_topk", k=50, momentum_type="virtual", error_type="virtual"),
        dict(mode="local_topk", k=50, momentum_type="none", error_type="local",
             num_clients=4),
        dict(mode="fedavg", momentum_type="none", error_type="none", num_local_iters=3),
    ],
    ids=["uncompressed", "uncompressed+mom", "sketch", "true_topk", "local_topk", "fedavg"],
)
def test_loss_decreases_every_mode(cfg_kw):
    W, B = 4, 16
    data = _data(jax.random.PRNGKey(6), W * B)
    if cfg_kw.get("mode") == "fedavg":
        L = cfg_kw["num_local_iters"]
        data = _data(jax.random.PRNGKey(6), W * L * B)
        batch = jax.tree.map(lambda a: a.reshape((W, L, B) + a.shape[1:]), data)
    else:
        batch = jax.tree.map(lambda a: a.reshape((W, B) + a.shape[1:]), data)
    cfg, state, step = _make(cfg_kw)
    rows = (
        jax.tree.map(lambda a: a[:W], modes.init_client_state(cfg.mode, 4))
        if cfg.mode.needs_local_state
        else {}
    )
    lr = jnp.float32(0.3)
    losses = []
    for i in range(12):
        state, rows, metrics = step(state, batch, rows, lr, jax.random.PRNGKey(i))
        losses.append(float(metrics["loss_sum"]) / float(metrics["count"]))
    assert losses[-1] < losses[0] * 0.7, losses


def test_hybrid_multislice_mesh_equals_unsharded():
    """A 2-slice x 4-device hybrid (DCN x ICI) mesh — BASELINE config #5 /
    SURVEY.md §7.7 — runs the same round step unchanged and matches the
    unsharded result: clients shard over (slices, clients), so the client
    mean lowers to an in-slice reduce plus one cross-slice all-reduce."""
    hmesh = meshlib.make_mesh(8, num_slices=2)
    assert dict(hmesh.shape) == {meshlib.DCN_AXIS: 2, meshlib.CLIENT_AXIS: 4}
    assert meshlib.client_shards(hmesh) == 8
    data = _data(jax.random.PRNGKey(5), 64)
    w8 = jax.tree.map(lambda a: a.reshape((8,) + (8,) + a.shape[1:]), data)
    lr = jnp.float32(0.1)
    cfg, state, step = _make(_ucfg())
    ref, _, _ = step(state, w8, {}, lr, jax.random.PRNGKey(0))

    _, state2, _ = _make(_ucfg())
    sharded = meshlib.shard_client_batch(hmesh, w8)
    got, _, _ = step(state2, sharded, {}, lr, jax.random.PRNGKey(0))
    for a, b in zip(jax.tree.leaves(got["params"]), jax.tree.leaves(ref["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


def test_hybrid_mesh_with_model_axis():
    """3-axis hybrid mesh (slices, clients, model): the TP axis stays
    innermost (never crosses DCN) and client_shards counts slices x clients."""
    m = meshlib.make_mesh(8, model_parallel=2, num_slices=2)
    assert dict(m.shape) == {
        meshlib.DCN_AXIS: 2, meshlib.CLIENT_AXIS: 2, meshlib.MODEL_AXIS: 2
    }
    assert meshlib.client_shards(m) == 4
    assert meshlib.client_axes(m) == (meshlib.DCN_AXIS, meshlib.CLIENT_AXIS)


def test_sharded_eval_matches_unsharded():
    """evaluate() shards eval batches over the client axes (VERDICT r2 weak
    #4: eval must not run 1-device while training runs 8-way); metric totals
    must be identical because padded rows carry mask 0."""
    from commefficient_tpu.data.fed_dataset import FedDataset
    from commefficient_tpu.federated.api import FederatedSession

    rng = np.random.RandomState(0)
    n = 100  # deliberately not divisible by 8: exercises pad + round-up
    x = rng.randn(n, 10).astype(np.float32)
    w_true = rng.randn(10, 4).astype(np.float32)
    y = (x @ w_true).argmax(-1).astype(np.int64)
    ds = FedDataset(x, y, [np.arange(i, n, 16) for i in range(16)])

    def build(mesh):
        return FederatedSession(
            train_loss_fn=mlp_loss, eval_loss_fn=mlp_loss,
            params=init_mlp(jax.random.PRNGKey(0)), net_state={},
            mode_cfg=ModeConfig(**_ucfg(d=ravel_pytree(init_mlp(jax.random.PRNGKey(0)))[0].size)),
            train_set=ds, num_workers=8, local_batch_size=4, seed=1, mesh=mesh,
        )

    ref = build(None).evaluate(ds, batch_size=32)
    got = build(meshlib.make_mesh(8)).evaluate(ds, batch_size=32)
    got_hybrid = build(meshlib.make_mesh(8, num_slices=2)).evaluate(ds, batch_size=24)
    assert ref["count"] == got["count"] == got_hybrid["count"] == float(n)
    for k in ref:
        np.testing.assert_allclose(got[k], ref[k], rtol=1e-5)
        np.testing.assert_allclose(got_hybrid[k], ref[k], rtol=1e-5)


@pytest.mark.parametrize("where", ["cv", "gpt2", "session"])
def test_split_compile_stub_rejects(where):
    """`--split_compile` / `split_compile=` went in PR 29. What is left is a
    stub that benchmark/builders/common.py still names: the parser takes the
    flag (default false) and `resolve_defaults` exits on it, the session takes
    the keyword and raises on it, both with the same words."""
    words = "removed in PR 29"
    if where == "session":
        from commefficient_tpu.federated.api import FederatedSession

        with pytest.raises(ValueError, match=words):
            FederatedSession(
                train_loss_fn=mlp_loss, eval_loss_fn=mlp_loss, params={},
                net_state={}, mode_cfg=None, train_set=None, num_workers=8,
                local_batch_size=2, split_compile=True)
        return
    from commefficient_tpu.utils.config import make_parser, resolve_defaults

    parser = make_parser(where)
    assert parser.parse_args([]).split_compile is False
    with pytest.raises(SystemExit, match=words):
        resolve_defaults(parser.parse_args(["--split_compile"]))


@pytest.mark.parametrize("chunk", [2, 4, 8])
def test_client_chunked_reduce_matches_unchunked(chunk):
    """cfg.client_chunk scans the grads in chunks accumulating additively —
    equal to the one-shot vmap up to fp summation order, with dropout
    active."""
    W = 8
    data = _data(jax.random.PRNGKey(1), W * 4)
    batch = jax.tree.map(lambda a: a.reshape((W, 4) + a.shape[1:]), data)
    lr, rng = jnp.float32(0.1), jax.random.PRNGKey(9)
    kw = dict(mode="sketch", k=16, num_rows=3, num_cols=1024,
              hash_family="rotation", momentum_type="virtual", error_type="virtual")

    _, s0, step0 = _make(dict(kw), wd=5e-4, client_dropout=0.3)
    _, sC, stepC = _make(dict(kw), wd=5e-4, client_dropout=0.3,
                            client_chunk=chunk)
    a, _, ma = step0(s0, batch, {}, lr, rng)
    b, _, mb = stepC(sC, batch, {}, lr, rng)
    assert float(ma["participants"]) == float(mb["participants"])
    np.testing.assert_allclose(float(ma["loss_sum"]), float(mb["loss_sum"]), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(ravel_pytree(a["params"])[0]),
        np.asarray(ravel_pytree(b["params"])[0]), rtol=1e-5, atol=1e-7,
    )


def test_client_chunk_must_divide_cohort():
    W = 8
    data = _data(jax.random.PRNGKey(1), W * 4)
    batch = jax.tree.map(lambda a: a.reshape((W, 4) + a.shape[1:]), data)
    _, state, step = _make(_ucfg(), client_chunk=3)
    with pytest.raises(ValueError, match="divide"):
        step(state, batch, {}, jnp.float32(0.1), jax.random.PRNGKey(0))


def test_client_chunked_sharded_matches_unsharded():
    """Chunking composes with the client mesh: each chunk's vmap stays
    sharded over the client axis."""
    from commefficient_tpu.parallel import mesh as meshlib

    mesh = meshlib.make_mesh(8)
    data = _data(jax.random.PRNGKey(5), 64)
    w16 = jax.tree.map(lambda a: a.reshape((16, 4) + a.shape[1:]), data)
    lr, rng = jnp.float32(0.1), jax.random.PRNGKey(4)
    _, s_ref, step_ref = _make(_ucfg(), client_chunk=4)
    ref, _, mref = step_ref(s_ref, w16, {}, lr, rng)
    _, s_m, step_m = _make(_ucfg(), client_chunk=4)
    got, _, mgot = step_m(s_m, meshlib.shard_client_batch(mesh, w16), {}, lr, rng)
    np.testing.assert_allclose(
        np.asarray(ravel_pytree(got["params"])[0]),
        np.asarray(ravel_pytree(ref["params"])[0]), rtol=1e-5, atol=1e-6,
    )
    assert float(mgot["count"]) == float(mref["count"])


def test_session_adjusts_client_chunk_to_cohort():
    """Constructor-time safety: cohort clamping/rounding can invalidate the
    requested chunk; the session must adjust it (largest viable divisor)
    rather than crash at the first jit trace."""
    from commefficient_tpu.data.fed_dataset import FedDataset, shard_iid
    from commefficient_tpu.federated.api import FederatedSession

    rngd = np.random.RandomState(0)
    n = 64
    x = rngd.normal(size=(n, 10)).astype(np.float32)
    y = rngd.randint(0, 4, size=n).astype(np.int32)
    params = init_mlp(jax.random.PRNGKey(0))
    d = ravel_pytree(params)[0].size
    s = FederatedSession(
        train_loss_fn=mlp_loss, eval_loss_fn=mlp_loss, params=params,
        net_state={}, mode_cfg=ModeConfig(**_ucfg(d=d)),
        train_set=FedDataset(x, y, shard_iid(n, 16, rngd)),
        num_workers=12, local_batch_size=2,
        mesh=meshlib.make_mesh(8),  # rounds cohort 12 -> 16
        client_chunk=6,             # divided 12; no longer divides 16
    )
    # on the 8-way mesh the SPMD round scans chunks WITHIN each shard, so
    # the chunk adjusts to the per-shard cohort (16/8 = 2), not the global 16
    assert s.num_workers == 16 and s.cfg.client_shards == 8
    assert s.cfg.client_chunk == 2
    m = s.run_round(0.1)  # and the round actually runs chunked
    assert np.isfinite(m["loss_sum"])


def test_negative_client_chunk_rejected():
    with pytest.raises(ValueError, match="client_chunk"):
        _make(_ucfg(), client_chunk=-2)


def test_session_queued_rounds_match_run_round():
    """Four rounds dispatched back to back on the sharded mesh and published
    by one commit_rounds: identical sampling/rng/metrics/comm to sequential
    run_round calls."""
    from commefficient_tpu.data.fed_dataset import FedDataset, shard_iid
    from commefficient_tpu.federated.api import FederatedSession

    rngd = np.random.RandomState(0)
    n = 64
    x = rngd.normal(size=(n, 10)).astype(np.float32)
    y = rngd.randint(0, 4, size=n).astype(np.int32)

    def make():
        params = init_mlp(jax.random.PRNGKey(0))
        d = ravel_pytree(params)[0].size
        return FederatedSession(
            train_loss_fn=mlp_loss, eval_loss_fn=mlp_loss,
            params=jax.tree.map(jnp.copy, params), net_state={},
            mode_cfg=ModeConfig(mode="sketch", d=d, k=16, num_rows=3,
                                num_cols=1024, hash_family="rotation",
                                momentum_type="virtual", error_type="virtual"),
            train_set=FedDataset(x, y, shard_iid(n, 16, np.random.RandomState(1))),
            num_workers=8, local_batch_size=2, seed=7,
            mesh=meshlib.make_mesh(8), client_dropout=0.25,
        )

    a, b = make(), make()
    seq = [a.run_round(lr) for lr in (0.1, 0.2, 0.05, 0.1)]
    blk = run_queued(b, [0.1, 0.2, 0.05, 0.1])
    assert len(blk) == 4
    for ma, mb in zip(seq, blk):
        assert set(ma) == set(mb)
        for k2 in ma:
            np.testing.assert_allclose(ma[k2], mb[k2], rtol=1e-5)
    assert a.round == b.round == 4
    np.testing.assert_allclose(a.comm_mb_total, b.comm_mb_total, rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(ravel_pytree(a.state["params"])[0]),
        np.asarray(ravel_pytree(b.state["params"])[0]), rtol=1e-5, atol=1e-7,
    )


def test_session_hybrid_mesh_matches_flat_mesh():
    """Rounds on the (slices, clients) DCN x ICI mesh: the client batch
    shards over both axes and the rounds match the flat-mesh session."""
    from commefficient_tpu.data.fed_dataset import FedDataset, shard_iid
    from commefficient_tpu.federated.api import FederatedSession

    rngd = np.random.RandomState(0)
    n = 64
    x = rngd.normal(size=(n, 10)).astype(np.float32)
    y = rngd.randint(0, 4, size=n).astype(np.int32)

    def make(mesh):
        params = init_mlp(jax.random.PRNGKey(0))
        d = ravel_pytree(params)[0].size
        return FederatedSession(
            train_loss_fn=mlp_loss, eval_loss_fn=mlp_loss,
            params=jax.tree.map(jnp.copy, params), net_state={},
            mode_cfg=ModeConfig(mode="sketch", d=d, k=16, num_rows=3,
                                num_cols=1024, hash_family="rotation",
                                momentum_type="virtual", error_type="virtual"),
            train_set=FedDataset(x, y, shard_iid(n, 16, np.random.RandomState(1))),
            num_workers=8, local_batch_size=2, seed=7, mesh=mesh,
        )

    a = make(meshlib.make_mesh(8))
    b = make(meshlib.make_mesh(8, num_slices=2))
    ma = [a.run_round(lr) for lr in (0.1, 0.2)]
    mb = [b.run_round(lr) for lr in (0.1, 0.2)]
    for ra, rb in zip(ma, mb):
        np.testing.assert_allclose(ra["loss_sum"], rb["loss_sum"], rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(ravel_pytree(a.state["params"])[0]),
        np.asarray(ravel_pytree(b.state["params"])[0]), rtol=1e-5, atol=1e-6,
    )


def test_queued_local_topk_virtual_downlink_accounting():
    """Queued rounds with local_topk (error_type=virtual): the per-round
    measured down_support must fold into comm accounting at the batch
    commit identically to sequential rounds."""
    from commefficient_tpu.data.fed_dataset import FedDataset, shard_iid
    from commefficient_tpu.federated.api import FederatedSession

    rngd = np.random.RandomState(0)
    n = 64
    x = rngd.normal(size=(n, 10)).astype(np.float32)
    y = rngd.randint(0, 4, size=n).astype(np.int32)

    def make():
        params = init_mlp(jax.random.PRNGKey(0))
        d = ravel_pytree(params)[0].size
        return FederatedSession(
            train_loss_fn=mlp_loss, eval_loss_fn=mlp_loss,
            params=jax.tree.map(jnp.copy, params), net_state={},
            mode_cfg=ModeConfig(mode="local_topk", d=d, k=16,
                                momentum_type="none", error_type="virtual"),
            train_set=FedDataset(x, y, shard_iid(n, 16, np.random.RandomState(1))),
            num_workers=8, local_batch_size=2, seed=7,
        )

    a, b = make(), make()
    seq = [a.run_round(0.1) for _ in range(3)]
    blk = run_queued(b, [0.1, 0.1, 0.1])
    for ma, mb in zip(seq, blk):
        assert "down_support" not in mb  # folded into the comm figures
        np.testing.assert_allclose(ma["comm_down_mb"], mb["comm_down_mb"], rtol=1e-6)
        np.testing.assert_allclose(ma["comm_total_mb"], mb["comm_total_mb"], rtol=1e-6)


def _mlp_session(mode_kw, **kw):
    """The 16-client MLP session the queued-round tests share."""
    from commefficient_tpu.data.fed_dataset import FedDataset, shard_iid
    from commefficient_tpu.federated.api import FederatedSession

    rngd = np.random.RandomState(0)
    n = 64
    x = rngd.normal(size=(n, 10)).astype(np.float32)
    y = rngd.randint(0, 4, size=n).astype(np.int32)
    params = init_mlp(jax.random.PRNGKey(0))
    d = ravel_pytree(params)[0].size
    return FederatedSession(
        train_loss_fn=mlp_loss, eval_loss_fn=mlp_loss,
        params=jax.tree.map(jnp.copy, params), net_state={},
        mode_cfg=ModeConfig(d=d, **mode_kw),
        train_set=FedDataset(x, y, shard_iid(n, 16, np.random.RandomState(1))),
        num_workers=8, local_batch_size=2, seed=7, **kw,
    )


_SKETCH_MLP = dict(mode="sketch", k=16, num_rows=3, num_cols=1024,
                   hash_family="rotation", momentum_type="virtual",
                   error_type="virtual")


def _recording_commits(session) -> list:
    """Every row the session's commits publish, in commit order."""
    rows = []
    commit = session.commit_rounds

    def commit_rounds(infls, hosts):
        got = commit(infls, hosts)
        rows.extend(got)
        return got

    session.commit_rounds = commit_rounds
    return rows


def test_commit_rounds_of_three_queued_dispatches_publishes_the_third():
    """Three dispatches in flight leave the committed view alone; one
    commit_rounds over all three advances session.round by 3 and publishes
    the THIRD dispatch's state and RNG snapshot, and the session is back to
    nothing in flight."""
    s = _mlp_session(_SKETCH_MLP)
    state0, snap0 = s.state, s.rng_snapshot
    infls = [s.dispatch_round(s.prepare_round(), 0.1) for _ in range(3)]
    assert s.round == 0 and s._inflight == 3
    assert s.state is state0 and s.rng_snapshot is snap0
    rows = s.commit_rounds(infls, jax.device_get([f.metrics for f in infls]))
    assert len(rows) == 3 and s.round == 3 and s._inflight == 0
    assert s.state is infls[2].new_state
    assert s.rng_snapshot is infls[2].snapshot


def test_prepare_round_counts_the_rounds_in_flight():
    """prepare_round() with no index prepares the round after every one
    already dispatched: committed rounds plus those in flight."""
    s = _mlp_session(_SKETCH_MLP)
    s.run_round(0.1)
    infls = [s.dispatch_round(s.prepare_round(), 0.1) for _ in range(2)]
    assert s.prepare_round().rnd == 3
    s.commit_rounds(infls, jax.device_get([f.metrics for f in infls]))
    assert s.round == 3


@pytest.mark.parametrize("sync_loop", [True, False], ids=["sync", "async"])
def test_run_loop_lr_follows_the_schedule(sync_loop):
    """Every committed round r carries lr = schedule(r / rounds_per_epoch),
    over 7 rounds whose eval (every 3) and checkpoint (every 2) boundaries
    fall at different rounds — the schedule advances once a round whatever
    the drains do."""
    from commefficient_tpu.federated.api import FedOptimizer
    from commefficient_tpu.runner import RunnerConfig, run_loop

    def schedule(epoch):
        return 0.1 * (1.0 + epoch)

    s = _mlp_session(_SKETCH_MLP)
    rows = _recording_commits(s)
    run_loop(s, FedOptimizer(schedule, rounds_per_epoch=3), RunnerConfig(
        total_rounds=7, eval_every=3, checkpoint_every=2,
        sync_loop=sync_loop, max_inflight=3, prefetch_depth=2),
        save_ckpt=lambda: None)
    assert s.round == 7
    assert [m["lr"] for m in rows] == [
        float(schedule(r / 3)) for r in range(7)]


def test_local_state_mode_queued_loop_matches_sync_loop():
    """A mode with per-client persistent state (local_topk with local error
    feedback: the host gathers and scatters its rows between rounds) through
    the async loop at depth 3 equals --sync_loop: params, client state and
    every committed row."""
    from commefficient_tpu.federated.api import FedOptimizer
    from commefficient_tpu.runner import RunnerConfig, run_loop

    kw = dict(mode="local_topk", k=16, momentum_type="none",
              error_type="local", num_clients=16)
    out = []
    for sync_loop in (True, False):
        s = _mlp_session(kw)
        assert s.client_state is not None
        rows = _recording_commits(s)
        run_loop(s, FedOptimizer(lambda _: 0.1, 1), RunnerConfig(
            total_rounds=6, eval_every=1 << 30, sync_loop=sync_loop,
            max_inflight=3, prefetch_depth=2))
        out.append((s, rows))
    (a, rows_a), (b, rows_b) = out
    assert a.round == b.round == 6 and len(rows_a) == 6
    assert rows_a == rows_b
    for x, y in zip(jax.tree.leaves(jax.device_get(a.state["params"])),
                    jax.tree.leaves(jax.device_get(b.state["params"]))):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    for x, y in zip(jax.tree.leaves(jax.device_get(a.client_state)),
                    jax.tree.leaves(jax.device_get(b.client_state))):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_localsgd_single_iter_matches_uncompressed():
    """mode=localSGD (SURVEY.md §2 L2: the sixth mode — zero coverage until
    round 4): with 1 local iteration and no momentum anywhere, the client's
    weight delta is exactly lr*grad and the server applies the survivor mean
    at unit rate — bit-for-bit the uncompressed control on the same rounds."""
    data = _data(jax.random.PRNGKey(11), 24)
    batch = jax.tree.map(lambda a: a.reshape((3, 1, 8) + a.shape[1:]), data)
    lr = jnp.float32(0.15)
    cfg_l, state_l, step_l = _make(
        dict(mode="localSGD", momentum_type="none", error_type="none",
             num_local_iters=1))
    cfg_u, state_u, step_u = _make(_ucfg(momentum_type="none"))
    ubatch = jax.tree.map(lambda a: a.reshape((3, 8) + a.shape[1:]), data)
    for i in range(3):
        state_l, _, _ = step_l(state_l, batch, {}, lr, jax.random.PRNGKey(i))
        state_u, _, _ = step_u(state_u, ubatch, {}, lr, jax.random.PRNGKey(i))
    for a, b in zip(jax.tree.leaves(state_l["params"]),
                    jax.tree.leaves(state_u["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_localsgd_virtual_momentum_multi_iter():
    """localSGD's own niche vs fedavg: SERVER (virtual) momentum over
    multi-iter weight deltas — V = rho*V + mean(delta), applied at
    server_lr=1. Pinned against a manual replay of the algebra."""
    data = _data(jax.random.PRNGKey(12), 12)
    micro = jax.tree.map(lambda a: a.reshape((1, 3, 4) + a.shape[1:]), data)
    lr, rho = jnp.float32(0.1), 0.6
    cfg, state, step = _make(
        dict(mode="localSGD", momentum_type="virtual", momentum=rho,
             error_type="none", num_local_iters=3))
    p0 = jax.tree.map(jnp.copy, state["params"])
    s1, _, _ = step(state, micro, {}, lr, jax.random.PRNGKey(0))
    s2, _, _ = step(s1, micro, {}, lr, jax.random.PRNGKey(1))

    # manual: delta_t = 3-step local SGD from the server params; V accumulates
    from jax.flatten_util import ravel_pytree as rav

    def local_delta(params, rng):
        pflat, unravel = rav(params)
        p = pflat
        rngs = jax.random.split(rng, 3)
        for j in range(3):
            mb = jax.tree.map(lambda a: a[0, j], micro)
            g = jax.grad(lambda q: mlp_loss(unravel(q), {}, mb, rngs[j])[0])(p)
            p = p - lr * g
        return pflat - p

    pflat0, unravel = rav(p0)
    V = jnp.zeros_like(pflat0)
    p = pflat0
    for i in range(2):
        V = rho * V + local_delta(unravel(p), jax.random.split(
            jax.random.split(jax.random.PRNGKey(i), 3)[0], 1)[0])
        p = p - V
    for a, b in zip(jax.tree.leaves(s2["params"]), jax.tree.leaves(unravel(p))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


# ---- the per-leaf cohort reduce (PR 26) -------------------------------------

@pytest.mark.parametrize("chunk", [0, 2])
@pytest.mark.parametrize("mode_kw", [
    dict(mode="sketch", k=16, num_rows=3, num_cols=1024, hash_family="rotation",
         momentum_type="virtual", error_type="virtual"),
    _ucfg(momentum_type="virtual", momentum=0.9),
], ids=["sketch", "uncompressed"])
def test_linear_round_program_holds_no_client_stack(mode_kw, chunk):
    """The guard that keeps the [W, d] stack of flat per-client gradients from
    coming back: the linear grad modes reduce the cohort leaf by leaf and
    ravel the reduced tree once, so neither the lowered nor the compiled
    round program holds an f32[W, d] (or, chunked, f32[chunk, d]) array."""
    W = 4
    data = _data(jax.random.PRNGKey(1), W * 4)
    batch = jax.tree.map(lambda a: a.reshape((W, 4) + a.shape[1:]), data)
    cfg, state, step = _make(dict(mode_kw), wd=5e-4, client_chunk=chunk)
    d = cfg.mode.d
    lowered = step.lower(state, batch, {}, jnp.float32(0.1), jax.random.PRNGKey(0))
    texts = (lowered.as_text(), lowered.compile().as_text())
    assert f"tensor<{d}xf32>" in texts[0] and f"f32[{d}]" in texts[1]
    for rows in {W, chunk or W}:
        for text in texts:
            assert f"tensor<{rows}x{d}xf32>" not in text
            assert f"f32[{rows},{d}]" not in text


def bn_mlp_loss(params, net_state, batch, rng):
    """`mlp_loss` with a batch norm on the hidden layer: the statistics are
    the CLIENT's own batch's, and the new running statistics come back as a
    mutable collection."""
    pre = batch["x"] @ params["w1"] + params["b1"]
    mean, var = pre.mean(0), pre.var(0)
    h = jnp.tanh((pre - mean) * jax.lax.rsqrt(var + 1e-5))
    logits = h @ params["w2"] + params["b2"]
    per_ex = -jnp.take_along_axis(
        jax.nn.log_softmax(logits), batch["y"][:, None], axis=1)[:, 0]
    mask = batch["mask"]
    stats = net_state["batch_stats"]
    new_stats = {"mean": 0.9 * stats["mean"] + 0.1 * mean,
                 "var": 0.9 * stats["var"] + 0.1 * var}
    return (per_ex * mask).sum() / jnp.maximum(mask.sum(), 1.0), {
        "net_state": {"batch_stats": new_stats},
        "metrics": {"loss_sum": (per_ex * mask).sum(), "count": mask.sum(),
                    "correct": ((logits.argmax(-1) == batch["y"]) * mask).sum()},
    }


def _one_at_a_time_reduce(cfg, params, batch, rngs, part, qmed, lmed,
                          loss=mlp_loss, net_state=None):
    """What `_weighted_client_reduce` must equal: every client's FLAT
    gradient computed alone (no vmap, no tree), then the screen, the clip
    and the masked sum in numpy, in the order the engine documents. The
    fifth value is the surviving clients' sum of the mutable collections."""
    net_state = {} if net_state is None else net_state
    pflat, _ = ravel_pytree(params)
    segs = engine._leaf_segments(params)
    W = part.shape[0]
    flats, nstates = [], []
    for i in range(W):
        cb = jax.tree.map(lambda a: a[i], batch)
        g, aux = jax.grad(lambda p: loss(p, net_state, cb, rngs[i]),
                          has_aux=True)(params)
        flats.append(np.asarray(ravel_pytree(g)[0] + cfg.weight_decay * pflat))
        nstates.append(np.asarray(ravel_pytree(aux["net_state"])[0]))
    flats = np.stack(flats)
    norms = np.sqrt((flats ** 2).sum(1))
    lnorms = np.stack([np.sqrt((flats[:, o:o + n] ** 2).sum(1)) for o, n in segs], 1)
    part_eff = np.asarray(part).copy()
    if cfg.client_update_clip > 0:
        bad = ~np.isfinite(norms)
        if qmed > 0:
            bad |= norms > cfg.client_update_clip * qmed
        if lmed is not None:
            bad |= (~np.isfinite(lnorms)).any(1)
            bad |= ((lmed[None] > 0) & (lnorms > cfg.client_update_clip * lmed[None])).any(1)
        part_eff = part_eff * (1.0 - bad)
    if cfg.dp_clip > 0:
        flats = flats * np.minimum(1.0, cfg.dp_clip / np.maximum(norms, 1e-12))[:, None]
    live = [i for i in range(W) if part_eff[i] > 0]
    wsum = sum((flats[i] for i in live), np.zeros_like(flats[0]))
    ns_sum = sum((nstates[i] for i in live), np.zeros_like(nstates[0]))
    return wsum, part_eff, norms, lnorms, ns_sum


# the cases of the test below that arm a per-client transform and so take
# `_weighted_client_reduce`'s per-client path; the others take the fused one
PER_CLIENT_CASES = ("quarantine_cohort", "quarantine_layer", "dp_clip")


@pytest.mark.parametrize("case", [
    "plain", "valid_mask_nan", "quarantine_cohort", "quarantine_layer",
    "dp_clip", "client_chunk", "batch_norm", "random_mask", "client_chunk_nan"])
def test_leafwise_reduce_equals_one_client_at_a_time(case):
    """sum_i w_i ravel(g_i) == ravel(sum_i w_i g_i) == ravel(grad sum_i w_i
    L_i): the cohort reduce against per-client flat gradients computed one
    client at a time, on both of its paths (PER_CLIENT_CASES take the
    per-leaf reduce of per-client gradients, the others the one backward
    pass of the masked sum of losses), under every transform that sits
    between the gradient and the sum."""
    W = 8
    params = init_mlp(jax.random.PRNGKey(0))
    d = ravel_pytree(params)[0].size
    data = _data(jax.random.PRNGKey(3), W * 4)
    batch = jax.tree.map(lambda a: a.reshape((W, 4) + a.shape[1:]), data)
    # distinct gradient scales, so that a norm screen has something to tell
    batch["x"] = batch["x"] * jnp.linspace(0.5, 3.0, W)[:, None, None]
    rngs = jax.random.split(jax.random.PRNGKey(5), W)
    part = np.ones(W, np.float32)
    eng_kw, nan_safe, qmed, lmed = {}, False, None, None
    loss, net_state = mlp_loss, {}
    if case == "valid_mask_nan":
        part[2] = 0.0
        batch["x"] = batch["x"].at[2].set(jnp.nan)
        nan_safe = True
    elif case.startswith("quarantine"):
        eng_kw = dict(client_update_clip=2.0, quarantine_scope=case.split("_")[1])
        batch["x"] = batch["x"].at[5].set(jnp.nan)   # live and poisoned
    elif case == "dp_clip":
        eng_kw = dict(dp_clip=0.05)
    elif case == "client_chunk":
        eng_kw = dict(client_chunk=2)
        part[6] = 0.0
    elif case == "batch_norm":
        # per-client statistics under the one vmap, a mutable collection
        # back, and two clients masked
        loss = bn_mlp_loss
        net_state = {"batch_stats": {"mean": jnp.full(16, 0.25), "var": jnp.ones(16)}}
        part[[1, 4]] = 0.0
        nan_safe = True
    elif case == "random_mask":
        # the dropout simulation's mask with no validity mask riding the
        # batch: the multiply form of the weighting
        part = np.asarray(engine.participation_mask(
            jax.random.PRNGKey(11), W, 0.4))
        assert 0 < part.sum() < W
    elif case == "client_chunk_nan":
        eng_kw = dict(client_chunk=4)
        part[5] = 0.0
        batch["x"] = batch["x"].at[5].set(jnp.nan)
        nan_safe = True
    cfg = engine.EngineConfig(mode=ModeConfig(**_ucfg(d=d)), weight_decay=5e-4, **eng_kw)
    assert (case in PER_CLIENT_CASES) != engine.cohort_backward_fused(cfg)

    if cfg.client_update_clip > 0:
        # thresholds between the two largest finite norms: the screen
        # rejects exactly one healthy client beside the poisoned one
        _, _, norms0, lnorms0, _ = _one_at_a_time_reduce(
            cfg, params, batch, rngs, part, 0.0, None)
        between = lambda v: float(np.sort(v[np.isfinite(v)])[-2:].mean()) / 2.0  # noqa: E731
        if case == "quarantine_cohort":
            qmed = between(norms0)
        else:
            qmed = 0.0
            lmed = np.zeros(lnorms0.shape[1], np.float32)
            lmed[2] = between(lnorms0[:, 2])
    want, want_part, want_norms, want_lnorms, want_ns = _one_at_a_time_reduce(
        cfg, params, batch, rngs, part, qmed, lmed, loss, net_state)

    got, ns_sum, m_sum, part_eff, norms, lnorms = jax.jit(
        lambda b, r, p: engine._weighted_client_reduce(
            cfg, loss, params, net_state, b, r, p,
            qmed=None if qmed is None else jnp.float32(qmed), nan_safe=nan_safe,
            lmed=None if lmed is None else jnp.asarray(lmed)))(
        batch, rngs, jnp.asarray(part))
    assert got.shape == (d,) and np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(part_eff), want_part)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6,
                               atol=1e-6 * np.abs(want).max())
    assert float(m_sum["count"]) == 4.0 * want_part.sum()
    if net_state:
        np.testing.assert_allclose(np.asarray(ravel_pytree(ns_sum)[0]), want_ns,
                                   rtol=1e-6, atol=1e-6)
    if case in ("valid_mask_nan", "client_chunk_nan"):
        assert want_part.sum() == W - 1
    if cfg.client_update_clip > 0:
        assert want_part.sum() == W - 2 and want_part[5] == 0
        live = np.isfinite(want_norms)
        np.testing.assert_allclose(np.asarray(norms)[live], want_norms[live], rtol=1e-5)
        assert not np.isfinite(np.asarray(norms)[~live]).any()
        if lmed is not None:
            np.testing.assert_allclose(np.asarray(lnorms)[live], want_lnorms[live], rtol=1e-5)
    else:
        assert norms is None and lnorms is None


@pytest.mark.parametrize("chunk", [0, 2])
@pytest.mark.parametrize("eng_kw,per_client", [
    ({}, False),
    (dict(client_update_clip=2.0), True),
    (dict(client_update_clip=2.0, quarantine_scope="layer"), True),
    (dict(dp_clip=0.05), True),
], ids=["no_transform", "quarantine", "quarantine_layer", "dp_clip"])
def test_cohort_backward_path_by_lowered_text(eng_kw, per_client, chunk):
    """Which path engaged, read from the round program's lowered text
    (before the compiler reorders dimensions): with no per-client transform
    armed no array of shape (clients,) + shape of the largest leaf exists,
    because the one backward pass contracts the client axis into the weight
    gradient; a quarantine or a DP clip, which must see one client's
    gradient alone, brings the [W, ...leaf] stack back."""
    W = 4
    data = _data(jax.random.PRNGKey(1), W * 4)
    batch = jax.tree.map(lambda a: a.reshape((W, 4) + a.shape[1:]), data)
    batch[engine.VALID_KEY] = jnp.ones(W)
    cfg, state, step = _make(_ucfg(momentum_type="virtual", momentum=0.9),
                             wd=5e-4, client_chunk=chunk, **eng_kw)
    assert engine.cohort_backward_fused(cfg) != per_client
    text = step.lower(state, batch, {}, jnp.float32(0.1),
                      jax.random.PRNGKey(0)).as_text()
    leaf = max(jax.tree.leaves(state["params"]), key=lambda a: a.size)   # w1, 10 x 16
    stack = f"tensor<{chunk or W}x{'x'.join(map(str, leaf.shape))}xf32>"
    assert (stack in text) == per_client
