"""The SPMD sharded round (ISSUE 3 tentpole): the CPU-mesh parity slice.

conftest forces an 8-device CPU mesh (XLA_FLAGS=
--xla_force_host_platform_device_count=8), so this whole file is the
forced-8-device tier-1 job slice — sharded-path regressions fail here, fast,
off-TPU (scripts/tier1_8dev.sh runs it standalone with the flags pinned
explicitly).

The bit-identity contract under test: client_shards=S is part of the round's
numerical contract (it fixes the fp summation order, like client_chunk), and
a given S produces IDENTICAL BITS on one device (the lax.map reference) and
on an S-way mesh (shard_map + all_gather ordered merge). Different shard
counts differ only at fp-reassociation level (allclose, pinned too).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.flatten_util import ravel_pytree

from commefficient_tpu.data.fed_dataset import FedDataset, shard_iid
from commefficient_tpu.federated import engine
from commefficient_tpu.federated.api import FederatedSession
from commefficient_tpu.modes import modes
from commefficient_tpu.modes.config import ModeConfig
from commefficient_tpu.parallel import mesh as meshlib


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
    return loss, {
        "net_state": net_state,
        "metrics": {"loss_sum": (per_ex * mask).sum(), "count": mask.sum()},
    }


def _data(key, n, din=10, dout=4):
    kx, kw = jax.random.split(key)
    x = jax.random.normal(kx, (n, din))
    w_true = jax.random.normal(kw, (din, dout))
    return {"x": x, "y": (x @ w_true).argmax(-1), "mask": jnp.ones(n)}


SKETCH_KW = dict(mode="sketch", k=16, num_rows=3, num_cols=1024,
                 hash_family="rotation", momentum_type="virtual",
                 error_type="virtual")

# >= 2 mode configs, deliberately exercising the full replicated tail:
# dropout + the compiled non-finite guard on the flagship sketch config, and
# DP clip+noise on the dense-wire control.
MODE_CASES = [
    ("sketch", dict(SKETCH_KW),
     dict(client_dropout=0.25, on_nonfinite="skip")),
    ("uncompressed_dp", dict(mode="uncompressed", momentum_type="virtual",
                             error_type="none"),
     dict(dp_clip=1.0, dp_noise=0.5, client_dropout=0.3)),
    ("true_topk_chunked", dict(mode="true_topk", k=24,
                               momentum_type="virtual", error_type="virtual"),
     dict(client_chunk=2)),
]


def _cfg(mode_kw, eng_kw, shards=8):
    params = init_mlp(jax.random.PRNGKey(0))
    d = ravel_pytree(params)[0].size
    mcfg = ModeConfig(**{**mode_kw, "d": d})
    cfg = engine.EngineConfig(mode=mcfg, weight_decay=5e-4,
                              client_shards=shards, **eng_kw)
    return params, cfg


def _flat(state):
    return np.asarray(ravel_pytree(state["params"])[0])


@pytest.mark.parametrize("name, mode_kw, eng_kw", MODE_CASES,
                         ids=[c[0] for c in MODE_CASES])
def test_sharded_mesh_bit_identical_to_single_device(name, mode_kw, eng_kw):
    """THE acceptance pin: the shard_map round on the 8-device mesh produces
    the same bits (params + every metric) as the same shard-structured
    program on one device, over multiple chained rounds. The server mode
    state is additionally pinned to last-bit tolerance: XLA:CPU's
    value-dependent vectorization of the identical per-shard subgraph
    differs between a while-loop body (the reference's lax.map) and the
    inlined shard_map body, leaving ~1e-9 on a handful of sketch-table
    entries — params and metrics still come out bit-equal, and everything
    structure-matched (hybrid vs flat mesh, block vs
    sequential, checkpoint resume) is pinned fully bitwise below."""
    mesh = meshlib.make_mesh(8)
    params, cfg = _cfg(mode_kw, eng_kw)
    W = 16
    data = _data(jax.random.PRNGKey(1), W * 4)
    batch = jax.tree.map(lambda a: a.reshape((W, 4) + a.shape[1:]), data)
    lr = jnp.float32(0.1)

    ref_step = jax.jit(engine.make_sharded_round_step(mlp_loss, cfg))
    mesh_step = jax.jit(engine.make_sharded_round_step(mlp_loss, cfg, mesh))
    s_ref = engine.init_server_state(cfg, jax.tree.map(jnp.copy, params), {})
    s_mesh = engine.init_server_state(cfg, jax.tree.map(jnp.copy, params), {})
    sharded_batch = meshlib.shard_client_batch(mesh, batch)
    for i in range(3):
        rng = jax.random.PRNGKey(100 + i)
        s_ref, _, m_ref = ref_step(s_ref, batch, {}, lr, rng)
        s_mesh, _, m_mesh = mesh_step(s_mesh, sharded_batch, {}, lr, rng)
        assert set(m_ref) == set(m_mesh)
        for k in m_ref:
            np.testing.assert_array_equal(np.asarray(m_ref[k]),
                                          np.asarray(m_mesh[k]), err_msg=k)
    np.testing.assert_array_equal(_flat(s_ref), _flat(s_mesh))
    for a, b in zip(jax.tree.leaves(s_ref["mode_state"]),
                    jax.tree.leaves(s_mesh["mode_state"])):
        # last-bit tolerance, not allclose-loose: see the docstring
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-7, atol=1e-8)


# The sharded round against the plain one, over every mode case and the two
# sketch configurations the split-compile pins ran until PR 29 (a chunked
# client scan; the layerwise sketch path): what ROADMAP D1 (one client
# program, one merge, one server program) will stand on.
PLAIN_CASES = MODE_CASES + [
    ("sketch_chunk4", dict(SKETCH_KW), dict(client_chunk=4)),
    ("sketch_layerwise", dict(SKETCH_KW), dict(sketch_path="layerwise")),
]


@pytest.mark.parametrize("name, mode_kw, eng_kw", PLAIN_CASES,
                         ids=[c[0] for c in PLAIN_CASES])
def test_sharded_allclose_to_plain_round(name, mode_kw, eng_kw):
    """Across shard counts the round changes only by fp summation order: the
    S=8 sharded round stays allclose to the plain (S=1) round. A chunked
    case takes a cohort of two chunks a shard, so both sides scan."""
    params, cfg = _cfg(mode_kw, eng_kw)
    cfg1 = dataclasses.replace(cfg, client_shards=1)
    W = 16 * max(1, eng_kw.get("client_chunk", 0))
    data = _data(jax.random.PRNGKey(2), W * 4)
    batch = jax.tree.map(lambda a: a.reshape((W, 4) + a.shape[1:]), data)
    lr, rng = jnp.float32(0.1), jax.random.PRNGKey(7)

    sharded = jax.jit(engine.make_sharded_round_step(mlp_loss, cfg))
    plain = jax.jit(engine.make_round_step(mlp_loss, cfg1))
    s_s, _, m_s = sharded(
        engine.init_server_state(cfg, jax.tree.map(jnp.copy, params), {}),
        batch, {}, lr, rng)
    s_p, _, m_p = plain(
        engine.init_server_state(cfg1, jax.tree.map(jnp.copy, params), {}),
        batch, {}, lr, rng)
    np.testing.assert_allclose(_flat(s_s), _flat(s_p), rtol=1e-5, atol=1e-7)
    assert float(m_s["participants"]) == float(m_p["participants"])
    np.testing.assert_allclose(float(m_s["loss_sum"]), float(m_p["loss_sum"]),
                               rtol=1e-6)


def test_sharded_multi_round_block_matches_sequential():
    """The K-round fused block scans the SPMD body: bitwise equal to K
    sequential sharded dispatches."""
    mesh = meshlib.make_mesh(8)
    params, cfg = _cfg(dict(SKETCH_KW), {})
    K, W = 3, 8
    x = jax.random.normal(jax.random.PRNGKey(4), (K, W, 4, 10))
    w_true = jax.random.normal(jax.random.PRNGKey(5), (10, 4))
    batches = {"x": x, "y": (x @ w_true).argmax(-1),
               "mask": jnp.ones((K, W, 4))}
    lrs = jnp.asarray([0.1, 0.2, 0.05], jnp.float32)
    rngs = jax.random.split(jax.random.PRNGKey(6), K)

    step = jax.jit(engine.make_sharded_round_step(mlp_loss, cfg, mesh))
    st = engine.init_server_state(cfg, jax.tree.map(jnp.copy, params), {})
    for i in range(K):
        b = meshlib.shard_client_batch(
            mesh, jax.tree.map(lambda a: a[i], batches))
        st, _, _ = step(st, b, {}, lrs[i], rngs[i])

    multi = jax.jit(engine.make_multi_round_step(mlp_loss, cfg, mesh))
    stm, ms = multi(
        engine.init_server_state(cfg, jax.tree.map(jnp.copy, params), {}),
        meshlib.shard_stacked_client_batch(mesh, batches), lrs, rngs)
    np.testing.assert_array_equal(_flat(st), _flat(stm))
    assert all(np.asarray(v).shape[0] == K for v in ms.values())


def test_sharded_scope_rejected_loudly():
    params = init_mlp(jax.random.PRNGKey(0))
    d = ravel_pytree(params)[0].size
    for kw in (
        dict(mode="local_topk", d=d, k=8, momentum_type="none",
             error_type="local", num_clients=4),
        dict(mode="fedavg", d=d, num_local_iters=2, error_type="none",
             momentum_type="none"),
    ):
        cfg = engine.EngineConfig(mode=ModeConfig(**kw), client_shards=8)
        with pytest.raises(ValueError, match="sharded round supports"):
            engine.make_sharded_round_step(mlp_loss, cfg)
    # nonlinear partial wires can't merge by addition
    with pytest.raises(ValueError, match="nonlinear"):
        modes.merge_partial_wires(
            ModeConfig(mode="local_topk", d=d, k=8, momentum_type="none",
                       error_type="none"),
            {"idx": jnp.zeros((2, 8), jnp.int32),
             "vals": jnp.zeros((2, 8))},
        )
    with pytest.raises(ValueError, match="client_shards"):
        engine.EngineConfig(mode=ModeConfig(mode="uncompressed", d=d,
                                            momentum_type="none",
                                            error_type="none"),
                            client_shards=0)


# --------------------------------------------------------------- session


def _mlp_dataset(n=64, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.normal(size=(n, 10)).astype(np.float32)
    y = rng.randint(0, 4, size=n).astype(np.int32)
    return FedDataset(x, y, shard_iid(n, 16, np.random.RandomState(1)))


def _session(mesh=None, client_shards=0, **kw):
    params = init_mlp(jax.random.PRNGKey(0))
    d = ravel_pytree(params)[0].size
    return FederatedSession(
        train_loss_fn=mlp_loss, eval_loss_fn=mlp_loss,
        params=jax.tree.map(jnp.copy, params), net_state={},
        mode_cfg=ModeConfig(**{**SKETCH_KW, "d": d}),
        train_set=_mlp_dataset(), num_workers=8, local_batch_size=2,
        seed=7, mesh=mesh, client_shards=client_shards, **kw,
    )


def test_session_mesh_bit_identical_to_reference_session():
    """Session-level acceptance: run_round + the run_rounds fused block on
    the 8-way mesh session == the client_shards=8 single-device reference
    session, bit for bit — params, mode state, and every logged metric
    (comm accounting included)."""
    a = _session(mesh=meshlib.make_mesh(8))
    b = _session(client_shards=8)
    assert a.cfg.client_shards == b.cfg.client_shards == 8
    seq_a = [a.run_round(0.1), a.run_round(0.2)] + a.run_rounds([0.05, 0.1])
    seq_b = [b.run_round(0.1), b.run_round(0.2)] + b.run_rounds([0.05, 0.1])
    for ma, mb in zip(seq_a, seq_b):
        assert ma == mb
    np.testing.assert_array_equal(
        np.asarray(ravel_pytree(a.state["params"])[0]),
        np.asarray(ravel_pytree(b.state["params"])[0]),
    )
    assert a.comm_mb_total == b.comm_mb_total


def test_session_hybrid_mesh_bit_identical_to_plain_mesh():
    """(slices, clients) DCN x ICI hybrid at the same total shard count:
    shard order is row-major over both axes, so the round is bit-identical
    to the flat 8-way mesh."""
    a = _session(mesh=meshlib.make_mesh(8))
    h = _session(mesh=meshlib.make_mesh(8, num_slices=2))
    assert a.run_round(0.1) == h.run_round(0.1)
    np.testing.assert_array_equal(
        np.asarray(ravel_pytree(a.state["params"])[0]),
        np.asarray(ravel_pytree(h.state["params"])[0]),
    )


def test_session_rejects_client_shards_for_out_of_scope_mode():
    """An EXPLICIT client_shards request for a mode outside the sharded
    scope must fail loudly (mirroring the engine's scope check) — silently
    running the plain round would hand a parity test a different program."""
    params = init_mlp(jax.random.PRNGKey(0))
    d = ravel_pytree(params)[0].size
    with pytest.raises(ValueError, match="sharded-round scope"):
        FederatedSession(
            train_loss_fn=mlp_loss, eval_loss_fn=mlp_loss, params=params,
            net_state={},
            mode_cfg=ModeConfig(mode="fedavg", d=d, momentum_type="none",
                                error_type="none", num_local_iters=2),
            train_set=_mlp_dataset(), num_workers=8, local_batch_size=2,
            client_shards=4,
        )


def test_session_rejects_client_shards_mesh_disagreement():
    """ANY explicit client_shards that disagrees with the mesh raises —
    including 1 ('force unsharded'), which must not silently compile the
    mesh's S-way program."""
    for shards in (1, 4):
        with pytest.raises(ValueError, match="disagrees"):
            _session(mesh=meshlib.make_mesh(8), client_shards=shards)


def test_session_out_of_scope_mode_keeps_gspmd_path():
    """local_topk with local error state is outside the SPMD scope: the
    session must keep the GSPMD path (client_shards stays 1) and still run."""
    params = init_mlp(jax.random.PRNGKey(0))
    d = ravel_pytree(params)[0].size
    s = FederatedSession(
        train_loss_fn=mlp_loss, eval_loss_fn=mlp_loss, params=params,
        net_state={},
        mode_cfg=ModeConfig(mode="local_topk", d=d, k=8,
                            momentum_type="none", error_type="local",
                            num_clients=16),
        train_set=_mlp_dataset(), num_workers=8, local_batch_size=2,
        seed=3, mesh=meshlib.make_mesh(8),
    )
    assert s.cfg.client_shards == 1 and not s._spmd
    assert np.isfinite(s.run_round(0.1)["loss_sum"])


def test_sharded_checkpoint_resume_bit_identical(tmp_path):
    """Checkpoint+resume mid-run ON THE SHARDED PATH: 2 rounds, save, fresh
    mesh session restores, 2 more rounds — bit-identical to 4 uninterrupted
    sharded rounds (params + metrics), so preemption recovery and the SPMD
    round compose."""
    from commefficient_tpu.utils import checkpoint as ckpt

    ckpt_dir = str(tmp_path / "ck")
    lrs = [0.1, 0.2, 0.05, 0.1]
    a = _session(mesh=meshlib.make_mesh(8), donate_state=False)
    straight = [a.run_round(lr) for lr in lrs]

    b = _session(mesh=meshlib.make_mesh(8), donate_state=False)
    first = [b.run_round(lr) for lr in lrs[:2]]
    ckpt.save(ckpt_dir, b)

    c = _session(mesh=meshlib.make_mesh(8), donate_state=False)
    assert ckpt.restore_latest(ckpt_dir, c)
    assert c.round == 2
    resumed = first + [c.run_round(lr) for lr in lrs[2:]]
    for ma, mb in zip(straight, resumed):
        assert ma == mb
    np.testing.assert_array_equal(
        np.asarray(ravel_pytree(a.state["params"])[0]),
        np.asarray(ravel_pytree(c.state["params"])[0]),
    )


# ------------------------------------------------- mesh spec + autotune


def test_parse_mesh_spec():
    assert meshlib.parse_mesh_spec("clients=8") == {"clients": 8, "slices": 1}
    assert meshlib.parse_mesh_spec("clients=4,slices=2") == {
        "clients": 4, "slices": 2}
    for bad in ("", "clients", "clients=0", "clients=4,model=2", "slices=2",
                "clients=x", "clients=8,clients=4"):
        with pytest.raises(ValueError):
            meshlib.parse_mesh_spec(bad)


def test_make_mesh_from_spec():
    m = meshlib.make_mesh_from_spec("clients=4,slices=2")
    assert meshlib.client_shards(m) == 8
    assert dict(m.shape) == {meshlib.DCN_AXIS: 2, meshlib.CLIENT_AXIS: 4}
    with pytest.raises(ValueError, match="devices"):
        meshlib.make_mesh_from_spec("clients=1024")


def test_merge_comm_bytes_headline():
    """The comm-efficiency arithmetic of the README's multi-chip section: at
    flagship dims the dense all-reduce costs ~d/(r*c) more than the sketch
    merge."""
    c = meshlib.merge_comm_bytes(8, r=5, c=500_000, d=6_500_000)
    assert c["dense_over_sketch_ratio"] == pytest.approx(2.6)
    assert c["sketch_table_mb"] == pytest.approx(10.0)
    assert (c["dense_allreduce_mb_per_device"]
            > c["sketch_psum_mb_per_device"])


def test_auto_inflight_policy():
    from commefficient_tpu.runner import auto_inflight

    # local backend: sub-ms RTT stays at the floor (3: a drain the depth
    # triggers keeps the newest dispatch queued and still reads two)
    assert auto_inflight(0.1, 50.0) == 3
    # slow host link: 70 ms RTT over a 50 ms round wants a deep chain
    assert auto_inflight(70.0, 50.0) == 14
    # clamped at the preemption-grace ceiling
    assert auto_inflight(500.0, 1.0) == 16
    # no round timed yet: the historical default
    assert auto_inflight(70.0, 0.0) == 4


def test_merge_tables_shape_guard():
    from commefficient_tpu.sketch import csvec

    spec = csvec.CSVecSpec(d=100, c=16, r=3, family="rotation")
    stacked = jnp.ones((4, 3, 16))
    np.testing.assert_array_equal(
        np.asarray(csvec.merge_tables(spec, stacked)), np.full((3, 16), 4.0))
    with pytest.raises(ValueError, match="stacked partial tables"):
        csvec.merge_tables(spec, jnp.ones((3, 16)))
