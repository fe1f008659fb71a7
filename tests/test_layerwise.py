"""Sketch-as-you-backprop (ISSUE 8 tentpole): layerwise Count-Sketch
accumulation — the dense [d] gradient never materializes — pinned
BIT-identical to the ravel path, plus the count-sketched server optimizer
state (--server_state sketch).

The bit-identity contract under test: `sketch_path="layerwise"` folds each
layer's gradient block into the running r x c table (sketch/layerwise.py)
instead of raveling the pytree into a flat [d] vector first, and produces
the IDENTICAL BITS — params, server mode state, and every logged metric —
across the fused, sharded (mesh == single-device reference), and
checkpoint+resume paths. The foundation is csvec._sketch_vec_rotation's
explicit slab-order left fold: per bucket both paths perform the same
ordered float sum (boundary slabs split across two leaves contribute an
exact ±0.0 from the non-owning leaf, which IEEE addition ignores).

conftest forces an 8-device CPU mesh, so the mesh tests run here and in
scripts/tier1_8dev.sh.

Known, deliberate non-bitwise caveat: the quarantine/dp_clip client NORMS
fold per-leaf partial sums (the flat path reduces one contiguous axis), so
the quarantine_median METRIC matches the ravel path at ~1e-6 relative, not
bitwise; the quarantine's behavior (rejected == dropped) is pinned bitwise
WITHIN the layerwise path below.
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
from commefficient_tpu.sketch import csvec, layerwise


# --------------------------------------------------------------- unit layer


def _leaf_partition(flat, sizes, shapes=None):
    leaves, off = {}, 0
    for i, s in enumerate(sizes):
        leaf = flat[off:off + s]
        if shapes and shapes[i] is not None:
            leaf = leaf.reshape(shapes[i])
        leaves[f"l{i:02d}"] = jnp.asarray(leaf)
        off += s
    assert off == flat.size
    return leaves


@pytest.mark.parametrize("family", ["rotation", "random"])
@pytest.mark.parametrize("d,c,r,sizes", [
    (1000, 64, 3, (37, 200, 463, 300)),       # boundary slabs split mid-leaf
    (777, 1024, 5, (100, 677)),               # c > d: single slab
    (4096, 256, 3, (256, 1024, 2816)),        # slab-aligned leaves
])
def test_sketch_tree_bitwise_equals_sketch_vec(family, d, c, r, sizes):
    """THE unit pin: leaf-by-leaf accumulation == one-shot sketch of the
    raveled vector, bit for bit, for any leaf partition — multi-dim leaf
    shapes included (ravel order is row-major reshape)."""
    spec = csvec.CSVecSpec(d=d, c=c, r=r, seed=13, family=family)
    flat = np.random.RandomState(0).randn(d).astype(np.float32)
    shapes = [None] * len(sizes)
    if sizes[1] % 4 == 0:
        shapes[1] = (4, sizes[1] // 4)
    tree = _leaf_partition(flat, sizes, shapes)
    ref = jax.jit(lambda v: csvec.sketch_vec(spec, v))(jnp.asarray(flat))
    got = jax.jit(lambda t: layerwise.sketch_tree(spec, t))(tree)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


def test_accumulate_leaf_single_block_matches_plan_path():
    spec = csvec.CSVecSpec(d=500, c=64, r=3, seed=5, family="rotation")
    flat = np.random.RandomState(1).randn(500).astype(np.float32)
    table = csvec.zero_table(spec)
    off = 0
    for s in (123, 250, 127):
        table = layerwise.accumulate_leaf(
            spec, table, jnp.asarray(flat[off:off + s]), off)
        off += s
    np.testing.assert_array_equal(
        np.asarray(csvec.sketch_vec(spec, jnp.asarray(flat))),
        np.asarray(table))


def test_apply_delta_tree_bitwise_equals_flat_apply():
    """Per-leaf sparse apply == flat scatter + unravel, bit for bit —
    idx = -1 padding and out-of-range entries contribute exactly nothing."""
    rs = np.random.RandomState(3)
    flat = rs.randn(600).astype(np.float32)
    tree = _leaf_partition(flat, (150, 250, 200), [None, (50, 5), None])
    pflat, unravel = ravel_pytree(tree)
    spec = csvec.CSVecSpec(d=600, c=128, r=3)
    idx = jnp.asarray(
        np.concatenate([rs.choice(600, size=20, replace=False),
                        [-1, -1, 650]]), jnp.int32)
    vals = jnp.asarray(rs.randn(23), jnp.float32)
    want = unravel(modes.apply_delta(pflat, {"idx": idx, "vals": vals}))
    got = layerwise.apply_delta_tree(tree, {"idx": idx, "vals": vals},
                                     spec=spec)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(want[k]), np.asarray(got[k]))
        assert want[k].shape == got[k].shape


def test_block_plan_and_config_validation():
    spec = csvec.CSVecSpec(d=100, c=32, r=3)
    with pytest.raises(ValueError, match="block plan covers"):
        layerwise.make_block_plan(spec, {"a": jnp.zeros(99)})
    mcfg = ModeConfig(mode="uncompressed", d=10, momentum_type="none",
                      error_type="none")
    with pytest.raises(ValueError, match="requires mode='sketch'"):
        engine.EngineConfig(mode=mcfg, sketch_path="layerwise")
    blocked = ModeConfig(mode="sketch", d=100, k=8, num_rows=3, num_cols=32,
                         hash_family="random", num_blocks=4)
    with pytest.raises(ValueError, match="num_blocks=1"):
        engine.EngineConfig(mode=blocked, sketch_path="layerwise")
    with pytest.raises(ValueError, match="sketch_path"):
        engine.EngineConfig(mode=blocked, sketch_path="bogus")


# ------------------------------------------------------------- engine layer


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
    loss = (per_ex * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    return loss, {
        "net_state": net_state,
        "metrics": {"loss_sum": (per_ex * mask).sum(), "count": mask.sum()},
    }


def _batch(key, W=8, n=4, din=10, dout=4):
    kx, kw = jax.random.split(key)
    x = jax.random.normal(kx, (W * n, din))
    w_true = jax.random.normal(kw, (din, dout))
    data = {"x": x, "y": (x @ w_true).argmax(-1), "mask": jnp.ones(W * n)}
    return jax.tree.map(lambda a: a.reshape((W, n) + a.shape[1:]), data)


SKETCH_KW = dict(mode="sketch", k=16, num_rows=3, num_cols=1024,
                 hash_family="rotation", momentum_type="virtual",
                 error_type="virtual")

ENGINE_CASES = [
    ("plain", {}),
    ("dropout_guard", dict(client_dropout=0.25, on_nonfinite="skip")),
    ("chunked", dict(client_chunk=2)),
    ("random_family", {}),  # hash_family overridden below
]


def _cfg(eng_kw, sketch_path, family="rotation", shards=1):
    params = init_mlp(jax.random.PRNGKey(0))
    d = ravel_pytree(params)[0].size
    mcfg = ModeConfig(**{**SKETCH_KW, "d": d, "hash_family": family})
    kw = dict(eng_kw)
    if shards > 1:
        kw["client_shards"] = shards
    return params, engine.EngineConfig(mode=mcfg, weight_decay=5e-4,
                                       sketch_path=sketch_path, **kw)


def _run_steps(make, params, cfg, rounds=3, W=8):
    step = jax.jit(make(cfg))
    state = engine.init_server_state(
        cfg, jax.tree.map(jnp.copy, params), {})
    out = []
    for i in range(rounds):
        b = dict(_batch(jax.random.PRNGKey(10 + i), W=W))
        b[engine.VALID_KEY] = jnp.ones(W)
        state, _, m = step(state, b, {}, jnp.float32(0.1),
                           jax.random.PRNGKey(100 + i))
        out.append(jax.device_get(m))
    return state, out


def _assert_bitwise(a, b, mode_state=True):
    sa, ma = a
    sb, mb = b
    np.testing.assert_array_equal(
        np.asarray(ravel_pytree(sa["params"])[0]),
        np.asarray(ravel_pytree(sb["params"])[0]))
    if mode_state:
        for k in ("Vvelocity", "Verror"):
            np.testing.assert_array_equal(
                np.asarray(sa["mode_state"][k]),
                np.asarray(sb["mode_state"][k]))
    for ra, rb in zip(ma, mb):
        assert set(ra) == set(rb)
        for k in ra:
            np.testing.assert_array_equal(np.asarray(ra[k]),
                                          np.asarray(rb[k]), err_msg=k)


@pytest.mark.parametrize("name, eng_kw", ENGINE_CASES,
                         ids=[c[0] for c in ENGINE_CASES])
def test_layerwise_fused_bit_identical_to_ravel(name, eng_kw):
    """THE acceptance pin (fused): the layerwise round — per-leaf reduce,
    table accumulation, per-leaf delta apply — produces the identical bits
    (params, server sketch state, every metric) as the ravel round, across
    dropout/nonfinite-guard/client_chunk configs and both hash families."""
    family = "random" if name == "random_family" else "rotation"
    params, cfg_r = _cfg(eng_kw, "ravel", family)
    _, cfg_l = _cfg(eng_kw, "layerwise", family)
    ref = _run_steps(lambda c: engine.make_round_step(mlp_loss, c),
                     params, cfg_r)
    got = _run_steps(lambda c: engine.make_round_step(mlp_loss, c),
                     params, cfg_l)
    _assert_bitwise(ref, got)


def test_layerwise_sharded_bit_identical_to_ravel():
    """Sharded acceptance: on the 8-device mesh the layerwise round ==
    the ravel round bit-for-bit (same program shape, same ordered table
    merge — only the accumulation differs), and the mesh == single-device
    layerwise reference holds to the same contract the ravel path pins
    (params + metrics bitwise; server tables to last-bit tolerance,
    the documented XLA:CPU while-body-vs-inlined fp difference)."""
    mesh = meshlib.make_mesh(8)
    params, cfg_r = _cfg(dict(client_dropout=0.25, on_nonfinite="skip"),
                         "ravel", shards=8)
    _, cfg_l = _cfg(dict(client_dropout=0.25, on_nonfinite="skip"),
                    "layerwise", shards=8)
    W = 16
    mesh_r = _run_steps(
        lambda c: engine.make_sharded_round_step(mlp_loss, c, mesh),
        params, cfg_r, W=W)
    mesh_l = _run_steps(
        lambda c: engine.make_sharded_round_step(mlp_loss, c, mesh),
        params, cfg_l, W=W)
    _assert_bitwise(mesh_r, mesh_l)
    ref_l = _run_steps(
        lambda c: engine.make_sharded_round_step(mlp_loss, c, None),
        params, cfg_l, W=W)
    _assert_bitwise(ref_l, mesh_l, mode_state=False)
    for k in ("Vvelocity", "Verror"):
        np.testing.assert_allclose(
            np.asarray(ref_l[0]["mode_state"][k]),
            np.asarray(mesh_l[0]["mode_state"][k]), rtol=0, atol=1e-7)


def test_layerwise_dead_client_nan_inert():
    """_valid masking on the layerwise path: a dead client's row may carry
    NaN garbage and still contribute exact zero — the round equals the one
    whose dead rows are zeros, bit for bit (mask_rows per leaf)."""
    params, cfg = _cfg({}, "layerwise")
    step = jax.jit(engine.make_round_step(mlp_loss, cfg))
    W = 8
    valid = np.ones(W, np.float32)
    valid[2] = 0.0
    valid[5] = 0.0

    def run(poison):
        b = dict(_batch(jax.random.PRNGKey(42), W=W))
        if poison:
            x = np.asarray(b["x"]).copy()
            x[2] = np.nan
            x[5] = np.inf
            b["x"] = jnp.asarray(x)
        else:
            x = np.asarray(b["x"]).copy()
            x[2] = 0.0
            x[5] = 0.0
            b["x"] = jnp.asarray(x)
        b[engine.VALID_KEY] = jnp.asarray(valid)
        state = engine.init_server_state(
            cfg, jax.tree.map(jnp.copy, params), {})
        state, _, m = step(state, b, {}, jnp.float32(0.1),
                           jax.random.PRNGKey(0))
        return state, [jax.device_get(m)]

    _assert_bitwise(run(poison=True), run(poison=False))


def test_layerwise_quarantine_rejected_equals_dropped():
    """Quarantine on the layerwise path: a poisoned client rejected by the
    update-norm screen == the same client dropped via the validity mask,
    bit for bit (round 2, once the running median is seeded). Cross-path:
    the quarantine_median metric matches ravel at tolerance only (per-leaf
    norm fold — the documented caveat)."""
    eng_kw = dict(client_update_clip=3.0)
    params, cfg = _cfg(eng_kw, "layerwise")
    step = jax.jit(engine.make_round_step(mlp_loss, cfg))
    W = 8

    def run(poison_pos=None, drop_pos=None):
        state = engine.init_server_state(
            cfg, jax.tree.map(jnp.copy, params), {})
        ms = []
        for i in range(3):
            b = dict(_batch(jax.random.PRNGKey(10 + i), W=W))
            b[engine.VALID_KEY] = jnp.ones(W)
            if i == 2 and poison_pos is not None:
                x = np.asarray(b["x"]).copy()
                x[poison_pos] = np.nan  # non-finite norm -> quarantined
                b["x"] = jnp.asarray(x)
            if i == 2 and drop_pos is not None:
                v = np.ones(W, np.float32)
                v[drop_pos] = 0.0
                b[engine.VALID_KEY] = jnp.asarray(v)
            state, _, m = step(state, b, {}, jnp.float32(0.1),
                               jax.random.PRNGKey(100 + i))
            ms.append(jax.device_get(m))
        return state, ms

    quarantined = run(poison_pos=3)
    dropped = run(drop_pos=3)
    assert quarantined[1][2]["clients_quarantined"] == 1.0
    np.testing.assert_array_equal(
        np.asarray(ravel_pytree(quarantined[0]["params"])[0]),
        np.asarray(ravel_pytree(dropped[0]["params"])[0]))

    _, cfg_r = _cfg(eng_kw, "ravel")
    step_r = jax.jit(engine.make_round_step(mlp_loss, cfg_r))
    sr = engine.init_server_state(cfg_r, jax.tree.map(jnp.copy, params), {})
    b = dict(_batch(jax.random.PRNGKey(10), W=W))
    b[engine.VALID_KEY] = jnp.ones(W)
    _, _, mr = step_r(sr, b, {}, jnp.float32(0.1), jax.random.PRNGKey(100))
    np.testing.assert_allclose(
        float(quarantined[1][0]["quarantine_median"]),
        float(jax.device_get(mr)["quarantine_median"]), rtol=1e-5)


# ------------------------------------------------------------ session layer


def _mlp_dataset(n=64, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.normal(size=(n, 10)).astype(np.float32)
    y = rng.randint(0, 4, size=n).astype(np.int32)
    return FedDataset(x, y, shard_iid(n, 16, np.random.RandomState(1)))


def _session(sketch_path="ravel", mesh=None, client_shards=0, **kw):
    params = init_mlp(jax.random.PRNGKey(0))
    d = ravel_pytree(params)[0].size
    return FederatedSession(
        train_loss_fn=mlp_loss, eval_loss_fn=mlp_loss,
        params=jax.tree.map(jnp.copy, params), net_state={},
        mode_cfg=ModeConfig(**{**SKETCH_KW, "d": d}),
        train_set=_mlp_dataset(), num_workers=8, local_batch_size=2,
        seed=7, mesh=mesh, client_shards=client_shards,
        sketch_path=sketch_path, **kw,
    )


def test_layerwise_session_bit_identical_to_ravel_session():
    """Session-level acceptance: run_round + the run_rounds fused K-round
    block on a layerwise session == the ravel session, bit for bit —
    params and EVERY logged metric row (comm accounting included)."""
    a = _session("ravel")
    b = _session("layerwise")
    seq_a = [a.run_round(0.1), a.run_round(0.2)] + a.run_rounds([0.05, 0.1])
    seq_b = [b.run_round(0.1), b.run_round(0.2)] + b.run_rounds([0.05, 0.1])
    for ma, mb in zip(seq_a, seq_b):
        assert ma == mb
    np.testing.assert_array_equal(
        np.asarray(ravel_pytree(a.state["params"])[0]),
        np.asarray(ravel_pytree(b.state["params"])[0]))
    assert a.comm_mb_total == b.comm_mb_total


def test_layerwise_session_mesh_and_split():
    """Layerwise over the 8-way mesh session == ravel over the same mesh:
    every row and the params bitwise."""
    a = _session("ravel", mesh=meshlib.make_mesh(8))
    b = _session("layerwise", mesh=meshlib.make_mesh(8))
    for _ in range(2):
        assert a.run_round(0.1) == b.run_round(0.1)
    np.testing.assert_array_equal(
        np.asarray(ravel_pytree(a.state["params"])[0]),
        np.asarray(ravel_pytree(b.state["params"])[0]))


def test_layerwise_checkpoint_resume_bit_identical(tmp_path):
    """Checkpoint+resume mid-run ON THE LAYERWISE PATH: 2 rounds, save,
    fresh layerwise session restores, 2 more rounds — bit-identical to 4
    uninterrupted rounds AND to the same schedule on the ravel path."""
    from commefficient_tpu.utils import checkpoint as ckpt

    lrs = [0.1, 0.2, 0.05, 0.1]
    a = _session("layerwise", donate_state=False)
    straight = [a.run_round(lr) for lr in lrs]

    b = _session("layerwise", donate_state=False)
    first = [b.run_round(lr) for lr in lrs[:2]]
    ckpt.save(str(tmp_path / "ck"), b)

    c = _session("layerwise", donate_state=False)
    assert ckpt.restore_latest(str(tmp_path / "ck"), c)
    assert c.round == 2
    resumed = first + [c.run_round(lr) for lr in lrs[2:]]
    for ma, mb in zip(straight, resumed):
        assert ma == mb
    np.testing.assert_array_equal(
        np.asarray(ravel_pytree(a.state["params"])[0]),
        np.asarray(ravel_pytree(c.state["params"])[0]))

    r = _session("ravel", donate_state=False)
    for lr in lrs:
        r.run_round(lr)
    np.testing.assert_array_equal(
        np.asarray(ravel_pytree(r.state["params"])[0]),
        np.asarray(ravel_pytree(c.state["params"])[0]))


# ----------------------------------------- count-sketched server optimizer


def test_sketched_momentum_bitwise_at_lossless_width():
    """--server_state sketch parity pin: with c >= d (rotation family) the
    table is a signed permutation — no collisions, exact estimates — so
    true_topk with sketch-resident momentum/error produces the IDENTICAL
    bits (params + metrics) as the dense default, round after round; the
    server state itself shrinks from 2*[d] to 2*[r, c]."""
    params = init_mlp(jax.random.PRNGKey(0))
    d = ravel_pytree(params)[0].size
    base = ModeConfig(mode="true_topk", d=d, k=24, momentum_type="virtual",
                      error_type="virtual")
    c_lossless = 1 << (d - 1).bit_length()  # next pow2 >= d
    sk = dataclasses.replace(base, server_state="sketch", num_rows=3,
                             num_cols=c_lossless, hash_family="rotation")
    assert modes.init_server_state(sk)["Vvelocity"].shape == (3, c_lossless)
    assert modes.init_server_state(base)["Vvelocity"].shape == (d,)

    def run(mcfg):
        cfg = engine.EngineConfig(mode=mcfg, weight_decay=5e-4)
        return _run_steps(lambda c: engine.make_round_step(mlp_loss, c),
                          params, cfg, rounds=4)

    (s_dense, m_dense), (s_sk, m_sk) = run(base), run(sk)
    np.testing.assert_array_equal(
        np.asarray(ravel_pytree(s_dense["params"])[0]),
        np.asarray(ravel_pytree(s_sk["params"])[0]))
    for ra, rb in zip(m_dense, m_sk):
        for k in ra:
            np.testing.assert_array_equal(np.asarray(ra[k]),
                                          np.asarray(rb[k]), err_msg=k)


def test_sketched_momentum_compressed_width_runs():
    """c < d: the FetchSGD-style approximation — still converging table
    arithmetic, finite state, r x c memory; local_topk's virtual-error
    variant rides the same branch."""
    params = init_mlp(jax.random.PRNGKey(0))
    d = ravel_pytree(params)[0].size
    for mode, extra in (("true_topk", {}),
                        ("local_topk", dict(error_type="virtual",
                                            momentum_type="virtual"))):
        mcfg = ModeConfig(**{**dict(mode=mode, d=d, k=16,
                                    momentum_type="virtual",
                                    error_type="virtual",
                                    server_state="sketch", num_rows=3,
                                    num_cols=128), **extra})
        cfg = engine.EngineConfig(mode=mcfg)
        state, ms = _run_steps(
            lambda c: engine.make_round_step(mlp_loss, c), params, cfg,
            rounds=2)
        assert state["mode_state"]["Vvelocity"].shape == (3, 128)
        assert np.isfinite(
            np.asarray(ravel_pytree(state["params"])[0])).all()
        assert all(np.isfinite(list(m.values())).all() for m in ms)


def test_server_state_validation():
    with pytest.raises(ValueError, match="top-k release"):
        ModeConfig(mode="uncompressed", d=10, server_state="sketch",
                   momentum_type="virtual", error_type="none")
    with pytest.raises(ValueError, match="error_type='virtual'"):
        ModeConfig(mode="local_topk", d=10, k=4, server_state="sketch",
                   momentum_type="virtual", error_type="local",
                   num_cols=32)
    with pytest.raises(ValueError, match="num_cols"):
        ModeConfig(mode="true_topk", d=10, k=4, server_state="sketch",
                   momentum_type="virtual", error_type="virtual")
    # mode=sketch is already sketch-state: both spellings are accepted
    for ss in ("dense", "sketch"):
        ModeConfig(mode="sketch", d=10, k=4, num_cols=32, server_state=ss)
