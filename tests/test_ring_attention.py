"""Ring attention == dense causal attention, with the seq axis sharded over
the 8-device CPU mesh (the long-context path's correctness oracle)."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from commefficient_tpu.ops.ring_attention import (
    _dense_causal,
    ring_attention,
    use_ring_mesh,
)


def _qkv(key, B=2, T=64, H=4, D=16):
    ks = jax.random.split(jax.random.PRNGKey(key), 3)
    return tuple(jax.random.normal(k, (B, T, H, D), dtype=jnp.float32) for k in ks)


def test_fallback_matches_reference_softmax():
    q, k, v = _qkv(0)
    out = ring_attention(q, k, v)
    ref = _dense_causal(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_ring_matches_dense_over_mesh():
    q, k, v = _qkv(1)
    ref = _dense_causal(q, k, v)
    for n in (2, 4, 8):
        mesh = Mesh(np.array(jax.devices()[:n]), ("seq",))
        with use_ring_mesh(mesh):
            out = ring_attention(q, k, v)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4,
            err_msg=f"ring_size={n}",
        )


def test_ring_under_jit():
    q, k, v = _qkv(2)
    mesh = Mesh(np.array(jax.devices()[:4]), ("seq",))
    with use_ring_mesh(mesh):
        out = jax.jit(ring_attention)(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_dense_causal(q, k, v)), rtol=2e-4, atol=2e-4
    )


def test_ring_inside_federated_round_matches_dense():
    """VERDICT r2 #8: ring attention INSIDE a federated GPT-2 round, combined
    with the client axis — a (clients=2, seq=4) mesh runs vmap-over-clients
    and shard_map-over-seq in one compiled program, matching the dense-attn
    unsharded round."""
    import dataclasses

    import numpy as np
    from jax.flatten_util import ravel_pytree
    from jax.sharding import NamedSharding, PartitionSpec as P

    from commefficient_tpu.federated import engine
    from commefficient_tpu.models.gpt2 import TINY, GPT2LMHead
    from commefficient_tpu.models.losses import make_lm_loss
    from commefficient_tpu.modes.config import ModeConfig
    from commefficient_tpu.parallel import mesh as meshlib

    T, W, B = 32, 2, 2
    mesh = meshlib.make_mesh(8, seq_parallel=4)
    assert dict(mesh.shape) == {meshlib.CLIENT_AXIS: 2, meshlib.SEQ_AXIS: 4}
    batch = {
        "input_ids": jax.random.randint(jax.random.PRNGKey(0), (W, B, T), 0, 512),
        "labels": jax.random.randint(jax.random.PRNGKey(0), (W, B, T), 0, 512),
        "mask": jnp.ones((W, B, T), jnp.float32),
    }

    def run(attn_impl, use_mesh):
        cfg = dataclasses.replace(TINY, n_positions=T, attn_impl=attn_impl)
        model = GPT2LMHead(cfg)
        params = model.init(
            jax.random.PRNGKey(1), jnp.zeros((1, T), jnp.int32), train=False
        )["params"]
        d = ravel_pytree(params)[0].size
        mcfg = ModeConfig(mode="uncompressed", d=d, momentum_type="none", error_type="none")
        ecfg = engine.EngineConfig(mode=mcfg)
        state = engine.init_server_state(ecfg, params, {})
        step = jax.jit(engine.make_round_step(make_lm_loss(model, train=True), ecfg))
        if use_mesh:
            b = jax.device_put(batch, meshlib.client_sharding(mesh))
            with jax.set_mesh(mesh):
                new, _, _ = step(state, b, {}, jnp.float32(0.1), jax.random.PRNGKey(2))
        else:
            new, _, _ = step(state, batch, {}, jnp.float32(0.1), jax.random.PRNGKey(2))
        return ravel_pytree(new["params"])[0]

    ref = run("dense", False)
    got = run("ring", True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-4, atol=2e-5)
