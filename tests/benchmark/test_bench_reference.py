"""The plain references against independent forms of the same mathematics,
and tied to the program at a small size on the CPU."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import fetchsgd, resnet9 as ref_model


@pytest.fixture(scope="module")
def cs():
    return fetchsgd.CountSketch(d=10_000, rows=5, cols=1024, seed=42)


def scatter_sketch(cs, v):
    """Count Sketch by its definition, one coordinate at a time (numpy)."""
    table = np.zeros((cs.r, cs.c), np.float64)
    idx = jnp.arange(cs.d)
    buckets, signs = (np.asarray(a) for a in cs._buckets_signs(idx))
    for j in range(cs.r):
        np.add.at(table[j], buckets[j], signs[j] * np.asarray(v, np.float64))
    return table


def test_accumulate_is_the_definition(cs):
    v = jax.random.normal(jax.random.PRNGKey(0), (cs.d,))
    np.testing.assert_allclose(np.asarray(cs.accumulate(v)), scatter_sketch(cs, v),
                               rtol=0, atol=1e-4)


def test_query_recovers_a_sparse_vector_and_matches_point_query(cs):
    idx = jnp.asarray([3, 1024 + 3, 7777, 9999])
    vals = jnp.asarray([5.0, -4.0, 3.0, 2.0])
    table = cs.sparse(idx, vals)
    est = cs.query_all(table)
    np.testing.assert_allclose(np.asarray(est[idx]), np.asarray(vals), atol=1e-6)
    np.testing.assert_allclose(np.asarray(cs.query(table, idx)), np.asarray(vals), atol=1e-6)
    v = jnp.zeros(cs.d).at[idx].set(vals)
    np.testing.assert_allclose(np.asarray(cs.accumulate(v)), np.asarray(table), atol=1e-6)


def test_hash_is_the_programs(cs):
    """The reference restates the deployment's hash; the program's oracle at
    the same seed must give the same table and the same estimates."""
    from commefficient_tpu.sketch import csvec

    spec = csvec.CSVecSpec(d=cs.d, c=cs.c, r=cs.r, num_blocks=1, seed=42, family="rotation")
    v = jax.random.normal(jax.random.PRNGKey(1), (cs.d,))
    table = cs.accumulate(v)
    np.testing.assert_allclose(np.asarray(table), np.asarray(csvec._sketch_vec_rotation(spec, v)),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(cs.query_all(table)),
                               np.asarray(csvec._query_all_rotation(spec, table)), atol=1e-5)


def test_server_step_by_hand(cs):
    """One step from zero state: E = lr * S, the k largest estimates leave E,
    and V loses its own mass at those coordinates."""
    v = jnp.zeros(cs.d).at[jnp.asarray([10, 20, 30])].set(jnp.asarray([9.0, -8.0, 0.5]))
    S = cs.accumulate(v)
    zeros = jnp.zeros_like(S)
    idx, vals, V, E = fetchsgd.sketch_server_step(cs, 2, 0.9, S, zeros, zeros, 0.5)
    assert sorted(np.asarray(idx).tolist()) == [10, 20]
    np.testing.assert_allclose(sorted(np.asarray(vals).tolist()), [-4.0, 4.5], atol=1e-6)
    np.testing.assert_allclose(np.asarray(cs.query_all(E))[jnp.asarray([10, 20, 30])],
                               [0.0, 0.0, 0.25], atol=1e-6)
    np.testing.assert_allclose(np.asarray(cs.query_all(V))[jnp.asarray([10, 20, 30])],
                               [0.0, 0.0, 0.5], atol=1e-6)


@pytest.mark.parametrize("pos, want", [(0, 0.0), (79, 0.08), (395, 0.4), (1145, 0.2),
                                       (1896, 0.0)])
def test_triangular_schedule(pos, want):
    assert fetchsgd.triangular_lr(0.4, 5, 24, 79, pos) == pytest.approx(want, abs=1e-3)


def test_resnet9_matches_the_trainers_model():
    from commefficient_tpu.models.resnet9 import ResNet9

    shapes = ref_model.param_shapes()
    params = ref_model.init_params(jax.random.PRNGKey(3), shapes)
    x = jax.random.normal(jax.random.PRNGKey(4), (4, 32, 32, 3))
    model = ResNet9()
    stats = jax.tree.map(jnp.zeros_like, model.init(
        jax.random.PRNGKey(0), x, train=False)["batch_stats"])
    got, _ = model.apply({"params": params, "batch_stats": stats}, x, train=True,
                         mutable=["batch_stats"])
    np.testing.assert_allclose(np.asarray(ref_model.logits(params, x)), np.asarray(got),
                               rtol=1e-3, atol=1e-4)
    n = sum(int(np.prod(s)) for s in jax.tree.leaves(
        shapes, is_leaf=lambda s: isinstance(s, tuple)))
    assert n == 6_573_130
