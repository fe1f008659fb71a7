"""Property tests for the count-sketch library (SURVEY.md §4 unit list):
linearity, seed-determinism, block-count invariance, heavy-hitter recovery,
unbiasedness of single-coordinate estimates, sparse==dense sketching."""

import re
from dataclasses import replace as dataclasses_replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.sketch import csvec as csvec_mod
from commefficient_tpu.sketch import (
    CSVecSpec,
    query,
    query_all,
    sketch_sparse,
    sketch_vec,
    to_dense,
    unsketch_topk,
)

SPEC = CSVecSpec(d=5000, c=1000, r=5, num_blocks=1, seed=7)


def _randn(key, shape):
    return jax.random.normal(jax.random.PRNGKey(key), shape)


def test_linearity():
    a = _randn(0, (SPEC.d,))
    b = _randn(1, (SPEC.d,))
    np.testing.assert_allclose(
        sketch_vec(SPEC, a) + sketch_vec(SPEC, b),
        sketch_vec(SPEC, a + b),
        rtol=1e-5,
        atol=1e-5,
    )


def test_seed_determinism_and_difference():
    v = _randn(2, (SPEC.d,))
    t1 = sketch_vec(SPEC, v)
    t2 = sketch_vec(CSVecSpec(**{**SPEC.__dict__}), v)
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))
    other = sketch_vec(CSVecSpec(d=SPEC.d, c=SPEC.c, r=SPEC.r, seed=8), v)
    assert not np.allclose(np.asarray(t1), np.asarray(other))


@pytest.mark.parametrize("num_blocks", [2, 4, 7])
def test_block_invariance(num_blocks):
    """num_blocks is a memory knob, not a semantics knob."""
    v = _randn(3, (SPEC.d,))
    blocked = CSVecSpec(d=SPEC.d, c=SPEC.c, r=SPEC.r, num_blocks=num_blocks, seed=SPEC.seed)
    np.testing.assert_allclose(
        np.asarray(sketch_vec(SPEC, v)), np.asarray(sketch_vec(blocked, v)), rtol=1e-5, atol=1e-5
    )
    t = sketch_vec(SPEC, v)
    np.testing.assert_allclose(
        np.asarray(query_all(SPEC, t)), np.asarray(query_all(blocked, t)), rtol=1e-5, atol=1e-5
    )
    ib, vb = unsketch_topk(blocked, t, 50)
    i1, v1 = unsketch_topk(SPEC, t, 50)
    assert set(np.asarray(ib).tolist()) == set(np.asarray(i1).tolist())


def test_heavy_hitter_recovery():
    """Plant k heavy coords in noise; assert exact recovery (SURVEY.md §4)."""
    d, k = 20000, 20
    spec = CSVecSpec(d=d, c=4000, r=5, num_blocks=4, seed=11)
    rng = np.random.RandomState(0)
    v = rng.normal(0, 0.01, size=d).astype(np.float32)
    heavy_idx = rng.choice(d, size=k, replace=False)
    heavy_vals = rng.choice([-10.0, 10.0], size=k) * rng.uniform(1.0, 2.0, size=k)
    v[heavy_idx] = heavy_vals
    idx, vals = unsketch_topk(spec, sketch_vec(spec, jnp.asarray(v)), k)
    assert set(np.asarray(idx).tolist()) == set(heavy_idx.tolist())
    # recovered values close to true values
    order = np.argsort(np.asarray(idx))
    torder = np.argsort(heavy_idx)
    np.testing.assert_allclose(
        np.asarray(vals)[order], heavy_vals[torder].astype(np.float32), rtol=0.15, atol=0.3
    )


def test_threshold_query():
    """unsketch_threshold (CSVec._findHHThr parity): every coordinate with
    |estimate| >= thr is returned, sub-threshold ones padded out."""
    from commefficient_tpu.sketch import unsketch_threshold

    d, k = 20000, 20
    spec = CSVecSpec(d=d, c=4000, r=5, num_blocks=4, seed=11)
    rng = np.random.RandomState(0)
    v = rng.normal(0, 0.01, size=d).astype(np.float32)
    heavy_idx = rng.choice(d, size=k, replace=False)
    v[heavy_idx] = rng.choice([-10.0, 10.0], size=k) * rng.uniform(1.0, 2.0, size=k)
    t = sketch_vec(spec, jnp.asarray(v))
    idx, vals = unsketch_threshold(spec, t, thr=5.0, max_k=3 * k)
    got = set(np.asarray(idx)[np.asarray(idx) >= 0].tolist())
    # exactly the planted heavies pass thr=5 (|vals| >= 10 planted, noise ~0.01)
    assert got == set(heavy_idx.tolist())
    assert np.all(np.abs(np.asarray(vals)[np.asarray(idx) >= 0]) >= 5.0)
    assert np.all(np.asarray(vals)[np.asarray(idx) < 0] == 0.0)
    # a threshold above everything returns an empty (all-padding) result
    idx2, _ = unsketch_threshold(spec, t, thr=1e6, max_k=8)
    assert np.all(np.asarray(idx2) == -1)


def test_unbiasedness():
    """Median-of-rows estimate of a fixed coord, averaged over seeds, ≈ truth."""
    d = 2000
    v = np.zeros(d, dtype=np.float32)
    v[123] = 5.0
    v[777] = -3.0
    rng = np.random.RandomState(1)
    v += rng.normal(0, 0.5, size=d).astype(np.float32)
    ests = []
    for seed in range(30):
        spec = CSVecSpec(d=d, c=500, r=5, seed=seed)
        t = sketch_vec(spec, jnp.asarray(v))
        ests.append(float(query(spec, t, jnp.array([123]))[0]))
    assert abs(np.mean(ests) - float(v[123])) < 0.3


def test_sparse_equals_dense():
    d = 1000
    spec = CSVecSpec(d=d, c=300, r=3, seed=5)
    idx = jnp.array([3, 500, 999, -1], dtype=jnp.int32)  # -1 = padding, ignored
    vals = jnp.array([1.5, -2.0, 4.0, 100.0], dtype=jnp.float32)
    dense = to_dense(d, idx, vals)
    np.testing.assert_allclose(
        np.asarray(sketch_sparse(spec, idx, vals)),
        np.asarray(sketch_vec(spec, dense)),
        rtol=1e-6,
        atol=1e-6,
    )


def test_to_dense_ignores_padding():
    dense = to_dense(10, jnp.array([-1, 2]), jnp.array([9.0, 1.0]))
    np.testing.assert_array_equal(np.asarray(dense), np.eye(10, dtype=np.float32)[2])


# ------------------------------------------------------- rotation family

ROT = CSVecSpec(d=5000, c=1000, r=5, seed=7, family="rotation")


def test_rotation_fast_paths_match_generic():
    """The roll-based dense accumulate/query must agree exactly with the
    generic (idx → buckets/signs) path shared with sparse sketching."""
    v = _randn(0, (ROT.d,))
    all_idx = jnp.arange(ROT.d, dtype=jnp.int32)
    np.testing.assert_allclose(
        np.asarray(sketch_vec(ROT, v)),  # fast path
        np.asarray(sketch_sparse(ROT, all_idx, v)),  # generic scatter path
        rtol=1e-5,
        atol=1e-5,
    )
    t = sketch_vec(ROT, v)
    np.testing.assert_allclose(
        np.asarray(query_all(ROT, t)),  # fast path
        np.asarray(query(ROT, t, all_idx)),  # generic gather path
        rtol=1e-6,
        atol=1e-6,
    )
    i_fast, v_fast = unsketch_topk(ROT, t, 50)
    est = np.asarray(query_all(ROT, t))
    i_ref = np.argsort(-np.abs(est))[:50]
    assert set(np.asarray(i_fast).tolist()) == set(i_ref.tolist())
    np.testing.assert_allclose(np.sort(np.asarray(v_fast)), np.sort(est[i_ref]), rtol=1e-6)


def test_rotation_linearity_and_determinism():
    a = _randn(1, (ROT.d,))
    b = _randn(2, (ROT.d,))
    np.testing.assert_allclose(
        sketch_vec(ROT, a) + sketch_vec(ROT, b), sketch_vec(ROT, a + b), rtol=1e-5, atol=1e-5
    )
    np.testing.assert_array_equal(
        np.asarray(sketch_vec(ROT, a)), np.asarray(sketch_vec(CSVecSpec(**ROT.__dict__), a))
    )
    other = sketch_vec(dataclasses_replace(ROT, seed=8), a)
    assert not np.allclose(np.asarray(sketch_vec(ROT, a)), np.asarray(other))


def test_rotation_heavy_hitter_recovery():
    d, k = 20000, 20
    spec = CSVecSpec(d=d, c=4000, r=5, seed=11, family="rotation")
    rng = np.random.RandomState(0)
    v = rng.normal(0, 0.01, size=d).astype(np.float32)
    heavy_idx = rng.choice(d, size=k, replace=False)
    heavy_vals = rng.choice([-10.0, 10.0], size=k) * rng.uniform(1.0, 2.0, size=k)
    v[heavy_idx] = heavy_vals
    idx, vals = unsketch_topk(spec, sketch_vec(spec, jnp.asarray(v)), k)
    assert set(np.asarray(idx).tolist()) == set(heavy_idx.tolist())
    order = np.argsort(np.asarray(idx))
    torder = np.argsort(heavy_idx)
    np.testing.assert_allclose(
        np.asarray(vals)[order], heavy_vals[torder].astype(np.float32), rtol=0.15, atol=0.3
    )


def test_rotation_unbiasedness():
    d = 2000
    v = np.zeros(d, dtype=np.float32)
    v[123] = 5.0
    v[777] = -3.0
    rng = np.random.RandomState(1)
    v += rng.normal(0, 0.5, size=d).astype(np.float32)
    ests = []
    for seed in range(30):
        spec = CSVecSpec(d=d, c=500, r=5, seed=seed, family="rotation")
        t = sketch_vec(spec, jnp.asarray(v))
        ests.append(float(query(spec, t, jnp.array([123]))[0]))
    assert abs(np.mean(ests) - float(v[123])) < 0.3


def test_rotation_d_not_multiple_of_c():
    """Partial last slab: padding must not contaminate sketches or top-k."""
    spec = CSVecSpec(d=1234, c=500, r=3, seed=3, family="rotation")
    v = _randn(5, (spec.d,))
    t = sketch_vec(spec, v)
    all_idx = jnp.arange(spec.d, dtype=jnp.int32)
    np.testing.assert_allclose(
        np.asarray(t), np.asarray(sketch_sparse(spec, all_idx, v)), rtol=1e-5, atol=1e-5
    )
    idx, vals = unsketch_topk(spec, t, 40)
    assert np.all(np.asarray(idx) < spec.d) and np.all(np.asarray(idx) >= 0)


def test_jit_and_vmap():
    """Sketch ops must compose with jit/vmap — they live inside the round step."""
    spec = CSVecSpec(d=256, c=64, r=3, num_blocks=2, seed=0)
    vs = _randn(4, (6, spec.d))
    tables = jax.jit(jax.vmap(lambda v: sketch_vec(spec, v)))(vs)
    assert tables.shape == (6, spec.r, spec.c)
    summed = tables.sum(0)
    np.testing.assert_allclose(
        np.asarray(summed), np.asarray(sketch_vec(spec, vs.sum(0))), rtol=1e-4, atol=1e-4
    )
    idx, vals = jax.jit(lambda t: unsketch_topk(spec, t, 10))(summed)
    assert idx.shape == (10,) and vals.shape == (10,)


def test_unsketch_single_shot_matches_chunked_scan(monkeypatch):
    """The single-shot unsketch (affordable [d] transient) and the
    memory-bounding slab scan must recover the same top-k set with the same
    values — for every impl, both rotation-family routes (on CPU the
    approx lowering is exact, so approx/oversample pin the PRESELECT
    plumbing of the chunked path: masking, index mapping, carry merge)."""
    spec = CSVecSpec(d=10000, c=1024, r=3, seed=3, family="rotation")
    rng = np.random.RandomState(4)
    v = rng.normal(0, 0.01, size=spec.d).astype(np.float32)
    v[rng.choice(spec.d, 30, replace=False)] = 25.0
    t = sketch_vec(spec, jnp.asarray(v))

    for impl in ("exact", "approx", "oversample"):
        monkeypatch.setattr(
            csvec_mod, "UNSKETCH_SINGLE_SHOT_BYTES", 1 << 30)
        i_single, v_single = unsketch_topk(spec, t, 30, impl=impl)
        monkeypatch.setattr(csvec_mod, "UNSKETCH_SINGLE_SHOT_BYTES", 0)
        i_scan, v_scan = unsketch_topk(spec, t, 30, impl=impl)
        assert set(np.asarray(i_single).tolist()) == \
            set(np.asarray(i_scan).tolist()), impl
        np.testing.assert_allclose(
            np.sort(np.asarray(v_single)), np.sort(np.asarray(v_scan)),
            rtol=1e-6)


# (d, c, k): d = 100,000 / 20,000 at c = 1,024 and k = 5,000 collide in most
# buckets; r * c = 2,500 is no whole number of 64- or 128-lane rows (both
# flat views padded), 2,880 of 128-lane rows only (V's view padded)
_MASK_CASES = {
    "c512": (4096, 512, 30),
    "c1024_collisions": (100_000, 1024, 5000),
    "c1024_dense_collisions": (20_000, 1024, 5000),
    "c500_padded": (5000, 500, 300),
    "c576_padded_query": (5000, 576, 300),
}


@pytest.mark.parametrize("family", ["rotation", "random"])
@pytest.mark.parametrize("case", list(_MASK_CASES))
def test_mask_transmitted_matches_unfused(case, family):
    """The masking tail by rows must be BIT-IDENTICAL to the unfused
    sequence E -= sketch_sparse(vals); vvals = query(V);
    V -= sketch_sparse(vvals), at every c: including idx = -1 padding and an
    idx >= d entry, whose contributions are zeros on both paths, +-0.0
    among the values and -0.0 in V (the pick of a lane adds -0.0 to it)."""
    d, c, k = _MASK_CASES[case]
    spec = CSVecSpec(d=d, c=c, r=5, seed=9, family=family)
    rng = np.random.RandomState(2)
    V = rng.randn(spec.r, spec.c).astype(np.float32)
    V[0, :7] = -0.0
    V[1, 3:9] = 0.0
    E = rng.randn(spec.r, spec.c).astype(np.float32)
    idx = np.concatenate([rng.choice(d, k - 3, replace=False),
                          [-1, d + 5, -1]]).astype(np.int32)
    vals = rng.randn(k).astype(np.float32)
    vals[:5] = 0.0
    vals[5:10] = -0.0
    V, E, idx, vals = map(jnp.asarray, (V, E, idx, vals))

    E_ref = E - sketch_sparse(spec, idx, vals)
    vvals = query(spec, V, idx)
    V_ref = V - sketch_sparse(spec, idx, vvals)

    V_f, E_f = jax.jit(lambda *a: csvec_mod.mask_transmitted(spec, *a))(
        V, E, idx, vals)
    for ref, got in ((V_ref, V_f), (E_ref, E_f)):
        np.testing.assert_array_equal(np.asarray(ref).view(np.int32),
                                      np.asarray(got).view(np.int32))


# --- exact top-k by threshold and compaction (select_topk_abs) -------------
# Called directly, so that toy sizes reach it whatever TOPK_SELECT_MIN_N says.
# Every case compares with jax.lax.top_k(jnp.abs(x), k)[1] ELEMENT FOR
# ELEMENT: the order is what mask_transmitted's scatter-adds see.

def _topk_ref(x, k):
    return np.asarray(jax.lax.top_k(jnp.abs(x), k)[1])


def _select_cases():
    rng = np.random.RandomState(11)
    normal = rng.randn(5000).astype(np.float32)
    eight = (rng.choice(np.arange(1, 9), 5000) * rng.choice([-1, 1], 5000)
             ).astype(np.float32)
    zeros = np.zeros(3000, np.float32)
    zeros[::3] = -0.0
    # T falls on a tie that has to be split: 40 elements of magnitude 2 among
    # 30 larger ones; k = 45 takes the 30 and the FIRST 15 of the 40 by index
    split = rng.uniform(-1, 1, 4000).astype(np.float32)
    split[rng.choice(4000, 70, replace=False)] = np.r_[
        rng.uniform(3, 9, 30), np.full(40, 2.0)] * rng.choice([-1, 1], 70)
    nonfinite = rng.randn(4000).astype(np.float32)
    nonfinite[[5, 2000]] = np.inf
    nonfinite[77] = -np.inf
    nonfinite[[100, 3999]] = np.nan
    nonfinite[3000] = -np.nan
    return {
        "normal": (normal, 777),
        "eight_magnitudes": (eight, 1234),
        "all_equal": (np.full(3000, -2.5, np.float32), 100),
        "all_zero_with_negative_zero": (zeros, 500),
        "tie_split_at_threshold": (split, 45),
        "k_1": (normal, 1),
        "k_n": (normal[:1280], 1280),
        "k_n_minus_1": (normal[:1280], 1279),
        "n_not_multiple_of_128": (normal[:1000], 137),
        "n_below_128": (normal[:100], 7),
        "n_1": (normal[:1], 1),
        "inf_and_nan": (nonfinite, 50),
        "nan_at_the_threshold": (nonfinite, 3),
        "bfloat16": (normal.astype(jnp.bfloat16), 300),
    }


_SELECT_CASES = _select_cases()


@pytest.mark.parametrize("case", sorted(_SELECT_CASES))
def test_select_topk_abs_equals_lax_top_k(case):
    x, k = _SELECT_CASES[case]
    x = jnp.asarray(x)
    got = np.asarray(csvec_mod.select_topk_abs(x, k))
    np.testing.assert_array_equal(got, _topk_ref(x, k))
    assert got.dtype == np.int32 and got.shape == (k,)


@pytest.mark.parametrize("bits", [1, 2, 3, 4, 5])
def test_select_topk_abs_any_bits_a_pass(bits, monkeypatch):
    """The threshold is the same k-th largest key however many value bits a
    counting pass settles (31 is a multiple of none of 2, 3, 4, 5, so the
    last, shorter pass is exercised). Called unjitted: the constant is read
    while tracing, and a cached trace would not see the patch."""
    monkeypatch.setattr(csvec_mod, "_THRESHOLD_BITS", bits)
    x, k = _SELECT_CASES["eight_magnitudes"]
    np.testing.assert_array_equal(
        np.asarray(csvec_mod.select_topk_abs(jnp.asarray(x), k)),
        _topk_ref(jnp.asarray(x), k))


def test_select_topk_abs_under_jit():
    x, k = _SELECT_CASES["tie_split_at_threshold"]
    got = jax.jit(csvec_mod.select_topk_abs, static_argnums=1)(jnp.asarray(x), k)
    np.testing.assert_array_equal(np.asarray(got), _topk_ref(jnp.asarray(x), k))


def test_select_topk_abs_under_vmap_rows_with_different_thresholds():
    """local_topk runs it per client under vmap: three rows whose k-th
    magnitudes differ by orders of magnitude, one of them all ties."""
    rng = np.random.RandomState(12)
    xs = rng.randn(3, 3000).astype(np.float32) * np.array(
        [[1.0], [1e-3], [1e4]], np.float32)
    xs[1] = np.sign(xs[1]) * 0.25
    xs = jnp.asarray(xs)
    got = jax.jit(jax.vmap(lambda v: csvec_mod.select_topk_abs(v, 200)))(xs)
    want = jax.vmap(lambda v: jax.lax.top_k(jnp.abs(v), 200)[1])(xs)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_topk_abs_exact_above_the_constant_equals_lax_top_k():
    """Through topk_abs at an n where it takes the selection, on ties."""
    n, k = csvec_mod.TOPK_SELECT_MIN_N + 77, 5000
    rng = np.random.RandomState(13)
    x = jnp.asarray((rng.choice(np.arange(1, 200), n) * rng.choice([-1, 1], n))
                    .astype(np.float32))
    got = jax.jit(lambda v: csvec_mod.topk_abs(v, k, impl="exact"))(x)
    np.testing.assert_array_equal(np.asarray(got), _topk_ref(x, k))


def _lowered(fn, n):
    low = jax.jit(fn).lower(jax.ShapeDtypeStruct((n,), jnp.float32))
    return low.as_text(), low.compile().as_text()


@pytest.mark.parametrize("n,k,selects", [
    (csvec_mod.TOPK_SELECT_MIN_N, 1000, True),
    (csvec_mod.TOPK_SELECT_MIN_N - 1, 1000, False),  # below the constant
    (csvec_mod.TOPK_SELECT_MIN_N,  # k rows of 128 would outweigh the sort
     csvec_mod.TOPK_SELECT_MIN_N // csvec_mod.TOPK_SELECT_MIN_N_PER_K + 1,
     False),
], ids=["at_the_constant", "below_it", "k_too_near_n"])
def test_topk_abs_exact_chooses_by_static_shape(n, k, selects):
    text, _ = _lowered(lambda v: csvec_mod.topk_abs(v, k, impl="exact"), n)
    assert ("chlo.top_k" not in text) == selects


def test_exact_topk_lowers_to_no_sort_scan_or_scatter_over_n():
    """The guard that keeps the full sort from coming back (in the manner of
    test_linear_round_program_holds_no_client_stack): above the constant the
    exact branch holds no sort, no top-k, no cumulative reduction and no
    scatter over n elements, in the lowered text or the compiled one. What
    it does sort is the k selected; what it scans is the n/128 row counts."""
    n, k = csvec_mod.TOPK_SELECT_MIN_N + 77, 1000
    rows = -(-n // 128)
    n_sized = (f"{n}", f"{rows * 128}", f"{rows}x128", f"{rows},128")
    text, compiled = _lowered(
        lambda v: csvec_mod.topk_abs(v, k, impl="exact"), n)
    assert f"tensor<{n}xf32>" in text and f"f32[{n}]" in compiled
    assert "chlo.top_k" not in text and "stablehlo.scatter" not in text
    sorts = re.findall(r'"stablehlo\.sort"\(.*?\) -> \((.*?)\)', text, re.S)
    assert sorts == [f"tensor<{k}xi32>, tensor<{k}xi32>"], sorts
    scans = re.findall(
        r'"stablehlo\.reduce_window"\(.*?\) -> tensor<(\w+)>', text, re.S)
    assert scans and all(s == f"{rows}xi32" for s in scans), scans
    # the CPU compiles lax.top_k to a custom call that names its n-long
    # operand without its shape, so it is refused by its target; the TPU
    # lowers it to a sort over f32[n], which the scan of the lines refuses
    assert 'custom_call_target="TopK"' not in compiled
    for line in compiled.splitlines():
        if re.search(r" (sort|reduce-window|scatter)\(", line):
            assert not any(s in line for s in n_sized), line


def _approx(k):
    return lambda v: csvec_mod.topk_abs(v, k, impl="approx", recall=0.99)


def _plain_approx(k):
    return lambda v: jax.lax.approx_max_k(
        jnp.abs(v), k, recall_target=0.99)[1].astype(jnp.int32)


# (n, k) on either side of topk_abs's rule for impl="approx": m = 175,104
# partial maxima at recall 0.99 against the constant's 350,000, and 500,736
_APPROX_BELOW = (csvec_mod.TOPK_SELECT_MIN_N + 77, 1000)
_APPROX_ABOVE = (1_000_003, 5000)


@pytest.mark.parametrize("n,k,m", [_APPROX_BELOW + (0,),
                                   _APPROX_ABOVE + (500_736,)],
                         ids=["lowers_as_before_below_the_constant",
                              "selects_above_it"])
def test_approx_topk_lowering(n, k, m):
    """Where the partial maxima are too few for the selection to pay,
    impl="approx" is lax.approx_max_k's own lowering, word for word, and
    holds nothing of the selection (no key bitcast, no sort of the k
    selected, no row gather, no prefix product). Above the constant it
    takes the m partial maxima unaggregated and sorts k pairs, nothing
    else: the guard that keeps the aggregation's sort of m pairs from
    coming back (tests/test_tpu_compile.py asks the TPU's compiler the
    same)."""
    assert csvec_mod.approx_select_size(n, k, 0.99) == m
    text, _ = _lowered(_approx(k), n)
    assert text.count("@ApproxTopK") == 1
    sorts = re.findall(r'"stablehlo\.sort"\(.*?\) -> \((.*?)\)', text, re.S)
    if not m:
        assert text == _lowered(_plain_approx(k), n)[0]
        assert not sorts
        for op in ("bitcast_convert", "gather", "dot_general", "reduce_window"):
            assert f"stablehlo.{op}" not in text, op
    else:
        assert "aggregate_to_topk = false" in text
        assert f"-> (tensor<{m}xf32>, tensor<{m}xi32>)" in text
        assert "chlo.top_k" not in text and "stablehlo.scatter" not in text
        assert sorts == [f"tensor<{k}xi32>, tensor<{k}xi32>"], sorts


def test_approx_topk_above_the_constant_equals_the_aggregated_call():
    """The k largest of the partial maxima, by selection or by
    approx_max_k's own sort: the same indices in the same order on a
    vector with no tied magnitudes (the CPU's fallback keeps the m
    largest, so the approximation itself is the same on both sides)."""
    n, k = _APPROX_ABOVE
    rng = np.random.RandomState(14)
    x = ((1.0 + rng.permutation(n) / n) * rng.choice([-1, 1], n)).astype(
        np.float32)
    assert np.unique(np.abs(x)).size == n  # no tied magnitudes
    got = jax.jit(_approx(k))(jnp.asarray(x))
    want = _plain_approx(k)(jnp.asarray(x))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert got.dtype == jnp.int32 and got.shape == (k,)


def test_select_topk_keys_never_takes_a_negative_key():
    """What the TPU's PartialReduce leaves in a slot no coordinate reached
    is its initial value, -inf or the lowest float: as int32 both are
    negative, and a negative key is never selected, wherever it lies."""
    rng = np.random.RandomState(15)
    n, k = 5000, 300
    mags = np.abs(rng.randn(n)).astype(np.float32)
    empty = np.concatenate([rng.choice(n, 700, replace=False), [0, n - 1]])
    mags[empty[::2]] = -np.inf
    mags[empty[1::2]] = np.finfo(np.float32).min
    keys = jnp.asarray(mags.view(np.int32))
    assert int(jnp.sum(keys < 0)) == empty.size
    got = np.asarray(jax.jit(
        lambda v: csvec_mod._select_topk_keys(v, k))(keys))
    assert not np.isin(got, empty).any()
    real = np.where(mags >= 0, mags, 0.0)
    np.testing.assert_array_equal(got, _topk_ref(jnp.asarray(real), k))


def test_approx_topk_under_vmap_rows_with_different_thresholds():
    """local_topk with --topk_impl approx runs it per client under vmap:
    approx_max_k batches over the leading axis, the selection is vmapped."""
    n, k = _APPROX_ABOVE
    rng = np.random.RandomState(16)
    xs = jnp.asarray(rng.randn(3, n).astype(np.float32) * np.array(
        [[1.0], [1e-3], [1e4]], np.float32))
    got = jax.jit(jax.vmap(_approx(k)))(xs)
    want = jax.vmap(_plain_approx(k))(xs)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
