"""Pallas kernel tests (interpreter mode on the CPU mesh): the rotation-family
accumulate/query kernels must match the pure-JAX oracle in csvec.py, which the
property tests in test_csvec.py already pin to the generic hash path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.sketch import CSVecSpec, csvec
from commefficient_tpu.sketch import pallas_kernels as pk

# small enough for the interpreter, c % 128 == 0, d not a multiple of c
SPEC = CSVecSpec(d=3000, c=1024, r=3, seed=13, family="rotation")


def _v(key, d):
    return jax.random.normal(jax.random.PRNGKey(key), (d,), jnp.float32)


def test_supported_layouts():
    assert pk.supported(SPEC)
    assert not pk.supported(CSVecSpec(d=3000, c=1000, r=3, family="rotation"))
    assert not pk.supported(CSVecSpec(d=3000, c=1024, r=3, family="random"))
    # bench dims are eligible; a table that can't stay VMEM-resident is not
    assert pk.supported(CSVecSpec(d=6_573_130, c=524_288, r=5, family="rotation"))
    assert pk.supported(CSVecSpec(d=124_000_000, c=1_048_576, r=5, family="rotation"))
    assert not pk.supported(CSVecSpec(d=124_000_000, c=8_388_608, r=5, family="rotation"))


def test_vmem_budget_selection():
    """Flagship dims keep the 48 MiB scoped limit and the language models'
    (c=2^20 r=5) the 96 MiB one — the tiers the whole-slab kernels had, so the
    round programs' compile options do not move. The model stays an upper
    bound on Mosaic's measured need at the calibration point: compiled for a
    described v5e at c=2^20 r=5 d=124,443,648 (PR 28), accumulate needs
    12.06 MiB and query 28.25 MiB with the table held in VMEM by XLA; the
    table's own buffers (two of 20 MiB) are the model's, not Mosaic's."""
    small = pk._compiler_params(524_288, 5).vmem_limit_bytes
    large = pk._compiler_params(1_048_576, 5).vmem_limit_bytes
    assert small == pk._VMEM_SMALL_BYTES
    assert large == pk._VMEM_LARGE_BYTES
    table = 5 * 1_048_576 * 4
    assert pk._worst_case_vmem(1_048_576, 5) >= int(12.06 * 2**20) + 2 * table
    assert pk._worst_case_vmem(1_048_576, 5) >= int(28.25 * 2**20) + table
    # every layout on the old model's 96 MiB edge is still taken
    for c, r in ((1_572_864, 5), (1_048_576, 9), (2_097_152, 3)):
        assert pk.supported(CSVecSpec(d=10 * c, c=c, r=r, family="rotation"))


def test_accumulate_matches_oracle():
    v = _v(0, SPEC.d)
    got = pk.sketch_vec(SPEC, v, interpret=True)
    want = csvec.sketch_vec(SPEC, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_query_matches_oracle():
    v = _v(1, SPEC.d)
    table = csvec.sketch_vec(SPEC, v)
    got = pk.query_all(SPEC, table, interpret=True)
    want = csvec.query_all(SPEC, table)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6)


def test_single_slab_and_exact_multiple():
    """d < c (one slab) and d == S*c (no padding) both round-trip."""
    for d in (700, 2048):
        spec = CSVecSpec(d=d, c=1024, r=3, seed=5, family="rotation")
        v = _v(2, d)
        np.testing.assert_allclose(
            np.asarray(pk.sketch_vec(spec, v, interpret=True)),
            np.asarray(csvec.sketch_vec(spec, v)),
            rtol=1e-5,
            atol=1e-5,
        )
        t = csvec.sketch_vec(spec, v)
        np.testing.assert_allclose(
            np.asarray(pk.query_all(spec, t, interpret=True)),
            np.asarray(csvec.query_all(spec, t)),
            rtol=1e-6,
            atol=1e-6,
        )


def test_even_rows_lower_median():
    """r even exercises the lower-median convention in the kernel's sort."""
    spec = CSVecSpec(d=1500, c=256, r=4, seed=8, family="rotation")
    v = _v(3, spec.d)
    t = csvec.sketch_vec(spec, v)
    np.testing.assert_allclose(
        np.asarray(pk.query_all(spec, t, interpret=True)),
        np.asarray(csvec.query_all(spec, t)),
        rtol=1e-6,
        atol=1e-6,
    )


def _edge_shifts(c: int, turn: int):
    """A `slab_shifts` that plants the window's edge cases, [r, S] in order
    from `turn`: no shift, a shift under one sublane (s // 128 == 0), whole
    sublanes (s % 128 == 0), c - 1, a window that crosses the slab's end by
    less than a sublane, and a sublane short of c with a lane remainder."""
    cq = c // 128
    edges = [0, 5, 128 * max(1, cq // 2), c - 1, c - 125, (cq - 1) * 128 + 64, 127, 128]

    def planted(seed, num_rows, num_slabs, num_cols):
        assert num_cols == c
        flat = [edges[(turn + i) % len(edges)] % c for i in range(num_rows * num_slabs)]
        return jnp.asarray(flat, jnp.int32).reshape(num_rows, num_slabs)

    return planted


def _assert_bit_equal_to_oracle(spec, key):
    v = _v(key, spec.d)
    want_t = csvec._sketch_vec_rotation(spec, v)
    got_t = pk.sketch_vec(spec, v, interpret=True)
    assert np.array_equal(np.asarray(got_t), np.asarray(want_t))
    got_q = pk.query_all(spec, want_t, interpret=True)
    assert np.array_equal(np.asarray(got_q), np.asarray(csvec._query_all_rotation(spec, want_t)))


# c/128 -> its tile: the whole slab as one tile, every rung of the tile ladder,
# and 8 x 17 (17 tiles: no power of two)
_TILE_OF = {2: 2, 8: 8, 48: 16, 96: 32, 64: 64, 136: 8}
_ROWS = (1, 4, 5)
# d: under c, an exact multiple of c, a padded last slab
_D_OF_C = {"one_slab": lambda c: c - 37, "exact": lambda c: 3 * c, "padded": lambda c: 2 * c + c // 3}


@pytest.mark.parametrize("d_kind", list(_D_OF_C))
@pytest.mark.parametrize("r", _ROWS)
@pytest.mark.parametrize("cq", list(_TILE_OF))
def test_kernels_equal_oracle_bit_for_bit(monkeypatch, cq, r, d_kind):
    """The tiled kernels return the oracle's BITS (sign times value is exact,
    the roll is a permutation, each table cell takes one addend a slab in slab
    order, the median is min/max), at every tile height and at the window's
    edge cases, which a planted `slab_shifts` brings on in both programs."""
    c = 128 * cq
    turn = list(_TILE_OF).index(cq) + 3 * _ROWS.index(r) + list(_D_OF_C).index(d_kind)
    planted = _edge_shifts(c, turn)
    monkeypatch.setattr(pk, "slab_shifts", planted)
    monkeypatch.setattr(csvec, "slab_shifts", planted)
    # the seed is in the jit cache's key and `slab_shifts` is not: one seed a case
    spec = CSVecSpec(d=_D_OF_C[d_kind](c), c=c, r=r, seed=1000 + turn + 100 * cq,
                     family="rotation")
    assert pk._tile_height(cq) == _TILE_OF[cq]
    _assert_bit_equal_to_oracle(spec, key=turn)


@pytest.mark.parametrize("cq", list(_TILE_OF))
def test_kernels_equal_oracle_bit_for_bit_at_hashed_shifts(cq):
    """The same pin at the shifts the seed really gives (several slabs)."""
    spec = CSVecSpec(d=5 * 128 * cq + 11, c=128 * cq, r=5, seed=29 + cq, family="rotation")
    _assert_bit_equal_to_oracle(spec, key=cq)


def _values_in(jaxpr):
    """Shapes of every non-ref value a (closed) jaxpr computes, its nested
    jaxprs (loops, conditionals) included."""
    from jax._src import core as jcore
    from jax._src.state.types import AbstractRef

    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            if not isinstance(var.aval, AbstractRef) and hasattr(var.aval, "shape"):
                yield eqn.primitive.name, tuple(var.aval.shape)
        for sub in jcore.jaxprs_in_params(eqn.params):
            yield from _values_in(sub)


@pytest.mark.parametrize("kernel", ("accumulate", "query"))
def test_no_slab_sized_value_in_kernel_body(kernel):
    """Structural guard at the language models' layout (tracing only, nothing
    runs): no value inside the kernel is a slab [c/128, 128] or anything
    larger than a tile. A whole-slab expression — what made both kernels
    20-odd VMEM passes a row before PR 28 — fails here."""
    d, c, r = 124_443_648, 1_048_576, 5
    cq = c // 128
    call, shape = {"accumulate": (pk._accumulate_call, (d,)), "query": (pk._query_call, (r, c))}[kernel]
    closed = jax.make_jaxpr(lambda x: call.__wrapped__(
        x, d=d, c=c, r=r, seed=42, interpret=False))(jax.ShapeDtypeStruct(shape, jnp.float32))
    (eqn,) = [e for e in closed.jaxpr.eqns if e.primitive.name == "pallas_call"]
    values = list(_values_in(eqn.params["jaxpr"]))
    assert values
    # the tallest tile of the ladder and a register of halo
    most = (64 + 8) * 128
    for name, shape in values:
        assert shape[-2:] != (cq, 128), (name, shape)
        assert int(np.prod(shape, dtype=np.int64)) <= most, (name, shape)


def test_probe_failure_raises_on_tpu_backend(monkeypatch):
    """The library-level gate: on a TPU backend with a supported layout, a
    kernel that fails to compile RAISES with the compiler's message — the
    process is never downgraded to the pure-JAX oracle. The oracle is taken
    only for a stated reason (unsupported layout, COMMEFFICIENT_NO_PALLAS)."""
    spec = CSVecSpec(d=3000, c=1024, r=3, seed=13, family="rotation")
    v = _v(7, spec.d)

    def boom(*a, **k):
        raise RuntimeError("MosaicError: simulated")

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(pk, "_accumulate_call", boom)
    monkeypatch.setattr(pk, "_PROBED", set())
    with pytest.raises(RuntimeError, match="MosaicError: simulated"):
        csvec.sketch_vec(spec, v)
    assert (spec.c, spec.r) not in pk._PROBED  # a failure is never cached
    with pytest.raises(RuntimeError, match="c=1024 r=3"):
        csvec.sketch_impl(spec)

    # the two stated reasons still take the oracle, and say so
    odd = CSVecSpec(d=3000, c=1000, r=3, seed=13, family="rotation")
    assert csvec.sketch_impl(odd) == ("oracle", "num_cols 1000 % 1024 != 0")
    monkeypatch.setenv("COMMEFFICIENT_NO_PALLAS", "1")
    assert csvec.sketch_impl(spec)[0] == "oracle"
    np.testing.assert_allclose(
        np.asarray(csvec.sketch_vec(spec, v)),
        np.asarray(csvec._sketch_vec_rotation(spec, v)), rtol=1e-6)


def test_probe_status_reports_layouts(monkeypatch):
    monkeypatch.setattr(pk, "_PROBED", set())
    assert pk.probe_status() == {"probed": False, "layouts": []}
    pk._PROBED.update({(2048, 5), (1024, 3)})
    assert pk.probe_status() == {
        "probed": True, "layouts": ["c=1024,r=3", "c=2048,r=5"]}


@pytest.mark.parametrize("client_chunk", [0, 2])
def test_engine_round_step_with_pallas_kernels(monkeypatch, client_chunk):
    """The EXACT composition that runs on hardware: the full federated round
    step (client grads, in one vmap or a `client_chunk` scan -> aggregate ->
    sketch -> virtual momentum/error -> unsketch_topk) with the library
    routed to the Pallas kernels, pinned against the oracle-engine result.
    COMMEFFICIENT_PALLAS_INTERPRET=1 runs the kernels in the Pallas
    interpreter, so this passes on the CPU mesh — it proves the composition
    traces, jits, and is numerically equal; only the Mosaic/native compile
    of the same module needs the TPU compiler (tests/test_tpu_compile.py
    compiles it for a described v5e; chip_smoke.py runs it on the chip)."""
    from jax.flatten_util import ravel_pytree

    from commefficient_tpu.federated import engine
    from commefficient_tpu.modes.config import ModeConfig

    from test_engine import _data, init_mlp, mlp_loss

    params = init_mlp(jax.random.PRNGKey(0), din=64, dh=128)
    d = ravel_pytree(params)[0].size
    assert d > 2 * 1024  # several slabs: the kernel grid loop is exercised
    data = _data(jax.random.PRNGKey(1), 24, din=64)
    batch = jax.tree.map(lambda a: a.reshape((4, 6) + a.shape[1:]), data)
    kw = dict(
        mode="sketch", d=d, k=32, num_rows=3, num_cols=1024,
        hash_family="rotation", momentum_type="virtual", error_type="virtual",
    )

    def run(pallas: bool):
        if pallas:
            monkeypatch.setenv("COMMEFFICIENT_PALLAS_INTERPRET", "1")
        else:
            monkeypatch.delenv("COMMEFFICIENT_PALLAS_INTERPRET", raising=False)
        cfg = engine.EngineConfig(mode=ModeConfig(**kw),
                                  client_chunk=client_chunk)
        assert csvec._use_pallas(cfg.mode.sketch_spec) == pallas
        state = engine.init_server_state(
            cfg, jax.tree.map(jnp.copy, params), {}
        )
        step = jax.jit(engine.make_round_step(mlp_loss, cfg))
        for i in range(3):
            state, _, _ = step(
                state, batch, {}, jnp.float32(0.1), jax.random.PRNGKey(i)
            )
        return ravel_pytree(state["params"])[0]

    got, want = run(pallas=True), run(pallas=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
