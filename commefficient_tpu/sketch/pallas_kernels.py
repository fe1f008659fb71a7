"""Pallas TPU kernels for the rotation-family count-sketch.

These are the "accumulate / query" kernel pair SURVEY.md §3.5 / §7.1 targets
(the reference's CSVec.accumulateVec / _findValues are pure-torch scatter and
gather programs; here the rotation hash family makes both ops *structured*,
and these kernels express that structure directly on the TPU vector unit):

- Both kernel bodies walk the slab, viewed as [c/128, 128], in tiles of a few
  vector registers, and everything a row needs of a tile happens between one
  load and one store. The roll of a slab becomes a WINDOW: a tile of the
  rolled slab is two loads of the source at a dynamic sublane offset, one row
  apart, a select on the lane and one lane rotate within the register
  (`_rolled_tile`; `pltpu.roll` → Mosaic `tpu.dynamic_rotate`). The source is
  kept with its first tile repeated after its end (`_extend`), so no window
  straddles the wrap — no scatter/gather at any granularity, no rotate across
  the slab, and no value of the slab's size.
- Bucket signs are recomputed inside the kernel from the integer seed with
  the same murmur mixer as `hashing.py` (uint32 elementwise VPU ops) on the
  tile where they are used, so no [r, d] hash tensor ever exists in HBM and
  no [c] one in VMEM. The mixer's ~15 operations a register and row are what
  the tile loop spends most of its vector slots on.
- The slab axis is the pipelined grid dimension: Pallas streams each slab of
  the input HBM→VMEM exactly once while the whole [r, c] table stays resident
  in VMEM, every slab feeding all r rows — HBM traffic is d reads + r·c
  writes (accumulate) or r·c reads + d writes (query), the algorithm's
  minimum.
- The median-of-rows query uses an odd-even-transposition network of
  `minimum`/`maximum` (r is tiny and static) — `sort` has no Mosaic lowering
  (the round-2 MosaicError), a comparator network lowers to plain VPU ops.

Layout requirements for this fast path (`unsupported_reason()`):
`c % 1024 == 0` (so the [c/128, 128] slab view is fully (8,128)-tiled for
f32) and the resident working set — the whole [r, c] table, twice, plus three
slabs (`_worst_case_vmem`) — must fit in VMEM.  Anything else, and any
non-TPU backend unless `interpret=True`, takes the pure-JAX oracle in
`csvec.py`, which remains the correctness reference (`tests/test_pallas.py`
pins the two together bit for bit in interpreter mode; `chip_smoke.py`
compares them on the chip).

`probe()` compiles and runs both kernels once per (c, r) layout at first
use. On a TPU backend with a supported layout a failure RAISES with the
compiler's message: a run that was asked for the kernels never silently
trains on the oracle instead.

A Mosaic custom call cannot be partitioned by the SPMD compiler, so under a
multi-device `jit` every kernel call must sit inside a `shard_map`. The
per-device partial sketch of the sharded round already does; call sites at
jit top level (the replicated server tail) trace under `replicated_on(mesh)`,
which wraps the call in a `shard_map` with replicated in/out specs — every
device runs the same kernel on the same operands.
"""

from __future__ import annotations

import contextlib
import functools
import threading

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .hashing import row_keys, sign_hash, slab_shifts

# resident-VMEM budgets. The default *scoped* vmem limit is 16 MiB, so every
# pallas_call raises it explicitly via CompilerParams — to 48 MiB when the
# spec's worst-case footprint fits, else to 96 MiB (a v5e core has 128 MiB of
# VMEM). Checked against the installed compiler for a described v5e (PR 21):
# it takes any vmem_limit_bytes as given and refuses only a kernel whose real
# need exceeds it. The two tiers are the ones the whole-slab kernels had, so
# the flagship layout (c=2^19 r=5) keeps 48 MiB and the language models'
# (c=2^20 r=5) 96 MiB: the limit is part of the round program's compile.
_VMEM_SMALL_BYTES = 48 * 1024 * 1024
_VMEM_LARGE_BYTES = 96 * 1024 * 1024
_VMEM_SLACK_BYTES = 1024 * 1024  # the tiles' halo rows and Mosaic's own scratch


def _worst_case_vmem(c: int, r: int) -> int:
    """Upper-bound scoped-VMEM model for BOTH kernels at a (c, r) layout: what
    Pallas allocates when XLA keeps none of the operands in VMEM for it.

    accumulate: the [r, c] output block (two buffers) + the double-buffered
    input slab + the extended slab (`_extend`) = (2r + 3)·c·4.
    query: the extended table + the double-buffered output slab, and the
    table operand itself where it counts = (2r + 2)·c·4.
    No slab-sized temporary exists in either body. Measured (PR 28, compiled
    for a described v5e at c=2^20 r=5 d=124,443,648, where XLA holds the
    table in VMEM itself): accumulate 12.06 MiB, query 28.25 MiB."""
    return (2 * r + 3) * c * 4 + _VMEM_SLACK_BYTES


def _compiler_params(c: int, r: int):
    need = _worst_case_vmem(c, r)
    limit = _VMEM_SMALL_BYTES if need <= _VMEM_SMALL_BYTES else _VMEM_LARGE_BYTES
    return pltpu.CompilerParams(vmem_limit_bytes=limit)


def unsupported_reason(spec) -> str | None:
    """Why the Pallas fast path cannot take this spec's layout (None when it
    can) — the text the CLIs' startup `sketch:` line shows."""
    if spec.family != "rotation":
        return f"hash family {spec.family!r} (the kernels are rotation-only)"
    if spec.c % 1024 != 0:
        return f"num_cols {spec.c} % 1024 != 0"
    need = _worst_case_vmem(spec.c, spec.r)
    if need > _VMEM_LARGE_BYTES:
        return (f"VMEM model (2r+3)*c*4 = {need >> 20} MiB > "
                f"{_VMEM_LARGE_BYTES >> 20} MiB at c={spec.c} r={spec.r}")
    return None


def supported(spec) -> bool:
    """Whether the Pallas fast path can handle this spec's layout."""
    return unsupported_reason(spec) is None


# ------------------------------------------- multi-device jit: replication

_TRACE_CTX = threading.local()


@contextlib.contextmanager
def replicated_on(mesh):
    """While tracing under this context, a kernel call that is not already
    inside a `shard_map` body runs under one over `mesh` with replicated
    in/out specs (module docstring). No-op for None / single-device meshes."""
    prev = getattr(_TRACE_CTX, "mesh", None)
    _TRACE_CTX.mesh = mesh if mesh is not None and mesh.size > 1 else None
    try:
        yield
    finally:
        _TRACE_CTX.mesh = prev


def _replicated(fn, x):
    mesh = getattr(_TRACE_CTX, "mesh", None)
    if mesh is None or jax.sharding.get_abstract_mesh().manual_axes:
        return fn(x)
    from jax.sharding import PartitionSpec as P

    return jax.shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                         check_vma=False)(x)


# sublane heights a tile may take: the largest that divides c/128 (one
# algorithm at every size; the height only sets how much a loop step holds in
# registers). A slab of fewer sublanes than the ladder's foot, or of a number
# none divides (the interpreter tests' c = 256), is one tile.
_TILE_LADDER = (64, 32, 16, 8)


def _tile_height(cq: int) -> int:
    return next((t for t in _TILE_LADDER if cq % t == 0), cq)


def _tile_rows(t, tile: int):
    """The sublanes of tile `t` as a ref index (an aligned dynamic slice)."""
    return pl.ds(pl.multiple_of(t * tile, tile), tile)


def _extend(src, ext, *, cq: int, tile: int) -> None:
    """ext[i] = src[i mod cq] for i in [0, cq + tile): the source slab
    followed by its own first tile, so that no window of `_rolled_tile` ever
    straddles the slab's end. `src` is a [cq, 128] ref view, `ext` the
    [cq + tile, 128] scratch view. One load and one store a register, once a
    slab — the only pass over the slab that is not the tile loop itself."""

    def body(t, carry):
        rows = _tile_rows(t, tile)
        ext[rows, :] = src[rows, :]
        return carry

    jax.lax.fori_loop(0, cq // tile, body, 0)
    ext[pl.ds(cq, tile), :] = src[pl.ds(0, tile), :]


def _rolled_tile(ext, row0, shift, *, cq: int, tile: int) -> jnp.ndarray:
    """Rows [row0, row0 + tile) of the flat roll-right by `shift` (traced
    scalar in [0, c)) of a [c] vector stored [cq, 128] (row-major: flat
    p = 128*sublane + lane), read from its extension `ext` (`_extend`).

    out[p] = in[(p - shift) mod c]. With shift = 128*sq + sl, output row i
    takes lanes >= sl from source row i - sq and the lanes below sl from row
    i - sq - 1 (the lane borrow), both lane-rotated by sl. So the tile is two
    loads of the window at a dynamic sublane offset, one row apart, a select
    on the source lane and one lane rotate — the wrap is in `ext`, and the
    only rotate left is within a register."""
    sl = shift & 127
    first = row0 + (cq - 1 - (shift >> 7))  # row0 - sq - 1, kept >= 0
    first = jnp.where(first >= cq, first - cq, first)
    lane = jax.lax.broadcasted_iota(jnp.int32, (tile, 128), 1)
    window = jnp.where(lane < 128 - sl,
                       ext[pl.ds(first + 1, tile), :], ext[pl.ds(first, tile), :])
    return pltpu.roll(window, sl, 1)


def _lower_median(vals: list[jnp.ndarray]) -> jnp.ndarray:
    """Lower median (sorted element (r-1)//2) of r same-shape arrays via an
    odd-even transposition network — elementwise min/max only, since `sort`
    has no Mosaic TPU lowering."""
    v = list(vals)
    n = len(v)
    for p in range(n):
        for i in range(p % 2, n - 1, 2):
            lo = jnp.minimum(v[i], v[i + 1])
            hi = jnp.maximum(v[i], v[i + 1])
            v[i], v[i + 1] = lo, hi
    return v[(n - 1) // 2]


def _tile_positions(row0, tile: int) -> jnp.ndarray:
    """Flat position within the slab of each element of the tile that starts
    at sublane `row0` (flat order: 128*sublane + lane)."""
    sub = jax.lax.broadcasted_iota(jnp.int32, (tile, 128), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (tile, 128), 1)
    return (row0 + sub) * 128 + lane


# --------------------------------------------------------------- accumulate


def _accumulate_kernel(shifts_ref, keys_ref, v_ref, out_ref, ext_ref, *, c: int, r: int):
    """Grid (S,): the whole [r, c] table stays VMEM-resident while the slab
    axis streams, and every input slab is read from HBM exactly ONCE,
    contributing sign ⊙ v rolled by shifts[j, b] to all r rows.

    The slab is walked in register-sized tiles of the TABLE: for a tile and a
    row, the window of the input that the roll brings there is loaded
    (`_rolled_tile`), the sign of each SOURCE coordinate is computed from the
    output position (p - shift, wrapped), and the table tile takes one
    read-modify-write. Nothing of the slab's size is ever a value."""
    b = pl.program_id(0)
    cq = c // 128
    tile = _tile_height(cq)

    @pl.when(b == 0)
    def _():
        def zero(t, carry):
            rows = _tile_rows(t, tile)
            for j in range(r):
                out_ref[j, rows, :] = jnp.zeros((tile, 128), out_ref.dtype)
            return carry

        jax.lax.fori_loop(0, cq // tile, zero, 0)

    _extend(v_ref.at[0], ext_ref, cq=cq, tile=tile)
    shifts = [shifts_ref[j, b] for j in range(r)]  # r is tiny and static
    keys = [keys_ref[j] for j in range(r)]

    def body(t, carry):
        row0 = pl.multiple_of(t * tile, tile)
        pos = _tile_positions(row0, tile)
        for j in range(r):
            src = pos - shifts[j]
            idx = b * c + jnp.where(src < 0, src + c, src)
            vals = _rolled_tile(ext_ref, row0, shifts[j], cq=cq, tile=tile)
            out_ref[j, pl.ds(row0, tile), :] += (
                sign_hash(idx, keys[j], dtype=out_ref.dtype) * vals)
        return carry

    jax.lax.fori_loop(0, cq // tile, body, 0)


@functools.partial(jax.jit, static_argnames=("d", "c", "r", "seed", "interpret"))
def _accumulate_call(v, *, d, c, r, seed, interpret):
    num_slabs = -(-d // c)
    cq = c // 128
    v3 = jnp.pad(v, (0, num_slabs * c - d)).reshape(num_slabs, cq, 128)
    shifts = slab_shifts(seed, r, num_slabs, c).astype(jnp.int32)
    _, ks = row_keys(seed, r)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(num_slabs,),
        in_specs=[pl.BlockSpec((1, cq, 128), lambda b, *_: (b, 0, 0))],
        out_specs=pl.BlockSpec((r, cq, 128), lambda b, *_: (0, 0, 0)),
        scratch_shapes=[pltpu.VMEM((cq + _tile_height(cq), 128), v.dtype)],
    )

    table = pl.pallas_call(
        functools.partial(_accumulate_kernel, c=c, r=r),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, cq, 128), v.dtype),
        compiler_params=_compiler_params(c, r),
        interpret=interpret,
    )(shifts, ks, v3)
    return table.reshape(r, c)


@functools.lru_cache(maxsize=None)
def _sketch_fn(d: int, c: int, r: int, seed: int):
    """sequential_vmap-wrapped accumulate: under ANY vmap (including through
    jit) the batch axis lowers to a lax.map over the unbatched kernel instead
    of pallas_call's batching rule. That rule hung an earlier Mosaic; the
    installed one compiles it for a described v5e (flagship layout, batch 4:
    13 s, PR 21), but it has not run on a chip, so the validated sequential
    form stays."""
    import jax.custom_batching

    @jax.custom_batching.sequential_vmap
    def f(v):
        return _accumulate_call(v, d=d, c=c, r=r, seed=seed, interpret=False)

    return f


def sketch_vec(spec, v: jnp.ndarray, *, interpret: bool = False) -> jnp.ndarray:
    """Pallas rotation-family CSVec.accumulateVec: [d] → [r, c] table."""
    if interpret:
        return _accumulate_call(
            v, d=spec.d, c=spec.c, r=spec.r, seed=spec.seed, interpret=True
        )
    return _replicated(_sketch_fn(spec.d, spec.c, spec.r, spec.seed), v)


# -------------------------------------------------------------------- query


def _query_kernel(shifts_ref, keys_ref, tab_ref, out_ref, ext_ref, *, c: int, r: int):
    """Grid (S,): the whole [r, c] table stays resident in VMEM, extended row
    by row at the first step; slab s's estimates are the lower median over
    rows of sign ⊙ (row unrolled by shifts[j, s]), tile by tile: r windows of
    the table, the sign of the tile's own coordinates, the median network in
    registers, one store."""
    s = pl.program_id(0)
    cq = c // 128
    tile = _tile_height(cq)

    @pl.when(s == 0)
    def _():
        for j in range(r):  # r is tiny and static
            _extend(tab_ref.at[j], ext_ref.at[j], cq=cq, tile=tile)

    # roll-left by shift == roll-right by (c - shift) mod c
    invs = [jax.lax.rem(c - shifts_ref[j, s], c) for j in range(r)]
    keys = [keys_ref[j] for j in range(r)]

    def body(t, carry):
        row0 = pl.multiple_of(t * tile, tile)
        idx = s * c + _tile_positions(row0, tile)
        out_ref[0, pl.ds(row0, tile), :] = _lower_median([
            sign_hash(idx, keys[j], dtype=out_ref.dtype)
            * _rolled_tile(ext_ref.at[j], row0, invs[j], cq=cq, tile=tile)
            for j in range(r)])
        return carry

    jax.lax.fori_loop(0, cq // tile, body, 0)


@functools.partial(jax.jit, static_argnames=("d", "c", "r", "seed", "interpret"))
def _query_call(table, *, d, c, r, seed, interpret):
    num_slabs = -(-d // c)
    cq = c // 128
    tab3 = table.reshape(r, cq, 128)
    shifts = slab_shifts(seed, r, num_slabs, c).astype(jnp.int32)
    _, ks = row_keys(seed, r)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(num_slabs,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((1, cq, 128), lambda s, *_: (s, 0, 0)),
        scratch_shapes=[pltpu.VMEM((r, cq + _tile_height(cq), 128), table.dtype)],
    )

    est = pl.pallas_call(
        functools.partial(_query_kernel, c=c, r=r),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_slabs, cq, 128), table.dtype),
        compiler_params=_compiler_params(c, r),
        interpret=interpret,
    )(shifts, ks, tab3)
    return est.reshape(-1)[:d]


@functools.lru_cache(maxsize=None)
def _query_fn(d: int, c: int, r: int, seed: int):
    """sequential_vmap-wrapped query (see _sketch_fn)."""
    import jax.custom_batching

    @jax.custom_batching.sequential_vmap
    def f(table):
        return _query_call(table, d=d, c=c, r=r, seed=seed, interpret=False)

    return f


def query_all(spec, table: jnp.ndarray, *, interpret: bool = False) -> jnp.ndarray:
    """Pallas rotation-family CSVec._findValues over every coordinate."""
    if interpret:
        return _query_call(
            table, d=spec.d, c=spec.c, r=spec.r, seed=spec.seed, interpret=True
        )
    return _replicated(_query_fn(spec.d, spec.c, spec.r, spec.seed), table)


# -------------------------------------------------------- first-use probe

_PROBED: set = set()


# graftlint: drain-point — one-shot availability probe at first use; the
# block_until_ready is the point (a deferred Mosaic failure must surface HERE)
def probe(c: int = 1024, r: int = 3) -> None:
    """Compile and run both kernels once PER (c, r) LAYOUT on the current
    default backend, at the caller's real (c, r) — so spec-scale VMEM
    exhaustion surfaces here and not inside a round's compile. Raises with
    the compiler's message on failure; a pass is cached. The probe uses
    d = 2c + c//2 (3 slabs: same kernel structure and VMEM class as any d
    at this (c, r); d only changes the grid length)."""
    if (c, r) in _PROBED:
        return
    d = 2 * c + c // 2
    v = jnp.linspace(-1.0, 1.0, d, dtype=jnp.float32)
    if isinstance(v, jax.core.Tracer):
        # first use inside a trace (a caller that built its program without
        # the CLIs' start-up line): nothing can run here, and the program
        # being traced carries the same kernels — its own compile is the check
        return
    try:
        # the unwrapped jits: a probe is one device's business
        t = _accumulate_call(v, d=d, c=c, r=r, seed=7, interpret=False)
        jax.block_until_ready(
            _query_call(t, d=d, c=c, r=r, seed=7, interpret=False))
    except Exception as e:
        raise RuntimeError(
            f"pallas sketch kernels failed on {jax.default_backend()!r} at "
            f"c={c} r={r}: {e}\n(COMMEFFICIENT_NO_PALLAS=1 runs the pure-JAX "
            "oracle instead)"
        ) from e
    _PROBED.add((c, r))


def eligible(spec) -> bool:
    """Whether the native kernels run for this spec on the current backend:
    a supported layout on a TPU backend. The first use per (c, r) probes,
    and a failed probe raises — it never downgrades to the oracle. Shared by
    `csvec.sketch_impl` (which layers the COMMEFFICIENT_NO_PALLAS /
    COMMEFFICIENT_PALLAS_INTERPRET env policy on top) and by callers that
    ask about the backend alone, whatever that env policy says."""
    if not (supported(spec) and jax.default_backend() == "tpu"):
        return False
    probe(spec.c, spec.r)
    return True


def probe_status() -> dict:
    """Layouts probed so far (read by tests/test_pallas.py)."""
    layouts = [f"c={c},r={r}" for c, r in sorted(_PROBED)]
    return {"probed": len(layouts) > 0, "layouts": layouts}
