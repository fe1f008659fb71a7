"""Pallas TPU kernels for the rotation-family count-sketch.

These are the "accumulate / query" kernel pair SURVEY.md §3.5 / §7.1 targets
(the reference's CSVec.accumulateVec / _findValues are pure-torch scatter and
gather programs; here the rotation hash family makes both ops *structured*,
and these kernels express that structure directly on the TPU vector unit):

- Every roll of a c-sized slab is two sublane rotates + two lane rotates + a
  select (`_flat_roll`, built on `pltpu.roll` → Mosaic `tpu.dynamic_rotate`)
  over the slab viewed as [c/128, 128] — no scatter/gather at any granularity
  and no DMA at unaligned offsets.
- Bucket signs are recomputed inside the kernel from the integer seed with
  the same murmur mixer as `hashing.py` (uint32 elementwise VPU ops), so no
  [r, d] hash tensor ever exists in HBM.
- The slab axis is the pipelined grid dimension: Pallas streams each slab of
  the input HBM→VMEM exactly once while the whole [r, c] table stays resident
  in VMEM, every slab feeding all r rows — HBM traffic is d reads + r·c
  writes, the algorithm's minimum.
- The median-of-rows query uses an odd-even-transposition network of
  `minimum`/`maximum` (r is tiny and static) — `sort` has no Mosaic lowering
  (the round-2 MosaicError), a comparator network lowers to plain VPU ops.

Layout requirements for this fast path (`unsupported_reason()`):
`c % 1024 == 0` (so the [c/128, 128] slab view is fully (8,128)-tiled for
f32) and the resident working set — the whole [r, c] table plus a couple of
slabs — must fit in VMEM.  Anything else, and any non-TPU backend unless
`interpret=True`, takes the pure-JAX oracle in `csvec.py`, which remains the
correctness reference (`tests/test_pallas.py` pins the two together in
interpreter mode; `chip_smoke.py` compares them on the chip).

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
# VMEM; at GPT-2 dims c=2^20 r=5 the accumulate kernel needs a little over
# 48 MiB scoped). Checked against the installed compiler for a described v5e
# (PR 21): it takes any vmem_limit_bytes as given and refuses only a kernel
# whose real need exceeds it; every layout on the 96 MiB edge of the model
# below (c=2^20 r=9, c=2^21 r=3) compiles under the 96 MiB limit, and the
# 128 MiB-model layouts (c=2^21 r=5, c=2^22 r=1) are refused at 96 MiB — the
# query at c=2^21 r=5 at 120 MiB too. So 96 MiB stays the screen.
_VMEM_SMALL_BYTES = 48 * 1024 * 1024
_VMEM_LARGE_BYTES = 96 * 1024 * 1024


def _worst_case_vmem(c: int, r: int) -> int:
    """Upper-bound scoped-VMEM model for BOTH kernels at a (c, r) layout.

    accumulate: [r, c] table resident + ~7 slab-sized buffers (double-buffered
    input slab, roll temporaries a/b, sign/iota intermediates) ≈ (r+7)·c·4 —
    at c=2^20 r=5 this gives 48 MiB, matching Mosaic's measured 48.21 MiB.
    query: table resident + r live median operands + out/temp slabs
    ≈ (2r+6)·c·4, the larger of the two for r ≥ 1."""
    return (2 * r + 6) * c * 4


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
        return (f"VMEM model (2r+6)*c*4 = {need >> 20} MiB > "
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


def _flat_roll(x: jnp.ndarray, shift: jnp.ndarray) -> jnp.ndarray:
    """Roll-right by `shift` (traced scalar in [0, c)) of the flat [c] vector
    stored as x[c//128, 128] (row-major: flat p = 128*sublane + lane).

    Flat roll by s = 128*sq + sl decomposes into sublane rolls and a lane
    roll with borrow: out lane l takes sublane-roll sq for l >= sl and
    sq + 1 (one extra carry row) for l < sl, both lane-rolled by sl.
    """
    shift = shift.astype(jnp.int32)
    sq = shift // 128
    sl = shift % 128
    a = pltpu.roll(x, sq, 0)
    b = pltpu.roll(x, sq + 1, 0)
    a = pltpu.roll(a, sl, 1)
    b = pltpu.roll(b, sl, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where(lane >= sl, a, b)


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


def _coord_iota(slab, c: int) -> jnp.ndarray:
    """Global coordinate index of each element of slab `slab`'s [c/128, 128]
    view (flat order: 128*sublane + lane)."""
    cq = c // 128
    sub = jax.lax.broadcasted_iota(jnp.int32, (cq, 128), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (cq, 128), 1)
    return slab * c + sub * 128 + lane


# --------------------------------------------------------------- accumulate


def _accumulate_kernel(shifts_ref, keys_ref, v_ref, out_ref, *, c: int, r: int):
    """Grid (S,): the whole [r, c] table stays VMEM-resident while the slab
    axis streams, and every input slab is read from HBM exactly ONCE,
    contributing sign ⊙ v rolled by shifts[j, b] to all r rows.

    (The previous (r, S) grid held one row resident and re-streamed the full
    input per row — r× the HBM input traffic. At r=5 those re-reads dominated
    the kernel's measured ~43% of the bandwidth roofline; this layout's
    traffic is d reads + r·c writes, the minimum the algorithm admits. The
    coordinate iota and the input slab load are shared across rows; only the
    sign hash and the roll are inherently per-row, since each row has its own
    key and shift.)"""
    b = pl.program_id(0)
    idx = _coord_iota(b, c)
    v = v_ref[0]

    @pl.when(b == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    for j in range(r):  # r is tiny and static
        signed = sign_hash(idx, keys_ref[j], dtype=out_ref.dtype) * v
        out_ref[j] += _flat_roll(signed, shifts_ref[j, b])


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


def _query_kernel(shifts_ref, keys_ref, tab_ref, out_ref, *, c: int, r: int):
    """Grid (S,): the whole [r, c] table stays resident in VMEM; slab s's
    estimates are the lower median over rows of sign ⊙ (row unrolled by
    shifts[j, s])."""
    s = pl.program_id(0)
    idx = _coord_iota(s, c)
    ests = []
    for j in range(r):  # r is tiny and static
        # roll-left by shift == roll-right by (c - shift) mod c
        inv = jax.lax.rem(c - shifts_ref[j, s], c)
        row = _flat_roll(tab_ref[j], inv)
        ests.append(sign_hash(idx, keys_ref[j], dtype=out_ref.dtype) * row)
    out_ref[0] = _lower_median(ests)


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
        in_specs=[pl.BlockSpec((r, cq, 128), lambda s, *_: (0, 0, 0))],
        out_specs=pl.BlockSpec((1, cq, 128), lambda s, *_: (s, 0, 0)),
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
    COMMEFFICIENT_PALLAS_INTERPRET env policy on top) and bench.py's kernel
    microbench (which deliberately ignores that env policy)."""
    if not (supported(spec) and jax.default_backend() == "tpu"):
        return False
    probe(spec.c, spec.r)
    return True


def probe_status() -> dict:
    """Layouts probed so far (bench.py embeds this in its JSON)."""
    layouts = [f"c={c},r={r}" for c, r in sorted(_PROBED)]
    return {"probed": len(layouts) > 0, "layouts": layouts}
