"""Count-Sketch of a length-`d` vector into an `r x c` table — pure-JAX oracle.

TPU-native re-design of the reference's vendored CSVec library (SURVEY.md L1:
`csvec/csvec.py`, `CSVec.accumulateVec` / `__add__` / `unSketch(k)` /
`_findValues` median-of-rows query).  Differences from the reference, by
design rather than accident:

- **Functional, not stateful.** A sketch is just an `[r, c]` float array; the
  static configuration lives in a hashable `CSVecSpec`.  Sketch addition is
  array addition, so cross-client aggregation is a plain `sum`/`psum` and XLA
  fuses it with whatever surrounds it.
- **Hashes are computed on the fly** from a seed (see `hashing.py`), never
  materialised as `[r, d]` tensors.  The reference's `numBlocks` memory
  workaround survives as `num_blocks`, but here it bounds the *transient*
  index/sign working set inside a `lax.scan`, not persistent hash tensors.
- **Static shapes throughout**: `unsketch_topk` returns exactly-`k` results by
  merging per-block `lax.top_k` candidates in the scan carry, so the whole
  thing jits and vmaps.
- **Exact top-k without a sort of d** (`select_topk_abs`): on the single-shot
  path the k largest of millions of estimates are found by a counted
  threshold and a two-level compaction, and only those k are sorted.

Estimate semantics match the reference: the estimate of coordinate `i` is the
median over the `r` rows of `sign[row, i] * table[row, bucket[row, i]]`, and
`unsketch_topk` takes the top-k of those estimates by magnitude
(SURVEY.md §3.5).
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from .hashing import bucket_hash, row_keys, sign_hash, slab_shifts

FAMILIES = ("random", "rotation")


@dataclasses.dataclass(frozen=True)
class CSVecSpec:
    """Static configuration of a count-sketch. Hashable; safe to close over.

    `family` selects the bucket-hash family:

    - "random" — murmur-mixed per-coordinate buckets, the closest analogue of
      the reference CSVec's polynomial hashes. Accumulate/query are
      scatter/gather, which TPUs execute serially — correct but slow.
    - "rotation" — coordinate i of row j lands in bucket
      (i mod c + shift[j, i // c]) mod c, with per-(row, slab) random shifts
      (hashing.slab_shifts) and the same per-(row, coordinate) random signs.
      Within a slab of c consecutive coordinates the bucket map is a pure
      rotation, so dense accumulate/query are sign-multiply + roll + add —
      all VPU-vectorizable, no scatter/gather anywhere. Estimates stay
      unbiased (signs are independent across coordinates); intra-slab
      collisions are impossible and cross-slab collision probability is
      approximately 1/c (bucket_hash's % c has modulo bias when c doesn't
      divide 2^32). Unlike per-coordinate hashing, collisions are
      block-correlated: two slabs collide at ALL offset-aligned coordinate
      pairs or none, a joint-distribution difference that leaves per-pair
      probability and per-coordinate variance unchanged.

    Both families share one generic (idx → buckets/signs) path for sparse
    sketching and point queries, so the fast dense paths can be property-tested
    against it.
    """

    d: int  # dimensionality of the sketched vector
    c: int  # number of columns (buckets per row)
    r: int  # number of rows (independent hash functions)
    num_blocks: int = 1  # chunks the d-axis to bound transient memory
    seed: int = 42
    family: str = "random"

    def __post_init__(self):
        if self.d <= 0 or self.c <= 0 or self.r <= 0 or self.num_blocks <= 0:
            raise ValueError(f"invalid CSVecSpec: {self}")
        if self.family not in FAMILIES:
            raise ValueError(f"unknown hash family {self.family!r}; expected {FAMILIES}")

    @property
    def block_size(self) -> int:
        return math.ceil(self.d / self.num_blocks)

    @property
    def padded_d(self) -> int:
        return self.block_size * self.num_blocks

    @property
    def table_shape(self) -> tuple[int, int]:
        return (self.r, self.c)

    @property
    def num_slabs(self) -> int:
        """c-sized slabs of the d-axis (rotation family's unit of structure)."""
        return math.ceil(self.d / self.c)


def zero_table(spec: CSVecSpec, dtype=jnp.float32) -> jnp.ndarray:
    return jnp.zeros(spec.table_shape, dtype=dtype)


def _block_hashes(spec: CSVecSpec, idx: jnp.ndarray, dtype):
    """buckets[r, n], signs[r, n] for coordinate indices idx[n]."""
    kb, ks = row_keys(spec.seed, spec.r)
    if spec.family == "rotation":
        shifts = slab_shifts(spec.seed, spec.r, spec.num_slabs, spec.c)  # [r, S]
        pos = (idx % spec.c).astype(jnp.int32)
        slab = (idx // spec.c).astype(jnp.int32)
        buckets = (pos[None, :] + shifts[:, slab]) % spec.c
    else:
        buckets = jax.vmap(lambda k: bucket_hash(idx, k, spec.c))(kb)
    signs = jax.vmap(lambda k: sign_hash(idx, k, dtype=dtype))(ks)
    return buckets, signs


def _roll_right(x: jnp.ndarray, shift: jnp.ndarray) -> jnp.ndarray:
    """out[(p + shift) mod c] = x[p] for a [c] vector and traced scalar shift.

    Expressed as one contiguous dynamic_slice of [x ‖ x] so XLA lowers it to a
    cheap windowed copy (and, vmapped over slabs, a batched contiguous gather)
    instead of a random-access gather.
    """
    c = x.shape[0]
    start = (c - shift.astype(jnp.int32)) % c
    return jax.lax.dynamic_slice(jnp.concatenate([x, x]), (start,), (c,))


def _roll_left(x: jnp.ndarray, shift: jnp.ndarray) -> jnp.ndarray:
    """out[p] = x[(p + shift) mod c] — inverse of `_roll_right`."""
    c = x.shape[0]
    start = shift.astype(jnp.int32) % c
    return jax.lax.dynamic_slice(jnp.concatenate([x, x]), (start,), (c,))


def _pad_to_slabs(spec: CSVecSpec, v: jnp.ndarray) -> jnp.ndarray:
    """[d] → [num_slabs, c], zero-padded."""
    return jnp.pad(v, (0, spec.num_slabs * spec.c - spec.d)).reshape(spec.num_slabs, spec.c)


def sketch_impl(spec: CSVecSpec) -> tuple[str, str | None]:
    """Which implementation `sketch_vec` / `query_all` run for this spec on
    the current backend, and why when it is not the kernels:
    ("pallas", None) | ("pallas-interpret", None) | ("oracle", reason).

    The Pallas kernels run on a TPU backend for supported layouts
    (pallas_kernels.unsupported_reason). The first use per (c, r) compiles
    and runs them once (pallas_kernels.probe); a failure there RAISES — the
    oracle is only ever taken for a stated reason, never as a silent
    downgrade. vmap is safe without a guard here — the kernels are
    sequential_vmap-wrapped — though the engine never needs it: sketching is
    linear, so the round step sketches the client-aggregated update once.
    COMMEFFICIENT_NO_PALLAS=1 forces the pure-JAX oracle (debugging).
    COMMEFFICIENT_PALLAS_INTERPRET=1 routes supported layouts through the
    Pallas interpreter on ANY backend — CPU tests can then exercise the
    exact engine+kernel composition that runs on hardware."""
    import os

    if os.environ.get("COMMEFFICIENT_NO_PALLAS"):
        return "oracle", "COMMEFFICIENT_NO_PALLAS is set"
    from . import pallas_kernels

    why = pallas_kernels.unsupported_reason(spec)
    if why is not None:
        return "oracle", why
    if _pallas_interpret():
        return "pallas-interpret", None
    if pallas_kernels.eligible(spec):
        return "pallas", None
    return "oracle", f"backend {jax.default_backend()!r} is not a TPU"


def describe_impl(spec: CSVecSpec) -> str:
    """`sketch_impl` as the CLIs' startup `sketch:` line shows it."""
    impl, why = sketch_impl(spec)
    return impl if why is None else f"{impl} ({why})"


def _use_pallas(spec: CSVecSpec) -> bool:
    return sketch_impl(spec)[0] != "oracle"


def _pallas_interpret() -> bool:
    import os

    return bool(os.environ.get("COMMEFFICIENT_PALLAS_INTERPRET"))


def _sketch_vec_rotation(spec: CSVecSpec, v: jnp.ndarray) -> jnp.ndarray:
    """Dense accumulate, rotation family: per row, sign the vector, roll each
    slab by its shift, and add slabs — no scatter. O(r·d) VPU work.

    The slab reduction is an EXPLICIT left fold (lax.scan in slab order),
    not a `.sum(axis=0)`: XLA lowers an axis reduce as a tree whose shape
    depends on the array extent, while the layerwise accumulation path
    (sketch/layerwise.py) folds each leaf's slabs into the running table
    one at a time. Making the oracle the same ordered fold is what lets
    `accumulate_leaf` over any leaf partition reproduce this function
    BIT-identically — the contract the engine's `--sketch_path` parity
    pin rests on. (Per bucket both orders are the plain sequential sum
    t_0 + t_1 + ... over slabs; a boundary slab split across two leaves
    contributes its value from the owning leaf and an exact +0.0 from the
    other, which IEEE addition ignores.)"""
    v_slabs = _pad_to_slabs(spec, v)  # zero-pad ⇒ padded coords contribute 0
    idx = jnp.arange(spec.num_slabs * spec.c, dtype=jnp.int32)
    _, ks = row_keys(spec.seed, spec.r)
    shifts = slab_shifts(spec.seed, spec.r, spec.num_slabs, spec.c)  # [r, S]

    def row_table(args):
        k_sign, row_shifts = args
        signed = v_slabs * sign_hash(idx, k_sign, dtype=v.dtype).reshape(v_slabs.shape)

        def body(acc, xs):
            slab, shift = xs
            return acc + _roll_right(slab, shift), None

        out, _ = jax.lax.scan(
            body, jnp.zeros((spec.c,), v.dtype), (signed, row_shifts))
        return out

    # sequential over the r rows (r is tiny) to bound transients to O(d)
    return jax.lax.map(row_table, (ks, shifts))


def _query_slab_rotation(spec: CSVecSpec, table: jnp.ndarray, slab: jnp.ndarray) -> jnp.ndarray:
    """[c] estimates for slab `slab` (traced scalar): per row, unroll the table
    row by the slab's shift and apply signs; then median over rows."""
    _, ks = row_keys(spec.seed, spec.r)
    shifts = slab_shifts(spec.seed, spec.r, spec.num_slabs, spec.c)  # [r, S]
    idx = slab * spec.c + jnp.arange(spec.c, dtype=jnp.int32)

    def row_est(tab_row, k_sign, s):
        return sign_hash(idx, k_sign, dtype=table.dtype) * _roll_left(tab_row, s)

    per_row = jax.vmap(row_est)(table, ks, shifts[:, slab])  # [r, c]
    return jnp.sort(per_row, axis=0)[(spec.r - 1) // 2]


def _query_all_rotation(spec: CSVecSpec, table: jnp.ndarray) -> jnp.ndarray:
    """Dense query, rotation family — the pure-JAX oracle of the Pallas query
    kernel (as `_sketch_vec_rotation` is of the accumulate kernel)."""
    slabs = jnp.arange(spec.num_slabs, dtype=jnp.int32)
    ests = jax.lax.map(lambda b: _query_slab_rotation(spec, table, b), slabs)
    return ests.reshape(-1)[: spec.d]


def _accumulate(
    spec: CSVecSpec, vals: jnp.ndarray, idx: jnp.ndarray, valid: jnp.ndarray
) -> jnp.ndarray:
    """Scatter (idx, vals) masked by `valid` into a fresh [r, c] table.

    Single scatter path shared by dense-block and sparse sketching, so the two
    can never diverge (and a future Pallas kernel swaps in at one place)."""
    buckets, signs = _block_hashes(spec, idx, vals.dtype)
    contrib = signs * (vals * valid.astype(vals.dtype))[None, :]  # [r, n]
    return jax.vmap(
        lambda c_row, b_row: jax.ops.segment_sum(c_row, b_row, num_segments=spec.c)
    )(contrib, buckets)


def _accumulate_block(spec: CSVecSpec, v_block: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """Sketch one contiguous block of the vector into a fresh [r, c] table."""
    return _accumulate(spec, v_block, idx, idx < spec.d)


def sketch_vec(spec: CSVecSpec, v: jnp.ndarray) -> jnp.ndarray:
    """Sketch a dense [d] vector into an [r, c] table (CSVec.accumulateVec)."""
    if v.shape != (spec.d,):
        raise ValueError(f"expected shape ({spec.d},), got {v.shape}")
    if spec.family == "rotation":
        # structural fast path (roll + add); num_blocks is irrelevant here —
        # the slab size is pinned to c by the hash family itself.
        if _use_pallas(spec):
            from . import pallas_kernels

            return pallas_kernels.sketch_vec(spec, v, interpret=_pallas_interpret())
        return _sketch_vec_rotation(spec, v)
    if spec.num_blocks == 1:
        return _accumulate_block(spec, v, jnp.arange(spec.d, dtype=jnp.int32))

    bs = spec.block_size
    v_pad = jnp.pad(v, (0, spec.padded_d - spec.d)).reshape(spec.num_blocks, bs)
    starts = jnp.arange(spec.num_blocks, dtype=jnp.int32) * bs

    def body(table, xs):
        v_blk, start = xs
        idx = start + jnp.arange(bs, dtype=jnp.int32)
        return table + _accumulate_block(spec, v_blk, idx), None

    table, _ = jax.lax.scan(body, zero_table(spec, v.dtype), (v_pad, starts))
    return table


def sketch_sparse(spec: CSVecSpec, idx: jnp.ndarray, vals: jnp.ndarray) -> jnp.ndarray:
    """Sketch a k-sparse vector given by (idx[k], vals[k]).

    Exactly equals `sketch_vec` of the scattered dense vector (used to subtract
    the transmitted top-k from sketched error/momentum state — FetchSGD's
    "error sketch subtract", SURVEY.md §3.1). Entries with idx < 0 or >= d are
    ignored, so callers can pad with idx = -1.
    """
    valid = (idx >= 0) & (idx < spec.d)
    return _accumulate(spec, vals, jnp.clip(idx, 0, spec.d - 1), valid)


def query(spec: CSVecSpec, table: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """Estimate coordinates idx[m] from the table: median over the r rows of
    sign * table[row, bucket] (CSVec._findValues)."""
    buckets, signs = _block_hashes(spec, idx, table.dtype)
    rows = jnp.arange(spec.r)[:, None]
    per_row = signs * table[rows, buckets]  # [r, m]
    # lower median (sorted element at index (r-1)//2), matching torch.median's
    # behavior in the reference CSVec for even r; true median for odd r.
    return jnp.sort(per_row, axis=0)[(spec.r - 1) // 2]


def mask_transmitted(
    spec: CSVecSpec, V: jnp.ndarray, E: jnp.ndarray,
    idx: jnp.ndarray, vals: jnp.ndarray,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """FetchSGD's sketch-space masking tail in one call: E -= sketch(vals at
    idx); V -= sketch(query(V, idx) at idx). Bit-identical to the unfused
    two-`sketch_sparse`-plus-`query` sequence (same clipped-index hashing as
    sketch_sparse; invalid idx < 0 / >= d entries contribute exactly 0 to
    both scatters, as before — the query value at an invalid index was
    unused garbage in the unfused form too). Pinned in tests/test_csvec.py.

    Note on cost: expressing this as one call changes nothing measured —
    inside one jitted program XLA already CSE's the three ops' identical
    (r, k) hash evaluations (the isolated algebra cost is the
    scatter/gather/sort itself, the round's `server_algebra`
    phase). So this is a plain composition of the shared
    primitives, preserving _accumulate's single-scatter-path invariant;
    the value is the single call site and the documented semantics."""
    E = E - sketch_sparse(spec, idx, vals)
    vvals = query(spec, V, idx)
    V = V - sketch_sparse(spec, idx, vvals)
    return V, E


def merge_tables(spec: CSVecSpec, tables: jnp.ndarray) -> jnp.ndarray:
    """Merge S partial sketch tables [S, r, c] into one [r, c] table — THE
    cross-shard merge entry point of the data-parallel round (FetchSGD's
    central linearity: Count Sketches of partial client sums add to the
    sketch of the full cohort sum, so a device mesh ships r*c floats per
    merge instead of the dense [d] gradient).

    Deliberately an ORDERED sum over the stacked leading axis: the engine's
    sharded round all_gathers the per-device partials into exactly this
    [S, r, c] layout (shard-index order) and calls this same function, so
    the mesh merge and the single-device reference execute the identical
    reduce — the bit-identity the CPU-mesh parity tests pin. A ring psum
    would reassociate the sum per topology and break that pin (measured:
    tree-reduction differences at the 1e-3 absolute level on an 8-way CPU
    mesh at table scale)."""
    if tables.ndim != 3 or tables.shape[1:] != spec.table_shape:
        raise ValueError(
            f"expected stacked partial tables [S, {spec.r}, {spec.c}], got "
            f"{tables.shape}"
        )
    return tables.sum(axis=0)


def query_all(spec: CSVecSpec, table: jnp.ndarray) -> jnp.ndarray:
    """Dense [d] vector of estimates for every coordinate. O(r*d) transient
    memory when num_blocks == 1; scanned per block otherwise."""
    if spec.family == "rotation":
        if _use_pallas(spec):
            from . import pallas_kernels

            return pallas_kernels.query_all(spec, table, interpret=_pallas_interpret())
        return _query_all_rotation(spec, table)
    if spec.num_blocks == 1:
        return query(spec, table, jnp.arange(spec.d, dtype=jnp.int32))

    bs = spec.block_size
    starts = jnp.arange(spec.num_blocks, dtype=jnp.int32) * bs

    def body(_, start):
        idx = start + jnp.arange(bs, dtype=jnp.int32)
        return None, query(spec, table, jnp.clip(idx, 0, spec.d - 1))

    _, blocks = jax.lax.scan(body, None, starts)
    return blocks.reshape(-1)[: spec.d]


# impl="oversample" preselects this many x k candidates before the exact
# refine; 4x puts the true top-k comfortably inside the candidate set
# (approx_max_k's misses concentrate at the selection boundary)
TOPK_OVERSAMPLE = 4

# impl="exact" selects (select_topk_abs) where n is at least this and at
# least TOPK_SELECT_MIN_N_PER_K * k, and sorts (lax.top_k) below. Measured on
# a v5e at k = 50,000 (PERF.md section 6, PR 32): n = 200,000 sort 0.255 ms /
# selection 0.418, n = 350,000 sort 0.465 / selection 0.405. The selection's
# floor is its k-sized steps, 7 ns a slot (two gathers of k rows of 128 keys,
# three passes over them, the sort of k pairs), against the sort's 1.3-2 ns
# an element; they also hold k x 128 keys, so a k near n would cost more
# memory than the sort it replaces.
TOPK_SELECT_MIN_N = 350_000
TOPK_SELECT_MIN_N_PER_K = 7

_LANES = 128  # a row of the selection's two-level prefix: one lane tile
# value bits a counting pass settles (2^bits - 1 counts a pass): on a v5e at
# n = 6,573,130 the whole selection takes 0.79 / 0.69 / 0.76 / 0.88 ms at
# 1 / 2 / 3 / 4 bits (a pass is 11 us at 2 bits once x sits in VMEM, 52 us at
# 4 bits, bound by the compares)
_THRESHOLD_BITS = 2


def _kth_largest_key(keys: jnp.ndarray, k: int) -> jnp.ndarray:
    """The k-th largest of int32 `keys`, of which at least k are
    non-negative (a negative key is at or above no candidate, so it counts
    for nothing), by counting: the value's 31 bits from the top,
    _THRESHOLD_BITS a pass; a pass counts the keys at or above every
    candidate prefix in one read (sibling reductions of one fusion) and
    keeps the largest that still has k."""
    t = jnp.int32(0)
    hi = 31
    while hi > 0:
        shift = max(hi - _THRESHOLD_BITS, 0)
        digit = jnp.int32(0)
        for j in range(1, 1 << (hi - shift)):
            at_or_above = jnp.sum(keys >= (t | jnp.int32(j << shift)),
                                  dtype=jnp.int32)
            digit += (at_or_above >= k).astype(jnp.int32)
        t = t | (digit << shift)
        hi = shift
    return t


def _slot_rows(incl: jnp.ndarray, k: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """For output slots 0..k-1 of a compaction whose rows hold `incl[r]`
    selected elements up to and including row r (non-decreasing, last = k):
    the row each slot falls in and the slot's rank inside that row. A
    two-level search with no scatter and no k-long chain of gathers: rows
    are grouped 128 to a block, a slot finds its block by comparing with
    every block's last count, gathers that block's 128 counts as ONE row
    (a row gather costs the chip a fifth of a scalar gather) and finds its
    row by comparing again."""
    pad = -incl.shape[0] % _LANES
    blocks = jnp.pad(incl, (0, pad), constant_values=k).reshape(-1, _LANES)
    last = blocks[:, -1]
    slot = jnp.arange(k, dtype=jnp.int32)[:, None]
    passed = last[None, :] <= slot  # blocks that end at or before the slot
    blk = jnp.sum(passed, axis=1, dtype=jnp.int32)
    counts = blocks[blk]  # [k, 128]
    before = counts <= slot
    row = blk * _LANES + jnp.sum(before, axis=1, dtype=jnp.int32)
    # selected elements in all rows before `row`: the largest count passed
    start = jnp.maximum(jnp.max(jnp.where(passed, last[None, :], 0), axis=1),
                        jnp.max(jnp.where(before, counts, 0), axis=1))
    return row, slot[:, 0] - start


def _magnitude_keys(mag: jnp.ndarray) -> jnp.ndarray:
    """A non-negative float's int32 bit pattern, which orders as the value
    does: +0.0 is 0 and a NaN sorts above inf, as in lax.top_k's total
    order. Anything with the sign bit set (a reduction's -inf or lowest
    float in a slot it never filled) is a negative key."""
    return jax.lax.bitcast_convert_type(mag.astype(jnp.float32), jnp.int32)


def select_topk_abs(x: jnp.ndarray, k: int) -> jnp.ndarray:
    """`jax.lax.top_k(jnp.abs(x), k)[1]` element for element (ties and
    non-finite values included) without sorting x: find the k-th largest
    magnitude by counting, compact what lies above it, sort those k.

    `lax.top_k` lowers to a full key-value sort on the TPU: 13.0 ms at
    n = 6,573,130, k = 50,000 on a v5e, 0.5% of what the bytes allow; this
    takes 0.69 ms there (PERF.md section 6, PR 32). Every n-sized step is an
    elementwise pass or a row reduction; the rest is k-sized (k x 128 keys
    at the widest) or n/128-sized. One-dimensional x (callers vmap).

    Keys: |x| as its int32 bit pattern (_magnitude_keys; -0.0 becomes
    +0.0); the rest works on keys alone (_select_topk_keys)."""
    return _select_topk_keys(_magnitude_keys(jnp.abs(x)), k)


def _select_topk_keys(keys: jnp.ndarray, k: int) -> jnp.ndarray:
    """Positions of the k largest of one-dimensional int32 `keys`, largest
    first, ties by lowest position (lax.top_k's order). A key below 0 is
    never selected, so at least k keys must be non-negative: a caller
    marks slots that hold nothing with any negative key, as the rows'
    own padding is.

    1. Threshold t: the k-th largest key (_kth_largest_key).
    2. Exactly k with lax.top_k's tie rule (its sort is stable: value
       descending, then index ascending): every key > t and the first
       k - #(key > t) keys == t in index order, i.e. those at or before the
       flat index `cut`.
    3. Compaction over rows of 128: per-row counts, one prefix over the
       n/128 rows, the row and in-row rank of each output slot
       (_slot_rows), a gather of those k rows, the lane from an in-row
       prefix (a product with a triangle of ones: exact, the counts are at
       most 128).
    4. lax.top_k's order: sort the k selected by key descending, index
       ascending."""
    (n,) = keys.shape
    t = _kth_largest_key(keys, k)
    # padding keys are -1: below every real key, so never selected
    rows = jnp.pad(keys, (0, -n % _LANES), constant_values=-1).reshape(
        -1, _LANES)
    lane = jax.lax.iota(jnp.int32, _LANES)
    upper = (lane[:, None] <= lane[None, :]).astype(jnp.bfloat16)

    def prefix(mask):  # inclusive count along the lanes
        return jnp.dot(mask.astype(jnp.bfloat16), upper,
                       preferred_element_type=jnp.float32).astype(jnp.int32)

    # the tie rule: `need` of the keys == t are taken, lowest index first;
    # `cut` is the flat index of the last one
    need = k - jnp.sum(rows > t, dtype=jnp.int32)
    ties = jnp.cumsum(jnp.sum(rows == t, axis=1, dtype=jnp.int32))
    tie_row = jnp.sum(ties < need, dtype=jnp.int32)  # the row `cut` lies in
    in_row = prefix(
        jax.lax.dynamic_index_in_dim(rows, tie_row, keepdims=False) == t)
    need_in_row = need - (ties[tie_row] - in_row[-1])
    cut = tie_row * _LANES + jnp.sum(in_row < need_in_row, dtype=jnp.int32)

    def selected(keys_, index):
        return (keys_ > t) | ((keys_ == t) & (index <= cut))

    index = jax.lax.iota(jnp.int32, rows.size).reshape(rows.shape)
    incl = jnp.cumsum(jnp.sum(selected(rows, index), axis=1, dtype=jnp.int32))
    row, rank = _slot_rows(incl, k)
    picked = rows[row]  # [k, 128]: the row each output slot falls in
    picked_index = row[:, None] * _LANES + lane[None, :]
    col = jnp.sum(prefix(selected(picked, picked_index)) <= rank[:, None],
                  axis=1, dtype=jnp.int32)  # lane of the row's rank-th selected
    key = jnp.sum(jnp.where(lane[None, :] == col[:, None], picked, 0), axis=1)
    # ~key ascending is key descending; the index breaks ties as top_k does
    _, idx = jax.lax.sort((~key, row * _LANES + col), num_keys=2)
    return idx


def _selection_pays(n: int, k: int) -> bool:
    return n >= max(TOPK_SELECT_MIN_N, TOPK_SELECT_MIN_N_PER_K * k)


def approx_select_size(n: int, k: int, recall: float) -> int:
    """The number m of partial maxima that `topk_abs(impl="approx")` picks
    its k from by selection at a vector of n, or 0 where m is too small for
    the selection to pay and `lax.approx_max_k` aggregates them itself. m
    is static and the same on every platform (the shape rule of the TPU's
    PartialReduce: whole tiles of 1024, so fewer than 1024 slots can be
    empty)."""
    vals, _ = jax.eval_shape(
        lambda v: jax.lax.approx_max_k(v, k, recall_target=recall,
                                       aggregate_to_topk=False),
        jax.ShapeDtypeStruct((n,), jnp.float32))
    m = vals.shape[0]
    return m if _selection_pays(m, k) else 0


def topk_abs(
    x: jnp.ndarray, k: int, approx: bool = False, recall: float = 0.95,
    impl: str | None = None,
) -> jnp.ndarray:
    """Indices of the k largest-|.| entries. Single home for the top-k
    selection branch (ModeConfig.topk_impl / topk_recall):

    - "exact": the k largest, ties by lowest index, in `lax.top_k`'s
      order. From max(TOPK_SELECT_MIN_N, TOPK_SELECT_MIN_N_PER_K * k)
      elements on by `select_topk_abs`
      (threshold and compaction: 0.69 ms at n = 6.57M, k = 50,000 on a
      v5e), below it by `lax.top_k` itself, which the TPU lowers to a full
      sort of x (13.0 ms there; 442 ms at d = 124M, r5 server_split). Both
      return the same array.
    - "approx": `lax.approx_max_k` at `recall`. On the TPU that is a
      PartialReduce, which keeps one maximum of every bucket of 2^j
      coordinates (m partial maxima; which coordinate of a bucket
      survives is all the approximation there is), and then the k largest
      of those m. From m >= max(TOPK_SELECT_MIN_N,
      TOPK_SELECT_MIN_N_PER_K * k) on (`approx_select_size`) the partial
      maxima are taken as they are (`aggregate_to_topk=False`) and their k
      largest found by `_select_topk_keys`; below it `approx_max_k`
      aggregates them itself, by a full sort of the m pairs (10.3 ms at
      m = 7.78M, GPT-2's d / 16, and 15.5 ms at GLM's 9.24M on a v5e:
      PERF.md section 6, PR 36). Both return the same array but where
      partial maxima tie exactly at the k-th place. Elsewhere the
      lowering is exact (the m largest, sorted). Accuracy impact at paper
      scale is within seed variance for recall 0.99 (2x2 seed replication
      inverted the single-seed ordering — results/README.md); any cost is
      below that study's resolution.
    - "oversample": approx preselect of TOPK_OVERSAMPLE*k candidates +
      exact top_k over them — near-exact selection at PartialReduce
      speed by construction (the exact refine sorts only 4k elements),
      sidestepping the recall question entirely.

    `impl` supersedes the legacy `approx` bool when given."""
    if impl is None:
        impl = "approx" if approx else "exact"
    if impl not in ("exact", "approx", "oversample"):
        raise ValueError(f"bad impl {impl!r}")
    if impl == "oversample":
        kk = TOPK_OVERSAMPLE * k
        if kk >= x.shape[0]:  # candidate set would be everything: go exact
            impl = "exact"
        else:
            cand = topk_abs(x, kk, impl="approx", recall=recall)
            sub = topk_abs(x[cand], k, impl="exact")
            return cand[sub]
    keyed = x.dtype in (jnp.float32, jnp.bfloat16, jnp.float16)
    if impl == "approx":
        if keyed and approx_select_size(x.shape[0], k, recall):
            # The partial maxima as the reduction leaves them. No second
            # abs: each is a magnitude (key >= 0), and a slot that no
            # coordinate reached holds the reduction's initial value (-inf,
            # a negative key, with an index that means nothing), which the
            # selection never takes while k real maxima exist; there are
            # fewer such slots than one tile of 1024 and m >= 7k.
            vals, pos = jax.lax.approx_max_k(
                jnp.abs(x), k, recall_target=recall, aggregate_to_topk=False)
            idx = pos[_select_topk_keys(_magnitude_keys(vals), k)]
        else:
            _, idx = jax.lax.approx_max_k(jnp.abs(x), k, recall_target=recall)
    elif keyed and _selection_pays(x.shape[0], k):
        idx = select_topk_abs(x, k)  # the same array, without the sort of x
    else:
        _, idx = jax.lax.top_k(jnp.abs(x), k)
    return idx.astype(jnp.int32)


# Single-shot unsketch ceiling: when the [d] estimates transient fits in
# this many bytes, materialize it and take ONE (approx_)top_k instead of the
# memory-bounding sequential slab scan — far fewer sequential steps on TPU,
# and with impl="approx" a single PartialReduce pass over d instead of a
# per-chunk preselect. 1 GiB covers GPT-2-small at f32 (d≈124M) with
# headroom on any TPU generation; set to 0 to force the scan (tests do).
UNSKETCH_SINGLE_SHOT_BYTES = 1 << 30


def unsketch_topk(
    spec: CSVecSpec, table: jnp.ndarray, k: int, impl: str = "exact",
    recall: float = 0.95,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k heavy hitters by |estimate|: (idx[k], vals[k]) (CSVec.unSketch(k)).

    Rotation family: single-shot when the [d] estimates transient is
    affordable (UNSKETCH_SINGLE_SHOT_BYTES, or whenever the Pallas kernel —
    which materializes the estimates anyway — is routed); otherwise scans
    the d-axis in blocks, keeping a running top-k in the carry, so peak
    transient memory is O(r * block_size) regardless of d.

    impl (ModeConfig.topk_impl, see topk_abs): "approx"/"oversample" use
    one PartialReduce pass over all d estimates on the single-shot path;
    the chunked path uses them only to PRESELECT k candidates within each
    chunk and merges the carry exactly — each coordinate faces exactly one
    approximate pass (its own chunk), so overall recall stays ~the target
    instead of compounding per chunk ("oversample" preselection refines
    exactly, making the whole chunked path near-exact). Exact results are
    path-independent (the same top-k set, up to ties in |estimate|).
    """
    if k > spec.d:
        raise ValueError(f"k={k} > d={spec.d}")

    if spec.family == "rotation":
        # chunk = slab (the rotation family's structural unit)
        chunks = jnp.arange(spec.num_slabs, dtype=jnp.int32)

        if _use_pallas(spec) or spec.d * 4 <= UNSKETCH_SINGLE_SHOT_BYTES:
            with jax.named_scope("server_query"):
                est = query_all(spec, table)  # routes Pallas/oracle internally
            with jax.named_scope("server_topk"):
                top_idx = topk_abs(est, k, recall=recall, impl=impl)
                return top_idx, est[top_idx]

        def chunk_estimates(slab):
            idx = slab * spec.c + jnp.arange(spec.c, dtype=jnp.int32)
            return idx, _query_slab_rotation(spec, table, slab)

    else:
        chunks = jnp.arange(spec.num_blocks, dtype=jnp.int32) * spec.block_size

        def chunk_estimates(start):
            idx = start + jnp.arange(spec.block_size, dtype=jnp.int32)
            return idx, query(spec, table, jnp.clip(idx, 0, spec.d - 1))

    def body(carry, chunk):
        with jax.named_scope("server_query"):
            idx, est = chunk_estimates(chunk)
        return select(carry, idx, est), None

    @jax.named_scope("server_topk")
    def select(carry, idx, est):
        run_idx, run_vals = carry
        valid = idx < spec.d
        if impl != "exact" and est.shape[0] > k:
            # within-chunk preselection (the one approximate pass; for
            # impl="oversample" the preselect itself refines exactly, so
            # the whole chunked path is near-exact)
            pre = topk_abs(jnp.where(valid, est, 0.0), k, recall=recall,
                           impl=impl)
            idx, est, valid = idx[pre], est[pre], valid[pre]
        cand_idx = jnp.concatenate([run_idx, idx])
        cand_vals = jnp.concatenate([run_vals, jnp.where(valid, est, 0.0)])
        cand_valid = jnp.concatenate([run_idx >= 0, valid])
        score = jnp.where(cand_valid, jnp.abs(cand_vals), -1.0)
        _, sel = jax.lax.top_k(score, k)
        return cand_idx[sel], cand_vals[sel]

    init = (jnp.full((k,), -1, dtype=jnp.int32), jnp.zeros((k,), dtype=table.dtype))
    (top_idx, top_vals), _ = jax.lax.scan(body, init, chunks)
    # entries that never filled (k > #valid coords) keep idx -1 / val 0
    return top_idx, jnp.where(top_idx >= 0, top_vals, 0.0)


def unsketch_threshold(
    spec: CSVecSpec, table: jnp.ndarray, thr: float | jnp.ndarray, max_k: int
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Heavy hitters by threshold (CSVec._findHHThr): all coordinates with
    |estimate| >= thr, as (idx[max_k], vals[max_k]) padded with idx = -1.

    Static shapes require a cap: if more than `max_k` coordinates pass the
    threshold, only the `max_k` largest are returned (they are the top-k, so
    nothing below a *kept* coordinate is dropped ahead of it). The reference
    returns a variable-length tensor instead; callers that need exactness
    must size max_k >= the expected count.
    """
    idx, vals = unsketch_topk(spec, table, max_k)
    keep = (jnp.abs(vals) >= thr) & (idx >= 0)
    return jnp.where(keep, idx, -1), jnp.where(keep, vals, 0.0)


def to_dense(d: int, idx: jnp.ndarray, vals: jnp.ndarray) -> jnp.ndarray:
    """Scatter (idx, vals) into a dense [d] vector; out-of-range entries
    (idx < 0 padding, idx >= d) contribute nothing — clip alone would fold
    an idx >= d contribution onto element d-1."""
    safe = jnp.clip(idx, 0, d - 1)
    contrib = jnp.where((idx >= 0) & (idx < d), vals, 0.0)
    return jnp.zeros((d,), dtype=vals.dtype).at[safe].add(contrib)
