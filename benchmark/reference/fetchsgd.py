"""FetchSGD's server side, written plainly (arXiv:2007.07682, Alg. 1).

A hash-and-sign Count Sketch over the flat parameter vector, momentum and
error feedback in sketch space, median-of-rows query, top-k, k-sparse apply;
and the dense momentum step of the uncompressed control. float32 throughout.

The hash is the deployment's: murmur3's 32-bit finaliser over uint32, per-row
keys from one integer seed, bucket of coordinate i in row j =
(i mod c + shift[j, i // c]) mod c, sign from bit 16 of fmix32(i ^ sign_key[j]).
The constants are those a deployment's clients and server share; they are
restated here, not imported.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

_C1, _C2 = 0x85EBCA6B, 0xC2B2AE35
_BUCKET_STREAM, _SIGN_STREAM = 0x9E3779B9, 0x7FEB352D
_M32 = 0xFFFFFFFF


def _fmix32_np(x: np.ndarray) -> np.ndarray:
    x = x.astype(np.uint64) & _M32
    x ^= x >> 16
    x = (x * _C1) & _M32
    x ^= x >> 13
    x = (x * _C2) & _M32
    x ^= x >> 16
    return x


def row_keys(seed: int, rows: int) -> tuple[np.ndarray, np.ndarray]:
    """(bucket_keys[r], sign_keys[r]) as uint64 arrays holding uint32 values."""
    j = np.arange(1, rows + 1, dtype=np.uint64)
    seed32 = np.uint64(seed & _M32)
    kb = _fmix32_np(((j * _BUCKET_STREAM) & _M32) ^ seed32)
    ks = _fmix32_np(((j * _SIGN_STREAM) & _M32) ^ ((seed32 * _C1 + 1) & _M32))
    return kb, ks


def slab_shifts(seed: int, rows: int, slabs: int, cols: int) -> np.ndarray:
    """shift[j, s] in [0, cols): the rotation of slab s in row j."""
    kb, _ = row_keys(seed, rows)
    s = np.arange(slabs, dtype=np.uint64)
    return (_fmix32_np(s[None, :] ^ kb[:, None]) % np.uint64(cols)).astype(np.int64)


def _fmix32(x):
    x = x.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(_C1)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(_C2)
    return x ^ (x >> 16)


def _signs(idx, sign_key):
    bit = (_fmix32(idx.astype(jnp.uint32) ^ jnp.uint32(sign_key)) >> 16) & 1
    return 1.0 - 2.0 * bit.astype(jnp.float32)


class CountSketch:
    """r x c Count Sketch of a length-d vector under one integer seed."""

    def __init__(self, d: int, rows: int, cols: int, seed: int):
        self.d, self.r, self.c = d, rows, cols
        self.slabs = -(-d // cols)
        self.shifts = slab_shifts(seed, rows, self.slabs, cols)  # host ints
        self.sign_keys = [int(k) for k in row_keys(seed, rows)[1]]

    def _all_signs(self, j):
        idx = jnp.arange(self.slabs * self.c, dtype=jnp.uint32)
        return _signs(idx, self.sign_keys[j]).reshape(self.slabs, self.c)

    def accumulate(self, v):
        """[d] -> [r, c]: table[j, bucket_j(i)] += sign_j(i) * v[i]."""
        vp = jnp.pad(v, (0, self.slabs * self.c - self.d)).reshape(self.slabs, self.c)
        rows = []
        for j in range(self.r):
            signed = vp * self._all_signs(j).astype(v.dtype)

            def fold(acc, xs):
                slab, shift = xs
                return acc + jnp.roll(slab, shift), None

            row, _ = jax.lax.scan(fold, jnp.zeros((self.c,), v.dtype),
                                  (signed, jnp.asarray(self.shifts[j])))
            rows.append(row)
        return jnp.stack(rows)

    def query_all(self, table):
        """[r, c] -> [d]: median over rows of sign_j(i) * table[j, bucket_j(i)]."""
        per_row = []
        for j in range(self.r):
            unrolled = jax.vmap(lambda s, t=table[j]: jnp.roll(t, -s))(
                jnp.asarray(self.shifts[j]))  # [slabs, c]
            per_row.append((unrolled * self._all_signs(j).astype(table.dtype)).reshape(-1)[: self.d])
        return jnp.sort(jnp.stack(per_row), axis=0)[(self.r - 1) // 2]

    def _buckets_signs(self, idx):
        shifts = jnp.asarray(self.shifts)  # [r, slabs]
        buckets = (idx[None, :] % self.c + shifts[:, idx // self.c]) % self.c
        signs = jnp.stack([_signs(idx, k) for k in self.sign_keys])
        return buckets, signs

    def query(self, table, idx):
        buckets, signs = self._buckets_signs(idx)
        per_row = signs.astype(table.dtype) * jnp.take_along_axis(table, buckets, axis=1)
        return jnp.sort(per_row, axis=0)[(self.r - 1) // 2]

    def sparse(self, idx, vals):
        """Sketch of the k-sparse vector (idx, vals)."""
        buckets, signs = self._buckets_signs(idx)
        rows = jnp.arange(self.r)[:, None]
        return jnp.zeros((self.r, self.c), vals.dtype).at[rows, buckets].add(
            signs.astype(vals.dtype) * vals[None, :])


def sketch_server_step(cs: CountSketch, k: int, rho: float, S, V, E, lr):
    """One FetchSGD server step on the round's sketch S. Returns
    (idx[k], vals[k], V', E'): params' = params - dense(idx, vals)."""
    V = rho * V + S
    E = E + lr * V
    est = cs.query_all(E)
    _, idx = jax.lax.top_k(jnp.abs(est), k)
    vals = est[idx]
    E = E - cs.sparse(idx, vals)
    V = V - cs.sparse(idx, cs.query(V, idx))
    return idx, vals, V, E


def dense_server_step(rho: float, g, V, lr):
    """The uncompressed control: momentum SGD on the dense mean gradient.
    Returns (delta[d], V')."""
    V = rho * V + g
    return lr * V, V


def triangular_lr(peak: float, pivot_epoch: float, num_epochs: float,
                  rounds_per_epoch: int, position: int) -> float:
    """0 -> peak at pivot_epoch -> 0 at num_epochs, read at a round index."""
    t = position / rounds_per_epoch
    end = max(num_epochs, pivot_epoch + 1e-6)
    if t <= 0 or t >= end:
        return 0.0
    if t <= pivot_epoch:
        return peak * t / pivot_epoch
    return peak * (end - t) / (end - pivot_epoch)
