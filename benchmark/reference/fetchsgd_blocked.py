"""fetchsgd.CountSketch at a d that does not fit a chip whole: the same
numbers, bit for bit, from programs that hold a block at a time
(tests/benchmark/test_bench_correct_qwen3next.py pins both).

query_all: fetchsgd's stacks the r rows' estimates as one [r, d] array and
sorts it: on a TPU that array is padded to 8 sublanes and the sort wants a
second one, 64 d bytes, which passes a v5e's 15.75 GB at d = 246M (the
compiler's own refusal at d = 424,340,544: 25.3 GB). The median of a
coordinate's r estimates depends on no other coordinate, so the same rolls,
signs and sort run over BLOCK slabs at a time.

accumulate: fetchsgd's multiplies the whole padded vector by each row's signs
before it folds the slabs, and the compiler computes the r signed copies in one
pass: 5 x 1.7 GB at d = 424M, 9.56 GB with the rest, which the runtime could
not place beside the 3.4 GB of weights and gradient (my chip run, PR 27, call
2). Here a slab is signed as it is folded, in the same order."""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from . import fetchsgd


class BlockedCountSketch(fetchsgd.CountSketch):
    BLOCK = 27  # slabs a block: 27 x 1,048,576 coordinates, 15 blocks at d = 424M

    def accumulate(self, v):
        vp = jnp.pad(v, (0, self.slabs * self.c - self.d)).reshape(self.slabs, self.c)

        def fold(acc, xs):
            s, slab, shift = xs
            idx = (s * self.c + jnp.arange(self.c)).astype(jnp.uint32)
            return jnp.stack([
                acc[j] + jnp.roll(slab * fetchsgd._signs(idx, self.sign_keys[j]).astype(v.dtype),
                                  shift[j]) for j in range(self.r)]), None

        table, _ = jax.lax.scan(fold, jnp.zeros((self.r, self.c), v.dtype),
                                (jnp.arange(self.slabs), vp, jnp.asarray(self.shifts.T)))
        return table

    def query_all(self, table):
        B = self.BLOCK
        blocks = -(-self.slabs // B)
        shifts = np.zeros((self.r, blocks * B), np.int64)
        shifts[:, : self.slabs] = self.shifts
        shifts = jnp.asarray(shifts.reshape(self.r, blocks, B))

        def block(b):
            slab = b * B + jnp.arange(B)
            idx = (slab[:, None] * self.c + jnp.arange(self.c)[None, :]).astype(jnp.uint32)
            per_row = []
            for j in range(self.r):
                unrolled = jax.vmap(lambda s, t=table[j]: jnp.roll(t, -s))(shifts[j, b])
                per_row.append(unrolled * fetchsgd._signs(idx, self.sign_keys[j]).astype(table.dtype))
            return jnp.sort(jnp.stack(per_row), axis=0)[(self.r - 1) // 2].reshape(-1)

        return jax.lax.map(block, jnp.arange(blocks)).reshape(-1)[: self.d]
