"""fetchsgd.sketch_server_step at a d whose [d] estimate and its top-k do not
fit a chip beside the weights and the gradient: the same k coordinates and
values from programs that hold a block of slabs at a time.

fetchsgd's step queries all d coordinates into one vector, takes its absolute
values and the k largest of them: at d = 591,294,720 the compiler gives that
program 10.2 GB of temporaries beside 7.3 GB of arguments and results (the
weights, the gradient, the new weights, which reference/rounds.py passes
whole), 17.4 GB of a v5e's 16.9 (compiled for a described v5e, PR 31). The k
largest of d are among the k largest of each block, so each block of
fetchsgd_blocked's query keeps its own k candidates and the k largest of those
are taken at the end: the same set, in the same order (by value, then by
index, as jax.lax.top_k orders both), and the [d] estimate is never written.
tests/benchmark/test_bench_correct_glm4.py pins it against fetchsgd's own.

`install()` puts both into the fetchsgd module, which reference/rounds.py
reads at call time; builders/glm4_moe_lite.build calls it. Another builder of
the same process may assign `fetchsgd.CountSketch` after that
(builders/qwen3_next.py does, at import, with a class that has no
`query_topk`), so the step that is installed hands any such sketch to the step
it replaced: the same numbers whatever the order of imports and builds."""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from . import fetchsgd, fetchsgd_blocked


class TopKBlockedCountSketch(fetchsgd_blocked.BlockedCountSketch):
    def query_topk(self, table, k: int):
        """(idx [k], est[idx]) of the k coordinates whose median-of-rows
        estimate is largest in absolute value."""
        B = self.BLOCK
        blocks = -(-self.slabs // B)
        shifts = np.zeros((self.r, blocks * B), np.int64)
        shifts[:, : self.slabs] = self.shifts
        shifts = jnp.asarray(shifts.reshape(self.r, blocks, B))
        keep = min(k, B * self.c)

        def block(b):
            first = b * (B * self.c)
            idx = (first + jnp.arange(B * self.c)).astype(jnp.uint32).reshape(B, self.c)
            per_row = []
            for j in range(self.r):
                unrolled = jax.vmap(lambda s, t=table[j]: jnp.roll(t, -s))(shifts[j, b])
                per_row.append(unrolled * fetchsgd._signs(idx, self.sign_keys[j]).astype(table.dtype))
            est = jnp.sort(jnp.stack(per_row), axis=0)[(self.r - 1) // 2].reshape(-1)
            # coordinates past d are padding: they never win
            size = jnp.where(first + jnp.arange(B * self.c) < self.d, jnp.abs(est), -1.0)
            _, local = jax.lax.top_k(size, keep)
            return est[local], first + local

        vals, idx = jax.lax.map(block, jnp.arange(blocks))
        vals, idx = vals.reshape(-1), idx.reshape(-1)
        _, best = jax.lax.top_k(jnp.abs(vals), k)
        return idx[best], vals[best]


plain_step = fetchsgd.sketch_server_step  # fetchsgd.py's own, before install() replaces it


def sketch_server_step(cs, k: int, rho: float, S, V, E, lr):
    """fetchsgd.sketch_server_step, line for line, but for the query and the
    top-k taken together; fetchsgd's own for a sketch that cannot do that."""
    if not hasattr(cs, "query_topk"):
        return plain_step(cs, k, rho, S, V, E, lr)
    V = rho * V + S
    E = E + lr * V
    idx, vals = cs.query_topk(E, k)
    E = E - cs.sparse(idx, vals)
    V = V - cs.sparse(idx, cs.query(V, idx))
    return idx, vals, V, E


def install() -> None:
    fetchsgd.CountSketch = TopKBlockedCountSketch
    fetchsgd.sketch_server_step = sketch_server_step
