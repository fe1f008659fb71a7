"""Device time of the delta rule's chunk inverses, (I + A)^{-1}, alone on the chip:
XLA's batched triangular solve against the Pallas kernel of
`ops/gated_delta.py`, at the shape the Qwen3-Next cell runs.

    python scripts/delta_inverse_microbench.py [blocks] [tag]

A is made on the device from a seed as the rule makes it: chunks of 64
L2-normalised keys of 128 (sharing a common direction of weight 3, so that
the inverse is not near I), beta in (0, 1), log decays in (-0.05, 0); shape
[2, 1, 32, 32, 64, 64], one vmap over two clients as the round program calls
it (`--client_chunk 2`: 2 x 32 heads x 32 chunks = 2,048 matrices a call).
Both forms are checked against numpy's float64 inverse (worst gap over
max |X| of its matrix), run six times inside one profiler capture, and timed
by the capture's own "XLA Modules" events: a device time, no host clock in
it. `blocks` (default 128,256) are the matrices a grid step of the kernel.
Writes chiprun_out/delta_inverse_<tag>.json. Chip only: on another backend
it checks the kernel in interpret mode and prints no time (`--rehearse`), or
exits 2.
"""
import json
import os
import sys

sys.path.insert(0, os.getcwd())

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from commefficient_tpu.ops import gated_delta as gd  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from topk_select_microbench import _time  # noqa: E402

SHAPE = (2, 1, 32, 32, 64)  # clients, batch, heads, chunks, chunk


def chunk_matrices(seed: int, common: float = 3.0):
    """A [*SHAPE, 64] as `chunk_gated_delta_rule` makes it."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    k = jax.random.normal(ks[0], SHAPE + (128,)) + common * jax.random.normal(
        ks[1], SHAPE[:-1] + (1, 128))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    beta = jax.random.uniform(ks[2], SHAPE)
    G = jnp.cumsum(jax.random.uniform(ks[3], SHAPE, minval=-0.05, maxval=0.0), -1)
    D = jnp.exp(jnp.minimum(G[..., :, None] - G[..., None, :], 0.0))
    C = SHAPE[-1]
    return jnp.where(jnp.tril(jnp.ones((C, C), bool), -1),
                     jnp.einsum("...id,...jd->...ij", k * beta[..., None], k) * D, 0.0)


def _named(name, fn):
    """jit of vmap(fn): the capture names its module jit_<name>, which is how
    `_time` finds it."""
    def one(a):
        return jax.vmap(fn)(a)

    one.__name__ = name
    return jax.jit(one)


def worst_gap(X, A):
    """max over matrices of max |X - inv(I + A)| / max |inv(I + A)|, in float64."""
    C = A.shape[-1]
    want = np.linalg.inv(np.eye(C) + np.asarray(A, np.float64).reshape(-1, C, C))
    got = np.asarray(X, np.float64).reshape(-1, C, C)
    return float((np.abs(got - want).max((1, 2)) / np.abs(want).max((1, 2))).max())


def main(argv):
    rehearse = "--rehearse" in argv
    argv = [a for a in argv if a != "--rehearse"]
    blocks = [int(b) for b in argv[0].split(",")] if argv else [128, 256]
    tag = argv[1] if len(argv) > 1 else "run"
    device = jax.devices()[0]
    print(f"device {device.platform} {device.device_kind}", flush=True)
    on_chip = device.platform == "tpu"
    if not on_chip and not rehearse:
        print("not a TPU: a device time comes only from the chip", file=sys.stderr)
        return 2
    fns = {"solve": _named("solve", gd.solve_inverse)}
    for b in blocks:
        fns[f"kernel_{b}"] = _named(f"kernel_{b}", lambda a, b=b: gd.kernel_inverse(
            a, block=b, interpret=not on_chip))
    A = chunk_matrices(40)
    results = {"device": f"{device.platform} {device.device_kind}",
               "shape": list(A.shape),
               "worst_gap": {name: worst_gap(fn(A), A) for name, fn in fns.items()}}
    if on_chip:
        results["device_ms"] = _time(fns, A)
    print(json.dumps(results), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(f"chiprun_out/delta_inverse_{tag}.json", "w") as f:
        json.dump(results, f, indent=1)
    return 0 if max(results["worst_gap"].values()) < 1e-5 else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
