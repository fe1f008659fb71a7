"""Device time of csvec.select_topk_abs against lax.top_k, alone, on the chip.

    python scripts/topk_select_microbench.py 6573130:50000,200000:50000 [tag]

For each n:k both are jitted, checked equal element for element on three
inputs (normal, eight magnitudes so that ties decide, inf/NaN/-0.0 present;
and lax.top_k itself against numpy's total order, which is where a chip
that placed a NaN elsewhere would show), run six times inside one profiler
capture, and timed by the capture's own "XLA Modules" events: a device time,
no host clock in it. Writes chiprun_out/topk_select_<tag>.json. Chip only:
on another backend it checks equality and prints no time (`--rehearse`),
or exits 2. PERF.md section 6 (PR 32) has the table this produced.

    python scripts/topk_select_microbench.py --approx 124443648:50000,... [tag]

times `csvec.topk_abs(impl="approx", recall=0.99)` against plain
`lax.approx_max_k` (which aggregates its partial maxima by a full sort of
them: what topk_abs was until PR 36) the same way, on one normal vector
made on the device from the seed n % 1000 + k; it prints the number m of
partial maxima and checks that the two index SETS are equal but where
partial maxima tie exactly at the k-th place (the selected magnitudes are
the same, and an index that one side has alone holds the smallest of them),
and lie in [0, n). PERF.md section 6 (PR 36) has that table.
"""
import collections
import glob
import json
import os
import sys
import tempfile

sys.path.insert(0, os.getcwd())

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from commefficient_tpu.sketch import csvec  # noqa: E402

RUNS = 6


def _numpy_order(x, k):
    """key descending, index ascending, keys as lax.top_k's total order has them."""
    keys = np.abs(x).view(np.int32).astype(np.int64)
    return np.lexsort((np.arange(x.size), -keys))[:k].astype(np.int32)


def _inputs(n, k):
    rng = np.random.default_rng(n % 1000 + k)
    normal = rng.standard_normal(n).astype(np.float32)
    ties = (rng.integers(1, 9, n) * rng.choice([-1, 1], n)).astype(np.float32)
    odd = normal.copy()
    odd[[5, n // 2]] = np.inf
    odd[[77, n - 3]] = np.nan
    odd[1234 % n] = -np.nan
    odd[99] = -0.0
    return {"normal": normal, "ties": ties, "nonfinite": odd}


def _device_ms(trace_dir, names):
    """mean device duration of each jitted module's executions in the capture."""
    path = sorted(glob.glob(trace_dir + "/plugins/profile/*/*.xplane.pb"))[-1]
    out = collections.defaultdict(list)
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        for line in plane.lines:
            if line.name != "XLA Modules":
                continue
            for event in line.events:
                name = event.name.split("(")[0].replace("jit_", "")
                if name in names:
                    out[name].append(event.duration_ns / 1e6)
    return {name: sum(v) / len(v) for name, v in out.items()}


def _time(fns, x):
    """device ms of each jitted function on x, RUNS executions in one capture."""
    with tempfile.TemporaryDirectory() as trace_dir:
        jax.profiler.start_trace(trace_dir)
        for fn in fns.values():
            for _ in range(RUNS):
                out = fn(x)
            out.block_until_ready()
        jax.profiler.stop_trace()
        return _device_ms(trace_dir, set(fns))


def exact_cell(n, k, device):
    def sort_all(v):
        return jax.lax.top_k(jnp.abs(v), k)[1]

    def select(v):
        return csvec.select_topk_abs(v, k)

    fns = {"sort_all": jax.jit(sort_all), "select": jax.jit(select)}
    cell = {"equal": {}, "top_k_is_numpy_order": {}}
    inputs = _inputs(n, k)
    for name, x in inputs.items():
        want = np.asarray(fns["sort_all"](jnp.asarray(x)))
        cell["top_k_is_numpy_order"][name] = bool(
            np.array_equal(want, _numpy_order(x, k)))
        cell["equal"][name] = bool(
            np.array_equal(np.asarray(fns["select"](jnp.asarray(x))), want))
    if device.platform == "tpu":
        cell["device_ms"] = _time(fns, jnp.asarray(inputs["normal"]))
    return cell


def approx_cell(n, k, device):
    def aggregated(v):
        return jax.lax.approx_max_k(jnp.abs(v), k, recall_target=0.99)[1]

    def selected(v):
        return csvec.topk_abs(v, k, impl="approx", recall=0.99)

    fns = {"aggregated": jax.jit(aggregated), "selected": jax.jit(selected)}
    # jax.random.normal alone has 2^-23 steps of the uniform it is made
    # from, 8e-5 wide at 3.4 sigma: the k largest of 1e8 would share a few
    # thousand magnitudes and tie at the k-th place for certain. A second
    # draw, scaled down, spreads them over the float32 values in between.
    key, fine = jax.random.split(jax.random.PRNGKey(n % 1000 + k))
    x = (jax.random.normal(key, (n,), jnp.float32)
         + 2.0 ** -10 * jax.random.normal(fine, (n,), jnp.float32))
    want, got = (np.sort(np.asarray(fn(x))) for fn in fns.values())
    # the two may differ only where partial maxima tie at the k-th place:
    # the same magnitudes, and every index one side has alone holds the
    # smallest of them
    def mag(idx):
        return np.abs(np.asarray(x[jnp.asarray(idx)]))

    alone = np.setxor1d(got, want)
    mag_got, mag_want = np.sort(mag(got)), np.sort(mag(want))
    cell = {"partial_maxima": csvec.approx_select_size(n, k, 0.99),
            "index_sets_equal": alone.size == 0,
            "indices_one_side_alone": int(alone.size),
            "equal": {
                "in_range": bool(0 <= got[0] and got[-1] < n
                                 and np.unique(got).size == k),
                "magnitudes": bool(np.array_equal(mag_got, mag_want)),
                "alone_only_at_the_kth": bool(
                    (mag(alone) == mag_want[0]).all())}}
    if device.platform == "tpu":
        cell["device_ms"] = _time(fns, x)
    return cell


def main(argv):
    rehearse = "--rehearse" in argv
    approx = "--approx" in argv
    argv = [a for a in argv if a not in ("--rehearse", "--approx")]
    sizes = [tuple(int(v) for v in s.split(":")) for s in argv[0].split(",")]
    tag = argv[1] if len(argv) > 1 else "run"
    device = jax.devices()[0]
    print(f"device {device.platform} {device.device_kind}", flush=True)
    if device.platform != "tpu" and not rehearse:
        print("not a TPU: a device time comes only from the chip", file=sys.stderr)
        return 2
    results = {"device": f"{device.platform} {device.device_kind}", "cells": {}}
    for n, k in sizes:
        cell = approx_cell(n, k, device) if approx else exact_cell(n, k, device)
        results["cells"][f"{n}:{k}"] = cell
        print(f"n={n} k={k} {json.dumps(cell)}", flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(f"chiprun_out/topk_select_{tag}.json", "w") as f:
        json.dump(results, f, indent=1)
    bad = [c for c in results["cells"].values() if not all(c["equal"].values())]
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
