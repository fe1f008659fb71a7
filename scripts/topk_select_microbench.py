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


def main(argv):
    rehearse = "--rehearse" in argv
    argv = [a for a in argv if a != "--rehearse"]
    sizes = [tuple(int(v) for v in s.split(":")) for s in argv[0].split(",")]
    tag = argv[1] if len(argv) > 1 else "run"
    device = jax.devices()[0]
    print(f"device {device.platform} {device.device_kind}", flush=True)
    if device.platform != "tpu" and not rehearse:
        print("not a TPU: a device time comes only from the chip", file=sys.stderr)
        return 2
    results = {"device": f"{device.platform} {device.device_kind}", "cells": {}}
    for n, k in sizes:
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
            x = jnp.asarray(inputs["normal"])
            with tempfile.TemporaryDirectory() as trace_dir:
                jax.profiler.start_trace(trace_dir)
                for fn in fns.values():
                    for _ in range(RUNS):
                        out = fn(x)
                    out.block_until_ready()
                jax.profiler.stop_trace()
                cell["device_ms"] = _device_ms(trace_dir, set(fns))
        results["cells"][f"{n}:{k}"] = cell
        print(f"n={n} k={k} {json.dumps(cell)}", flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(f"chiprun_out/topk_select_{tag}.json", "w") as f:
        json.dump(results, f, indent=1)
    bad = [c for c in results["cells"].values() if not all(c["equal"].values())]
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
