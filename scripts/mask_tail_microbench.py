"""Device time of FetchSGD's sketch-space masking tail (`csvec.mask_transmitted`)
alone on the chip, against the scalar form it replaced.

    python scripts/mask_tail_microbench.py [d:c,...] [tag]

Forms, at r = 5 and k = 50,000 (default cells 6573130:524288 and
124443648:1048576, the ResNet-9 and GPT-2 sketch cells' d and c; any c
runs, 500000 is the trainers' default):

- `scalars`: `sketch_sparse` / `query` / `sketch_sparse` composed, a
  gather and two scatters of r * k scalars (the tail until it went by rows);
- `packed`: what `mask_transmitted` runs: V queried by 128-lane rows a
  table row at a time, the updates sorted once, V's and E's contributions
  in lanes 0-63 / 64-127 of one row, one scatter.

V, E and the values are normal, the k indices distinct and uniform in
[0, d), two of them -1 and one d (the padding the tail ignores). Each form
is jitted, checked bit for bit against `scalars` (and, where it differs, by
its largest gap), run six times inside one profiler capture, and timed by
the capture's own "XLA Modules" events: a device time, no host clock in it,
beside the compiled program's temporary bytes and its largest "XLA Ops".
Writes chiprun_out/mask_tail_<tag>.json. Chip only: on another backend it
checks equality and prints no time (`--rehearse`), or exits 2. PERF.md
section 6 has the table this produced.
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

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from topk_select_microbench import RUNS, _device_ms  # noqa: E402

R, K = 5, 50_000


def _scalars(spec, V, E, idx, vals):
    E = E - csvec.sketch_sparse(spec, idx, vals)
    return V - csvec.sketch_sparse(spec, idx, csvec.query(spec, V, idx)), E


FORMS = {"scalars": _scalars, "packed": csvec.mask_transmitted}


def _named(name, form, spec):
    def tail(V, E, idx, vals):
        return form(spec, V, E, idx, vals)

    tail.__name__ = name
    return jax.jit(tail)


def _inputs(d, c):
    rng = np.random.default_rng(d % 1000 + c)
    idx = np.concatenate([rng.choice(d, K - 3, replace=False), [-1, d, -1]])
    return (jnp.asarray(rng.standard_normal((R, c), np.float32)),
            jnp.asarray(rng.standard_normal((R, c), np.float32)),
            jnp.asarray(idx.astype(np.int32)),
            jnp.asarray(rng.standard_normal(K, np.float32)))


def _top_ops(trace_dir, names, top=6):
    """the largest operations of each jitted module: ms an execution, by the
    capture's "XLA Ops" events that start inside the module's events."""
    path = sorted(glob.glob(trace_dir + "/plugins/profile/*/*.xplane.pb"))[-1]
    mods, ops = [], []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if line.name == "XLA Modules":
                    name = ev.name.split("(")[0].replace("jit_", "")
                    if name in names:
                        mods.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                     name))
                elif line.name == "XLA Ops":
                    ops.append((ev.start_ns, ev.duration_ns, ev.name))
    runs = collections.Counter(m for _, _, m in mods)
    out = collections.defaultdict(collections.Counter)
    for start, dur, op in ops:
        for lo, hi, m in mods:
            if lo <= start < hi:
                out[m][op] += dur / 1e6 / runs[m]
    return {m: [(op, round(ms, 4)) for op, ms in c.most_common(top)]
            for m, c in out.items()}


def _time(fns, args):
    """device ms of each jitted function on args, RUNS executions in one
    capture, and each one's largest operations."""
    with tempfile.TemporaryDirectory() as trace_dir:
        jax.profiler.start_trace(trace_dir)
        for fn in fns.values():
            for _ in range(RUNS):
                out = fn(*args)
            jax.block_until_ready(out)
        jax.profiler.stop_trace()
        try:
            top = _top_ops(trace_dir, set(fns))
        except Exception as e:  # the times stand without the breakdown
            top = {"error": repr(e)}
        return _device_ms(trace_dir, set(fns)), top


def cell(d, c, device):
    spec = csvec.CSVecSpec(d=d, c=c, r=R, family="rotation")
    fns = {name: _named(name, form, spec) for name, form in FORMS.items()}
    args = _inputs(d, c)
    outs = {name: [np.asarray(a) for a in fn(*args)] for name, fn in fns.items()}
    want = outs["scalars"]
    res = {"equal": {}, "max_gap": {}}
    for name, got in outs.items():
        res["equal"][name] = all(
            np.array_equal(g.view(np.int32), w.view(np.int32))
            for g, w in zip(got, want))
        res["max_gap"][name] = max(float(np.abs(g - w).max())
                                   for g, w in zip(got, want))
    res["temp_mb"] = {
        name: fn.lower(*args).compile().memory_analysis().temp_size_in_bytes / 1e6
        for name, fn in fns.items()}
    if device.platform == "tpu":
        res["device_ms"], res["top_ops"] = _time(fns, args)
    return res


def main(argv):
    rehearse = "--rehearse" in argv
    argv = [a for a in argv if a != "--rehearse"]
    cells = argv[0] if argv else "6573130:524288,124443648:1048576"
    sizes = [tuple(int(v) for v in s.split(":")) for s in cells.split(",")]
    tag = argv[1] if len(argv) > 1 else "run"
    device = jax.devices()[0]
    print(f"device {device.platform} {device.device_kind}", flush=True)
    if device.platform != "tpu" and not rehearse:
        print("not a TPU: a device time comes only from the chip", file=sys.stderr)
        return 2
    results = {"device": f"{device.platform} {device.device_kind}",
               "r": R, "k": K, "cells": {}}
    for d, c in sizes:
        res = cell(d, c, device)
        results["cells"][f"{d}:{c}"] = res
        print(f"d={d} c={c} {json.dumps(res)}", flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(f"chiprun_out/mask_tail_{tag}.json", "w") as f:
        json.dump(results, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
