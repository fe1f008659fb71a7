#!/usr/bin/env python3
"""Readings for the limits of `correct`, many seeds in one process:

    python3 benchmark/calibrate.py --workload <cell> --seeds 1 2 3 \
        [--control] [--fault half_batch] [--out chiprun_out/readings.jsonl]

For each seed: build the cell's session, drive its first three rounds through
run_loop, free it, follow the same rounds with the plain reference, and print
the numbers check.py compares (one JSON line a seed). --control reference_bf16 puts the
reference, computed in bfloat16 throughout, in the program's place;
--control program_bf16 builds the trainer's own --dtype bfloat16 path;
--fault plants one of faults.py's. The benchmark's own runs never call this;
PERF.md section 2 gives the readings the committed limits were set from.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if sys.path and os.path.abspath(sys.path[0]) == HERE:
    sys.path.pop(0)
sys.path.insert(0, ROOT)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", choices=("reference_bf16", "program_bf16"), default=None)
    ap.add_argument("--precision", default=None,
                    help="matmul precision of the reference (default: the backend's)")
    ap.add_argument("--details", default=None, help="file for the per-leaf norms")
    ap.add_argument("--fault", default=None)
    ap.add_argument("--out", default=None)
    a = ap.parse_args(argv)

    import importlib

    import jax

    from benchmark import faults, harness

    if jax.devices()[0].platform == "tpu":
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        from commefficient_tpu.utils.compile_cache import ensure_compile_cache

        ensure_compile_cache()
    loaded = harness.load_cell(harness.load_manifest(), a.workload)
    builder = importlib.import_module("benchmark.builders." + loaded["config"]["builder"])
    extra = ["--dtype", "bfloat16"] if a.control == "program_bf16" else []
    for seed in a.seeds:
        t0 = time.perf_counter()
        cell = builder.build(loaded["config"], loaded["traffic"], seed, extra_argv=extra)
        if a.fault:
            faults.FAULTS[a.fault](cell)
        first, snaps = harness.first_rounds(cell)
        details = {} if a.details else None
        values = harness.compare(cell, first, snaps, control=a.control == "reference_bf16",
                                 precision=a.precision, details=details)
        if a.details:
            with open(a.details, "a") as f:
                f.write(json.dumps({"workload": a.workload, "seed": seed, "control": a.control,
                                    "fault": a.fault, "precision": a.precision, **details}) + "\n")
        line = json.dumps({"workload": a.workload, "seed": seed, "control": a.control,
                           "fault": a.fault, "precision": a.precision,
                           "device": jax.devices()[0].device_kind,
                           "seconds": time.perf_counter() - t0, **values})
        print(line, flush=True)
        if a.out:
            os.makedirs(os.path.dirname(os.path.abspath(a.out)), exist_ok=True)
            with open(a.out, "a") as f:
                f.write(line + "\n")


if __name__ == "__main__":
    main()
