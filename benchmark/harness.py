"""One run of one cell: set-up, the timed window over run_loop, the trace,
the reference and the comparison. `run.py` is the command line around it."""

from __future__ import annotations

import importlib
import json
import math
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WARM_ROUNDS = 8
TRACED_ROUNDS = 10

def load_manifest(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_cell(manifest: dict, workload: str, root: str = ROOT) -> dict:
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r}; BENCHMARK.json has {sorted(cells)}")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    with open(os.path.join(root, cfg_entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return {"entry": cell, "config": config, "traffic": traffic}


class Context:
    """What a per-layer metric's reader is given."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


class _FirstRounds:
    """A commit sink (the session's ledger hook) that keeps the cohort and
    the metrics of each round committed while it is attached."""

    def __init__(self):
        self.rounds = []

    def append_round(self, rnd, cohort=None, metrics=None, **_):
        self.rounds.append((rnd, [int(i) for i in cohort], dict(metrics)))


def _registry_snapshot() -> dict:
    """Every counter's value and every histogram's cumulative sum and count
    in the program's registry, so that a new reader needs no edit here."""
    from commefficient_tpu.obs import registry as obreg

    reg, snap = obreg.default(), {}
    for name, shown in reg.snapshot().items():
        if isinstance(shown, dict) and "p50" in shown:
            h = reg.histogram(name)
            snap[name] = {"sum": h.sum, "count": h.count}
        elif not isinstance(shown, dict):
            snap[name] = reg.counter(name).value
    return snap


def _delta(after: dict, before: dict) -> dict:
    """What the window added; a metric first seen inside it started at 0."""
    out = {}
    for k, v in after.items():
        if isinstance(v, dict):
            was = before.get(k, {})
            out[k] = {f: v[f] - was.get(f, 0) for f in v}
        else:
            out[k] = v - before.get(k, 0)
    return out


def _host_state(session) -> dict:
    import jax

    st = session.state
    return jax.device_get({"params": st["params"],
                           "Vvelocity": st["mode_state"]["Vvelocity"],
                           "Verror": st["mode_state"]["Verror"]})


def _segment(cell, upto: int, runner_kwargs=None):
    """run_loop from the session's round to `upto`, no eval, no logging: the
    call the window makes."""
    import jax
    from commefficient_tpu.runner import RunnerConfig, run_loop

    cfg = RunnerConfig.from_args(cell.args, upto, eval_every=1 << 30)
    for k, v in (runner_kwargs or {}).items():
        setattr(cfg, k, v)
    t0 = time.perf_counter()
    stats = run_loop(cell.session, cell.opt, cfg)
    jax.block_until_ready(cell.session.state)
    return stats, time.perf_counter() - t0


def _round_program(cell):
    """(optimized HLO text, bytes the compiled round program needs) of the
    session's own jit at the shapes the loop dispatches (chip_smoke.py's
    compiled_round_hlo)."""
    import jax.numpy as jnp

    s = cell.session
    prep = s.prepare_round(s.round)
    # prepare_round drew from the live streams; put them back
    with s.mutate_lock:
        s.rng.set_state(s.rng_snapshot[0])
        s._rng_key = s.rng_snapshot[1]
    compiled = s._step.lower(s.state, prep.batch, {}, jnp.float32(0.0), prep.sub).compile()
    ma = compiled.memory_analysis()
    need = None
    if ma is not None:
        need = (ma.temp_size_in_bytes + ma.argument_size_in_bytes
                + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    return compiled.as_text(), need


def _free(cell):
    """Drop the program's device state before the reference runs."""
    import gc

    import jax

    s = cell.session
    for leaf in jax.tree.leaves((s.state, s.client_state)):
        if hasattr(leaf, "delete"):
            try:
                leaf.delete()
            except RuntimeError:
                pass
    cell.session = None
    gc.collect()
    jax.clear_caches()


def run_reference(cell, cohorts, lrs, **kw):
    from benchmark import federation
    from benchmark.reference import rounds

    batches = [cell.to_reference_batch(federation.cohort_rows(cell.federation, ids))
               for ids in cohorts]
    return rounds.follow(cell.client_loss, cell.params0, batches, lrs, cell.recipe,
                         cell.reference_block, **kw)


def first_rounds(cell, took=lambda what: None):
    """The first three rounds, through the window's own call, on the session
    the window will drive. Returns the commit sink that saw them and the
    host copies of the state after rounds 0, 1 and 3."""
    session = cell.session
    first = _FirstRounds()
    session.ledger = first
    snaps = {0: _host_state(session)}
    took("state read back")
    _segment(cell, 1)
    took("round 1")
    snaps[1] = _host_state(session)
    _segment(cell, 3)
    took("rounds 2 and 3")
    snaps[3] = _host_state(session)
    session.ledger = None
    return first, snaps


def compare(cell, first, snaps, *, control: bool = False, precision: str | None = None,
            details: dict | None = None) -> dict:
    """Free the program's state, follow the same three rounds plainly, and
    return the numbers compared (check.readings). With `control`, the
    reference computed in bfloat16 throughout stands in the program's place
    (on the cohorts the program drew)."""
    import jax.numpy as jnp

    from benchmark import check

    program = {"losses": [m["loss_sum"] / m["count"] for _, _, m in first.rounds],
               "snaps": snaps, "lr1": cell.lr_at(cell.start_position)}
    cohorts = [ids for _, ids, _ in first.rounds]
    lrs = [cell.lr_at(cell.start_position + t) for t in range(len(cohorts))]
    _free(cell)
    ref = run_reference(cell, cohorts, lrs, precision=precision)
    if control:
        low = run_reference(cell, cohorts, lrs, dtype=jnp.bfloat16)
        program = {"losses": low["losses"], "lr1": program["lr1"],
                   "snaps": {0: {"params": cell.params0}, **low["snaps"]}}
    return check.readings(cell.recipe, program, ref, details)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, manifest: dict | None = None,
             loaded: dict | None = None, limits: dict | None = None,
             fault: str | None = None, extra_argv=(), t_process: float | None = None,
             warm_rounds: int = WARM_ROUNDS, min_rounds: int = 4, control: bool = False,
             log=lambda *a: print(*a, file=sys.stderr, flush=True)) -> dict:
    """Returns the result line's object. Raises SystemExit where the contract
    says the run exits non-zero with no result. `require_tpu=False`, `fault`,
    `control` and the round counts are for the tests; run.py passes none."""
    t_process = time.perf_counter() if t_process is None else t_process
    manifest = manifest or load_manifest()
    loaded = loaded or load_cell(manifest, workload)
    entry, config, traffic = loaded["entry"], loaded["config"], loaded["traffic"]

    import jax

    from benchmark import check, counting, trace_reduce
    from benchmark.compile_clock import CompileClock

    if require_tpu:
        # keep every program, however quickly it compiled, so that only a
        # checkout's first run of a cell compiles
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        from commefficient_tpu.utils.compile_cache import ensure_compile_cache

        log(f"compile cache: {ensure_compile_cache()}")
    devices = jax.devices()
    dev0 = devices[0]
    if require_tpu and (dev0.platform != "tpu" or len(devices) < entry["chips"]):
        raise SystemExit(f"benchmark: cell {workload} needs {entry['chips']} TPU chip(s); "
                         f"JAX sees {len(devices)} x {dev0.platform}:{dev0.device_kind}")
    peaks = counting.peaks(dev0.device_kind) if require_tpu else None
    clock = CompileClock()

    lap = [time.perf_counter()]

    def took(what):
        now = time.perf_counter()
        log(f"set-up: {what} {now - lap[0]:.2f} s ({clock.since(0)['programs']} programs, "
            f"{clock.since(0)['compile_s_total']:.1f} s compiling or reading the cache, "
            f"{clock.hits} hits {clock.misses} misses)")
        lap[0] = now

    log(f"set-up: jax and the device {lap[0] - t_process:.2f} s")
    importlib.import_module("commefficient_tpu.runner")
    took("importing the run loop (it imports orbax for checkpoints; PERF.md, Open questions)")
    builder = importlib.import_module("benchmark.builders." + config["builder"])
    cell = builder.build(config, traffic, seed, extra_argv=extra_argv)
    took("federation, weights, session")
    session, W = cell.session, cell.cohort
    log(f"cell {workload}: d={cell.facts['d']:,} mode={cell.facts['mode']} W={W} "
        f"sketch={cell.facts.get('sketch_line')} dtype={cell.facts['dtype']}")
    if fault:
        from benchmark import faults

        faults.FAULTS[fault](cell)

    program_bytes = None
    if require_tpu:
        hlo, program_bytes = _round_program(cell)
        calls = hlo.count("tpu_custom_call")
        log(f"round program: {calls} tpu_custom_call, needs {program_bytes} bytes")
        if cell.facts["mode"] == "sketch" and calls < 2:
            raise SystemExit(f"benchmark: {calls} kernel custom calls in the round's HLO; "
                             "a sketch cell times the two Pallas kernels")
        del hlo
        took("round program lowered, compiled and read")

    first, snaps = first_rounds(cell, took)
    took("state read back")
    # warm rounds: the loop tunes its own depth; the window keeps what it chose
    stats, warm_s = _segment(cell, 3 + warm_rounds)
    round_s = warm_s / warm_rounds
    pinned = {"max_inflight": max(int(stats.max_inflight_used), 1), "prefetch_depth": 2}
    n_rounds = max(int(math.ceil(seconds / round_s)), min_rounds)
    first_round = session.round
    took("warm rounds")
    log(f"warm: {1e3 * round_s:.2f} ms/round over {warm_rounds}, depth "
        f"{pinned['max_inflight']}, rtt {stats.rtt_ms:.3f} ms; window {n_rounds} rounds")

    # the window. A traced run ends it with a second run_loop segment of
    # TRACED_ROUNDS + 2 rounds under the profiler: the host counters, the
    # window's time and round_mfu come from the first, untraced segment, so
    # that the profiler's own stalls are in none of them.
    traced_tail = TRACED_ROUNDS + 2 if trace else 0
    before, cmark = _registry_snapshot(), clock.mark()
    setup_s = time.perf_counter() - t_process
    t0 = time.perf_counter()
    _segment(cell, first_round + n_rounds, pinned)
    window_s = time.perf_counter() - t0
    reg = _delta(_registry_snapshot(), before)
    profile_dir = None
    if trace:
        profile_dir = tempfile.mkdtemp(prefix="bench_trace_")
        lo = first_round + n_rounds + 1
        _segment(cell, first_round + n_rounds + traced_tail,
                 dict(pinned, profile_rounds=f"{lo}:{lo + TRACED_ROUNDS - 1}",
                      profile_dir=profile_dir))
    compiled_in_window = clock.since(cmark)["programs"]
    if compiled_in_window:
        raise SystemExit(f"benchmark: {compiled_in_window} program(s) compiled inside "
                         "the measured window; warm them up in set-up")

    rounds_done = int(reg.get("runner_rounds_total", 0))
    attempted = rounds_done * W
    failed = int(reg.get("cohort_clients_dropped_total", 0)
                 + reg.get("cohort_clients_quarantined_total", 0)
                 + W * reg.get("runner_nonfinite_rounds_total", 0))
    mem = [d.memory_stats() or {} for d in devices[: entry["chips"]]]
    log(f"memory_stats: {json.dumps(mem[0])}")
    # on this runtime peak_bytes_in_use counts live arrays and
    # peak_bytes_reserved what the programs reserve for their temporaries, in
    # a region of its own (PERF.md section 6): the peak is their sum
    peak = max(int(m.get("peak_bytes_in_use", 0)) + int(m.get("peak_bytes_reserved", 0))
               for m in mem)
    device = {"platform": dev0.platform, "kind": dev0.device_kind, "count": len(devices),
              "memory_peak_bytes": peak, "round_program_bytes": program_bytes}

    metrics = {}
    breakdown = None
    if not trace:
        metrics["client_updates_per_s"] = {
            "value": (attempted - failed) / window_s, "unit": "updates/s"}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    else:
        red = None
        try:
            red = trace_reduce.reduce_planes(trace_reduce.load(
                trace_reduce.find_xplane(profile_dir)))
        finally:
            shutil.rmtree(profile_dir, ignore_errors=True)
        main = red.main_module()
        traced_rounds = len(red.modules.get(main, [])) if main else 0
        device["busy_s"], device["window_s"] = red.busy_s, red.window_s
        ctx = Context(registry=reg, rounds=rounds_done, window_s=window_s, trace=red,
                      traced_rounds=traced_rounds, facts=cell.facts, peaks=peaks,
                      chips=entry["chips"], config=config, traffic=traffic)
        for m in manifest["per_layer"]:
            if "workloads" in m and workload not in m["workloads"]:
                continue
            value = importlib.import_module("benchmark.layer_metrics." + m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        top = sorted(red.op_self_s.items(), key=lambda kv: -kv[1])[:10]
        breakdown = {"device_ops": [[red.label(n), s] for n, s in top],
                     "idle_gaps": [[n, s] for n, s in red.gaps[:10]],
                     "traced_rounds": traced_rounds, "round_program": main}

    # the reference, once the window has closed and the program's state is freed
    t_ref = time.perf_counter()
    values = compare(cell, first, snaps, control=control)
    ref_s = time.perf_counter() - t_ref
    limits = check.load_limits(workload) if limits is None else limits
    correct, compared = check.verdict(values, limits)
    if (len(first.rounds) != 3 or rounds_done != n_rounds
            or session.round != first_round + n_rounds + traced_tail):
        correct = False
    log(f"reference + comparison: {ref_s:.2f} s; window {window_s:.3f} s, "
        f"{rounds_done} rounds, {failed} of {attempted} failed")
    for name, value in values.items():
        if name not in compared:
            log(f"read, not compared: {name} {value:.6g}")
    for name, c in compared.items():
        log(f"compared {name}: {c['value']:.6g} (limit {c['limit']})")

    result = {"correct": bool(correct), "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["window"] = {"seconds": window_s, "rounds": rounds_done, "round_ms_warm": 1e3 * round_s,
                        "max_inflight": pinned["max_inflight"], "reference_s": ref_s}
    result["compared"] = compared
    return result
