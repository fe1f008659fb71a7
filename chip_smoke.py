#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py             # one TPU chip (what the driver runs)
    python chip_smoke.py --chips 4   # the sharded round on one four-chip host

One process, which therefore owns the chip: the trainer runs through
`cv_train.main` with an argv, as a user would run it — CIFAR-10 ResNet-9 at
full published width (d = 6,573,130), FetchSGD sketch mode at the flagship
layout, the default async run loop and the default fused compile, synthetic
CIFAR made from the seed. Prints one JSON object per line; any phase that
fails raises, and the process exits non-zero without the last line. The last
line of stdout is the contract line:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Default (one chip): host<->device sync check; both sketch kernels against the
pure-JAX oracle at the flagship layout; 8 training rounds with an eval every
4; then what the run compiled and how it went — sketch implementation, kernel
custom calls in the round's HLO, compile vs steady seconds, per-round loss
(finite and falling, every round committed through the run loop's deferred
drain), run-loop round-trip and in-flight depth, peak device memory, compile
cache entries.

--chips 4 runs ONLY the sharded path and what it is compared with: the same
configuration under `--mesh clients=4` and, in the same process, under
`--mesh clients=1` (one device); loss rows must agree within LOSS_RTOL, work
must really be spread over the four devices.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import statistics
import sys
import tempfile
import time
from importlib.metadata import version

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

ROUNDS, EVAL_EVERY = 8, 4
SHARDED_ROUNDS = 4
SYNC_N = 2048  # side of the sync check's matmul chain
# the flagship FetchSGD configuration (benchmark/configs/'s layout; README
# §Usage): 64 of 512 clients a round, 8 images each, r x c = 5 x 2^19, k=50k
FLAGSHIP = [
    "--dataset", "cifar10", "--mode", "sketch",
    "--num_rows", "5", "--num_cols", "524288", "--k", "50000",
    "--num_blocks", "4", "--num_workers", "64", "--local_batch_size", "8",
    "--num_clients", "512", "--data_root", os.path.join(HERE, "data"),
    # 8 rounds = one epoch of this federation: the triangular schedule's
    # rising edge, 0 -> lr_scale
    "--num_epochs", "2", "--pivot_epoch", "1", "--lr_scale", "0.2",
    "--seed", "0",
]
# kernel vs oracle, f32, unit-normal input: a bucket sums ~13 signed values
# (|table| reaches ~18), both sides fold them in slab order, so they agree to
# rounding (~1e-5); a wrong roll or sign would be O(1)
KERNEL_ATOL = 1e-3
# sharded vs one device: client_shards fixes the fp summation order, so the
# two differ by reassociation, amplified through top-k selection over rounds
LOSS_RTOL = 2e-2


def emit(**obj) -> None:
    print(json.dumps(obj), flush=True)


class CompileClock:
    """Seconds XLA spent compiling (or fetching from the persistent cache),
    and the cache's hits and misses, from jax.monitoring."""

    BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring as mon

        self.durations: list[float] = []
        self.hits = self.misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == self.BACKEND:
            self.durations.append(float(secs))

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def mark(self) -> int:
        return len(self.durations)

    def since(self, mark: int) -> dict:
        ds = self.durations[mark:]
        return {"programs": len(ds), "compile_s_total": round(sum(ds), 3),
                "compile_s_largest": round(max(ds, default=0.0), 3)}


def sync_check() -> None:
    """Does block_until_ready wait for the device? Time ONE chain of matmuls
    both ways: if block_until_ready returned at enqueue, its time would be a
    small fraction of the device_get time."""
    from commefficient_tpu.runner.loop import measure_rtt_ms

    def chain(x):
        def body(c, _):
            return c @ c / jnp.maximum(jnp.abs(c).max(), 1.0), ()

        return jax.lax.scan(body, x, None, length=200)[0]

    full, scalar = jax.jit(chain), jax.jit(lambda x: chain(x)[0, 0])
    x = jnp.ones((SYNC_N, SYNC_N), jnp.float32)
    jax.block_until_ready(full(x))  # compile + warm, both programs
    jax.device_get(scalar(x))

    def median_ms(sync):
        ts = []
        for _ in range(3):
            t0 = time.perf_counter()
            sync()
            ts.append((time.perf_counter() - t0) * 1e3)
        return statistics.median(ts)

    bur_ms = median_ms(lambda: jax.block_until_ready(full(x)))
    get_ms = median_ms(lambda: jax.device_get(scalar(x)))
    emit(phase="sync", chain=f"200 x ({SYNC_N}x{SYNC_N} f32 matmul)",
         block_until_ready_ms=round(bur_ms, 3), device_get_scalar_ms=round(get_ms, 3),
         trivial_jit_round_trip_ms=round(measure_rtt_ms(), 4))
    if bur_ms < 0.5 * get_ms:
        raise SystemExit("block_until_ready returned long before the device "
                         "finished: timings that rely on it are wrong here")


def kernels_vs_oracle(d: int, want_impl: str) -> None:
    """Both kernels, standalone, at the flagship layout, against the pure-JAX
    oracle on the same inputs."""
    from commefficient_tpu.sketch import csvec
    from commefficient_tpu.sketch.csvec import CSVecSpec

    spec = CSVecSpec(d=d, c=524_288, r=5, num_blocks=4, seed=42, family="rotation")
    impl = csvec.sketch_impl(spec)
    if impl[0] != want_impl:
        raise SystemExit(f"standalone kernels: expected {want_impl!r}, got {impl}")
    v = jax.random.normal(jax.random.PRNGKey(0), (d,), jnp.float32)
    t0 = time.perf_counter()
    table = jax.block_until_ready(jax.jit(lambda x: csvec.sketch_vec(spec, x))(v))
    est = jax.block_until_ready(jax.jit(lambda t: csvec.query_all(spec, t))(table))
    kernel_s = time.perf_counter() - t0
    table_ref = jax.jit(lambda x: csvec._sketch_vec_rotation(spec, x))(v)
    est_ref = jax.jit(lambda t: csvec._query_all_rotation(spec, t))(table)
    acc_err = float(jnp.abs(table - table_ref).max())
    qry_err = float(jnp.abs(est - est_ref).max())
    emit(phase="kernels_vs_oracle", impl=impl[0], d=d, c=spec.c, r=spec.r,
         accumulate_max_abs_err=acc_err, query_max_abs_err=qry_err,
         table_abs_max=float(jnp.abs(table_ref).max()), atol=KERNEL_ATOL,
         first_call_s=round(kernel_s, 3))
    if not (acc_err <= KERNEL_ATOL and qry_err <= KERNEL_ATOL):
        raise SystemExit("kernel and oracle disagree beyond the tolerance")


def train(argv: list[str], rounds: int, eval_every: int, clock: CompileClock,
          workdir: str, tag: str):
    """Run the trainer through cv_train's own entry point; return the session,
    the per-round losses the ledger recorded at commit, and the eval rows."""
    import cv_train
    from commefficient_tpu.obs import registry as obreg

    ledger = os.path.join(workdir, f"{tag}.ledger.jsonl")
    rows = os.path.join(workdir, f"{tag}.rows.jsonl")
    reg = obreg.default()
    mark, cmark = reg.mark(), clock.mark()
    t0 = time.perf_counter()
    session = cv_train.main(argv + [
        "--num_rounds", str(rounds), "--eval_every", str(eval_every),
        "--ledger", ledger, "--log_jsonl", rows])
    wall_s = time.perf_counter() - t0

    recs = [json.loads(line) for line in open(ledger)]
    losses = [r["metrics"]["loss_sum"] / r["metrics"]["count"]
              for r in recs if r.get("kind") == "round"]
    evals = [json.loads(line) for line in open(rows)]
    committed = int(mark.delta("runner_rounds_total"))
    drains = int(mark.delta("runner_drains_total"))
    emit(phase="train", tag=tag, rounds=rounds, rounds_committed=committed,
         drains=drains, wall_s=round(wall_s, 3), **clock.since(cmark),
         rtt_ms=round(reg.gauge("runner_rtt_ms").value, 4),
         max_inflight=int(reg.gauge("runner_max_inflight").value),
         train_loss_per_round=[round(x, 5) for x in losses],
         evals=[{k: r[k] for k in ("round", "train_loss", "test_loss", "test_acc")}
                for r in evals])
    if not (committed == rounds == len(losses) and session.round == rounds):
        raise SystemExit(f"{tag}: {rounds} rounds asked, {committed} committed, "
                         f"{len(losses)} in the ledger")
    if drains >= rounds:
        raise SystemExit(f"{tag}: {drains} drains for {rounds} rounds — the "
                         "deferred drain never held more than one round")
    if not all(math.isfinite(x) for x in losses):
        raise SystemExit(f"{tag}: non-finite training loss {losses}")
    if not evals or not all(math.isfinite(r["test_loss"]) for r in evals):
        raise SystemExit(f"{tag}: no finite eval row")
    return session, losses, evals


def compiled_round_hlo(session):
    """(optimized HLO, device batch) of the round program the session
    compiled, lowered again from the session's own jit at the shapes — and
    with the placement — the loop dispatched."""
    from commefficient_tpu.parallel import mesh as meshlib

    prep = session.prepare_round()
    batch = prep.batch
    if session.mesh is not None:
        batch = meshlib.shard_client_batch(session.mesh, batch)
    with session._mesh_ctx():
        lowered = session._step.lower(session.state, batch, {},
                                      jnp.float32(0.0), prep.sub)
    return lowered.compile().as_text(), batch


def steady_round_s(session, n: int = 5) -> list[float]:
    """Wall seconds of n more rounds, one at a time through the session's
    public run_round (prepare + dispatch + device + metrics back)."""
    out = []
    for _ in range(n):
        t0 = time.perf_counter()
        session.run_round(0.05)
        out.append(time.perf_counter() - t0)
    return out


def peak_bytes(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def one_chip(clock, workdir, want_impl="pallas") -> None:
    from commefficient_tpu import native
    from commefficient_tpu.sketch import csvec

    sync_check()
    kernels_vs_oracle(6_573_130, want_impl)

    session, losses, _ = train(FLAGSHIP, ROUNDS, EVAL_EVERY, clock, workdir, "flagship")
    d = session.cfg.mode.d
    impl = csvec.sketch_impl(session.cfg.mode.sketch_spec)
    hlo, _ = compiled_round_hlo(session)
    n_calls = hlo.count("tpu_custom_call")
    steady = steady_round_s(session)
    emit(phase="round_program", d=d, sketch_impl=impl[0], sketch_impl_why=impl[1],
         tpu_custom_calls_in_round_hlo=n_calls,
         batch_assembly="native (g++ .so)" if native.available() else "numpy fallback",
         steady_round_s_median=statistics.median(steady),
         steady_round_s=[round(x, 4) for x in steady],
         peak_bytes_in_use=peak_bytes(jax.devices()[0]))
    if d != 6_573_130:
        raise SystemExit(f"not the full-width ResNet-9: d={d}")
    if impl[0] != want_impl:
        raise SystemExit(f"the round compiled the {impl} sketch, not {want_impl!r}")
    if want_impl == "pallas" and n_calls < 2:
        raise SystemExit(f"{n_calls} kernel custom calls in the round's HLO")
    # falling: the mean of the last three rounds under the first three
    # (round 0 runs at lr 0, so single rounds are too noisy a comparison)
    head, tail = statistics.mean(losses[:3]), statistics.mean(losses[-3:])
    emit(phase="loss", first3_mean=round(head, 5), last3_mean=round(tail, 5))
    if not tail < head:
        raise SystemExit(f"training loss did not fall: {losses}")


def four_chips(clock, workdir, want_impl="pallas") -> None:
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from commefficient_tpu.parallel import mesh as meshlib
    from commefficient_tpu.sketch import csvec

    s4, loss4, _ = train(FLAGSHIP + ["--mesh", "clients=4"], SHARDED_ROUNDS,
                         SHARDED_ROUNDS, clock, workdir, "mesh4")
    s1, loss1, _ = train(FLAGSHIP + ["--mesh", "clients=1"], SHARDED_ROUNDS,
                         SHARDED_ROUNDS, clock, workdir, "one_device")
    dev = max(abs(a - b) / abs(b) for a, b in zip(loss4, loss1))
    emit(phase="sharded_vs_one_device", max_rel_dev=dev, rtol=LOSS_RTOL,
         client_shards=s4.cfg.client_shards)
    if s4.cfg.client_shards != 4 or s1.cfg.client_shards != 1:
        raise SystemExit("the sessions did not compile the programs compared")
    if not dev <= LOSS_RTOL:
        raise SystemExit(f"sharded and one-device loss rows differ: {loss4} {loss1}")

    spec = s4.cfg.mode.sketch_spec
    impl = csvec.sketch_impl(spec)
    hlo, batch = compiled_round_hlo(s4)
    batch_devs = sorted({s.device.id for s in batch["x"].addressable_shards})

    # per-device partial tables: the same kernel under the same mesh, one
    # table per device, and their ordered merge against one sketch of the sum
    mesh = s4.mesh
    x = jax.device_put(jax.random.normal(jax.random.PRNGKey(1), (4, spec.d)),
                       meshlib.client_sharding(mesh))
    partial = jax.jit(jax.shard_map(
        lambda xl: csvec.sketch_vec(spec, xl[0])[None], mesh=mesh,
        in_specs=P(meshlib.CLIENT_AXIS), out_specs=P(meshlib.CLIENT_AXIS),
        check_vma=False))(x)
    table_devs = sorted({s.device.id for s in partial.addressable_shards})
    merged = np.asarray(csvec.merge_tables(spec, partial))
    ref = np.asarray(jax.jit(lambda v: csvec._sketch_vec_rotation(spec, v))(
        jax.device_put(np.asarray(x).sum(axis=0), jax.devices()[0])))
    merge_err = float(np.abs(merged - ref).max())
    peaks = [peak_bytes(d) for d in jax.devices()]
    emit(phase="spread", sketch_impl=impl[0],
         tpu_custom_calls_in_round_hlo=hlo.count("tpu_custom_call"),
         all_gathers_in_round_hlo=hlo.count("all-gather"),
         cohort_batch_shard_devices=batch_devs, partial_table_shard_devices=table_devs,
         merged_partials_vs_sketch_of_sum_max_abs_err=merge_err, atol=4 * KERNEL_ATOL,
         peak_bytes_in_use=peaks)
    if impl[0] != want_impl:
        raise SystemExit(f"the sharded round compiled the {impl} sketch")
    if want_impl == "pallas" and hlo.count("tpu_custom_call") < 2:
        raise SystemExit("no kernel custom calls in the sharded round's HLO")
    if "all-gather" not in hlo:
        raise SystemExit("no cross-device merge in the sharded round's HLO")
    if not (len(batch_devs) == len(table_devs) == 4 and all(p > 0 for p in peaks)):
        raise SystemExit("work is not spread over the four devices")
    if not merge_err <= 4 * KERNEL_ATOL:
        raise SystemExit("merged per-device tables disagree with the sketch of the sum")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    from commefficient_tpu.utils.compile_cache import ensure_compile_cache

    cache_dir = ensure_compile_cache()
    devices = jax.devices()
    dev0 = devices[0]
    if dev0.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (JAX sees {dev0.platform}:"
                         f"{dev0.device_kind}); this script proves nothing off the chip")
    if args.chips == 4 and len(devices) != 4:
        raise SystemExit(f"--chips 4 needs four devices, JAX sees {len(devices)}")
    emit(phase="start", jax=jax.__version__, jaxlib=version("jaxlib"),
         libtpu=version("libtpu"),
         device_kind=dev0.device_kind, devices=len(devices), chips=args.chips,
         compile_cache_dir=cache_dir,
         compile_cache_entries_at_start=(
             len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0))

    clock = CompileClock()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
        (four_chips if args.chips == 4 else one_chip)(clock, workdir)
    emit(phase="compile_cache", dir=cache_dir,
         entries=len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0,
         hits=clock.hits, misses=clock.misses, **clock.since(0))
    emit(ok=True, device={"platform": dev0.platform, "kind": dev0.device_kind,
                          "count": len(devices)})


if __name__ == "__main__":
    main()
