"""`jax.profiler` capture window: `--profile_rounds START:END`.

Whole-run profiler traces (`--profile_dir` alone) are unusable at scale —
hours of trace for a question about one steady-state round. The window
wraps WHOLE rounds instead: `start_trace` fires just before round START
dispatches, `stop_trace` after the drain that COMMITS round END, so the
capture covers complete dispatch->compute->commit cycles of the async
pipeline (starting or stopping mid-round would split in-flight work across
the capture edge and make the profile lie).

Where the profiler is unavailable (no jax, a backend without profiling
support, a second concurrent capture), the window degrades to a LOUD
no-op: one stderr line, the run continues untouched — observability must
never take down the run it observes. jax imports stay inside the start/
stop methods so this module (and the rest of obs/) is importable in a
bare, jax-free environment.

While a capture runs the window tells the global tracer (`obs.trace`), which
mirrors its spans into the capture as TraceAnnotations. After `stop_trace`
it reads the capture back (`summarize`): the device's operations, each
attributed to the round-program phase its `jax.named_scope` names, as
registry gauges and one stderr line — what a machine with no TensorBoard
can show. A second, separate reduction of the same capture goes by kind of
model block (`BLOCK_SCOPES`, the scopes a model names inside `client_grad`):
a program that names none publishes nothing there, and that is no failure.
A third summary of the same capture puts a round's launch on one clock
(`summarize_launches`): the host plane's events are read too (the program's
own annotations and the runtime's events; `load_capture`), every
execution of the round program is paired with the `runner/ready` mark of its
round, and what a queued round adds to the wall beyond its operations is
split into what the capture can place (between two module events, before the
first operation, after the last, between operations) and what it cannot
(drift: the ready stamps see it and the device line does not).
"""

from __future__ import annotations

import bisect
import functools
import glob
import os
import re
import sys
import time

from . import registry as obreg
from . import trace as obtrace

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# The stat that carries an operation's JAX op_name, scopes and all
# ("jit(step)/client_grad/vmap(cohort_reduce)/concatenate:"), on libtpu
# 0.0.34. It is a stat of the event's METADATA (XEventMetadata.stats), which
# jax.profiler.ProfileData does not show: its events list their own stats
# only (device_offset_ps, device_duration_ps). So the capture is read with
# the protobuf's own generated classes (`_xplane_pb2`).
SCOPE_STAT = "tf_op"
OTHER = "other"
# The kinds of block a model may name inside its forward pass
# (models/qwen3_next.py, glm4_moe_lite.py and lfm2_moe.py do): the second
# reduction's scopes. Backward operations carry their block's name as
# transpose(jvp(<name>)).
BLOCK_SCOPES = ("gdn", "gated_attn", "moe_route", "moe_experts", "moe_shared",
                "lm_head", "mla", "dense_mlp", "short_conv", "gqa_attn")
_WORD = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")
# What a capture records of the host (jax.profiler.ProfileOptions). JAX's
# defaults are 2 and 1. The Python tracer's frames have no reader (the loop's
# phases are annotations), so they are off. Read on a TPU v5e (PR 35, call 1:
# six captures of 12 rounds in one process a cell, in the order (host,
# python) = (2,1) (2,0) (1,0) (1,1) (2,0) (2,1); PERF.md section 6):
#   (2,1) gpt2s_sketch_w8 81.84 ms a traced round (81.8 untraced), the host's
#         dispatch 6.7-7.4 ms, capture 12.90 MB; resnet9_uncompressed_w128
#         101.6 ms as the process's first capture and 31.3 as its sixth
#         (31.0 untraced), 27.9 MB
#   (2,0) 81.84 ms, dispatch 4.8-5.0 ms, 12.60 MB; 71.4 second, 40.1 fifth,
#         26.8 MB
#   (1,0) 81.84 ms, dispatch 4.75 ms, 12.56 MB; 53.9 third, 26.7 MB
#   (1,1) 81.96 ms, dispatch 8.45 ms, 12.86 MB; 49.9 fourth, 27.8 MB
# In every one of them the device events keep their `tf_op` (645 / 255 event
# metadata) and the annotations are there. The frames cost the host 2-3.5 ms
# a dispatch and nothing the device shows; what a ResNet-9 capture costs falls
# with the capture's ordinal in its process, not with the setting: it is the
# runtime's own `Transpose` events (750,000 a capture at every level, 6 us
# each in a first capture and 0.8 us in a sixth). Level 1 keeps that flood and
# drops nine kinds of runtime event (`Linearize`, `Transpose::Execute`,
# `ReadSyncFlag`, `CompleteCallbacks`, `EnqueueContinuationProgram`, the
# allocator's two, ...) for 0.4% of the bytes, so it is no cheaper: 2 stays.
HOST_TRACER_LEVEL = 2
PYTHON_TRACER_LEVEL = 0
HOST_PLANE = "/host:CPU"
# the program's own marks in a capture (obs/trace.py mirrors them), and the
# arguments of theirs that say which round
ANNOTATIONS = ("runner/", "session/", "federated/")
ROUND_ARGS = ("round", "round_first", "rounds")
PYTHON_FRAME = "$"  # how the Python tracer names a frame
MIN_RUNTIME_NS = 10_000  # shorter runtime events explain no gap (and flood)
# libtpu 0.0.34: the stat that an "XLA Modules" event on the device and the
# host's ENQUEUE event of the same execution share
RUN_ID = "run_id"
ENQUEUE = "DoEnqueueProgram"
COMPLETE = "CompleteCallbacks"  # its id: every execution up to it is done
# the launch summary's gauges: profile_launch_<part>_ms
LAUNCH_PARTS = ("gap", "between", "head", "tail", "inside", "drift",
                "ready_lag", "call", "busy")


def parse_rounds_spec(spec: str) -> tuple[int, int] | None:
    """'START:END' (inclusive, 0-based global round indices) -> (start,
    end); None for empty. Malformed specs raise ValueError at launch — a
    typo must not surface hours later as a silently-missing capture."""
    if not spec or not spec.strip():
        return None
    head, sep, tail = spec.partition(":")
    try:
        if not sep:
            raise ValueError("missing ':'")
        start, end = int(head), int(tail)
    except ValueError:
        raise ValueError(
            f"--profile_rounds expects START:END (two integers), got "
            f"{spec!r}") from None
    if start < 0 or end < start:
        raise ValueError(
            f"--profile_rounds {spec!r}: need 0 <= START <= END")
    return start, end


class ProfileWindow:
    """Programmatic start_trace/stop_trace around rounds [start, end].

    The runner calls `on_dispatch(rnd)` before each round's dispatch and
    `on_committed(committed_round)` after each drain; `close()` on the
    loop's exit path force-stops a window the run ended inside."""

    def __init__(self, start: int, end: int, log_dir: str,
                 phases: tuple[str, ...] = ()):
        if not log_dir:
            raise ValueError(
                "--profile_rounds needs --profile_dir (the capture has to "
                "be written somewhere)")
        self.start = start
        self.end = end
        self.log_dir = log_dir
        # the named scopes of the round program (engine.ROUND_PHASES) that
        # the capture's summary attributes device time to
        self.phases = tuple(phases)
        self._active = False
        self._done = False

    @classmethod
    def parse(cls, spec: str, log_dir: str,
              phases: tuple[str, ...] = ()) -> "ProfileWindow | None":
        rounds = parse_rounds_spec(spec)
        if rounds is None:
            return None
        return cls(rounds[0], rounds[1], log_dir, phases)

    def _note(self, msg: str) -> None:
        print(f"obs: profile window — {msg}", file=sys.stderr, flush=True)

    def on_dispatch(self, rnd: int, rounds: int = 1) -> None:
        """`rnd` is the first round about to dispatch, `rounds` the size of
        the dispatch block — the capture starts as soon as a block OVERLAPS
        the window (a fused block cannot be split, so the capture is a
        round-aligned superset). A window entirely behind the run (resume
        past it) is declared dead LOUDLY instead of silently arming at the
        wrong rounds."""
        if self._active or self._done:
            return
        if rnd > self.end:
            self._note(
                f"rounds {self.start}:{self.end} are behind the run "
                f"(dispatching round {rnd}, e.g. a resume past the "
                "window); no capture will be taken")
            self._done = True
            return
        if rnd + rounds <= self.start:
            return  # block ends before the window opens
        # an earlier capture's summary is no reading of this one: the gauges
        # say so until this capture's own summary is published, and keep
        # saying so where it fails
        obreg.default().gauge("profile_traced_rounds").set(0)
        obreg.default().gauge("profile_block_traced_rounds").set(0)
        obreg.default().gauge("profile_launch_pairs").set(0)
        try:
            import jax

            options = jax.profiler.ProfileOptions()
            options.host_tracer_level = HOST_TRACER_LEVEL
            options.python_tracer_level = PYTHON_TRACER_LEVEL
            jax.profiler.start_trace(self.log_dir, profiler_options=options)
        except Exception as e:  # noqa: BLE001 — LOUD no-op by contract
            self._note(
                f"jax profiler unavailable ({type(e).__name__}: {e}); "
                f"--profile_rounds {self.start}:{self.end} degrades to a "
                "no-op and the run continues unprofiled")
            self._done = True
            return
        self._active = True
        obtrace.get().profiling(True)
        self._note(f"start_trace at round {rnd} -> {self.log_dir}")

    def on_committed(self, committed_round: int) -> None:
        """Stop once every round of the window has COMMITTED (the drain
        published round `end`, i.e. the session counter moved past it)."""
        if self._active and committed_round > self.end:
            self._stop(f"stop_trace after round {self.end} committed")

    def declare_unreachable(self, total_rounds: int) -> None:
        """Loud launch-time rejection: the runner calls this when the
        window starts at or past the run's last round (the capture could
        never begin — the silently-missing-capture failure mode)."""
        self._note(
            f"--profile_rounds {self.start}:{self.end} can never fire — "
            f"the run ends at round {total_rounds} (rounds are 0-based "
            "global indices); no capture will be taken")
        self._done = True

    def close(self) -> None:
        if self._active:
            self._stop("run ended inside the window; stop_trace at exit")
        elif not self._done:
            # backstop for segment runs the launch check cannot see: the
            # loop ended before the window ever opened
            self._note(
                f"run ended before rounds {self.start}:{self.end} "
                "dispatched; no capture was taken")
            self._done = True

    def _stop(self, why: str) -> None:
        obtrace.get().profiling(False)
        self._active = False
        self._done = True
        try:
            import jax

            jax.profiler.stop_trace()
            self._note(why)
        except Exception as e:  # noqa: BLE001 — LOUD no-op by contract
            self._note(f"stop_trace failed ({type(e).__name__}: {e})")
            return
        try:
            t0 = time.perf_counter()
            planes, host, run_ids = load_capture(
                newest_capture(self.log_dir))
            summary = summarize(planes, self.phases)
            publish(summary, obreg.default())
            self._note(format_summary(summary, self.phases)
                       + f" ({time.perf_counter() - t0:.2f} s to read)")
        except Exception as e:  # noqa: BLE001 — LOUD no-op by contract
            self._note(f"no summary of the capture ({type(e).__name__}: {e})")
            return
        try:
            blocks = summarize(planes, BLOCK_SCOPES)
        except ValueError:
            pass  # the model names no block: nothing to publish
        else:
            publish_blocks(blocks, obreg.default())
            self._note("by block, " + format_summary(blocks, BLOCK_SCOPES))
        try:
            launches = summarize_launches(planes, host, run_ids)
        except Exception as e:  # noqa: BLE001 — LOUD no-op by contract
            self._note(f"no launch summary ({type(e).__name__}: {e})")
            return
        publish_launches(launches, obreg.default())
        self._note(format_launches(launches)
                   + f" ({time.perf_counter() - t0:.2f} s to read in all)")


def newest_capture(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


@functools.cache
def _xplane_pb2():
    """The generated reader of .xplane.pb that tensorflow ships, loaded from
    its file: the file needs google.protobuf alone, and importing the
    tensorflow package for it would cost seconds in a process that holds
    the chip. Where tensorflow is not installed there is no summary (the
    loud no-op)."""
    import importlib.util

    tf = importlib.util.find_spec("tensorflow")
    if tf is None or not tf.submodule_search_locations:
        raise ImportError("xplane_pb2 comes with tensorflow, which is not "
                          "installed")
    spec = importlib.util.spec_from_file_location(
        "_commefficient_xplane_pb2", os.path.join(
            tf.submodule_search_locations[0], "tsl", "profiler", "protobuf",
            "xplane_pb2.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _parse(path: str):
    space = _xplane_pb2().XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


def load_capture(path: str) -> tuple[list, list, dict]:
    """(device planes, host events, run ids) of an .xplane.pb, parsed once.
    The device planes as `load_device_planes` gives them. The host plane's
    events, every thread's in one list: [(event name, start_ns,
    duration_ns, args)]: the program's own annotations (ANNOTATIONS) with
    their ROUND_ARGS, and the runtime's events of MIN_RUNTIME_NS or longer
    with their `run_id` where they carry one. The Python tracer's frames
    ("$file.py:line function") are dropped by their metadata, and so is what
    floods a thread: libtpu 0.0.34 records one `Transpose` of a microsecond
    for every 185 bytes of a host batch it lays out for the device, 68,000
    for a ResNet-9 cohort's 12.6 MB, and the `Linearize` around them covers
    the same time. The run ids: the runtime's `run_id` of each "XLA
    Modules" event, by the event's start_ns."""
    space = _parse(path)
    return _device_planes(space), _host_events(space), _module_run_ids(space)


def load_device_planes(path: str) -> list:
    """The device planes of an .xplane.pb in the shape `summarize` takes:
    [(plane name, [(line name, [(event name, start_ns, duration_ns,
    scope)])])], lines "XLA Ops" and "XLA Modules" only; the scope is the
    SCOPE_STAT of the event's metadata."""
    return _device_planes(_parse(path))


def _event_times(line, e) -> tuple[float, float]:
    return line.timestamp_ns + e.offset_ps / 1e3, e.duration_ps / 1e3


def _device_planes(space) -> list:
    planes = []
    for plane in space.planes:
        if not plane.name.startswith("/device:"):
            continue
        stat_names = {i: m.name for i, m in plane.stat_metadata.items()}
        named = {}  # event metadata id -> (event name, scope)
        for i, meta in plane.event_metadata.items():
            scope = ""
            for stat in meta.stats:
                if stat_names.get(stat.metadata_id) == SCOPE_STAT:
                    # the string itself, or a reference to a stat metadata
                    # whose name is the string
                    scope = (stat.str_value
                             or stat_names.get(stat.ref_value, ""))
            named[i] = (meta.name, scope)
        lines = []
        for line in plane.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            lines.append((line.name, [
                (named[e.metadata_id][0],
                 line.timestamp_ns + e.offset_ps / 1e3, e.duration_ps / 1e3,
                 named[e.metadata_id][1]) for e in line.events]))
        planes.append((plane.name, lines))
    return planes


def _int_stats(e, stat_names: dict, wanted) -> dict:
    return {stat_names[s.metadata_id]: s.int64_value or s.uint64_value
            for s in e.stats if stat_names.get(s.metadata_id) in wanted}


def _module_run_ids(space) -> dict:
    ids = {}
    for plane in space.planes:
        if not plane.name.startswith("/device:"):
            continue
        stat_names = {i: m.name for i, m in plane.stat_metadata.items()}
        for line in plane.lines:
            if line.name != MODULES_LINE:
                continue
            for e in line.events:
                run = _int_stats(e, stat_names, (RUN_ID,))
                if run:
                    ids[_event_times(line, e)[0]] = run[RUN_ID]
    return ids


def _host_events(space) -> list:
    events = []
    for plane in space.planes:
        if plane.name != HOST_PLANE:
            continue
        stat_names = {i: m.name for i, m in plane.stat_metadata.items()}
        kept = {i: (m.name, m.name.startswith(ANNOTATIONS))
                for i, m in plane.event_metadata.items()
                if not m.name.startswith(PYTHON_FRAME)}
        floor_ps = MIN_RUNTIME_NS * 1e3
        for line in plane.lines:
            for e in line.events:
                name, ours = kept.get(e.metadata_id, (None, False))
                if name is None or not (ours or e.duration_ps >= floor_ps):
                    continue
                events.append((name, *_event_times(line, e), _int_stats(
                    e, stat_names, ROUND_ARGS if ours else (RUN_ID,))))
    return events


def phase_of(scope: str, phases) -> str:
    """The innermost of `phases` on a scope path, `other` where there is
    none. Innermost, because scopes refine: the query and the top-k sit
    inside the server algebra, the ravel inside the client's gradient. A
    path's words are taken whole ("transpose(jvp(client_grad))/mul" holds
    client_grad, "my_client_grad_x" does not)."""
    for word in reversed(_WORD.findall(scope)):
        if word in phases:
            return word
    return OTHER


def summarize(planes, phases) -> dict:
    """Device ms per traced round by phase, from the planes
    `load_device_planes` gives (the first device plane that holds
    operations). Each operation's self time (its duration less that of the
    operations nested directly inside it, so that nesting counts once) goes
    to its own phase, or to the phase of the operation around it where its
    own scope names none. The traced rounds are the executions of the
    module that took most device time."""
    for _, lines in sorted(planes):
        by_name = dict(lines)
        ops = by_name.get(OPS_LINE)
        if ops:
            break
    else:
        raise ValueError("no device plane with operations in the capture: "
                         f"{[n for n, _ in planes]}")
    per_module: dict = {}
    for name, _, dur, _ in by_name.get(MODULES_LINE, []):
        took, runs = per_module.get(name, (0, 0))
        per_module[name] = (took + dur, runs + 1)
    main = max(per_module, key=lambda m: per_module[m][0], default=None)
    rounds = per_module[main][1] if main else 0
    if not rounds:
        raise ValueError("no program execution in the capture")

    phase_ns = dict.fromkeys((*phases, OTHER), 0.0)
    busy_ns, busy_end = 0.0, 0.0
    stack: list = []  # [end, phase, duration, child time]

    def close(item):
        _, phase, dur, child = item
        phase_ns[phase] += max(dur - child, 0.0)

    for _, start, dur, scope in sorted(ops, key=lambda e: (e[1], -e[2])):
        end = start + dur
        while stack and stack[-1][0] <= start:
            close(stack.pop())
        phase = phase_of(scope, phases)
        if stack:
            stack[-1][3] += dur
            if phase == OTHER:
                phase = stack[-1][1]
        else:  # outermost: the busy union
            busy_ns += max(end - max(start, busy_end), 0.0)
            busy_end = max(busy_end, end)
        stack.append([end, phase, dur, 0.0])
    while stack:
        close(stack.pop())
    if phases and not any(phase_ns[p] for p in phases):
        raise ValueError(
            f"no operation of the capture names a phase ({SCOPE_STAT!r} is "
            "missing from the events' metadata, or the program has no scopes)")
    per_round = 1e-6 / rounds
    return {"traced_rounds": rounds, "round_program": main,
            "device_busy_ms": busy_ns * per_round,
            "phase_device_ms": {p: ns * per_round
                                for p, ns in phase_ns.items()}}


def publish(summary: dict, reg) -> None:
    """The summary as gauges: profile_phase_device_ms_<phase> (and _other),
    profile_device_busy_ms, all ms per traced round, profile_traced_rounds."""
    for phase, ms in summary["phase_device_ms"].items():
        reg.gauge(f"profile_phase_device_ms_{phase}").set(ms)
    reg.gauge("profile_device_busy_ms").set(summary["device_busy_ms"])
    reg.gauge("profile_traced_rounds").set(summary["traced_rounds"])


def publish_blocks(summary: dict, reg) -> None:
    """The second reduction as gauges: profile_block_device_ms_<block>, ms per
    traced round (what no block names is the phases' to account for), and
    profile_block_traced_rounds."""
    for block, ms in summary["phase_device_ms"].items():
        if block != OTHER:
            reg.gauge(f"profile_block_device_ms_{block}").set(ms)
    reg.gauge("profile_block_traced_rounds").set(summary["traced_rounds"])


def format_summary(summary: dict, phases) -> str:
    parts = " | ".join(f"{p} {summary['phase_device_ms'][p]:.1f}"
                       for p in (*phases, OTHER))
    return (f"device ms/round: {parts} | busy "
            f"{summary['device_busy_ms']:.1f} over "
            f"{summary['traced_rounds']} rounds of "
            f"{summary['round_program']}")


def _main_program(by_name: dict) -> tuple[str | None, list]:
    """(name, [(start, end)] in time order) of the module that took most
    device time: the round program, as `summarize` finds it."""
    per_module: dict = {}
    for name, start, dur, _ in by_name.get(MODULES_LINE, []):
        per_module.setdefault(name, []).append((start, start + dur))
    if not per_module:
        return None, []
    main = max(per_module, key=lambda m: sum(e - s for s, e in per_module[m]))
    return main, sorted(per_module[main])


def _union_ns(spans) -> float:
    """The length of the union of [(start, end)]."""
    total, upto = 0.0, float("-inf")
    for start, end in sorted(spans):
        total += max(end - max(start, upto), 0.0)
        upto = max(upto, end)
    return total


def summarize_launches(planes, host, run_ids=None) -> dict:
    """Every execution of the round program in the capture, paired with the
    loop's own marks of the same round, and what passes between two of them.

    `planes`, `host` and `run_ids` as `load_capture` gives them. Per
    execution: the module event's start and end, the first operation's
    start, the last one's end and the busy union of the operations inside.
    Executions and `runner/ready` marks are both in launch order; how many
    executions the capture's start cut is known from the runtime's `run_id`
    (the mark that follows the host's COMPLETE event for a module event's id
    is that execution's), else taken as the shift under which most marks
    follow the end of their execution most closely (`by`). What the
    capture's edges cut is dropped and counted: operations with no module
    event around them, module events with no `runner/ready`, and a first
    module event that begins with the capture's first device event is the
    end of an execution, not a whole one (`first_cut`).

    A pair of consecutive rounds (k, k+1) is QUEUED if the device had k+1 in
    hand before k's last operation ended: by the runtime's ENQUEUE of k+1
    where the capture has it (a program is enqueued once its inputs are on
    the device), else by the return of `runner/dispatch`. Over the queued
    pairs, in ms a round (a block of n rounds divides by n): gap =
    (ready[k+1] - ready[k]) - busy[k+1]; between = module end of k to module
    start of k+1; head, tail = module start to first operation, last
    operation to module end (of k+1, of k); inside = the holes between k+1's
    operations; drift = (ready[k+1] - ready[k]) - (last operation end[k+1] -
    last operation end[k]); so gap = drift + tail + between + head + inside.
    ready_lag = `runner/ready` less the module's end; call = duration of
    `session/launch`; busy = busy[k+1], a whole execution's."""
    for _, lines in sorted(planes):
        by_name = dict(lines)
        ops = by_name.get(OPS_LINE)
        if ops:
            break
    else:
        raise ValueError("no device plane with operations in the capture: "
                         f"{[n for n, _ in planes]}")
    main, runs = _main_program(by_name)
    if not runs:
        raise ValueError("no program execution in the capture")
    every = sorted((start, start + dur)
                   for _, start, dur, _ in by_name[MODULES_LINE])

    # each execution's first operation, busy union and last operation's end
    # (nesting and overlap count once); what no module event of any program
    # holds is an edge's
    spans = sorted((start, start + dur) for _, start, dur, _ in ops)
    inside: list = [None] * len(runs)  # [first start, busy, last end]
    cut_start = cut_end = 0
    i = j = 0
    for start, end in spans:
        while j < len(runs) and runs[j][1] <= start:
            j += 1
        if j < len(runs) and runs[j][0] <= start:
            if inside[j] is None:
                inside[j] = [start, 0.0, start]
            seen = inside[j]
            seen[1] += max(end - max(start, seen[2]), 0.0)
            seen[2] = max(seen[2], end)
            continue
        while i < len(every) and every[i][1] <= start:
            i += 1
        if i == len(every) or start < every[i][0]:
            cut_start += start < every[0][0]
            cut_end += i == len(every)
    first_cut = runs[0][0] <= min(spans[0][0], every[0][0])

    readies, dispatched, launched, enqueued, completed, runtime = (
        [], {}, {}, {}, [], [])
    for name, start, dur, args in host:
        rnd = int(args.get("round", args.get("round_first", -1)))
        if name == "runner/ready":
            readies.append((start, rnd, max(int(args.get("rounds", 1)), 1)))
        elif name == "runner/dispatch":
            dispatched[rnd] = start + dur
        elif name == "session/launch":
            launched[rnd] = dur
        elif not name.startswith(ANNOTATIONS):
            runtime.append((name, start, start + dur))
            if name == ENQUEUE and RUN_ID in args:
                enqueued[args[RUN_ID]] = start
            elif name == COMPLETE and RUN_ID in args:
                completed.append((start, args[RUN_ID]))
    readies.sort()
    completed.sort()
    run_of = [(run_ids or {}).get(start) for start, _ in runs]

    # how many executions before the first ready mark. By the runtime's ids,
    # on the host's clock alone: a COMPLETE event says every id up to its own
    # is done, and the mark that follows the first such event for an
    # execution's id is that execution's. Else every mark votes for the
    # execution that ended nearest to it. The commonest answer wins (a loop
    # that comes late to a read sees two executions done before one mark).
    votes: dict = {}
    done_upto = [upto for _, upto in completed]
    marks = [ready for ready, _, _ in readies]
    for i, run in enumerate(run_of):
        if run is None:
            continue
        k = bisect.bisect_left(done_upto, run)
        if k < len(completed):
            j = bisect.bisect_left(marks, completed[k][0])
            if j < len(marks):
                votes[i - j] = votes.get(i - j, 0) + 1
    by = "run_id" if votes else "order"
    if not votes:
        for k, (ready, _, _) in enumerate(readies):
            i = min(range(len(runs)), key=lambda n: abs(runs[n][1] - ready))
            votes[i - k] = votes.get(i - k, 0) + 1
    shift = max(votes, key=votes.get) if votes else -len(runs)

    execs = []  # one dict an execution that has a ready mark, in time order
    for i, ((m_start, m_end), seen) in enumerate(zip(runs, inside)):
        if not 0 <= i - shift < len(readies) or seen is None:
            continue
        ready, rnd, n = readies[i - shift]
        execs.append({"round": rnd, "rounds": n, "start": m_start,
                      "end": m_end, "first_op": seen[0], "busy": seen[1],
                      "last_op": seen[2], "ready": ready,
                      "enqueued": enqueued.get(run_of[i])})

    sums = dict.fromkeys(LAUNCH_PARTS, 0.0)
    pairs = starved = unknown = timed = holes = 0
    covered: dict = {}
    for a, b in zip(execs, execs[1:]):
        if b["round"] != a["round"] + a["rounds"]:
            continue
        holes += 1  # every hole is put down to the host, queued or not
        hole: dict = {}
        for name, start, end in runtime:
            if start < b["first_op"] and end > a["last_op"]:
                hole.setdefault(name, []).append(
                    (max(start, a["last_op"]), min(end, b["first_op"])))
        for name, clipped in hole.items():  # threads and nesting count once
            covered[name] = covered.get(name, 0.0) + _union_ns(clipped)
        in_hand = b["enqueued"]
        if in_hand is None:
            in_hand = dispatched.get(b["round"])
        if in_hand is None:
            unknown += 1  # handed over before the capture began
            continue
        if in_hand >= a["last_op"]:
            starved += 1
            continue
        pairs += 1
        n = b["rounds"]
        step = b["ready"] - a["ready"]
        sums["gap"] += (step - b["busy"]) / n
        sums["between"] += (b["start"] - a["end"]) / n
        sums["head"] += (b["first_op"] - b["start"]) / n
        sums["tail"] += (a["end"] - a["last_op"]) / n
        sums["inside"] += (b["last_op"] - b["first_op"] - b["busy"]) / n
        sums["drift"] += (step - (b["last_op"] - a["last_op"])) / n
        sums["ready_lag"] += (b["ready"] - b["end"]) / n
        sums["busy"] += b["busy"] / n
        if b["round"] in launched:
            sums["call"] += launched[b["round"]]
            timed += 1
    out = {"round_program": main, "by": by, "executions": len(runs),
           "paired": len(execs), "pairs": pairs, "starved": starved,
           "unknown": unknown, "first_cut": first_cut,
           "dropped": {
               "ops_before_first_module": cut_start,
               "ops_after_last_module": cut_end,
               "modules_before_first_ready": (
                   min(max(shift, 0), len(runs)) if readies else 0),
               "modules_after_last_ready": min(max(
                   len(runs) - shift - len(readies), 0), len(runs))},
           "between_hosts": [
               (name, 1e-6 * ns / holes) for name, ns in sorted(
                   covered.items(), key=lambda kv: -kv[1])[:3]]}
    for part in LAUNCH_PARTS:
        count = timed if part == "call" else pairs
        out[f"{part}_ms"] = 1e-6 * sums[part] / count if count else 0.0
    return out


def publish_launches(summary: dict, reg) -> None:
    """The launch summary as gauges: profile_launch_<part>_ms for each of
    LAUNCH_PARTS, ms a round over the queued pairs, and profile_launch_pairs
    (under 3: no reading)."""
    for part in LAUNCH_PARTS:
        reg.gauge(f"profile_launch_{part}_ms").set(summary[f"{part}_ms"])
    reg.gauge("profile_launch_pairs").set(summary["pairs"])


def format_launches(summary: dict) -> str:
    """The line after `format_summary`'s two: the gap and its parts, the pairs
    used of the executions seen, what the edges cut, and what the host's
    threads were doing between two consecutive executions."""
    d = summary["dropped"]
    parts = " + ".join(f"{p} {summary[p + '_ms']:.3f}" for p in
                       ("drift", "tail", "between", "head", "inside"))
    hosts = ", ".join(f"{n} {ms:.3f}" for n, ms in summary["between_hosts"])
    why = ""
    if summary["pairs"] < 3:
        why = (" NO READING (under 3 queued pairs: the device did not have "
               "the next round in hand before the last operation)")
    return (f"launch ms/round: gap {summary['gap_ms']:.3f} = {parts} | "
            f"busy {summary['busy_ms']:.3f} | ready lag "
            f"{summary['ready_lag_ms']:.3f} | launch call "
            f"{summary['call_ms']:.3f} | {summary['pairs']} queued pairs "
            f"({summary['starved']} starved, {summary['unknown']} handed over "
            f"before the capture) of {summary['paired']} paired by "
            f"{summary['by']} of {summary['executions']} executions of "
            f"{summary['round_program']}{why} | cut: "
            f"{'the first module event, ' if summary['first_cut'] else ''}"
            f"{d['ops_before_first_module']} operations before the first "
            f"module event, {d['ops_after_last_module']} after the last; "
            f"{d['modules_before_first_ready']} executions before the first "
            f"ready mark, {d['modules_after_last_ready']} after the last | "
            f"host between two executions: {hosts or 'nothing'}")
