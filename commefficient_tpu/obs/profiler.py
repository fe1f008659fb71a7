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
"""

from __future__ import annotations

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
        try:
            import jax

            jax.profiler.start_trace(self.log_dir)
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
            planes = load_device_planes(newest_capture(self.log_dir))
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
            return  # the model names no block: nothing to publish
        publish_blocks(blocks, obreg.default())
        self._note("by block, " + format_summary(blocks, BLOCK_SCOPES))


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


def load_device_planes(path: str) -> list:
    """The device planes of an .xplane.pb in the shape `summarize` takes:
    [(plane name, [(line name, [(event name, start_ns, duration_ns,
    scope)])])], lines "XLA Ops" and "XLA Modules" only; the scope is the
    SCOPE_STAT of the event's metadata. The host planes' events are parsed
    but never walked: they hold the Python tracer's and the runtime's
    events, by the hundred thousand."""
    space = _xplane_pb2().XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
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
