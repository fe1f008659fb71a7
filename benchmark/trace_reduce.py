"""From a profiler trace (.xplane.pb) to the numbers the per-layer metrics
read: device busy union, traced window, self time per device operation,
executions of each program, and the longest idle gaps with what a host thread
was doing in them. Reads the file with jax.profiler.ProfileData alone.

What one real v5e trace showed (PERF.md section 3): device planes are named
"/device:TPU:<n>"; their line "XLA Ops" holds one event per executed HLO
operation (nested where an operation has a body), "XLA Modules" one event per
program execution; the host is the plane "/host:CPU", one line per thread.
"""

from __future__ import annotations

import dataclasses
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


@dataclasses.dataclass
class Reduction:
    chips: int
    window_s: float  # first device operation's start to the last one's end
    busy_s: float  # union of device operation intervals, mean over chips
    op_self_s: dict  # operation name -> self seconds, summed over chips
    op_total_s: dict  # operation name -> whole duration (children included)
    op_count: dict
    op_kind: dict  # operation name -> "opcode result-shape", from the trace's HLO text
    modules: dict  # program name -> list of (start_s, duration_s), chip 0
    gaps: list  # [(host event name, seconds)], longest first, chip 0

    def idle_share(self) -> float | None:
        return None if self.window_s <= 0 else 1.0 - self.busy_s / self.window_s

    def main_module(self) -> str | None:
        """The program that took most device time: the round program."""
        if not self.modules:
            return None
        return max(self.modules, key=lambda m: sum(d for _, d in self.modules[m]))

    def label(self, op: str) -> str:
        """An operation's name with its HLO opcode and result shape."""
        return f"{op} {self.op_kind.get(op, '')}".strip()

    def ops_matching(self, *needles: str) -> tuple[float, int]:
        """(seconds, executions) of operations whose own name (what stands
        before ' = ' in the trace's HLO text) has every needle."""
        secs = sum(s for n, s in self.op_total_s.items() if all(x in n for x in needles))
        runs = sum(c for n, c in self.op_count.items() if all(x in n for x in needles))
        return secs, runs


def _union(intervals):
    """Merged [(start, end)] of possibly nested or overlapping intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _self_times(events):
    """events: [(name, start, end)] of one line. Self time of an event is its
    duration less that of the events nested directly inside it."""
    self_s, total_s, count = {}, {}, {}
    stack = []  # [name, end, child seconds, duration]

    def close(item):
        name, _, child, dur = item
        self_s[name] = self_s.get(name, 0.0) + max(dur - child, 0.0)

    for name, s, e in sorted(events, key=lambda t: (t[1], -(t[2] - t[1]))):
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        if stack:
            stack[-1][2] += e - s
        stack.append([name, e, 0.0, e - s])
        total_s[name] = total_s.get(name, 0.0) + (e - s)
        count[name] = count.get(name, 0) + 1
    while stack:
        close(stack.pop())
    return self_s, total_s, count


def reduce_planes(planes, device_prefix: str = "/device:TPU:", top_gaps: int = 10) -> Reduction:
    """planes: iterable of (plane name, [(line name, [(event name, start_ns,
    duration_ns)])]) — the shape `load` gives and the tests build by hand."""
    planes = list(planes)
    devices = [(n, ls) for n, ls in planes if n.startswith(device_prefix)]
    if not devices:
        raise ValueError(f"no plane named {device_prefix}* in the trace: "
                         f"{[n for n, _ in planes]}")
    host_events = []
    for name, lines in planes:
        if name == HOST_PLANE:
            for _, evs in lines:
                host_events += [(n, s * 1e-9, (s + d) * 1e-9) for n, s, d in evs if d > 0]

    busy = window = 0.0
    self_s, total_s, count, modules, gaps, kinds = {}, {}, {}, {}, [], {}
    for i, (_, lines) in enumerate(sorted(devices)):
        by_name = {ln: evs for ln, evs in lines}
        ops = []
        for n, s, d in by_name.get(OPS_LINE, []):
            short, kind = _split_hlo(n)
            kinds.setdefault(short, kind)
            ops.append((short, s * 1e-9, (s + d) * 1e-9))
        if not ops:
            continue
        merged = _union((s, e) for _, s, e in ops)
        busy += sum(e - s for s, e in merged)
        window += merged[-1][1] - merged[0][0]
        ss, ts, cs = _self_times(ops)
        for src, dst in ((ss, self_s), (ts, total_s), (cs, count)):
            for k, v in src.items():
                dst[k] = dst.get(k, 0) + v
        if i == 0:
            for n, s, d in by_name.get(MODULES_LINE, []):
                modules.setdefault(n, []).append((s * 1e-9, d * 1e-9))
            holes = sorted(((b[0] - a[1], a[1], b[0]) for a, b in zip(merged, merged[1:])),
                           reverse=True)[:top_gaps]
            gaps = [(_host_doing(host_events, s, e), e - s) for _, s, e in holes]
    n = len(devices)
    return Reduction(n, window / n, busy / n, self_s, total_s, count, kinds, modules, gaps)


def _split_hlo(text: str) -> tuple[str, str]:
    """'%sort.1 = (f32[8]{0}, s32[8]{0}) sort(...)' -> ('%sort.1', 'sort (f32[8], s32[8])').
    A name that is no HLO text is kept whole."""
    name, sep, rest = text.partition(" = ")
    if not sep:
        return text, ""
    depth, i = 0, 0
    for i, ch in enumerate(rest):  # the result shape ends at the first space outside brackets
        depth += ch in "([{"
        depth -= ch in ")]}"
        if ch == " " and depth == 0:
            break
    shape = re.sub(r"\{[^{}]*\}", "", rest[:i])
    opcode = rest[i + 1:].split("(", 1)[0]
    return name, f"{opcode} {shape}"[:80]


WAITS = ("acquire", "wait", "put", "get", "sleep", "join")


def _host_doing(host_events, start, end) -> str:
    """What the host was doing in [start, end]: of the host events that cover
    at least half of it, the shortest (host events nest, and the outermost
    covers everything); failing that, the one that covers most."""
    half, best, widest = 0.5 * (end - start), None, ("(no host event)", 0.0)
    for name, s, e in host_events:
        cover = min(e, end) - max(s, start)
        # a thread parked on a lock or a queue covers any gap and explains none
        if cover <= 0 or name.rsplit(" ", 1)[-1].lstrip("_") in WAITS:
            continue
        if cover >= half and (best is None or e - s < best[1]):
            best = (name, e - s)
        if cover > widest[1]:
            widest = (name, cover)
    return best[0] if best else widest[0]


def load(path: str):
    """The planes of an .xplane.pb file in the shape reduce_planes takes."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    return [(p.name, [(ln.name, [(e.name, e.start_ns, e.duration_ns) for e in ln.events])
                      for ln in p.lines]) for p in data.planes]


def find_xplane(profile_dir: str) -> str:
    import glob
    import os

    found = sorted(glob.glob(os.path.join(profile_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    return found[-1]


def describe(planes, per_line: int = 12) -> dict:
    """What a trace holds, for a look by hand: planes, lines, event counts and
    the names that took most time in each line."""
    out = {}
    for name, lines in planes:
        out[name] = {}
        for ln, evs in lines:
            tot = {}
            for n, _, d in evs:
                tot[n] = tot.get(n, 0) + d
            top = sorted(tot.items(), key=lambda kv: -kv[1])[:per_line]
            out[name][ln] = {"events": len(evs), "top_ns": top}
    return out
