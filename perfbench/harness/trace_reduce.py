"""From a profiler trace to numbers: device busy and idle time, device time
by program and by operation, and the idle gaps by what the host was doing.

The reduction works on plain records, one per event:
`{"plane", "line", "name", "start_ns", "dur_ns"}`, so that it can be
checked on a small recorded trace kept as JSON beside the tests.
`load_xplane` makes such records from the profiler's `.xplane.pb`.

On a TPU each chip is a plane `/device:TPU:<n>`; its line `XLA Modules`
holds one event per run of a compiled program, named `jit_<fn>(<id>)`, and
its line `XLA Ops` one event per operation inside it. The host's plane
`/host:CPU` has a line per thread; Python frames on it are named
`$<file>:<line> <function>`.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re

MODULE_LINE, OPS_LINE = "XLA Modules", "XLA Ops"
# operations that only hold others: their time is their bodies' time,
# which the trace also lists, so a sum by operation leaves them out
_CONTAINERS = ("while", "conditional", "call")
# frames in which a thread only waits say nothing about a gap
_WAITING = ("threading.py", "queue.py", "selectors.py", "socket.py",
            "concurrent/futures", "<built-in", "time.sleep")


def load_xplane(trace_dir: str) -> list:
    """Events of the newest trace under `trace_dir` as plain records."""
    import jax.profiler

    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(files[-1])
    out = []
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        if not device and plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            if device and line.name not in (MODULE_LINE, OPS_LINE):
                continue
            for ev in line.events:
                if not device and not (ev.name.startswith("$")
                                       or ev.name.startswith("perfbench.")):
                    continue
                out.append({"plane": plane.name, "line": line.name,
                            "name": ev.name, "start_ns": float(ev.start_ns),
                            "dur_ns": float(ev.duration_ns)})
    return out


def program_name(event_name: str) -> str:
    """`jit_decode_step(1234)` -> `decode_step`."""
    name = re.sub(r"\(.*\)$", "", event_name).strip()
    return name[4:] if name.startswith("jit_") else name


def op_name(event_name: str) -> str:
    """An operation's name without the number XLA gives it, so that the
    same operation of 24 layers is one row. A TPU trace names an
    operation by its whole HLO line: `%fusion.123 = bf16[..] fusion(..)`
    -> `fusion`."""
    return re.sub(r"[._]\d+$", "", event_name.split(" = ")[0].lstrip("%"))


def opcode(event_name: str) -> str:
    """The HLO opcode of an operation named by its whole HLO line (the
    first lower-case word that opens a bracket after the `=`), or ""."""
    m = re.search(r"[\s)]([a-z][a-z0-9_\-]*)\(",
                  event_name.partition(" = ")[2])
    return m.group(1) if m else ""


def is_pallas_kernel(event_name: str) -> bool:
    """A Pallas (Mosaic) kernel: a custom call to `tpu_custom_call`."""
    return opcode(event_name) == "custom-call" \
        and "tpu_custom_call" in event_name


def _union(intervals: list) -> list:
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


class TraceView:
    """One traced window. `mark` names the host event that brackets it
    (a `TraceAnnotation`); without one the window runs from the first
    device event's start to the last one's end."""

    def __init__(self, events: list, mark: str = "perfbench.window"):
        self.events = events
        self._lines = collections.defaultdict(list)  # (plane, line) -> events
        for e in events:
            self._lines[e["plane"], e["line"]].append(e)
        self._runs = None
        marks = [e for e in events if e["name"] == mark]
        dev = [e for e in events if e["plane"].startswith("/device:")]
        if marks:
            self.t0 = marks[0]["start_ns"]
            self.t1 = self.t0 + marks[0]["dur_ns"]
        elif dev:
            self.t0 = min(e["start_ns"] for e in dev)
            self.t1 = max(e["start_ns"] + e["dur_ns"] for e in dev)
        else:
            self.t0 = self.t1 = 0.0
        self.chips = sorted({e["plane"] for e in dev})

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    def _clipped(self, plane: str, line: str) -> list:
        out = []
        for e in self._lines[plane, line]:
            a = max(e["start_ns"], self.t0)
            b = min(e["start_ns"] + e["dur_ns"], self.t1)
            if b > a:
                out.append((a, b, e["name"]))
        return out

    def busy_intervals(self, plane: str) -> list:
        return _union([(a, b) for a, b, _ in self._clipped(plane, OPS_LINE)])

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        if not self.chips:
            return 0.0
        return sum(b - a for p in self.chips
                   for a, b in self.busy_intervals(p)) / 1e9 / len(self.chips)

    def idle_pct(self):
        if not self.chips or self.window_s <= 0:
            return None
        return 100.0 * (1.0 - self.busy_s() / self.window_s)

    def program_runs(self) -> dict:
        """program -> [(start, end)] of its runs that lie wholly inside
        the window, on the first chip."""
        if self._runs is None:
            self._runs = collections.defaultdict(list)
            for e in self._lines[self.chips[0], MODULE_LINE] \
                    if self.chips else []:
                a, b = e["start_ns"], e["start_ns"] + e["dur_ns"]
                if a >= self.t0 and b <= self.t1:
                    self._runs[program_name(e["name"])].append((a, b))
        return self._runs

    def program_seconds(self, program: str) -> tuple:
        """(runs, device seconds) of one program's whole runs."""
        runs = self.program_runs().get(program, [])
        return len(runs), sum(b - a for a, b in runs) / 1e9

    def op_seconds_within(self, programs, match) -> float:
        """Device seconds of the operations whose name `match` accepts
        and that start inside a whole run of one of `programs`."""
        spans = sorted(r for p in programs
                       for r in self.program_runs().get(p, []))
        starts = [a for a, _ in spans]
        total = 0.0
        for e in self._lines[self.chips[0], OPS_LINE] if self.chips else []:
            if not match(e["name"]):
                continue
            i = bisect.bisect_right(starts, e["start_ns"]) - 1
            if i >= 0 and e["start_ns"] < spans[i][1]:
                total += e["dur_ns"]
        return total / 1e9

    def top_ops(self, n: int = 10) -> list:
        """[[operation, seconds]] of the first chip's costliest."""
        if not self.chips:
            return []
        by = collections.Counter()
        for a, b, name in self._clipped(self.chips[0], OPS_LINE):
            if opcode(name) not in _CONTAINERS:
                by[op_name(name)] += (b - a) / 1e9
        return [[k, v] for k, v in by.most_common(n)]

    def idle_gaps(self, n: int = 10) -> list:
        """[[host frame, seconds]]: the first chip's idle time by the
        innermost Python frame that was running, on any thread that was
        not merely waiting, at the middle of each gap."""
        if not self.chips:
            return []
        busy = self.busy_intervals(self.chips[0])
        edges = [self.t0] + [x for ab in busy for x in ab] + [self.t1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        frames = sorted(
            (e["start_ns"], e["start_ns"] + e["dur_ns"], e["name"])
            for e in self.events
            if e["plane"] == "/host:CPU" and e["name"].startswith("$")
            and not any(w in e["name"] for w in _WAITING))
        starts = [f[0] for f in frames]
        by = collections.Counter()
        for a, b in gaps:
            mid = 0.5 * (a + b)
            best = None
            i = bisect.bisect_right(starts, mid) - 1
            # innermost = the latest-starting frame that still covers mid
            for j in range(i, max(-1, i - 400), -1):
                if frames[j][1] >= mid:
                    best = frames[j][2]
                    break
            by[_frame_label(best)] += (b - a) / 1e9
        return [[k, v] for k, v in by.most_common(n)]


def _frame_label(name) -> str:
    if name is None:
        return "no_python_frame"
    m = re.match(r"^\$(?:.*/)?([^/ ]+) (\S+)", name)
    return f"{m.group(1)}_{m.group(2)}" if m else name
