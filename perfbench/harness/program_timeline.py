"""The program's own account of its scheduler thread, read two ways.

As counters: `DecodeEngine.stats()["loop"]` holds the seconds and counts
of each leaf phase of the scheduler thread, cumulative; the serve runner
already keeps `stats()` from the window's opening and close, and
`window_delta` gives the window's share of each.

As spans on the device trace's clock: the program keeps the same phases
as spans on `time.perf_counter()` in `observability.TIMELINE`. The serve
runner reads `perf_counter` as the first and the last statement inside
the `perfbench.window` annotation (`run.traced`), whose start and end on
the trace's clock are `run.trace.t0` and `.t1`: the first pair gives the
offset between the clocks, the second has to agree with it. The chip's
idle time inside the window is then cut by the phase the scheduler was
in, with no use of the profiler's Python frames.

A program without the counters or the timeline (a parent commit from
before they existed) reads as nothing: every function here returns None.
"""
from __future__ import annotations

import bisect
import collections
import sys

# how far the two anchors may disagree before no span is trusted
ANCHOR_TOLERANCE_S = 0.5e-3
# phases in which the scheduler thread itself only waits
WAITING = ("decode.wait", "prefill.wait", "wait-work")


def window_delta(run):
    """(loop, front): how far the engine's `loop` counters grew over the
    window, and `queue_wait_s`, `admitted` and `decode_steps` beside
    them."""
    before = run.facts.get("stats_before") or {}
    after = run.facts.get("stats_after") or {}
    if "loop" not in before or "loop" not in after:
        return None
    loop = {k: after["loop"][k] - before["loop"][k] for k in after["loop"]}
    front = {k: after[k] - before[k]
             for k in ("queue_wait_s", "admitted", "decode_steps")}
    return loop, front


def host_share_pct(run):
    """Share of the scheduler thread's time between the two readings of
    `stats()` in which it was doing something itself, and not waiting
    for the device or for work. The phases' seconds add up to that time
    (a phase still open counts with what it has lasted), so the thread's
    own total is the denominator."""
    d = window_delta(run)
    if d is None:
        return None
    seconds = {k[:-2]: v for k, v in d[0].items()
               if k.endswith("_s") and k != "sink_s"}
    total = sum(seconds.values())
    if total <= 0:
        return None
    return 100.0 * sum(v for p, v in seconds.items()
                       if p not in WAITING) / total


def ratio(run, numerator: str, denominator: str, scale: float = 1.0):
    d = window_delta(run)
    if d is None:
        return None
    both = {**d[0], **d[1]}
    if not both[denominator]:
        return None
    return scale * both[numerator] / both[denominator]


def program_spans(t0: float, t1: float):
    """The program's timeline between two `perf_counter` readings, or
    None where the program has none."""
    try:
        from deeplearning4j_tpu.serving.observability import TIMELINE
    except ImportError:
        return None
    return TIMELINE.snapshot(t0, t1)


def spans_on_trace_clock(run):
    """[(start_ns, end_ns, name, cause, attrs)] of the scheduler thread
    over the traced stretch, on the trace's clock and cut to its window,
    in order of time; None without a trace, a chip, a timeline or
    anchors that agree."""
    view, traced = run.trace, run.traced
    if view is None or not traced or not view.chips:
        return None
    raw = program_spans(traced["t0"], traced["t1"])
    if not raw:
        return None
    offset = view.t0 - 1e9 * traced["t0"]
    apart = abs(view.t1 - (1e9 * traced["t1"] + offset)) / 1e9
    if apart > ANCHOR_TOLERANCE_S:
        print(f"perfbench: the clock anchors of the traced stretch are "
              f"{1e3 * apart:.3f} ms apart: no span is read",
              file=sys.stderr)
        return None
    # one scheduler thread's timeline: the one that recorded the most
    threads = collections.Counter(s[4] for s in raw)
    tid = threads.most_common(1)[0][0]
    out = []
    for name, a, b, cause, thread, attrs in raw:
        if thread != tid:
            continue
        a = max(1e9 * a + offset, view.t0)
        b = min(1e9 * b + offset, view.t1)
        if b > a:
            out.append((a, b, name, cause, attrs))
    return sorted(out)


def idle_seconds_by_phase(run):
    """{phase: seconds the first chip was idle while the scheduler was
    in it}, with the idle seconds under no span as `None`'s."""
    spans = spans_on_trace_clock(run)
    if spans is None:
        return None
    view = run.trace
    busy = view.busy_intervals(view.chips[0])
    edges = [view.t0] + [x for ab in busy for x in ab] + [view.t1]
    by = collections.Counter()
    starts = [s[0] for s in spans]
    for i in range(0, len(edges), 2):
        a, b = edges[i], edges[i + 1]
        if b <= a:
            continue
        by[None] += b - a
        # spans do not overlap, so the one before `a` is the only one
        # that starts earlier and can still reach into the gap
        j = max(0, bisect.bisect_right(starts, a) - 1)
        while j < len(spans) and spans[j][0] < b:
            cut = min(b, spans[j][1]) - max(a, spans[j][0])
            if cut > 0:
                by[spans[j][2]] += cut
                by[None] -= cut
            j += 1
    return {k: v / 1e9 for k, v in by.items()}


def idle_pct(run, phases):
    """Share of the traced window in which the chip was idle and the
    scheduler in one of `phases`; `phases=None` reads the idle share
    under no span at all."""
    by = idle_seconds_by_phase(run)
    if by is None:
        return None
    names = (None,) if phases is None else phases
    return 100.0 * sum(by.get(p, 0.0) for p in names) / run.trace.window_s


def dispatch_containment(run, programs=("decode_chunked", "decode_step",
                                        "prefill"),
                         tolerance_s: float = ANCHOR_TOLERANCE_S):
    """How well the spans sit where the device's work sits: of the
    device runs of `programs` in the traced window, how many lie inside
    a `.dispatch` + `.wait` stretch of the scheduler that names the
    program, to within `tolerance_s` at either end, and by how many
    seconds the worst one sticks out."""
    spans = spans_on_trace_clock(run)
    if spans is None:
        return None
    stretches = collections.defaultdict(list)
    for i, (a, _, name, cause, attrs) in enumerate(spans):
        if not name.endswith(".dispatch") or not attrs:
            continue
        end = next((s[1] for s in spans[i + 1:i + 3]
                    if s[2] == name[:-9] + ".wait" and s[3] == cause), None)
        if end is not None:
            stretches[attrs.get("program")].append((a, end))
    runs = inside = 0
    worst = 0.0
    for program in programs:
        have = stretches.get(program, [])
        for a, b in run.trace.program_runs().get(program, []):
            runs += 1
            out = min((max(w0 - a, b - w1, 0.0) for w0, w1 in have),
                      default=float("inf"))
            inside += out <= 1e9 * tolerance_s
            worst = max(worst, out / 1e9)
    return {"runs": runs, "inside": inside, "worst_outside_s": worst}
