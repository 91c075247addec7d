"""What one run hands to the metric readers."""
from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass
class Run:
    """The raw material of every metric of one run. A reader takes what
    it needs and returns a number, or None where there is nothing to
    read (a run without a trace has no `trace`)."""
    workload: str
    kind: str                       # fit | closed | open
    chips: int
    device_kind: str
    sizes: dict                     # the family's sizes of the configuration
    mix: dict                       # the traffic file
    setup_s: float                  # process start to window open
    window_s: float                 # the measured window, as it was
    setup_compile: dict             # CompileMeter reading at window open
    window_programs: int            # compiled or loaded inside the window
    facts: dict                     # the runner's own counts and times
    trace: Optional[object] = None  # trace_reduce.TraceView, traced runs
    traced: Optional[dict] = None   # facts of the traced stretch alone


def percentile(values, pct: float):
    """The `pct`-th percentile by the nearest rank, or None of nothing."""
    if not values:
        return None
    ordered = sorted(values)
    rank = max(0, min(len(ordered) - 1,
                      int(round(pct / 100.0 * len(ordered) + 0.5)) - 1))
    return ordered[rank]

