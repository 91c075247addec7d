"""Share of the window's prefill dispatches whose routed blocks' rows
went through the sorted expert product (each expert over the rows that
chose it) and not the walk of every row through every held expert: the
engine's `loop` counters `prefill_sorted_n` over `prefill.dispatch_n`.
The program's rule over shapes admits a bucket and the kernel's probe
passed, or the dispatch counts as walked. A program without the counter
reads as nothing."""
from perfbench.harness import program_timeline


def read(run):
    d = program_timeline.window_delta(run)
    if d is None or "prefill_sorted_n" not in d[0]:
        return None
    issued = d[0]["prefill.dispatch_n"]
    return 100.0 * d[0]["prefill_sorted_n"] / issued if issued else None
