"""Share of the window's dispatches (decode and one-shot prefill) that
the scheduler issued while an earlier one was still unread: how often
the chip had its next program before the host waited for the last. From
the engine's `loop` counters; a program without `ahead_n` reads as
nothing."""
from perfbench.harness import program_timeline


def read(run):
    d = program_timeline.window_delta(run)
    if d is None or "ahead_n" not in d[0]:
        return None
    issued = d[0]["decode.dispatch_n"] + d[0]["prefill.dispatch_n"]
    return 100.0 * d[0]["ahead_n"] / issued if issued else None
