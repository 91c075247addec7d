"""Share of the traced window with the chip idle under no span of the
scheduler's timeline: it gauges the tracing itself."""
from perfbench.harness import program_timeline


def read(run):
    return program_timeline.idle_pct(run, None)
