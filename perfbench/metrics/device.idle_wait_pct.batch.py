"""Share of the traced window with the chip idle while the scheduler was
already waiting: launch latency and the results' way back (and waiting
for work, which a closed loop with a backlog never does)."""
from perfbench.harness import program_timeline


def read(run):
    return program_timeline.idle_pct(run, program_timeline.WAITING)
