"""Share of the window the scheduler thread spent doing something itself
(admitting, dispatching, delivering, housekeeping) and not waiting for
the device or for work: the program's own `loop` counters."""
from perfbench.harness import program_timeline


def read(run):
    return program_timeline.host_share_pct(run)
