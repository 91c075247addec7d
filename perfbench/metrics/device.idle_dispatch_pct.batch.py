"""Share of the traced window with the chip idle while the scheduler
admitted, kept house or prepared and made the next dispatch."""
from perfbench.harness import program_timeline


def read(run):
    return program_timeline.idle_pct(run, ("admit", "housekeeping",
                                           "decode.dispatch",
                                           "prefill.dispatch"))
