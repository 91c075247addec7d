"""Share of the traced window with the chip idle while the scheduler
delivered the tokens of the dispatch before."""
from perfbench.harness import program_timeline


def read(run):
    return program_timeline.idle_pct(run, ("decode.deliver",
                                           "prefill.deliver"))
