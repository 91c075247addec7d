"""Mean wait between a request's submission and its leaving the queue,
summed by the engine where admission happens."""
from perfbench.harness import program_timeline


def read(run):
    return program_timeline.ratio(run, "queue_wait_s", "admitted", 1e3)
