"""Device time of one one-shot prefill dispatch."""
from perfbench.harness import readers


def read(run):
    return readers.per_run_ms(run, (readers.PREFILL,))
