"""Device time of one one-shot prefill dispatch (one prompt, every held
expert read)."""
from perfbench.harness import readers


def read(run):
    return readers.per_run_ms(run, (readers.PREFILL,))
