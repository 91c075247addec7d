"""Device time of one fused decode chunk."""
from perfbench.harness import readers


def read(run):
    return readers.per_run_ms(run, (readers.DECODE_CHUNKED,))
