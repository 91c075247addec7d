"""Device time per decode step: the fused chunk over its steps, and the
single step."""
from perfbench.harness import readers


def read(run):
    return readers.per_run_ms(
        run, (readers.DECODE_CHUNKED, readers.DECODE_STEP),
        {readers.DECODE_CHUNKED: run.facts["decode_chunk"]})
