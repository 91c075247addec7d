"""Device time of one run of the compiled train step."""
from perfbench.harness import readers


def read(run):
    return readers.per_run_ms(run, readers.TRAIN_STEP)
