"""Process start to the opening of the window: loading, making the
weights, warming up and, in a first run, compiling."""


def read(run):
    return run.setup_s
