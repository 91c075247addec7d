"""Kernel families of `kernel_dispatch.kernel_verdicts()` with a shape
class that passed its probe in this process."""
from perfbench.harness import readers


def read(run):
    return readers.families_engaged(run)
