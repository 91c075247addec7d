"""Flash attention forward and backward: share of its roofline."""
from perfbench.harness import readers


def read(run):
    return readers.flash_roofline_pct(run)
