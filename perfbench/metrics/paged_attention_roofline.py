"""Paged decode attention: share of its roofline."""
from perfbench.harness import readers


def read(run):
    return readers.paged_roofline_pct(run)
