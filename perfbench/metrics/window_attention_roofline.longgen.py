"""Paged attention inside the decode programs of a net whose K/V blocks
read a window of their context: the `paged_attention` calls' device time
a decode step against the least the chip could take for the positions
the engine counts as attended (`window_roofline.paged_window_decode`)."""
from perfbench.harness import window_roofline


def read(run):
    return window_roofline.roofline_pct(run)
