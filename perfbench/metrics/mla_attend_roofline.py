"""Paged latent attention inside the decode programs: share of its
roofline."""
from perfbench.harness import mla_roofline


def read(run):
    return mla_roofline.roofline_pct(run)
