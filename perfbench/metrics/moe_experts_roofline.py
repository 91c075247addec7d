"""The grouped expert product inside the decode programs: share of its
roofline."""
from perfbench.harness import moe_roofline


def read(run):
    return moe_roofline.roofline_pct(run)
