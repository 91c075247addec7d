"""The ungated relu^2 grouped expert product inside the decode programs:
share of its roofline."""
from perfbench.harness import moe_relu2_roofline


def read(run):
    return moe_relu2_roofline.roofline_pct(run)
