"""The gated delta-rule step inside the decode programs: share of its
roofline."""
from perfbench.harness import gdn_roofline


def read(run):
    return gdn_roofline.roofline_pct(run)
