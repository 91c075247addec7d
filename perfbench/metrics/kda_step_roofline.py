"""The delta-rule step with a decay a key channel inside the decode
programs: share of its roofline."""
from perfbench.harness import kda_roofline


def read(run):
    return kda_roofline.roofline_pct(run)
