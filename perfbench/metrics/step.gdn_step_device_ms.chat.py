"""Device time of the gated delta-rule step kernel per decode step."""
from perfbench.harness import gdn_roofline


def read(run):
    return gdn_roofline.step_device_ms(run)
