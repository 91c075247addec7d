"""Device time of the channel-gated delta-rule step kernel per decode
step, all linear-attention blocks together."""
from perfbench.harness import kda_roofline


def read(run):
    return kda_roofline.step_device_ms(run)
