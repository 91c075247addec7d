"""Device time of the grouped expert kernel per decode step."""
from perfbench.harness import moe_relu2_roofline


def read(run):
    return moe_relu2_roofline.step_device_ms(run)
