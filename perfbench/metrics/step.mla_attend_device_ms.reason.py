"""Device time of the paged latent-attention kernel per decode step, all
sub-layers together."""
from perfbench.harness import mla_roofline


def read(run):
    return mla_roofline.step_device_ms(run)
