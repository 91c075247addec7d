"""Share of the router's top-k choices that fell on experts held here,
over the window's decode steps (about held / published experts by
construction: a fault in the share shows here)."""
from perfbench.harness import moe_roofline


def read(run):
    routed = moe_roofline.stats_delta(run, "moe_routed")
    if not routed:
        return None
    return 100.0 * moe_roofline.stats_delta(run, "moe_held_choices") \
        / routed
