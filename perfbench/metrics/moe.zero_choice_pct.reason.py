"""Share of the router's top-k choices that fell on zero-compute experts,
over the window's decode steps: the engine's `moe_zero_choices` over
`moe_routed` (with near-uniform scores about zero / all router outputs,
a third here). Those choices cost no expert's weights. A program without
the counter reads as nothing."""
from perfbench.harness import moe_roofline


def read(run):
    routed = moe_roofline.stats_delta(run, "moe_routed")
    zero = moe_roofline.stats_delta(run, "moe_zero_choices")
    if not routed or zero is None:
        return None
    return 100.0 * zero / routed
