"""Share of the held experts that some live slot chose, over the
window's decode steps and blocks: the engine's `moe_experts_hit` over
`moe_steps` x `moe_experts_held`."""
from perfbench.harness import moe_roofline


def read(run):
    steps = moe_roofline.stats_delta(run, "moe_steps")
    held = run.facts["stats_after"].get("moe_experts_held")
    if not steps or not held:
        return None
    return 100.0 * moe_roofline.stats_delta(run, "moe_experts_hit") \
        / (steps * held)
