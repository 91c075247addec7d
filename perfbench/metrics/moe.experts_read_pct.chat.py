"""Share of the held experts that the grouped product was told to read,
over the window's decode steps and blocks: the engine's
`moe_experts_read` over `moe_steps` x `moe_experts_held`. Beside
`moe.experts_hit_pct.chat` it says whether the kernel's walk leaves out
what no live slot chose (equal) or reads every held expert (100). A
program without the counter reads as nothing."""
from perfbench.harness import moe_roofline


def read(run):
    steps = moe_roofline.stats_delta(run, "moe_steps")
    held = run.facts["stats_after"].get("moe_experts_held")
    read_ = moe_roofline.stats_delta(run, "moe_experts_read")
    if not steps or not held or read_ is None:
        return None
    return 100.0 * read_ / (steps * held)
