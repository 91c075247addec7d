"""Share of the live (slot, routed block) rows that chose at least one
expert held here, over the window's decode steps: the engine's
`moe_rows_local` over `moe_routed` / top_k. Under device-limited routing
(8 groups, 3 reached, one group held) about 3/8 times the chance that one
of a row's choices then falls in the held group: the rows an exchange
would bring to this chip. A program without the counter reads as
nothing."""
from perfbench.harness import moe_roofline


def read(run):
    routed = moe_roofline.stats_delta(run, "moe_routed")
    local = moe_roofline.stats_delta(run, "moe_rows_local")
    top_k = run.sizes.get("topk")
    if not routed or local is None or not top_k:
        return None
    return 100.0 * local * top_k / routed
