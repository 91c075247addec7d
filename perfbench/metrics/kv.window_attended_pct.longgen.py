"""Of the positions in the K/V blocks' contexts over the window's decode
steps, the share their attention reads: the engine's
`loop.kv_positions_attended` over `loop.kv_positions_context`. 100 on a
net without window blocks; a program without the counters reads as
nothing."""
from perfbench.harness import window_roofline


def read(run):
    return window_roofline.attended_pct(run)
