"""Share of the window the scheduler spent inside prefill dispatches,
during which every decode slot waits."""


def read(run):
    f = run.facts
    inside = [post - pre for pre, post, _ in f["prefills"]
              if f["t_open"] <= pre and post <= f["t_close"]]
    return 100.0 * sum(inside) / run.window_s
