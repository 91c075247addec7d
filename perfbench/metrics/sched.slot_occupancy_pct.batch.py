"""Share of decode slots that held a live request, over the decode steps
dispatched inside the window."""


def read(run):
    f = run.facts
    inside = [d for d in f["decodes"]
              if f["t_open"] <= d[0] and d[1] <= f["t_close"]]
    steps = sum(c for _, _, c, _, _ in inside)
    if not steps:
        return None
    return 100.0 * sum(c * a for _, _, c, a, _ in inside) \
        / (steps * f["n_slots"])
