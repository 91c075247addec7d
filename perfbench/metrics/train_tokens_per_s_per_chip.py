"""All tokens of all steps in the window over its length, a chip."""


def read(run):
    f = run.facts
    return f["steps"] * f["tokens_per_step"] / run.window_s / run.chips
