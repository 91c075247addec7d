"""Every token whose `on_token` time lies in the window, over its
length."""
from perfbench.harness import readers


def read(run):
    return len(readers.in_window(run, run.facts["token_times"])) \
        / run.window_s
