"""Queries answered in the window, per second of the window."""


def read(run):
    close = run.window[1]
    return sum(1 for r in run.answered if r.answered <= close) / run.seconds
