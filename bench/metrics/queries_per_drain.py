"""Queries the window's drains answered, per drain."""


def read(run):
    lo, hi = run.window
    drains = [d for d in run.drains if lo <= d.t0 < hi]
    if not drains:
        return None
    return sum(d.queries for d in drains) / len(drains)
