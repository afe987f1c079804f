"""Host time in annotation, dictionary rewrite and planning
(``batch.annotate`` + ``batch.rewrite_strings`` + ``batch.plan``) per query
drained in the window."""

PLANNING = ("batch.annotate", "batch.rewrite_strings", "batch.plan")


def read(run):
    lo, hi = run.window
    queries = sum(d.queries for d in run.drains if lo <= d.t0 < hi)
    if not queries:
        return None
    secs = sum(b - a for name in PLANNING
               for _, a, b, _ in run.window_spans(name))
    return secs / queries * 1e3
