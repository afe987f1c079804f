"""Records the engine evaluated (the paper's cost metric, the backend's
``records_evaluated`` counter) per query answered, over the window."""


def read(run):
    c = run.counters
    if not c or not c["completed"]:
        return None
    return c["records_evaluated"] / c["completed"]
