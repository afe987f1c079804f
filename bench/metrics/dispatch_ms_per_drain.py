"""Host time in ``batch.dispatch`` (the asynchronous enqueue of the
drain's device work) per drain of the window."""


def read(run):
    drains = run.window_spans("stream.drain")
    if not drains:
        return None
    return sum(b - a for _, a, b, _ in run.window_spans("batch.dispatch")) \
        / len(drains) * 1e3
