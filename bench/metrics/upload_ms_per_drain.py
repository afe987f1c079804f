"""Host time in ``batch.upload`` (the device backend's refresh after
appends) per drain of the window."""


def read(run):
    drains = run.window_spans("stream.drain")
    if not drains:
        return None
    return sum(b - a for _, a, b, _ in run.window_spans("batch.upload")) \
        / len(drains) * 1e3
