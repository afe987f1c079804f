"""Column bytes uploaded to the device (the backend's ``uploaded_bytes``
counter), in MB (10^6 bytes), per drain of the window."""


def read(run):
    c = run.counters
    drains = run.window_spans("stream.drain")
    if not c or not drains:
        return None
    return c["uploaded_bytes"] / 1e6 / len(drains)
