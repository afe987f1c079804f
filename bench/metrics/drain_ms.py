"""Mean wall time of the ``stream.drain`` spans that open in the window."""


def read(run):
    spans = run.window_spans("stream.drain")
    if not spans:
        return None
    return sum(b - a for _, a, b, _ in spans) / len(spans) * 1e3
