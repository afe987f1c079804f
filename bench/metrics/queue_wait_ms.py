"""Mean wait of a request between admission and the drain that takes it
(the program's ``stream.queued`` spans that open in the window)."""


def read(run):
    spans = run.window_spans("stream.queued")
    if not spans:
        return None
    return sum(b - a for _, a, b, _ in spans) / len(spans) * 1e3
