"""Mean wall time of a ``StreamSession.append`` call that opens in the
window (``stream.append``), its wait for the drain lock included."""


def read(run):
    spans = run.window_spans("stream.append")
    if not spans:
        return None
    return sum(b - a for _, a, b, _ in spans) / len(spans) * 1e3
