"""Host time drains waited for the session's drain lock behind an append
or delete (``stream.lock_wait``) per drain of the window.  A program that
records no lock waits at all reads nothing: it predates the span, which
every drain records."""


def read(run):
    drains = run.window_spans("stream.drain")
    if not drains or not any(s[0] == "stream.lock_wait" for s in run.spans):
        return None
    waits = run.window_spans("stream.lock_wait")
    return sum(b - a for _, a, b, _ in waits) / len(drains) * 1e3
