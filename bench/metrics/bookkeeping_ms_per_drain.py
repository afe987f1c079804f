"""Host time issuing the eager device ops around the kernels (cost
counters, feedback popcounts, stacks and uploads: the backend's
``bookkeeping_host_s``) per drain.  The counters run from the window's
open until every request of the window is answered, so the drains counted
are the ``stream.drain`` spans that open in that stretch."""


def read(run):
    c = run.counters
    drains = [s for s in run.spans
              if s[0] == "stream.drain" and s[1] >= run.window[0]]
    if not c or not drains or "bookkeeping_host_s" not in c:
        return None
    return c["bookkeeping_host_s"] / len(drains) * 1e3
