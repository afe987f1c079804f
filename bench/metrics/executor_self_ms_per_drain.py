"""Host time of ``batch.dispatch`` outside the device backend's launches
and zone verdicts, per drain: the BestD machines' and the lockstep
executor's own host logic.  The dispatch spans less the backend's
``kernel_host_s``, ``setop_host_s``, ``bookkeeping_host_s`` and
``zone_host_s``, all over the stretch the counters cover: from the
window's open until every request of the window is answered."""

HOST_S = ("kernel_host_s", "setop_host_s", "bookkeeping_host_s",
          "zone_host_s")


def read(run):
    c = run.counters
    lo = run.window[0]
    drains = [s for s in run.spans if s[0] == "stream.drain" and s[1] >= lo]
    if not c or not drains or any(k not in c for k in HOST_S):
        return None
    dispatch = sum(b - a for name, a, b, _ in run.spans
                   if name == "batch.dispatch" and a >= lo)
    return (dispatch - sum(c[k] for k in HOST_S)) / len(drains) * 1e3
