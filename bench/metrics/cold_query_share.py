"""Share of the queries answered over the window that ran op by op because
their whole-tape program was not compiled yet (the device backend's
``cold_tapes`` counter over the session's ``completed``), in percent.  A
program that does not count cold runs, or a window without drains, reads
nothing."""


def read(run):
    c = run.counters
    if not c or not c["completed"] or "cold_tapes" not in c:
        return None
    return 100.0 * c["cold_tapes"] / c["completed"]
