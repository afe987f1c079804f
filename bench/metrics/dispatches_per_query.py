"""Device dispatches and kernel invocations (the backend's counters) per
query answered, over the window."""


def read(run):
    c = run.counters
    if not c or not c["completed"]:
        return None
    return (c["device_dispatches"] + c["kernel_invocations"]) / c["completed"]
