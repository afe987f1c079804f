"""Device launches per query answered over the window: the device
backend's jitted kernels, set-op programs and eager bookkeeping ops
(``kernel_launches + setop_launches + bookkeeping_launches``)."""

LAUNCHES = ("kernel_launches", "setop_launches", "bookkeeping_launches")


def read(run):
    c = run.counters
    if not c or not c["completed"] or any(k not in c for k in LAUNCHES):
        return None
    return sum(c[k] for k in LAUNCHES) / c["completed"]
