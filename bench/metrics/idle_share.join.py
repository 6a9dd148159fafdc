"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's operation intervals) / window."""


def read(run):
    r = run.reduction
    if r is None or r.window_s <= 0:
        return None
    return 100.0 * (1.0 - r.busy_s / r.window_s)
