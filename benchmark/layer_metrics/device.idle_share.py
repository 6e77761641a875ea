"""Share of the traced sub-window in which no operation ran on the device:
1 - union of the device planes' operation intervals / sub-window, %."""


def read(run):
    t = run["trace"]
    if t is None:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
