"""Share of the traced window in which no operation ran on the device:
1 - (union of device-op intervals) / window."""


def read(win):
    t = win.trace
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
