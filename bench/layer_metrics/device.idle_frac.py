"""device.idle_frac: 1 - (union of op intervals on a device) / traced
window, mean over chips."""


def read(r):
    red = r.reduction
    if red.window_ns <= 0 or red.ndev == 0:
        return None
    return 1.0 - red.busy_ns / red.window_ns
