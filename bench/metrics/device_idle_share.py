"""device_idle_share: 1 - (union of device-operation intervals) / (traced
window), in per cent, averaged over the cell's chips."""


def read(r):
    s = r.summary
    if s is None or s.window_s <= 0:
        return None
    return 100.0 * (1.0 - s.busy_mean_s / s.window_s)
