"""Share of the traced window in which no operation ran on the device,
averaged over the chips the cell uses."""


def read(r):
    t = r.trace
    if t.window_s <= 0 or not t.busy_s:
        return None
    return 100.0 * (1.0 - t.mean_busy_s / t.window_s)
