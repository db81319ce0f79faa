"""Share of the window in which no operation ran on the device, from the
trace's busy union, averaged over the cell's chips, in percent."""


def compute(rec):
    t = rec["trace"]
    if t["window_s"] <= 0 or t["n_devices"] == 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
