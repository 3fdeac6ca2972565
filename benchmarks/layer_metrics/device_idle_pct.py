"""Device: 1 - union of device-op intervals / traced window, averaged over
the cell's chips."""


def read(run):
    t = run["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
