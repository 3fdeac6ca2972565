"""Kernels layer: summed device durations of the Mosaic custom calls (the
three flash kernels) on the first device, per step."""


def read(run):
    t = run["trace"]
    if run["cost"]["kernel"] is None:
        return None
    return t["devices"][t["first"]]["kernel_s"] / t["steps"] * 1e3
