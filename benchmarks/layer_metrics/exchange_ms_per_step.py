"""Fusion + exchange layer: summed device durations of collective ops on the
slowest device, per step. 0 on one chip: XLA removes a world of one."""


def read(run):
    t = run["trace"]
    return t["devices"][t["slowest"]]["collective_s"] / t["steps"] * 1e3
