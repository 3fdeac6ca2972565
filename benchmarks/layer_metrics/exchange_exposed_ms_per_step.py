"""Fusion + exchange layer: the part of the collective intervals during which
no compute op runs on that device (slowest device), per step."""


def read(run):
    t = run["trace"]
    return t["devices"][t["slowest"]]["collective_exposed_s"] / t["steps"] * 1e3
