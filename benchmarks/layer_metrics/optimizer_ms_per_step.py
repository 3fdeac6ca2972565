"""Optimizer wrapper layer: device time of non-collective ops under the
step's ``bench_optimizer`` scope (fusion pack/unpack and the optax update),
first device, per step."""

from benchmarks.reduce_trace import SCOPE_OPTIMIZER


def read(run):
    t = run["trace"]
    compute = t["devices"][t["first"]]["compute_s"]
    return compute.get(SCOPE_OPTIMIZER, 0.0) / t["steps"] * 1e3
