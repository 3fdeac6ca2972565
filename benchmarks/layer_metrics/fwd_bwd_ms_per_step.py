"""Models layer: device time of non-collective, non-kernel ops under the
step's ``bench_fwd_bwd`` scope, first device, per step."""

from benchmarks.reduce_trace import SCOPE_FWD_BWD


def read(run):
    t = run["trace"]
    compute = t["devices"][t["first"]]["compute_s"]
    return compute.get(SCOPE_FWD_BWD, 0.0) / t["steps"] * 1e3
