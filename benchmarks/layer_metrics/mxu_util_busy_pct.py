"""Models layer: operations the forward and backward need per step (from
shapes; causal half, recompute not counted) over the first device's busy
seconds, against the published bf16 peak. End-to-end MFU = this x (1 - idle)."""


def read(run):
    t = run["trace"]
    busy = t["devices"][t["first"]]["busy_s"]
    achieved = run["cost"]["model_flops"] * t["steps"] / busy
    return 100.0 * achieved / run["peaks"]["bf16_flops_per_s"]
