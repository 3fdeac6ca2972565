"""Mamba-2 mixer layer: the least time the chip could take for the scans of a
step - max(operations / peak FLOP/s, bytes / peak bytes/s), from shapes
(``benchmarks/ssd_cost.ssd_step_cost``) - over the time ``ssd_ms_per_step``
measured (a lower bound of the scan's time, so this is an upper bound of its
share while a label of the scan is outside the ten kept). An earlier line
says which bound applies."""

from benchmarks.ssd_cost import SSD_LABELS, seconds_per_step


def read(run):
    needed = run["cost"].get("ssd")
    seconds = seconds_per_step(run["trace"], SSD_LABELS)
    if needed is None or seconds is None:
        return None
    by_flops = needed["flops"] / run["peaks"]["bf16_flops_per_s"]
    by_bytes = needed["bytes"] / run["peaks"]["hbm_bytes_per_s"]
    run["log"](f"state-space scans: least time {max(by_flops, by_bytes) * 1e3:.3f} "
               f"ms per step, bound by "
               f"{'compute' if by_flops >= by_bytes else 'HBM bandwidth'} "
               f"({by_flops * 1e3:.3f} ms by operations, "
               f"{by_bytes * 1e3:.3f} ms by bytes)")
    return 100.0 * max(by_flops, by_bytes) / seconds
