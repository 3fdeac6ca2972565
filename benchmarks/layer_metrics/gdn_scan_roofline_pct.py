"""GDN mixer layer: the least time the chip could take for the delta-rule
calls of a step - max(operations / peak FLOP/s, bytes / peak bytes/s), from
shapes and the configuration's chunk (``benchmarks/gdn_cost.gdn_scan_step_cost``:
what the MODEL needs in its chunked form at its own widths, whatever
implements it) - over the time ``gdn_scan_ms_per_step`` measured under the
scan's names. An earlier line says which bound applies."""

from benchmarks.gdn_cost import SCAN
from benchmarks.named_device_time import ms


def read(run):
    needed = run["cost"].get("gdn_scan")
    measured = ms(run, *SCAN)
    if needed is None or not measured:
        return None
    by_flops = needed["flops"] / run["peaks"]["bf16_flops_per_s"]
    by_bytes = needed["bytes"] / run["peaks"]["hbm_bytes_per_s"]
    run["log"](f"GDN scans: least time {max(by_flops, by_bytes) * 1e3:.3f} "
               f"ms per step, bound by "
               f"{'compute' if by_flops >= by_bytes else 'HBM bandwidth'} "
               f"({by_flops * 1e3:.3f} ms by operations, "
               f"{by_bytes * 1e3:.3f} ms by bytes)")
    return 100.0 * max(by_flops, by_bytes) * 1e3 / measured
