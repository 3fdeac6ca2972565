"""Mamba-2 mixer layer: the least time the chip could take for the scans of a
step - max(operations / peak FLOP/s, bytes / peak bytes/s), from shapes
(``benchmarks/ssd_cost.ssd_step_cost``: NEEDED work, the forward that
``remat`` runs a second time not counted) - over the time
``ssd_scan_ms_per_step`` measured: everything under ``hvd_ssd_scan`` by the
program's own names, whatever the rank of its labels
(``benchmarks/named_device_time.py``). An earlier line says which bound
applies."""

from benchmarks.named_device_time import ms


def read(run):
    needed = run["cost"].get("ssd")
    measured = ms(run, "hvd_ssd_scan")
    if needed is None or not measured:
        return None
    by_flops = needed["flops"] / run["peaks"]["bf16_flops_per_s"]
    by_bytes = needed["bytes"] / run["peaks"]["hbm_bytes_per_s"]
    run["log"](f"state-space scans: least time {max(by_flops, by_bytes) * 1e3:.3f} "
               f"ms per step, bound by "
               f"{'compute' if by_flops >= by_bytes else 'HBM bandwidth'} "
               f"({by_flops * 1e3:.3f} ms by operations, "
               f"{by_bytes * 1e3:.3f} ms by bytes)")
    return 100.0 * max(by_flops, by_bytes) * 1e3 / measured
