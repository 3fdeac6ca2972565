"""Loop layer: the least time the chip could take for the head's readings of
a step, one a pass of the stack, and their loss - max(operations / peak
FLOP/s, bytes / peak bytes/s), from shapes
(``benchmarks/loop_cost.loop_head_step_cost``: three vocabulary products a
pass, the kernel and its gradient moved once, every pass's stream and its
gradient once: what the MODEL needs, whatever implements it) - over the time
measured under ``hvd_lm_head``. An earlier line says which bound applies."""

from benchmarks.loop_cost import HEAD
from benchmarks.named_device_time import ms


def read(run):
    needed = run.get("cost", {}).get("loop_head")
    measured = ms(run, *HEAD)
    if needed is None or not measured:
        return None
    by_flops = needed["flops"] / run["peaks"]["bf16_flops_per_s"]
    by_bytes = needed["bytes"] / run["peaks"]["hbm_bytes_per_s"]
    run["log"](f"loop head passes: least time "
               f"{max(by_flops, by_bytes) * 1e3:.3f} ms per step, bound by "
               f"{'compute' if by_flops >= by_bytes else 'HBM bandwidth'} "
               f"({by_flops * 1e3:.3f} ms by operations, "
               f"{by_bytes * 1e3:.3f} ms by bytes)")
    return 100.0 * max(by_flops, by_bytes) * 1e3 / measured
