"""Latent attention layer: the least time the chip could take for the flash
kernel calls of a step - max(operations / peak FLOP/s, bytes / peak bytes/s),
from shapes (``benchmarks/mla_cost.flash_calls_step_cost``: products at 192
and at 128 counted apart, the forward as often as it is called) - over the
time ``mla_flash_ms_per_step`` measured. An earlier line says which bound
applies."""

from benchmarks.mla_cost import FLASH_LABELS, seconds_per_step


def read(run):
    needed = run["cost"].get("mla_flash")
    seconds = seconds_per_step(run["trace"], FLASH_LABELS)
    if needed is None or seconds is None:
        return None
    by_flops = needed["flops"] / run["peaks"]["bf16_flops_per_s"]
    by_bytes = needed["bytes"] / run["peaks"]["hbm_bytes_per_s"]
    run["log"](f"flash kernels at 192 | 128: least time "
               f"{max(by_flops, by_bytes) * 1e3:.3f} ms per step, bound by "
               f"{'compute' if by_flops >= by_bytes else 'HBM bandwidth'} "
               f"({by_flops * 1e3:.3f} ms by operations, "
               f"{by_bytes * 1e3:.3f} ms by bytes)")
    return 100.0 * max(by_flops, by_bytes) / seconds
