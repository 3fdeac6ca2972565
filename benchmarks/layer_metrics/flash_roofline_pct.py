"""Kernels layer: the least time the chip could take for the flash kernels'
needed operations and bytes - max(FLOPs / peak FLOP/s, bytes / peak bytes/s),
from shapes - over the kernel time measured. An earlier line says which bound
applies."""


def read(run):
    t, kernel = run["trace"], run["cost"]["kernel"]
    seconds = t["devices"][t["first"]]["kernel_s"] / t["steps"]
    if kernel is None or not seconds > 0:
        return None
    by_flops = kernel["flops"] / run["peaks"]["bf16_flops_per_s"]
    by_bytes = kernel["bytes"] / run["peaks"]["hbm_bytes_per_s"]
    run["log"](f"flash kernels: least time {max(by_flops, by_bytes) * 1e3:.3f} ms "
               f"per step, bound by {'compute' if by_flops >= by_bytes else 'HBM bandwidth'} "
               f"({by_flops * 1e3:.3f} ms by operations, {by_bytes * 1e3:.3f} ms by bytes)")
    return 100.0 * max(by_flops, by_bytes) / seconds
