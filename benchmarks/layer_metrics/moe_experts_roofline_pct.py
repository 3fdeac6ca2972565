"""Experts layer: the least time the chip could take for the nine grouped
products of a step - max(operations / peak FLOP/s, bytes / peak bytes/s), from
shapes (``benchmarks/moe_cost.grouped_products_step_cost``) - over the time
``moe_experts_ms_per_step`` measured. An earlier line says which bound applies."""

from benchmarks.moe_cost import experts_seconds_per_step


def read(run):
    needed = run["cost"].get("experts")
    seconds = experts_seconds_per_step(run["trace"])
    if needed is None or seconds is None:
        return None
    by_flops = needed["flops"] / run["peaks"]["bf16_flops_per_s"]
    by_bytes = needed["bytes"] / run["peaks"]["hbm_bytes_per_s"]
    run["log"](f"grouped products: least time {max(by_flops, by_bytes) * 1e3:.3f} "
               f"ms per step, bound by "
               f"{'compute' if by_flops >= by_bytes else 'HBM bandwidth'} "
               f"({by_flops * 1e3:.3f} ms by operations, "
               f"{by_bytes * 1e3:.3f} ms by bytes)")
    return 100.0 * max(by_flops, by_bytes) / seconds
