"""Experts layer: device time of the experts' grouped products per step, from
the ``breakdown`` labels that name them (``benchmarks/moe_cost.EXPERT_LABELS``).
The reduction keeps its ten longest labels, so this is a lower bound that is
exact while the grouped products are among them."""

from benchmarks.moe_cost import experts_seconds_per_step


def read(run):
    seconds = experts_seconds_per_step(run["trace"])
    return None if seconds is None else seconds * 1e3
