"""Experts layer: the least time the chip could take for the held experts'
grouped products of a step - max(operations / peak FLOP/s, bytes / peak
bytes/s) from shapes (``benchmarks/latent_moe_cost.grouped_step_cost``:
experts without a gate, two products forward as often as the forward is
called, four backward) over the rows the routing REALLY sent this rank in
the window's last steps (gauge ``horovod_moe_live_rows_per_step``; not a
balanced router's, so that an unbalanced router does not read as slow
kernels) - over the time of ``hvd_moe_experts_gmm`` + ``_tgmm`` by the
program's own names. An earlier line says which bound applies. A program
without the gauge gives nothing."""

from benchmarks.latent_moe_cost import GROUPED, at_rows
from benchmarks.program_counters import gauge
from benchmarks.swa_cost import roofline_pct


def read(run):
    needed, live = run["cost"].get("latent_moe"), gauge(
        "horovod_moe_live_rows_per_step")
    if needed is None or not live:
        return None
    return roofline_pct(run, at_rows(needed, live), GROUPED,
                        f"grouped products of the latent experts at {live:.0f} "
                        f"live rows a step ({needed['rows']:.0f} balanced)")
