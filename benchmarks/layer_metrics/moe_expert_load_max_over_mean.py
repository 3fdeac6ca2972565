"""Experts layer: rows of the fullest expert over the mean, on the cell's own
first batch with its seeded parameters (gauge
``horovod_moe_expert_load_max_over_mean``, set by the configuration's check
phase through ``ops.moe.record_expert_load``; the step never sets it)."""

from benchmarks.program_counters import gauge


def read(run):
    return gauge("horovod_moe_expert_load_max_over_mean")
