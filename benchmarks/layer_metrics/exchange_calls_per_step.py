"""Fusion + exchange layer: collective calls per step of the latest compiled
plan: one per bucket (gauge ``horovod_fusion_buckets``), and one per fabric
tier of each bucket where the plan is hierarchical
(``horovod_compiled_hierarchical``)."""

from benchmarks.program_counters import gauge


def read(run):
    buckets = gauge("horovod_fusion_buckets")
    if buckets is None:
        return None
    return buckets * (2.0 if gauge("horovod_compiled_hierarchical") else 1.0)
