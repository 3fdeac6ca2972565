"""Fusion + exchange layer: bytes each chip hands to the gradient exchange per
step, in MiB: the latest compiled plan's buckets after their wire casts
(gauge ``horovod_compiled_wire_bytes_per_step``, set at trace time). Not 0 on
one chip: the plan is made, then XLA drops a world of one's collective."""

from benchmarks.program_counters import gauge


def read(run):
    sent = gauge("horovod_compiled_wire_bytes_per_step")
    return None if sent is None else sent / 2 ** 20
