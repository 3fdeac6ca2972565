"""Experts layer: rows of the sorted buffer the latest traced
``ops.moe.dropless_experts`` gathers its tokens into and its results out of
(gauge ``horovod_moe_dispatch_rows``, set at trace time): N x top_k while the
gathers move the worst-case buffer, the held pairs' rows once they follow the
live ones. A program without the gauge, or one that traced no such layer,
gives nothing."""

from benchmarks.program_counters import gauge


def read(run):
    return gauge("horovod_moe_dispatch_rows") or None
