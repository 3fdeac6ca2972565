"""GDN mixer layer: positions a chunk of the latest traced ``ops.gdn.gdn``
(gauge ``horovod_gdn_chunk_len``, set at trace time from the call); a program
without the gauge, or one that traced no such scan, gives nothing."""

from benchmarks.program_counters import gauge


def read(run):
    return gauge("horovod_gdn_chunk_len") or None
