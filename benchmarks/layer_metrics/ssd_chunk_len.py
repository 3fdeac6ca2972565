"""Mamba-2 mixer layer: positions a chunk of the latest traced
``ops.ssd.ssd`` (gauge ``horovod_ssd_chunk_len``, set at trace time); a
program without the gauge, or one that traced no scan, gives nothing."""

from benchmarks.program_counters import gauge


def read(run):
    return gauge("horovod_ssd_chunk_len") or None
