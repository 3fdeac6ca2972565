"""Window attention layer: block steps the latest traced windowed flash call
executes over those of the causal-dense call at the same blocks (gauge
``horovod_flash_window_block_share``, set at trace time). A program without
the gauge, or one that traced no windowed call, gives nothing."""

from benchmarks.program_counters import gauge


def read(run):
    return gauge("horovod_flash_window_block_share") or None
