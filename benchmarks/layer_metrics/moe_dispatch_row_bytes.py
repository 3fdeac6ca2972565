"""Experts layer: bytes of ONE row the latest traced
``ops.moe.dropless_experts`` dispatches (gauge
``horovod_moe_dispatch_row_bytes``, set at trace time): the width the experts
read times the activations' itemsize, 2,048 where they live in a 1,024-wide
latent. What an expert-parallel exchange would carry a pair. A program
without the gauge, or one that traced no such layer, gives nothing."""

from benchmarks.program_counters import gauge


def read(run):
    return gauge("horovod_moe_dispatch_row_bytes") or None
