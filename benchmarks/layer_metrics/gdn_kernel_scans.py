"""GDN mixer layer: the Gated DeltaNet layers whose latest traced scan took
the scan's pallas kernels (gauge ``horovod_gdn_kernel_scans``, by the mixer's
path in the model): the cell's three linear layers read 3 while every one of
them runs ``hvd_gdn_scan_fwd`` / ``_bwd`` in the step as it was traced. A
program without the gauge, or one whose layers all kept ``jax.numpy``, gives
nothing."""

from benchmarks.program_counters import gauge


def read(run):
    return gauge("horovod_gdn_kernel_scans") or None
