"""Short-convolution mixer layer: the taps of the latest traced gated short
convolution (gauge ``horovod_short_conv_taps``, set at trace time from the
call); 3 says LFM2's mixer is live in what was timed. A program without the
gauge, or one that traced no such mixer, gives nothing."""

from benchmarks.program_counters import gauge


def read(run):
    return gauge("horovod_short_conv_taps") or None
