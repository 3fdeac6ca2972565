"""KDA mixer layer: the upper end of beta's range in the latest traced
``models.kda.KDAMixer`` (gauge ``horovod_kda_beta_range``, set at trace time):
2 says the doubled beta (negative eigenvalues allowed) is live in what was
timed, 1 that beta is the sigmoid itself. A program without the gauge, or one
that traced no mixer, gives nothing."""

from benchmarks.program_counters import gauge


def read(run):
    return gauge("horovod_kda_beta_range") or None
