"""Loop layer: passes the residual stream makes over the one shared stack in
the latest traced looped ``TransformerLM`` (gauge ``horovod_loop_passes``,
set at trace time from the model's own fields): 4 in ``ouro_seq8192_1chip``.
A program without the gauge, or one that traced no looped model, gives
nothing."""

from benchmarks.program_counters import gauge


def read(run):
    return gauge("horovod_loop_passes") or None
