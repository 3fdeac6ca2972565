"""Loop layer: block applications of the latest traced looped
``TransformerLM``, layers x passes (gauge ``horovod_loop_block_applications``,
set at trace time): what the step runs and, under ``remat``, recomputes one
by one; 32 in ``ouro_seq8192_1chip``. A program without the gauge, or one
that traced no looped model, gives nothing."""

from benchmarks.program_counters import gauge


def read(run):
    return gauge("horovod_loop_block_applications") or None
