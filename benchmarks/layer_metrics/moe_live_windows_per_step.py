"""Experts layer: windows of 2,048 sorted rows that hold those live rows,
one pass of every expert layer, mean over the window's last steps (gauge
``horovod_moe_live_windows_per_step``, set beside
``horovod_moe_live_rows_per_step``): the trip counts of the held path's
loops, which a step's time follows. 18 in ``nemotron3s_seq8192_1chip`` under
a balanced router (three a layer). A program without the gauge gives
nothing."""

from benchmarks.program_counters import gauge


def read(run):
    return gauge("horovod_moe_live_windows_per_step") or None
