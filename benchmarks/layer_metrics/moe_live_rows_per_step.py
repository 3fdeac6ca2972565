"""Experts layer: rows on the held experts that the expert layers' passes
visited a step, summed over the layers, mean over the window's last steps
(gauge ``horovod_moe_live_rows_per_step``, which the configuration's step
feeds from the ``moe_live_rows`` each layer sows, through
``metrics.overlap.record_moe_live_rows``). The routing decides it: 33,792 in
``nemotron3s_seq8192_1chip`` under a balanced router. A program without the
gauge gives nothing."""

from benchmarks.program_counters import gauge


def read(run):
    return gauge("horovod_moe_live_rows_per_step") or None
