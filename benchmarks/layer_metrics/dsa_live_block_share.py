"""Selected attention layer: block steps a head of the selected flash kernels
ran a step - those that hold a selected pair - over those of the causal-dense
call at the same blocks (gauges ``horovod_dsa_live_block_steps_per_step`` over
``horovod_dsa_dense_block_steps_per_step``, both fed by the configuration's
step from the data). 1 while the selection is scattered over the row, as
seeded random weights leave it. A program without the gauges gives nothing."""

from benchmarks.program_counters import gauge


def read(run):
    live = gauge("horovod_dsa_live_block_steps_per_step")
    dense = gauge("horovod_dsa_dense_block_steps_per_step")
    return live / dense if live and dense else None
