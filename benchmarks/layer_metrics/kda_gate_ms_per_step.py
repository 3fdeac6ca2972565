"""KDA mixer layer: device time per step of the L2 norms, softplus, log-decay and beta
(``hvd_kda_gate``), by the program's own
names from the whole trace (``benchmarks/named_device_time.py``); 0.0 where
the window never ran them, nothing for a program without these names."""

from benchmarks.kda_cost import GATE
from benchmarks.named_device_time import ms


def read(run):
    return ms(run, *GATE)
