"""GDN mixer layer: device time per step of the L2 norms, the split into heads, the log-decay
and beta (``hvd_gdn_gate``), by the program's own
names from the whole trace (``benchmarks/named_device_time.py``); 0.0 where
the window never ran them, nothing for a program without these names."""

from benchmarks.gdn_cost import GATE
from benchmarks.named_device_time import ms


def read(run):
    return ms(run, *GATE)
