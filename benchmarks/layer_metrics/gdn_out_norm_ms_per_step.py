"""GDN mixer layer: device time per step of the head-wise RMSNorm and the silu gate
(``hvd_gdn_out_norm``), by the program's own
names from the whole trace (``benchmarks/named_device_time.py``); 0.0 where
the window never ran them, nothing for a program without these names."""

from benchmarks.gdn_cost import OUT_NORM
from benchmarks.named_device_time import ms


def read(run):
    return ms(run, *OUT_NORM)
