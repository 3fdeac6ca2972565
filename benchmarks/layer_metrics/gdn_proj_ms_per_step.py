"""GDN mixer layer: device time per step of the mixer's q, k, v, decay, beta, gate and o
products (``hvd_gdn_proj``), by the program's own
names from the whole trace (``benchmarks/named_device_time.py``); 0.0 where
the window never ran them, nothing for a program without these names."""

from benchmarks.gdn_cost import PROJ
from benchmarks.named_device_time import ms


def read(run):
    return ms(run, *PROJ)
