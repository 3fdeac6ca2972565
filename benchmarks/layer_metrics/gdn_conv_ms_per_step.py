"""GDN mixer layer: device time per step of the three causal depthwise convolutions + silu
(``hvd_gdn_conv`` and the kernels ``hvd_gdn_conv_fwd`` / ``_bwd``), by the program's own
names from the whole trace (``benchmarks/named_device_time.py``); 0.0 where
the window never ran them, nothing for a program without these names."""

from benchmarks.gdn_cost import CONV
from benchmarks.named_device_time import ms


def read(run):
    return ms(run, *CONV)
