"""GDN mixer layer: device time per step of the whole Gated DeltaNet mixer
(everything under ``hvd_gdn``: projections, convolutions, gates, the chunked
delta rule, the head norm and output gate), by the program's own
names from the whole trace (``benchmarks/named_device_time.py``); 0.0 where
the window never ran them, nothing for a program without these names."""

from benchmarks.gdn_cost import MIXER
from benchmarks.named_device_time import ms


def read(run):
    return ms(run, *MIXER)
