"""GDN mixer layer: device time per step of the chunked gated delta rule, forward and backward
(``hvd_gdn_scan``: the kernels ``hvd_gdn_scan_fwd`` / ``_bwd`` and the lanes' padding
round them), by the program's own
names from the whole trace (``benchmarks/named_device_time.py``); 0.0 where
the window never ran them, nothing for a program without these names."""

from benchmarks.gdn_cost import SCAN
from benchmarks.named_device_time import ms


def read(run):
    return ms(run, *SCAN)
