"""Kernels layer: device time per step of the flash forward kernel
(``hvd_flash_fwd``; twice a layer under ``remat``), by the program's own names
from the whole trace (``benchmarks/named_device_time.py``); 0.0 where the
window never ran them."""

from benchmarks.named_device_time import ms


def read(run):
    return ms(run, "hvd_flash_fwd")
