"""Kernels layer: device time per step of the flash backward kernel for dq
(``hvd_flash_bwd_dq``), by the program's own names from the whole trace
(``benchmarks/named_device_time.py``); 0.0 where the window never ran them."""

from benchmarks.named_device_time import ms


def read(run):
    return ms(run, "hvd_flash_bwd_dq")
