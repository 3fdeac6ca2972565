"""Experts layer: device time per step of back to token order and the weighted
sum (``hvd_moe_combine``; its loops with their bodies, once), by the program's
own names from the whole trace (``benchmarks/named_device_time.py``); 0.0 where
the window never ran them."""

from benchmarks.named_device_time import ms


def read(run):
    return ms(run, "hvd_moe_combine")
