"""Experts layer: device time per step of the sort by expert and the gathers
into that order (``hvd_moe_dispatch``; the loops over live windows with their
bodies, once), by the program's own names from the whole trace
(``benchmarks/named_device_time.py``); 0.0 where the window never ran them."""

from benchmarks.named_device_time import ms


def read(run):
    return ms(run, "hvd_moe_dispatch")
