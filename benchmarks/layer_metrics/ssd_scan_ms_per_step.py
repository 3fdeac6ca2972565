"""Mamba-2 mixer layer: device time per step of the chunked state-space scan,
everything under ``hvd_ssd_scan`` once: the forward's loop and the backward's
with their bodies, the broadcast, the relayouts XLA puts under the scope, by
the program's own names from the whole trace
(``benchmarks/named_device_time.py``); 0.0 where the window never ran them."""

from benchmarks.named_device_time import ms


def read(run):
    return ms(run, "hvd_ssd_scan")
