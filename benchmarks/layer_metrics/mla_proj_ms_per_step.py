"""Latent attention layer: device time per step of latent attention's four
projections and the latent's norm (``hvd_mla_proj``), by the program's own
names from the whole trace (``benchmarks/named_device_time.py``); 0.0 where the
window never ran them."""

from benchmarks.named_device_time import ms


def read(run):
    return ms(run, "hvd_mla_proj")
