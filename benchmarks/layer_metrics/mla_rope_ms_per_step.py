"""Latent attention layer: device time per step of latent attention's split,
rotary and assembling of q and k (``hvd_mla_rope``), by the program's own names
from the whole trace (``benchmarks/named_device_time.py``); 0.0 where the
window never ran them."""

from benchmarks.named_device_time import ms


def read(run):
    return ms(run, "hvd_mla_rope")
