"""Experts layer: device time per step of the two latent projections of the
LatentMoE layers (``hvd_moe_latent``: dim -> latent before the dispatch, latent
-> dim after the weighted sum, forward and backward), by the program's own
names from the whole trace (``benchmarks/named_device_time.py``); nothing for
a program that does not know the name."""

from benchmarks.named_device_time import ms


def read(run):
    return ms(run, "hvd_moe_latent")
