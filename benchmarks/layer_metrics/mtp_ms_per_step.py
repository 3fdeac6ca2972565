"""Models layer: device time per step of the multi-token-prediction module's
own work (``hvd_mtp``: its two norms, the concatenation, the projection 2 dim
-> dim, its final norm and its pass of the shared head with the loss; its
blocks go by their own names), by the program's own names from the whole
trace (``benchmarks/named_device_time.py``); nothing for a program that does
not know the name."""

from benchmarks.named_device_time import ms


def read(run):
    return ms(run, "hvd_mtp")
