"""Models layer: device time per step of the blocks' pre-norms, their residual
adds and the model's final norm (``hvd_norm_add``: one name, because XLA fuses
an add with the norm it feeds), by the program's own names from the whole trace
(``benchmarks/named_device_time.py``); 0.0 where the window never ran them,
nothing for a program that does not know the name."""

from benchmarks.named_device_time import ms


def read(run):
    return ms(run, "hvd_norm_add")
