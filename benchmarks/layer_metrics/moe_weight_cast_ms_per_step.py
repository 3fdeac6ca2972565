"""Experts layer: device time per step of the expert weights' cast from float32
to the layer's dtype and of their gradients' cast back
(``hvd_moe_weight_cast``), by the program's own names from the whole trace
(``benchmarks/named_device_time.py``); 0.0 where the window never ran them,
nothing for a program that does not know the name."""

from benchmarks.named_device_time import ms


def read(run):
    return ms(run, "hvd_moe_weight_cast")
