"""Loop layer: device time per step of a looped model's early exit
(``hvd_loop_exit``: the gates' product and sigmoid after every pass, the exit
distribution, its entropy and the weights handed to the loss, forward and
backward), by the program's own names from the whole trace
(``benchmarks/named_device_time.py``); 0.0 where the window never ran them,
nothing for a program that does not know the name."""

from benchmarks.loop_cost import EXIT
from benchmarks.named_device_time import ms


def read(run):
    return ms(run, *EXIT)
