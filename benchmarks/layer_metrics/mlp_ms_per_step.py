"""Models layer: device time per step of the blocks' dense MLP halves
(``hvd_mlp``: ``mlp_in`` / gelu / ``mlp_out``, or ``mlp_gate`` / ``mlp_up`` /
silu x up / ``mlp_down``; an expert layer's work goes by the experts' names),
by the program's own names from the whole trace
(``benchmarks/named_device_time.py``); 0.0 where the window never ran them,
nothing for a program that does not know the name."""

from benchmarks.named_device_time import ms


def read(run):
    return ms(run, "hvd_mlp")
