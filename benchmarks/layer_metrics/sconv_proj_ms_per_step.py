"""Short-convolution mixer layer: device time per step of the mixer's two
projections, dim -> 3 dim and dim -> dim (``hvd_sconv_proj``), by the
program's own names from the whole trace (``benchmarks/named_device_time.py``);
0.0 where the window never ran them, nothing for a program without the name."""

from benchmarks.lfm2_cost import PROJ
from benchmarks.named_device_time import ms


def read(run):
    return ms(run, *PROJ)
