"""Short-convolution mixer layer: device time per step of the doubly gated
convolution, ``C * conv(B * x)`` (``hvd_sconv_conv`` and the kernels
``hvd_sconv_conv_fwd`` / ``_bwd``), by the program's own names from the whole
trace (``benchmarks/named_device_time.py``); 0.0 where the window never ran
them, nothing for a program without these names."""

from benchmarks.lfm2_cost import CONV
from benchmarks.named_device_time import ms


def read(run):
    return ms(run, *CONV)
