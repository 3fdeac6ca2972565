"""Window attention layer: device time per step of the three flash kernels
under a window (``hvd_flash_win_fwd`` + ``_bwd_dq`` + ``_bwd_dkv``; the forward
twice a layer under ``remat``), by the program's own names from the whole trace
(``benchmarks/named_device_time.py``); 0.0 where the window never ran them."""

from benchmarks.named_device_time import ms
from benchmarks.swa_cost import WINDOWED


def read(run):
    return ms(run, *WINDOWED)
