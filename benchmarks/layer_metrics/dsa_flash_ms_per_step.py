"""Selected attention layer: device time per step of the three flash kernels
under a selection that is data (``hvd_flash_sel_fwd`` + ``_bwd_dq`` +
``_bwd_dkv``; the forward twice a layer under ``remat``), by the program's own
names from the whole trace (``benchmarks/named_device_time.py``); nothing for a
program that does not know the names."""

from benchmarks.dsa_cost import SELECTED
from benchmarks.named_device_time import ms


def read(run):
    return ms(run, *SELECTED)
