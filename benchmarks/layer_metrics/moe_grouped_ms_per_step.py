"""Experts layer: device time per step of the experts' grouped products, the
two kernels of ``ops/grouped_matmul.py`` (``hvd_moe_experts_gmm`` +
``hvd_moe_experts_tgmm``), by the program's own names from the whole trace
(``benchmarks/named_device_time.py``); 0.0 where the window never ran them."""

from benchmarks.named_device_time import ms


def read(run):
    return ms(run, "hvd_moe_experts_gmm", "hvd_moe_experts_tgmm")
