"""Window attention layer: device time per step of the per-head sigmoid gate on
the attention output (``hvd_attn_gate``: its projection, the sigmoid, the
product and their backward), by the program's own names from the whole trace
(``benchmarks/named_device_time.py``); 0.0 where the window never ran them."""

from benchmarks.named_device_time import ms


def read(run):
    return ms(run, "hvd_attn_gate")
