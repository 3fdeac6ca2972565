"""Window attention layer: device time per step of the layers' rotary schemes
(``hvd_attn_rope``: all of a head at base 10,000 on the sliding layers, half of
one with YaRN's frequencies and factor on the full ones), by the program's own
names from the whole trace (``benchmarks/named_device_time.py``); 0.0 where the
window never ran them."""

from benchmarks.named_device_time import ms


def read(run):
    return ms(run, "hvd_attn_rope")
