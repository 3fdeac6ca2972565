"""Device layer: device time per step of the device time no program name covers
(``unnamed``): with the names it sums to the first device's busy time, by the
program's own names from the whole trace (``benchmarks/named_device_time.py``);
0.0 where the window never ran them."""

from benchmarks.named_device_time import ms


def read(run):
    return ms(run, "unnamed")
