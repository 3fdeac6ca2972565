"""Device layer: the share of the first device's busy time that goes by one of
the program's own names, ``100 x (busy - unnamed) / busy`` of the whole trace's
table (``benchmarks/named_device_time.py``); nothing without a table or a busy
device."""

from benchmarks.named_device_time import table


def read(run):
    found = table(run)
    if found is None or not found["busy"]:
        return None
    return 100.0 * (found["busy"] - found["unnamed"]) / found["busy"]
