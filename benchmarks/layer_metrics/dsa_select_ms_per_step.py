"""Selected attention layer: device time per step of the selection itself
(scope ``hvd_dsa_select``): of each chunk of query rows the exact top-k by a
radix select on the scores' bit patterns, and packing the kept pairs as bits;
once a layer, the selection being saved across the recomputation. By the
program's own names from the whole trace; nothing for a program that does not
know the name."""

from benchmarks.dsa_cost import SELECT
from benchmarks.named_device_time import ms


def read(run):
    return ms(run, *SELECT)
