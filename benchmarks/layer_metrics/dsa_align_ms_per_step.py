"""Selected attention layer: device time per step of the alignment loss and
the indexer's backward, which come out of one tile loop (kernel
``hvd_dsa_align_tiles``: the main heads' probabilities recomputed from the
forward's logsumexp, the indexer's scores, KL, ``r - p`` through the weighted
sum and the ReLU) and its relayouts and sums (scope ``hvd_dsa_align``). By the
program's own names from the whole trace; nothing for a program that does not
know the names."""

from benchmarks.dsa_cost import ALIGN
from benchmarks.named_device_time import ms


def read(run):
    return ms(run, *ALIGN)
