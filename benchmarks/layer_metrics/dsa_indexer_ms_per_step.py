"""Selected attention layer: device time per step of the indexer - its three
projections, the key's LayerNorm and the rotary embedding (scope
``hvd_dsa_indexer``) and the score tiles' kernel (``hvd_dsa_indexer_scores``,
once a layer: the selection is saved across the recomputation) - by the
program's own names from the whole trace (``benchmarks/named_device_time.py``);
nothing for a program that does not know the names."""

from benchmarks.dsa_cost import INDEXER
from benchmarks.named_device_time import ms


def read(run):
    return ms(run, *INDEXER)
