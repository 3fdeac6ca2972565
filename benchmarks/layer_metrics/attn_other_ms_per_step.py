"""Models layer: device time per step of what an attention mixer runs under no
narrower name (``hvd_attn``, the outer scope of the three attention forms: the
head split and merge relayouts round the kernel calls, the q and k norms, the
grouped-query repeat, the dense einsum path; the kernels, the projections, the
rotary parts, the gate and the indexer keep their own names and times), by the
program's own names from the whole trace (``benchmarks/named_device_time.py``);
0.0 where the window never ran them, nothing for a program that does not know
the name."""

from benchmarks.named_device_time import ms


def read(run):
    return ms(run, "hvd_attn")
