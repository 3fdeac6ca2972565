"""Experts layer: device time per step of the routers' float32 product at the
highest precision and the cast before it (``hvd_moe_logits``; the softmax or
sigmoid and the top-k after it go by ``hvd_moe_route``), by the program's own
names from the whole trace (``benchmarks/named_device_time.py``); 0.0 where the
window never ran them, nothing for a program that does not know the name."""

from benchmarks.named_device_time import ms


def read(run):
    return ms(run, "hvd_moe_logits")
