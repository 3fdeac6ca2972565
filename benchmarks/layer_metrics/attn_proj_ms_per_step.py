"""Models layer: device time per step of the projections of multi-head and
selected attention (``hvd_attn_proj``: ``qkv`` / ``q_proj`` / ``kv_proj`` /
``o_proj``; latent attention's go by ``hvd_mla_proj``), by the program's own
names from the whole trace (``benchmarks/named_device_time.py``); 0.0 where the
window never ran them, nothing for a program that does not know the name."""

from benchmarks.named_device_time import ms


def read(run):
    return ms(run, "hvd_attn_proj")
