"""Kernels layer: the least time the chip could take for the causal-dense
flash kernel calls of a step at grouped-query heads - max(operations / peak
FLOP/s, bytes / peak bytes/s) from shapes (``benchmarks/swa_cost.py``: k, v,
dk, dv at the key/value heads; the forward as often as it is called) - over
the time of ``hvd_flash_fwd`` + ``_bwd_dq`` + ``_bwd_dkv`` by the program's
own names. An earlier line says which bound applies."""

from benchmarks.swa_cost import FULL, roofline_pct


def read(run):
    return roofline_pct(run, run["cost"].get("full_flash"), FULL,
                        "flash kernels of the full-attention layers")
