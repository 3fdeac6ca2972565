"""Window attention layer: the least time the chip could take for the windowed
flash kernel calls of a step - max(operations / peak FLOP/s, bytes / peak
bytes/s) of the BAND's area from shapes (``benchmarks/swa_cost.py``: needed
work, so blocks coarser than the band read low; the forward as often as it is
called) - over the time ``swa_flash_ms_per_step`` measured. An earlier line
says which bound applies."""

from benchmarks.swa_cost import WINDOWED, roofline_pct


def read(run):
    return roofline_pct(run, run["cost"].get("swa_flash"), WINDOWED,
                        "flash kernels under the window")
