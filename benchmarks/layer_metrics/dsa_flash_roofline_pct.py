"""Selected attention layer: the least time the chip could take for the
selected flash kernel calls of a step - max(operations / peak FLOP/s, bytes /
peak bytes/s) of the SELECTED pairs' products from shapes
(``benchmarks/dsa_cost.py``: needed work, so kernels that run every live block
step dense under the mask read low; the forward as often as it is called) -
over the time ``dsa_flash_ms_per_step`` measured. An earlier line says which
bound applies."""

from benchmarks.dsa_cost import SELECTED, roofline_pct


def read(run):
    return roofline_pct(run, run["cost"].get("dsa_flash"), SELECTED,
                        "flash kernels over the selection")
