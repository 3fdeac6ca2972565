"""Mamba-2 mixer layer: device time per step of the gated RMSNorm: the two
kernels of ``ops/mamba_fused.py`` and whatever stays under the
``hvd_mamba_gate_norm`` scope, by the program's own names from the whole trace
(``benchmarks/named_device_time.py``); 0.0 where the window never ran them."""

from benchmarks.named_device_time import ms


def read(run):
    return ms(run, "hvd_mamba_gate_norm", "hvd_mamba_gate_norm_fwd", "hvd_mamba_gate_norm_bwd")
