"""Short-convolution mixer layer: the least time the chip could take for the
gated convolution's passes of a step - bytes / peak bytes/s, from shapes
(``benchmarks/lfm2_cost.sconv_conv_step_cost``: what the MODEL needs, whatever
implements it; the pass has no MXU work, so HBM bandwidth is the bound that
applies) - over the time ``sconv_conv_ms_per_step`` measured under the pass's
names. An earlier line says so. While the pass is ``jax.numpy`` it reads low:
XLA is free to split the chain, and its backward keeps float32 in HBM."""

from benchmarks.lfm2_cost import CONV
from benchmarks.named_device_time import ms


def read(run):
    needed = run["cost"].get("sconv_conv")
    measured = ms(run, *CONV)
    if needed is None or not measured:
        return None
    by_bytes = needed["bytes"] / run["peaks"]["hbm_bytes_per_s"]
    run["log"](f"gated convolutions: least time {by_bytes * 1e3:.3f} ms per "
               f"step, bound by HBM bandwidth ({needed['bytes'] / 1e9:.3f} GB)")
    return 100.0 * by_bytes * 1e3 / measured
