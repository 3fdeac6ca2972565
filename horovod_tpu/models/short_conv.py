"""The gated short-convolution mixer as a flax module (LFM2, Liquid AI: the
layer ``LFM2-24B-A2B`` puts in three of four places, grouped-query attention
in the fourth; docs/short-conv.md).

For normed hidden states ``h`` (B, T, dim), no bias anywhere::

    [B | C | X] = h W_in          dim -> 3 dim, three runs of dim in that order
    u = B * X                     the first gate, an element
    c_t = sum_j k_j * u_{t - (K - 1) + j}      a channel: a causal depthwise
                                  convolution of K taps (``conv_L_cache``),
                                  ``k`` (K, dim), u zero before the row's
                                  start; NO activation
    y = C * c                     the second gate
    out = y W_out                 dim -> dim

The mixer carries no state along the row but the K - 1 tokens before a
position and computes no scores: its cost is two matrix products and one
pass over HBM.

Numerics: float32 parameters; both projections in ``dtype`` (bf16 as
trained); the two products and the taps in float32 inside the pass
(:func:`gated_conv` is the definition), its result rounded to ``dtype`` once.

Where its shape tiles (``ops.mamba_fused.gated_conv_takes_kernel``: ``dim`` a
multiple of 128 lanes, the row a whole number of row tiles, bf16 or f32, at
most 9 taps) the pass runs as ``ops/mamba_fused.py``'s kernel pair
``hvd_sconv_conv_fwd`` / ``_bwd``: ``[B | C | X]`` is read ONCE where
``W_in`` wrote it and ``y`` written once; the backward reads those and ``dy``
once, recomputes ``c`` and writes ONE gradient as wide as ``W_in``'s output
and the taps'. Every other shape runs :func:`gated_conv`. The shapes choose
and nothing else does; the gauge ``horovod_short_conv_taps`` says what the
latest traced mixer convolved with and ``horovod_short_conv_kernel_passes``
how many traced passes took the kernels.

Initialisation: projections and taps at flax's defaults (lecun normal). The
taps take no weight decay by the convention of this repo's other
convolutions: that is the optimizer's to arrange (the leaf ``conv_kernel``).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..common import device_names
from ..ops import mamba_fused


@dataclasses.dataclass(frozen=True)
class ShortConvDims:
    """The mixer's sizes as a model's configuration states them
    (``conv_L_cache``: the taps of the depthwise convolution)."""
    taps: int = 3


def gated_conv(bcx, taps):
    """``C * conv(B * X)`` for ``bcx`` (B, T, 3 D) = ``[B | C | X]`` and
    ``taps`` (K, D): the two products and the causal depthwise convolution
    (zeros before the row's start, no activation, no bias) in float32, the
    result rounded to ``bcx``'s dtype once. The definition: what the kernels
    are held to and what a shape they do not tile runs."""
    t, d = bcx.shape[1], bcx.shape[2] // 3
    k = taps.shape[0]
    with jax.named_scope(device_names.SCONV_CONV):
        b, c, x = (bcx[..., i * d:(i + 1) * d].astype(jnp.float32)
                   for i in range(3))
        u = jnp.pad(b * x, ((0, 0), (k - 1, 0), (0, 0)))
        conv = sum(taps[j].astype(jnp.float32) * u[:, j:j + t]
                   for j in range(k))
        return (c * conv).astype(bcx.dtype)


def gated_conv_pass(bcx, taps, interpret: bool = False):
    """``(C * conv(B * X), whether the kernels ran)``: the pass as the mixer
    runs it, ``ops/mamba_fused.py``'s kernel pair where the shape tiles and
    :func:`gated_conv` otherwise. The shapes choose and nothing else does."""
    if mamba_fused.gated_conv_takes_kernel(bcx, taps):
        return mamba_fused.gated_conv(bcx, taps, interpret), True
    return gated_conv(bcx, taps), False


class ShortConvMixer(nn.Module):
    dim: int
    dims: ShortConvDims
    dtype: Any = jnp.bfloat16
    # True runs the pass's kernels (ops/mamba_fused.py), where the shape takes
    # them, in the Pallas interpreter: ``Block`` hands its ``flash_interpret``
    # down, one flag for every Pallas kernel of a block.
    interpret: bool = False

    @nn.compact
    def __call__(self, h):
        from ..metrics import record_short_conv_plan

        if self.dims.taps < 1:
            raise ValueError(f"a short convolution of {self.dims.taps} taps")
        with jax.named_scope(device_names.SCONV_PROJ):
            bcx = nn.Dense(3 * self.dim, use_bias=False, dtype=self.dtype,
                           name="in_proj")(h)
        taps = self.param("conv_kernel", nn.initializers.lecun_normal(),
                          (self.dims.taps, self.dim), jnp.float32)
        y, fused = gated_conv_pass(bcx, taps, self.interpret)
        record_short_conv_plan(self.dims.taps, fused)
        with jax.named_scope(device_names.SCONV_PROJ):
            return nn.Dense(self.dim, use_bias=False, dtype=self.dtype,
                            name="out_proj")(y)
