"""The Mamba-2 mixer as a flax module (Dao & Gu, arXiv:2405.21060 §7; the
layer Granite 4.0-H, Nemotron-H, Falcon-H1 and Zamba2 put beside attention).

For normed hidden states ``h`` (B, T, dim), with ``inner = heads x head_dim``
and ``G`` groups of ``B`` / ``C`` of ``state`` numbers each, no bias on either
projection::

    [z | xBC | dt] = h W_in            widths inner | inner + 2 G state | heads
    xBC = silu(conv(xBC))              causal, depthwise, width ``conv``, bias
    [u | B | C] = xBC
    dt = softplus(dt + dt_bias);  A = -exp(A_log)
    y = ssd(u, dt, A, B, C, D)         ops/ssd.py: S_t = exp(dt A) S + dt u B^T
    y = RMSNorm(y * silu(z))           the GATE FIRST, then the norm, a group
                                       of inner / G features at a time
    out = y W_out

Numerics: float32 parameters; the projections, the convolution's output and
``u``, ``B``, ``C`` in ``dtype`` (bf16 as trained); ``dt``, ``A``, the decays
and the carried state in float32 (``ops.ssd``); the gated norm's statistics
in float32, its result in ``dtype``.

The two elementwise chains, ``silu(conv(xBC))`` and ``RMSNorm(y * silu(z))``,
run as fused kernels with a backward of their own (``ops/mamba_fused.py``:
one pass over HBM each way, float32 inside, the same roundings) where their
shapes tile: channels and a group's features multiples of 128, the row a
whole number of row tiles. Every other shape keeps the ``jax.numpy`` forms
(``ops.ssd.causal_depthwise_conv`` + silu, :func:`gated_rms_norm`), which
stay the definitions. The shapes choose and nothing else does; the gauge
``horovod_mamba_fused_passes`` says how many of the two took a kernel. The
scan between them (``ops.ssd.ssd``) runs as kernels of its own on the same
terms: it reads ``u``, ``B``, ``C`` and ``dt`` as they lie here and writes
``y`` (B, T, inner), where its shapes tile (``ops.ssd.takes_kernel``;
``horovod_ssd_kernel_scans``), and as ``jax.numpy`` otherwise.

Initialisation is Mamba-2's: ``A`` uniform in [1, 16], ``dt`` log-uniform in
[0.001, 0.1] stored through the inverse of the softplus, ``D`` = 1, norm
weight 1, convolution and projections at flax's defaults (lecun normal).
``A_log``, ``D`` and ``dt_bias`` take no weight decay by Mamba-2's
convention: that is the optimizer's to arrange (leaves with one axis).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..common import device_names
from ..ops import mamba_fused
from ..ops.ssd import causal_depthwise_conv, ssd


@dataclasses.dataclass(frozen=True)
class Mamba2Dims:
    """The mixer's sizes as a model's configuration states them
    (``mamba_n_heads``, ``mamba_d_head``, ``mamba_d_state``, ``mamba_n_groups``,
    ``mamba_d_conv``, ``mamba_chunk_size``)."""
    heads: int
    head_dim: int
    state: int
    groups: int = 1
    conv: int = 4
    chunk: int = 256


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(key, shape, dtype=jnp.float32, lo=1e-3, hi=1e-1, floor=1e-4):
    dt = jnp.exp(jax.random.uniform(key, shape, dtype)
                 * (math.log(hi) - math.log(lo)) + math.log(lo))
    dt = jnp.maximum(dt, floor)
    return dt + jnp.log(-jnp.expm1(-dt))        # softplus^-1(dt)


def gated_rms_norm(y, z, scale, groups, eps):
    """``RMSNorm(y * silu(z))``, the GATE FIRST (Mamba-2's ``norm_before_gate
    = False``), over each of ``groups`` runs of the features, in float32.
    y, z: (B, T, inner); scale: (inner,)."""
    b, t, inner = y.shape
    with jax.named_scope(device_names.MAMBA_GATE_NORM):
        gated = y.astype(jnp.float32) * nn.silu(z.astype(jnp.float32))
        grouped = gated.reshape(b, t, groups, inner // groups)
        normed = grouped * jax.lax.rsqrt(
            jnp.mean(grouped * grouped, axis=-1, keepdims=True) + eps)
        return normed.reshape(b, t, inner) * scale


class Mamba2Mixer(nn.Module):
    dim: int
    dims: Mamba2Dims
    rms_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # True runs the two chains' kernels (ops/mamba_fused.py) and the scan's
    # (ops/ssd.py), where the shapes take them, in the Pallas interpreter:
    # ``Block`` hands its ``flash_interpret`` down, one flag for every Pallas
    # kernel of a block.
    interpret: bool = False

    @nn.compact
    def __call__(self, h):
        from ..metrics import record_mamba_fused_passes

        m = self.dims
        b, t, _ = h.shape
        inner, bc = m.heads * m.head_dim, m.groups * m.state
        if inner % m.groups or m.heads % m.groups:
            raise ValueError(f"{m.heads} heads x {m.head_dim} do not divide "
                             f"into {m.groups} groups")
        channels = inner + 2 * bc
        with jax.named_scope(device_names.MAMBA_PROJ):
            zxbcdt = nn.Dense(inner + channels + m.heads, use_bias=False,
                              dtype=self.dtype, name="in_proj")(h)
        z, xbc, dt = jnp.split(zxbcdt, [inner, inner + channels], axis=-1)
        conv_kernel = self.param("conv_kernel", nn.initializers.lecun_normal(),
                                 (m.conv, channels), jnp.float32)
        conv_bias = self.param("conv_bias", nn.initializers.zeros,
                               (channels,), jnp.float32)
        # Where the shapes tile, the kernels read xBC and z out of zxbcdt
        # where they lie and write u and B | C as two outputs: no slice of
        # 16,384 x 4,096 is copied on either side (B and C are 3% of it).
        fused_conv = mamba_fused.conv_takes_kernel(xbc, conv_kernel,
                                                   (inner, 2 * bc))
        if fused_conv:
            u, BC = mamba_fused.conv_silu(
                xbc, conv_kernel, conv_bias, self.interpret,
                splits=(inner, 2 * bc), wide=zxbcdt, start=inner)
            B, C = jnp.split(BC, 2, axis=-1)
        else:
            xbc = causal_depthwise_conv(xbc, conv_kernel, conv_bias)
            with jax.named_scope(device_names.MAMBA_CONV):
                xbc = nn.silu(xbc)
            u, B, C = jnp.split(xbc, [inner, inner + bc], axis=-1)
        a_log = self.param("A_log", _a_log_init, (m.heads,), jnp.float32)
        dt_bias = self.param("dt_bias", _dt_bias_init, (m.heads,), jnp.float32)
        skip = self.param("D", nn.initializers.ones, (m.heads,), jnp.float32)
        y = ssd(u.reshape(b, t, m.heads, m.head_dim),
                jax.nn.softplus(dt.astype(jnp.float32) + dt_bias),
                -jnp.exp(a_log), B.reshape(b, t, m.groups, m.state),
                C.reshape(b, t, m.groups, m.state), skip, m.chunk,
                self.interpret)
        scale = self.param("gate_norm", nn.initializers.ones, (inner,),
                           jnp.float32)
        y = y.reshape(b, t, inner)
        fused_norm = mamba_fused.norm_takes_kernel(y, z, m.groups)
        if fused_norm:
            y = mamba_fused.gate_norm(y, z, scale, m.groups,
                                      self.rms_norm_eps, self.interpret,
                                      wide=zxbcdt)
        else:
            y = gated_rms_norm(y, z, scale, m.groups,
                               self.rms_norm_eps).astype(self.dtype)
        record_mamba_fused_passes(fused_conv + fused_norm)
        with jax.named_scope(device_names.MAMBA_PROJ):
            return nn.Dense(self.dim, use_bias=False, dtype=self.dtype,
                            name="out_proj")(y)
