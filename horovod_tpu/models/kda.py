"""Kimi Delta Attention's mixer as a flax module (Kimi Linear,
arXiv:2510.26692; the layer Kimi-Linear-48B-A3B puts in three of four
places, latent attention in the fourth; docs/linear-attention.md).

For normed hidden states ``h`` (B, T, dim), ``H`` heads of ``d``, ``inner = H
d``, no bias anywhere::

    q~ = h Wq;  k~ = h Wk;  v~ = h Wv          dim -> inner each
    q, k, v = silu(conv(q~)), silu(conv(k~)), silu(conv(v~))
                                        each its OWN causal depthwise
                                        convolution of ``conv`` taps, no bias
    q = L2norm(q) d^-0.5;  k = L2norm(k)       a head, eps 1e-6
    g = -exp(A_log) softplus((h Wf_a) Wf_b + dt_bias)
                                        dim -> d -> inner, float32: the log
                                        of a decay a CHANNEL; A_log a head
    beta = sigmoid(h Wb)                       dim -> H, one a head; TWICE
                                        that where the configuration allows
                                        negative eigenvalues
                                        (``KDADims.allow_neg_eigval``)
    o = kda(q, k, v, g, beta)                  ops/kda.py: the gated delta rule
    o = RMSNorm(o) sigmoid((h Wg_a) Wg_b)      the norm a head with ONE weight
                                        of d shared by the heads, THEN the
                                        gate (Mamba-2's layer gates first)
    out = o Wo                                 inner -> dim

Numerics: float32 parameters; projections, convolutions, q, k, v and the
gate in ``dtype`` (bf16 as trained); the L2 norms' and the head norm's
statistics, ``g`` and ``beta`` in float32.

A rank that holds some of the layer's heads (tensor parallelism) builds the
mixer with that many in ``KDADims.heads``: q, k, v, the convolutions, the
second factors of the two low-rank gates, ``A_log``, ``dt_bias``, beta and
``o_proj``'s rows are cut by heads; the gates' FIRST factors (dim -> d) and
the head norm's one weight are whole on every rank, and ``o_proj`` gives the
rank's partial sum (docs/linear-attention.md, "cut by heads").

The convolution + silu runs as ``ops/mamba_fused.py``'s kernel pair under
this layer's names where its shape tiles (``conv_takes_kernel``), three calls
a layer, and as ``jax.numpy`` otherwise. The scan runs as ``ops/kda.py``'s
kernel pair where ITS shapes tile (``ops.kda.takes_kernel``: heads of 128
lanes, chunks of 64, bf16 or float32; the gauge ``horovod_kda_kernel_scans``),
and as ``jax.numpy`` otherwise. Where the scan takes its kernels AND the rows
are a whole number of row tiles (``ops.kda_fused.takes_kernel``; the gauge
``horovod_kda_fused_mixers``), the two elementwise chains round it run as
``ops/kda_fused.py``'s kernel pairs too (the L2 norms with ``g``; the head norm
then the gate) and the scan through ``ops.kda.kda_lanes``: q, k, v, g and o
then go from the convolutions' kernels to ``o_proj`` as (B, T, H d), each read
once and written once, and are never laid out (B, T, H, d). Every other shape
runs ``l2_norm``, the softplus line, ``kda`` and ``head_norm_then_gate``
below, which are the definitions.

Initialisation: ``A_log`` the log of uniform(1, 16) a head and ``dt_bias``
by Mamba-2's inverse-softplus rule (``models/mamba.py``), the family's; norm
weight 1; projections and taps at flax's defaults. ``A_log``, ``dt_bias``,
the norm's weight and the taps take no weight decay: the optimizer's to
arrange.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..common import device_names
from ..ops import kda_fused, mamba_fused
from ..ops.kda import CHUNK, kda, kda_lanes
from ..ops.ssd import causal_depthwise_conv
from .mamba import _a_log_init, _dt_bias_init

CONV_NAMES = (device_names.KDA_CONV_FWD, device_names.KDA_CONV_BWD)


@dataclasses.dataclass(frozen=True)
class KDADims:
    """The mixer's sizes as a model's ``linear_attn_config`` states them
    (``num_heads``, ``head_dim``, ``short_conv_kernel_size``); ``chunk`` is
    the training path's, which no result depends on in exact arithmetic.
    ``allow_neg_eigval``, as the model's own configuration states it
    (``kda_allow_neg_eigval``): beta is ``2 sigmoid`` in (0, 2) where it is
    ``sigmoid`` in (0, 1) otherwise, so that the transition ``I - beta k
    k^T`` has an eigenvalue in (-1, 1) along the key."""
    heads: int
    head_dim: int
    conv: int = 4
    chunk: int = CHUNK
    allow_neg_eigval: bool = False

    def __post_init__(self):
        if min(self.heads, self.head_dim, self.conv, self.chunk) < 1:
            raise ValueError(f"{self}: heads, head_dim, conv and chunk are "
                             f"counts, each at least 1")


def conv_silu(x, kernel):
    """``silu`` of the causal depthwise convolution without a bias
    (``ops/ssd.py``'s, under this layer's name): what shapes that do not tile
    run; the others take the kernels."""
    conv = causal_depthwise_conv(x, kernel, jnp.zeros(kernel.shape[1:]),
                                 scope=device_names.KDA_CONV)
    with jax.named_scope(device_names.KDA_CONV):
        return nn.silu(conv)


def l2_norm(x, eps=kda_fused.L2_EPS):
    """``x / sqrt(sum(x^2) + eps)`` over the last axis, in float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def head_norm_then_gate(o, gate, scale, eps):
    """``RMSNorm(o) * scale * sigmoid(gate)``: the norm a head (o: (B, T, H,
    d); ``scale`` ONE weight of d shared by the heads), THEN the gate (B, T,
    H d), in float32. Returns (B, T, H d) float32."""
    with jax.named_scope(device_names.KDA_OUT_NORM):
        o = o.astype(jnp.float32)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
        return (o * scale).reshape(gate.shape) * nn.sigmoid(
            gate.astype(jnp.float32))


class KDAMixer(nn.Module):
    dim: int
    dims: KDADims
    rms_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # True runs the convolution's, the scan's and the elementwise chains'
    # kernels, where the shapes take them, in the Pallas interpreter:
    # ``Block`` hands its ``flash_interpret`` down.
    interpret: bool = False

    @nn.compact
    def __call__(self, h):
        from ..metrics import record_kda_beta_range, record_kda_fused_mixer

        m = self.dims
        b, t, _ = h.shape
        inner = m.heads * m.head_dim

        def dense(width, name):
            return nn.Dense(width, use_bias=False, dtype=self.dtype, name=name)

        def conv(x, name):
            taps = self.param(name, nn.initializers.lecun_normal(),
                              (m.conv, inner), jnp.float32)
            if mamba_fused.conv_takes_kernel(x, taps):
                return mamba_fused.conv_silu(
                    x, taps, jnp.zeros((inner,), jnp.float32), self.interpret,
                    names=CONV_NAMES)
            return conv_silu(x, taps)

        with jax.named_scope(device_names.KDA_PROJ):
            q, k, v = (dense(inner, name)(h)
                       for name in ("q_proj", "k_proj", "v_proj"))
            decay = dense(inner, "f_b_proj")(dense(m.head_dim, "f_a_proj")(h))
            beta = dense(m.heads, "b_proj")(h)
            gate = dense(inner, "g_b_proj")(dense(m.head_dim, "g_a_proj")(h))
        q, k, v = (conv(x, name)
                   for x, name in ((q, "q_conv"), (k, "k_conv"), (v, "v_conv")))
        a_log = self.param("A_log", _a_log_init, (m.heads,), jnp.float32)
        dt_bias = self.param("dt_bias", _dt_bias_init, (inner,), jnp.float32)
        scale = self.param("o_norm", nn.initializers.ones, (m.head_dim,),
                           jnp.float32)
        with jax.named_scope(device_names.KDA_GATE):
            beta = nn.sigmoid(beta.astype(jnp.float32))
            if m.allow_neg_eigval:
                beta = 2.0 * beta
        record_kda_beta_range(2 if m.allow_neg_eigval else 1)
        # (named only where asked for, as ``interpret`` below)
        solve = {"neg_eigval": True} if m.allow_neg_eigval else {}
        fused = kda_fused.takes_kernel(q, k, v, decay, gate, m.heads, m.chunk)
        record_kda_fused_mixer(fused)
        if fused:
            q, k, g = kda_fused.gate(q, k, decay, a_log, dt_bias,
                                     self.interpret)
            o = kda_lanes(q, k, v, g, beta, m.chunk, interpret=self.interpret,
                          **solve)
            o = kda_fused.out_norm(o, gate, scale, self.rms_norm_eps,
                                   self.interpret)
        else:
            q, k, v = (x.reshape(b, t, m.heads, m.head_dim) for x in (q, k, v))
            with jax.named_scope(device_names.KDA_GATE):
                q = (l2_norm(q) * m.head_dim ** -0.5).astype(self.dtype)
                k = l2_norm(k).astype(self.dtype)
                g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
                    decay.astype(jnp.float32) + dt_bias
                ).reshape(b, t, m.heads, m.head_dim)
            # (``interpret`` named only where it is asked for: the plain call
            # is the one a stand-in for ``kda`` with its six operands answers)
            o = kda(q, k, v, g, beta, m.chunk, **solve,
                    **({"interpret": True} if self.interpret else {}))
            o = head_norm_then_gate(o, gate, scale,
                                    self.rms_norm_eps).astype(self.dtype)
        with jax.named_scope(device_names.KDA_PROJ):
            return dense(self.dim, "o_proj")(o)
