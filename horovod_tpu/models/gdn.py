"""Gated DeltaNet's mixer as a flax module (arXiv:2412.06464; the layer
Olmo-Hybrid-7B puts in three of four places under the name
``linear_attention``, full attention in the fourth;
docs/linear-attention.md, "one decay a head").

For hidden states ``h`` (B, T, dim), ``H`` heads with keys of ``dk`` and
values of ``dv``, no bias anywhere::

    q~ = h Wq;  k~ = h Wk                      dim -> H dk each
    v~ = h Wv                                  dim -> H dv
    q, k, v = silu(conv(q~)), silu(conv(k~)), silu(conv(v~))
                                        each its OWN causal depthwise
                                        convolution of ``conv`` taps, no bias
    q = L2norm(q) dk^-0.5;  k = L2norm(k)      a head, eps 1e-6
    g = -exp(A_log) softplus(h Wa + dt_bias)   dim -> H, float32: the log of
                                        ONE decay a head and position
    beta = sigmoid(h Wb)                       dim -> H; TWICE that where the
                                        configuration allows negative
                                        eigenvalues (``allow_neg_eigval``)
    o = gdn(q, k, v, g, beta)                  ops/gdn.py: the gated delta rule
    o = RMSNorm(o) silu(h Wg)                  the norm a head with ONE weight
                                        of dv shared by the heads, THEN the
                                        full-rank gate (dim -> H dv)
    out = o Wo                                 H dv -> dim

Beside ``models/kda.py``'s mixer: the decay and beta come straight from the
hidden state (no low-rank factors), the decay is one number a head (KDA's is
one a channel), the gate is full rank under ``silu`` (KDA's low rank under a
sigmoid), and a head's keys and values differ in width. A mixer of its own
for that: ``KDAMixer``'s fused passes and its scan's kernels are built on
heads of 128 | 128 with the decay on the lanes, none of which holds here.

Numerics: float32 parameters; projections, convolutions, q, k, v and the
gate in ``dtype`` (bf16 as trained); the L2 norms' and the head norm's
statistics, ``g`` and ``beta`` in float32.

q~, k~ and v~ are ONE product with ``[Wq | Wk | Wv]`` and ONE convolution
with the three sets of taps side by side (a depthwise convolution knows
nothing of its neighbours' columns): at Olmo-Hybrid's 15 held heads the
three widths, 1,440 | 1,440 | 2,880, are no whole lane tiles apart and
5,760 together, which ``ops/mamba_fused.py``'s kernel pair tiles
(``conv_takes_kernel``); other shapes run ``jax.numpy``. The scan runs as
``ops/gdn.py``'s kernel pair where ITS shapes tile (the gauge
``horovod_gdn_kernel_scans``).

A rank that holds some of the layer's heads (tensor parallelism) builds the
mixer with that many in ``GDNDims.heads``: q, k, v, the convolutions, ``Wa``,
``Wb``, ``A_log``, ``dt_bias``, the gate and ``o_proj``'s rows are cut by
heads; the head norm's one weight is whole on every rank, and ``o_proj``
gives the rank's partial sum.

Initialisation: ``A_log`` the log of uniform(1, 16) a head and ``dt_bias``
by Mamba-2's inverse-softplus rule (``models/mamba.py``); norm weight 1;
projections and taps at flax's defaults. ``A_log``, ``dt_bias``, the norm's
weight and the taps take no weight decay: the optimizer's to arrange.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..common import device_names
from ..ops import mamba_fused
from ..ops.gdn import gdn, takes_kernel
from ..ops.kda import CHUNK
from ..ops.ssd import causal_depthwise_conv
from .kda import l2_norm
from .mamba import _a_log_init, _dt_bias_init

CONV_NAMES = (device_names.GDN_CONV_FWD, device_names.GDN_CONV_BWD)


@dataclasses.dataclass(frozen=True)
class GDNDims:
    """The mixer's sizes as a model's configuration states them
    (``linear_num_key_heads`` = ``linear_num_value_heads``,
    ``linear_key_head_dim``, ``linear_value_head_dim``,
    ``linear_conv_kernel_dim``, ``linear_allow_neg_eigval``); ``chunk`` is the
    training path's, which no result depends on in exact arithmetic."""
    heads: int
    key_dim: int
    value_dim: int
    conv: int = 4
    chunk: int = CHUNK
    allow_neg_eigval: bool = False

    def __post_init__(self):
        if min(self.heads, self.key_dim, self.value_dim, self.conv,
               self.chunk) < 1:
            raise ValueError(f"{self}: heads, key_dim, value_dim, conv and "
                             f"chunk are counts, each at least 1")


def head_norm_then_gate(o, gate, scale, eps):
    """``RMSNorm(o) * scale * silu(gate)``: the norm a head (o: (B, T, H,
    dv); ``scale`` ONE weight of dv shared by the heads), THEN the gate (B,
    T, H dv), in float32. Returns (B, T, H dv) float32."""
    o = o.astype(jnp.float32)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    return (o * scale).reshape(gate.shape) * nn.silu(gate.astype(jnp.float32))


class GDNMixer(nn.Module):
    dim: int
    dims: GDNDims
    rms_norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    # True runs the convolution's and the scan's kernels, where the shapes
    # take them, in the Pallas interpreter: ``Block`` hands its
    # ``flash_interpret`` down.
    interpret: bool = False

    @nn.compact
    def __call__(self, h):
        from ..metrics import record_gdn_kernel_scan

        m = self.dims
        b, t, _ = h.shape
        keys, values = m.heads * m.key_dim, m.heads * m.value_dim
        widths = {"q": keys, "k": keys, "v": values}

        def weight(name, width, rows=self.dim):
            return self.param(name, nn.initializers.lecun_normal(),
                              (rows, width), jnp.float32)

        def product(x, *weights):
            w = (weights[0] if len(weights) == 1
                 else jnp.concatenate(weights, axis=1))
            return jnp.dot(x.astype(self.dtype), w.astype(self.dtype))

        with jax.named_scope(device_names.GDN_PROJ):
            qkv = product(h, *(weight(f"{x}_proj", width)
                               for x, width in widths.items()))
            decay, beta = jnp.split(product(
                h, weight("a_proj", m.heads), weight("b_proj", m.heads)
            ).astype(jnp.float32), 2, axis=-1)
            gate = product(h, weight("g_proj", values))
        taps = jnp.concatenate([
            self.param(f"{x}_conv", nn.initializers.lecun_normal(),
                       (m.conv, width), jnp.float32)
            for x, width in widths.items()], axis=1)
        no_bias = jnp.zeros(taps.shape[1:], jnp.float32)
        if mamba_fused.conv_takes_kernel(qkv, taps):
            qkv = mamba_fused.conv_silu(qkv, taps, no_bias, self.interpret,
                                        names=CONV_NAMES)
        else:
            qkv = causal_depthwise_conv(qkv, taps, no_bias,
                                        scope=device_names.GDN_CONV)
            with jax.named_scope(device_names.GDN_CONV):
                qkv = nn.silu(qkv)
        a_log = self.param("A_log", _a_log_init, (m.heads,), jnp.float32)
        dt_bias = self.param("dt_bias", _dt_bias_init, (m.heads,), jnp.float32)
        scale = self.param("o_norm", nn.initializers.ones, (m.value_dim,),
                           jnp.float32)
        with jax.named_scope(device_names.GDN_GATE):
            q, k, v = jnp.split(qkv, (keys, 2 * keys), axis=-1)
            q, k = (x.reshape(b, t, m.heads, m.key_dim) for x in (q, k))
            q = (l2_norm(q) * m.key_dim ** -0.5).astype(self.dtype)
            k = l2_norm(k).astype(self.dtype)
            v = v.reshape(b, t, m.heads, m.value_dim)
            g = -jnp.exp(a_log) * jax.nn.softplus(decay + dt_bias)
            beta = nn.sigmoid(beta)
            if m.allow_neg_eigval:
                beta = 2.0 * beta
        record_gdn_kernel_scan("/".join(self.path),
                               takes_kernel(q, k, v, min(m.chunk, t)))
        # (``interpret`` and ``neg_eigval`` named only where asked for: the
        # plain call is the one a stand-in for ``gdn`` answers)
        o = gdn(q, k, v, g, beta, m.chunk,
                **({"neg_eigval": True} if m.allow_neg_eigval else {}),
                **({"interpret": True} if self.interpret else {}))
        with jax.named_scope(device_names.GDN_OUT_NORM):
            o = head_norm_then_gate(o, gate, scale,
                                    self.rms_norm_eps).astype(self.dtype)
        with jax.named_scope(device_names.GDN_PROJ):
            return product(o, weight("o_proj", self.dim, values))
