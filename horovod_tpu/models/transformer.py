"""Decoder-only transformer LM — the long-context flagship.

Beyond the reference's CNN benchmark zoo: this model exists to exercise the
sequence-parallel / long-context path (SURVEY.md §5.7 notes the reference has
none; the TPU build makes it first-class). Design:

- bfloat16 activations, float32 params;
- attention is pluggable: dense causal attention by default, ring attention
  (horovod_tpu.ops.ring_attention) when a sequence-parallel axis is given;
- weights laid out for tensor parallelism: QKV and MLP-in are sharded on the
  output feature dim, O-proj and MLP-out on the input feature dim, so tp only
  needs one psum per block (inserted automatically by XLA under jit with
  sharding constraints).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..common import device_names
from ..ops.moe import ROUTER_SAVED, save_names
from ..ops.sparse_attention import ALIGN_GRADS, SELECTED
from .gdn import GDNDims, GDNMixer
from .kda import KDADims, KDAMixer
from .mamba import Mamba2Dims, Mamba2Mixer
from .short_conv import ShortConvDims, ShortConvMixer


@dataclasses.dataclass(frozen=True)
class LatentDims:
    """Latent attention's (MLA's) sizes, as a DeepSeek-V3-family ``config.json``
    states them: ``kv_lora_rank``, ``qk_nope_head_dim``, ``qk_rope_head_dim``,
    ``v_head_dim``. q and k have head size ``qk_nope + qk_rope``, v has ``v``."""
    kv_rank: int
    qk_nope: int
    qk_rope: int
    v: int


@dataclasses.dataclass(frozen=True)
class SparseDims:
    """Learned sparse attention's sizes, as a configuration's ``sa_config``
    states them (DeepSeek Sparse Attention; docs/sparse-attention.md): an
    indexer of ``index_heads`` query heads of ``index_dim`` against ONE shared
    key head scores every earlier token, each query keeps its ``topk`` best,
    and attention runs over that selection. ``kv_chunk`` / ``q_chunk``: the
    tile in which scores are computed, selected and packed (they change no
    result)."""
    index_heads: int
    index_dim: int
    topk: int
    kv_chunk: int = 512
    q_chunk: int = 512


@dataclasses.dataclass(frozen=True)
class RotaryScheme:
    """One kind of layer's rotary embedding, as a Hugging Face
    ``rope_parameters`` entry states it: the base ``theta``; ``dims``, how
    many of a head's LEADING dimensions turn (``partial_rotary_factor`` x the
    head size; None: all of them), the rest passing through; with a
    ``factor`` YaRN's blended frequencies over those dimensions
    (``rope_type`` ``"yarn"``: ``factor``, ``original_max``, ``beta_fast``,
    ``beta_slow``, as ``transformers``' ``_compute_yarn_parameters`` computes
    them with ``truncate`` at its default), and ``attention_factor``, which
    multiplies cos and sin, so q's and k's rotated dimensions alone (None:
    YaRN's ``0.1 ln(factor) + 1``, and 1 without a factor). Pairs are
    ``(i, i + dims / 2)``. ``sections`` (``rope_scaling.mrope_section``: three
    counts that sum to ``dims / 2``): positions come in three streams
    (temporal, height, width: ``(3, B, T)``), the first ``sections[0]`` pairs
    turned by the first stream, the next ``sections[1]`` by the second, the
    rest by the third; one stream given stands for all three (a text row)."""
    theta: float = 10000.0
    dims: Optional[int] = None
    factor: Optional[float] = None
    original_max: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: Optional[float] = None
    sections: Optional[tuple] = None

    def inv_freq(self, dims):
        """(dims / 2,) float32: the angle a position turns each pair by."""
        f = self.theta ** (-np.arange(0, dims, 2, dtype=np.float64) / dims)
        if self.factor is None:
            return f.astype(np.float32)

        def correction(rotations):      # the pair that turns that often
            return (dims * np.log(self.original_max / (rotations * 2 * np.pi))
                    / (2 * np.log(self.theta)))

        low = max(np.floor(correction(self.beta_fast)), 0)
        high = min(np.ceil(correction(self.beta_slow)), dims - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(dims // 2) - low) / (high - low), 0, 1)
        return (f / self.factor * ramp + f * (1 - ramp)).astype(np.float32)

    def scale(self):
        if self.attention_factor is not None:
            return float(self.attention_factor)
        return 1.0 if self.factor is None else 0.1 * np.log(self.factor) + 1.0


def _swap(x, lo, hi):
    """``swap(x)`` on the last dim, in float32: ``swap(x)[lo] = -x[hi]`` and
    ``swap(x)[hi] = x[lo]`` for the pairs' index arrays ``lo`` / ``hi``, zero
    elsewhere: a product with a constant matrix of 0 and +-1 (exact in any
    dtype: one term a sum). A rotation is then ``x * cos + swap(x) * sin`` in
    place and lane-dense. Slicing a head into halves of 32 or 64 lanes and
    concatenating them again took 2.6 x and 1.8 x as long on the chip, and a
    reshape to (..., half, 2) or a stride-2 slice would put 2 of 128 lanes to
    use (PERF.md §6, PR 36 and PR 39)."""
    d = x.shape[-1]
    swap = np.zeros((d, d), np.float32)
    swap[hi, lo], swap[lo, hi] = -1.0, 1.0
    return jnp.dot(x, jnp.asarray(swap, x.dtype),
                   preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST
                   if x.dtype == jnp.float32 else None)


def _rope_scheme(x, positions, scheme):
    """A :class:`RotaryScheme` on the last dim of ``x`` (..., T, heads, D):
    the leading ``scheme.dims`` turn in pairs (i, i + half), the rest pass:
    ``x * cos + swap(x) * sin`` (:func:`_swap`), cos 1 and sin 0 on the
    dimensions that pass."""
    d = x.shape[-1]
    dims = d if scheme.dims is None else scheme.dims
    half = dims // 2
    if positions.ndim == 3 and scheme.sections is None:
        positions = positions[0]    # three streams, no sections: the temporal
    angles = positions[..., None].astype(jnp.float32) * jnp.asarray(
        scheme.inv_freq(dims))
    if scheme.sections is not None:
        if sum(scheme.sections) != half or len(scheme.sections) != 3:
            raise ValueError(f"sections {scheme.sections} are not three "
                             f"counts of the {half} pairs")
        if positions.ndim == 3:     # (3, B, T): pair i by its section's stream
            stream = np.repeat(np.arange(3), scheme.sections)
            angles = sum(jnp.where(stream == c, angles[c], 0.0)
                         for c in range(3))

    def widen(turned, passing):     # (..., T, half) -> (..., T, 1, D)
        rest = jnp.full(angles.shape[:-1] + (d - dims,), passing, jnp.float32)
        return jnp.concatenate([turned * scheme.scale()] * 2 + [rest],
                               axis=-1)[..., None, :]

    i = np.arange(half)
    swapped = _swap(x, i, i + half)
    return (x * widen(jnp.cos(angles), 1.0)
            + swapped * widen(jnp.sin(angles), 0.0)).astype(x.dtype)


def _rope(x, positions, theta=10000.0, interleave=False, lead=0):
    """Rotary position embedding on the last dim (pairs): component i with
    i + half, or with ``interleave`` 2i with 2i + 1, turned by
    ``positions * theta ** (-i / half)``: ``x * cos + swap(x) * sin``
    (:func:`_swap`) on whole heads, whichever way the pairs lie. The first
    ``lead`` lanes of a head pass (cos 1, sin 0, no pair: latent attention's
    q, whose rotary lanes trail) and the pairs are the others': the head is
    neither cut nor put together again, forward or backward
    (:func:`_turned`)."""
    half = (x.shape[-1] - lead) // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., T, half]
    cos, sin = jnp.cos(angles)[..., None, :], jnp.sin(angles)[..., None, :]  # add head dim
    i = np.arange(half)
    lo, hi = (2 * i, 2 * i + 1) if interleave else (i, i + half)
    if not lead:
        swapped = _swap(x, lo, hi)
    if interleave:
        cos, sin = (jnp.repeat(t, 2, axis=-1) for t in (cos, sin))
    else:
        cos, sin = (jnp.concatenate([t, t], axis=-1) for t in (cos, sin))
    if not lead:
        return (x * cos + swapped * sin).astype(x.dtype)
    cos, sin = (jnp.concatenate([jnp.full(
        t.shape[:-1] + (lead,), passing, t.dtype), t], axis=-1)
        for t, passing in ((cos, 1.0), (sin, 0.0)))
    return _turned(x, cos, sin, (tuple(lead + lo), tuple(lead + hi)))


def _turn(x, cos, sin, pairs):
    return (x * cos + _swap(x, *map(np.asarray, pairs)) * sin).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _turned(x, cos, sin, pairs):
    """``x * cos + swap(x) * sin`` over whole heads, ``pairs`` the (lo, hi)
    lanes of :func:`_swap`, whose gradient is the same pass turned the other
    way, ``g * cos - swap(g) * sin``: the swap is antisymmetric and a pair's
    two lanes share one sine, so ``(g * sin) @ swap.T`` is that. JAX's own
    transpose writes ``g * sin`` out in float32 at every lane of the head
    before the product (6.8 ms a latent layer of kanana2's where this form
    takes 3.7: PERF.md §6, PR 61) and rounds the turned term to x's dtype
    twice; this rounds it once. cos and sin carry no gradient."""
    return _turn(x, cos, sin, pairs)


def _turned_fwd(x, cos, sin, pairs):
    return _turn(x, cos, sin, pairs), (cos, sin)


def _turned_bwd(pairs, res, g):
    cos, sin = res
    return _turn(g, cos, -sin, pairs), None, None


_turned.defvjp(_turned_fwd, _turned_bwd)


def gate_form(attn_gate):
    """``Block.attn_gate`` / ``TransformerLM.attn_gate`` as one of None (no
    gate), ``"head"`` (one sigmoid gate a head and token; True says the
    same) or ``"element"`` (one an element of the attention output)."""
    forms = {False: None, None: None, True: "head", "head": "head",
             "element": "element"}
    if not isinstance(attn_gate, (bool, str, type(None))) or attn_gate not in forms:
        raise ValueError(f"attn_gate {attn_gate!r}: False, 'head' (or True) "
                         f"or 'element'")
    return forms[attn_gate]


def causal_attention(q, k, v, seq_offset=0, scale=None, window=None):
    """Dense causal attention. q,k,v: [B, T, H, D]. Runs on-chip in one block —
    fine up to ~8k tokens; ring attention takes over beyond that. ``scale``
    multiplies the scores (None: D ** -0.5). ``window``: a query at position
    p sees the keys ``p - window < j <= p`` (None: every earlier key)."""
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    t_q, t_k = q.shape[1], k.shape[1]
    q_pos = jnp.arange(t_q) + seq_offset
    k_pos = jnp.arange(t_k)
    mask = q_pos[:, None] >= k_pos[None, :]
    if window is not None:
        mask &= k_pos[None, :] > q_pos[:, None] - window
    logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


class Block(nn.Module):
    dim: int
    heads: int
    mlp_ratio: int = 4
    dtype: Any = jnp.bfloat16
    sp_axis: Optional[str] = None  # sequence-parallel mesh axis (ring attention)
    moe_experts: int = 0           # >0: MoE MLP (models/moe.py) instead of dense
    moe_top_k: int = 0             # experts a token uses: 0 < it <= moe_experts
    moe_hidden: Optional[int] = None    # one expert's width (None: mlp_ratio * dim)
    qk_norm: bool = False          # RMSNorm over the whole projected q and k
    rms_norm_eps: float = 1e-6
    attention: str = "dense"       # "dense" | "flash" (pallas fused kernel)
    kv_heads: Optional[int] = None  # < heads: grouped-query attention
    # flash kernel tile sizes (None = kernel defaults; sweep with
    # examples/transformer_benchmark.py --sweep-blocks)
    block_q: Optional[int] = None
    block_k: Optional[int] = None
    # True runs the flash kernels in the Pallas interpreter (the CPU tests
    # ask for it); the default compiles them for the TPU and raises without
    # one — never inferred from the platform.
    flash_interpret: bool = False
    # What a hybrid model's configuration states (TransformerLM documents
    # them): a Mamba-2 mixer in place of attention, a SwiGLU MLP of width
    # mlp_hidden in place of the ungated GELU one, no rotary embedding, a
    # softmax scale of its own, every branch times residual_scale.
    mamba: Optional[Mamba2Dims] = None
    mlp_hidden: Optional[int] = None
    rope: bool = True
    attention_scale: Optional[float] = None
    residual_scale: float = 1.0
    # What a DeepSeek-V3-family configuration states (TransformerLM documents
    # them): latent attention in place of multi-head, the rotary base and
    # pairing, the experts' router, its scale, the shared expert's width, the
    # share of the experts held here.
    mla: Optional[LatentDims] = None
    rope_theta: float = 10000.0
    rope_interleave: bool = False
    moe_router: str = "softmax"
    moe_route_scale: float = 1.0
    moe_shared_hidden: int = 0
    moe_held: Optional[tuple] = None
    # What a window / full hybrid's configuration states (Laguna:
    # TransformerLM documents them): a head size of its own, q and o then
    # heads x head_dim wide whatever dim is; a window (the query at p sees
    # the keys p - window < j <= p); a rotary scheme in place of rope_theta's
    # plain one; a sigmoid gate on the attention output, "head" (or True:
    # one number a head and token) or "element" (one an element).
    head_dim: Optional[int] = None
    window: Optional[int] = None
    rotary: Optional[RotaryScheme] = None
    attn_gate: Any = False
    # What a Nemotron-H-family configuration states (TransformerLM documents
    # them): a layer that is ONE sub-layer, ``x + f(norm x)`` with f the
    # mixer alone ("mixer") or the experts alone ("mlp") where every other
    # model's block is both, one after the other ("both"); the experts'
    # activation and the latent they live in (models/moe.py).
    sublayers: str = "both"
    moe_activation: str = "swiglu"
    moe_latent: int = 0
    # What a sparse-attention mixture of experts' configuration states
    # (Keye-VL-2.0's language model: TransformerLM documents them): an indexer
    # and a selection inside attention, RMSNorm over EACH head of q and k, the
    # softmax router's weights over their sum.
    sparse: Optional[SparseDims] = None
    qk_head_norm: bool = False
    moe_norm_topk: bool = False
    # What a linear-attention hybrid's configuration states (Kimi-Linear:
    # TransformerLM documents it): a Kimi Delta Attention mixer in
    # attention's place.
    kda: Optional[KDADims] = None
    # What a short-convolution hybrid's configuration states (LFM2:
    # TransformerLM documents them): a gated short-convolution mixer in
    # attention's place; the epsilon under the sigmoid router's chosen scores.
    conv: Optional[ShortConvDims] = None
    moe_route_eps: float = 1e-20
    # What a delta-rule hybrid of the OLMo family's configuration states
    # (Olmo-Hybrid: TransformerLM documents them): a Gated DeltaNet mixer in
    # attention's place; the norm of each half on its OUTPUT,
    # ``x + norm(f(x))``, where every other model's is ``x + f(norm(x))``.
    gdn: Optional[GDNDims] = None
    norm_after: bool = False
    # What a looped model's configuration states (Ouro: TransformerLM
    # documents it): a norm on BOTH sides of each half,
    # ``x + norm(f(norm(x)))``, four RMSNorms a layer.
    sandwich_norm: bool = False

    @nn.compact
    def __call__(self, x, positions):
        if self.attention not in ("dense", "flash"):
            raise ValueError(
                f"unknown attention={self.attention!r}; use 'dense' or 'flash'")
        if self.norm_after and self.sandwich_norm:
            raise ValueError("norm_after and sandwich_norm state two "
                             "placements of one half's norm")
        self._check_halves()
        if self.sublayers != "mlp":
            x = self._add(x, self._mixer(x, positions))
        if self.sublayers == "mixer":
            return x
        h = x if self.norm_after else self._norm(x)
        if self.moe_experts > 0:
            from .moe import MoEMLP

            if self.norm_after or self.sandwich_norm:
                raise ValueError("norm_after and sandwich_norm are stated for "
                                 "dense MLP halves: this layer's second half "
                                 "is experts")

            hidden = (self.mlp_ratio * self.dim if self.moe_hidden is None
                      else self.moe_hidden)
            return self._add(x, MoEMLP(
                dim=self.dim, hidden=hidden, n_experts=self.moe_experts,
                top_k=self.moe_top_k, dtype=self.dtype,
                interpret=self.flash_interpret, router=self.moe_router,
                route_scale=self.moe_route_scale,
                shared_hidden=self.moe_shared_hidden, held=self.moe_held,
                activation=self.moe_activation, latent=self.moe_latent,
                norm_topk=self.moe_norm_topk, route_eps=self.moe_route_eps,
                name="moe")(h))

        def dense(width, name):
            return nn.Dense(width, use_bias=False, dtype=self.dtype, name=name)

        with jax.named_scope(device_names.MLP):
            if self.mlp_hidden is not None:
                gate, up = (dense(self.mlp_hidden, name)(h)
                            for name in ("mlp_gate", "mlp_up"))
                h = dense(self.dim, "mlp_down")(nn.silu(gate) * up)
            else:
                h = nn.gelu(dense(self.mlp_ratio * self.dim, "mlp_in")(h))
                h = dense(self.dim, "mlp_out")(h)
        return self._add(x, self._norm(h) if self.norm_after
                         or self.sandwich_norm else h)

    def _norm(self, x):
        """One RMSNorm of a half, in one of three placements: of the half's
        input (the block's first call is then the mixer's, its second the
        MLP's); with ``norm_after`` of its output; with ``sandwich_norm`` of
        both (four calls a layer: the mixer's input and output, then the
        MLP's)."""
        with jax.named_scope(device_names.NORM_ADD):
            return nn.RMSNorm(epsilon=self.rms_norm_eps, dtype=self.dtype)(x)

    def _check_halves(self):
        """A size stated for a half this layer does not have is an error,
        not something to ignore."""
        if self.sublayers not in ("both", "mixer", "mlp"):
            raise ValueError(f"sublayers {self.sublayers!r}: 'both', 'mixer' "
                             f"or 'mlp'")
        stated = {"mixer": {"mlp_hidden": self.mlp_hidden is not None,
                            "moe_experts": self.moe_experts > 0},
                  "mlp": {"mamba": self.mamba is not None,
                          "mla": self.mla is not None,
                          "kda": self.kda is not None,
                          "conv": self.conv is not None,
                          "gdn": self.gdn is not None},
                  "both": {}}[self.sublayers]
        stated["moe_shared_hidden"] = (self.moe_shared_hidden > 0
                                       and self.moe_experts <= 0)
        extra = sorted(k for k, v in stated.items() if v)
        if extra:
            raise ValueError(
                f"{', '.join(extra)} stated for a layer (sublayers="
                f"{self.sublayers!r}, moe_experts={self.moe_experts}) that "
                f"has no such half")
        other = [name for name in ("conv", "gdn", "kda", "mamba", "mla")
                 if getattr(self, name) is not None]
        if self.gdn is not None and len(other) > 1:
            raise ValueError(f"{' and '.join(other)} stated for ONE layer: "
                             f"a 'linear_attention' layer's mixer is the "
                             f"gated delta rule")
        if self.conv is not None and len(other) > 1:
            raise ValueError(f"{' and '.join(other)} stated for ONE layer: "
                             f"a 'conv' layer's mixer is the convolution")
        if gate_form(self.attn_gate) is not None and (
                other or self.sublayers == "mlp"):
            raise ValueError(
                f"attn_gate={self.attn_gate!r} gates softmax attention's "
                f"output before o_proj: this layer (sublayers="
                f"{self.sublayers!r}, mixer {other or 'none'}) runs none")

    def _mixer(self, x, positions):
        """The mixer's branch: of the normed ``x``, with ``norm_after`` the
        normed branch of ``x`` itself, with ``sandwich_norm`` the normed
        branch of the normed ``x``."""
        if self.norm_after:
            return self._norm(self._mixed(x, positions))
        branch = self._mixed(self._norm(x), positions)
        return self._norm(branch) if self.sandwich_norm else branch

    def _mixed(self, h, positions):
        """The mixer on ``h``: a gated short convolution, a Mamba-2 mixer, a
        Kimi Delta Attention mixer, a Gated DeltaNet mixer, latent attention
        or multi-head attention."""
        if self.gdn is not None:
            return GDNMixer(dim=self.dim, dims=self.gdn,
                            rms_norm_eps=self.rms_norm_eps, dtype=self.dtype,
                            interpret=self.flash_interpret, name="mixer")(h)
        if self.conv is not None:
            return ShortConvMixer(dim=self.dim, dims=self.conv,
                                  dtype=self.dtype,
                                  interpret=self.flash_interpret,
                                  name="mixer")(h)
        if self.kda is not None:
            return KDAMixer(dim=self.dim, dims=self.kda,
                            rms_norm_eps=self.rms_norm_eps, dtype=self.dtype,
                            interpret=self.flash_interpret, name="mixer")(h)
        if self.mamba is not None:
            return Mamba2Mixer(dim=self.dim, dims=self.mamba,
                               rms_norm_eps=self.rms_norm_eps, dtype=self.dtype,
                               interpret=self.flash_interpret, name="mixer")(h)
        # the outer name of the three attention forms: every kernel and
        # scope inside is the last name on its own path and keeps its time
        with jax.named_scope(device_names.ATTN):
            if self.mla is not None:
                return self._latent_attention(h, positions)
            if self.sparse is not None:
                return self._selected_attention(h, positions)
            return self._attention(h, positions)

    def _add(self, x, branch):
        with jax.named_scope(device_names.NORM_ADD):
            if self.residual_scale != 1.0:
                branch = branch * jnp.asarray(self.residual_scale, branch.dtype)
            return x + branch

    def _attention(self, h, positions):
        """Causal self-attention of the normed ``h``, through o_proj."""
        if self.head_dim is None and self.dim % self.heads:
            raise ValueError(
                f"dim {self.dim} is no multiple of heads {self.heads}: a head "
                f"size of its own is head_dim's to state")
        head_dim = (self.dim // self.heads if self.head_dim is None
                    else self.head_dim)
        width = self.heads * head_dim       # of q and of o_proj's input
        kvh = self.heads if self.kv_heads is None else self.kv_heads
        if kvh < 1 or self.heads % kvh:
            raise ValueError(
                f"kv_heads {kvh} must be >= 1 and divide heads {self.heads} "
                f"(each of head_dim {head_dim})")
        if self.window is not None and self.sp_axis is not None:
            raise ValueError("the ring schedules have no window: "
                             "window needs sp_axis=None")
        b, t = h.shape[0], h.shape[1]
        with jax.named_scope(device_names.ATTN_PROJ):
            if kvh == self.heads:
                qkv = nn.Dense(3 * width, use_bias=False, dtype=self.dtype, name="qkv")(h)
                q, k, v = jnp.split(qkv, 3, axis=-1)
            else:
                q = nn.Dense(width, use_bias=False, dtype=self.dtype,
                             name="q_proj")(h)
                kv = nn.Dense(2 * kvh * head_dim, use_bias=False,
                              dtype=self.dtype, name="kv_proj")(h)
                k, v = jnp.split(kv, 2, axis=-1)
        if self.qk_norm:
            # OLMoE: over ALL heads x head_dim features, before the split
            # into heads, each with a weight of that length.
            q = nn.RMSNorm(epsilon=self.rms_norm_eps, dtype=self.dtype,
                           name="q_norm")(q)
            k = nn.RMSNorm(epsilon=self.rms_norm_eps, dtype=self.dtype,
                           name="k_norm")(k)
        # q wholly before k, as ever
        plain_rope = self.rope and self.rotary is None
        q = q.reshape(b, t, self.heads, head_dim)
        if self.qk_head_norm:
            # Qwen3's and LFM2's: over EACH head, one weight of head_dim,
            # before the rotation
            q = nn.RMSNorm(epsilon=self.rms_norm_eps, dtype=self.dtype,
                           name="q_head_norm")(q)
        if plain_rope:
            q = _rope(q, positions, self.rope_theta, self.rope_interleave)
        k = k.reshape(b, t, kvh, head_dim)
        if self.qk_head_norm:
            k = nn.RMSNorm(epsilon=self.rms_norm_eps, dtype=self.dtype,
                           name="k_head_norm")(k)
        if plain_rope:
            k = _rope(k, positions, self.rope_theta, self.rope_interleave)
        if self.rotary is not None:
            with jax.named_scope(device_names.ATTN_ROPE):
                q, k = (_rope_scheme(x, positions, self.rotary) for x in (q, k))
        v = v.reshape(b, t, kvh, head_dim)
        if self.attention == "dense" and kvh != self.heads and self.sp_axis is None:
            # The local dense einsum path is plain multi-head; replicate kv
            # heads for it. The ring path replicates INSIDE the per-step
            # block product (ring_attention GQA support) so the ring rotates
            # small kv blocks over ICI; the flash kernels alias the shared
            # head via the grid index map and never materialize the copies.
            k = jnp.repeat(k, self.heads // kvh, axis=2)
            v = jnp.repeat(v, self.heads // kvh, axis=2)
        bq, bk = self._flash_blocks()
        if self.sp_axis is not None:
            if self.attention_scale is not None:
                raise ValueError("the ring schedules scale by head_dim ** -0.5; "
                                 "attention_scale needs sp_axis=None")
            if self.attention == "flash":
                from ..ops.ring_flash import ring_flash_attention

                # positional: custom_vjp nondiff_argnums
                attn = ring_flash_attention(q, k, v, self.sp_axis, False,
                                            bq, bk, self.flash_interpret)
            else:
                from ..ops.ring_attention import ring_attention

                attn = ring_attention(q, k, v, axis_name=self.sp_axis)
        elif self.attention == "flash":
            from ..ops.flash_attention import flash_attention

            # positional: custom_vjp nondiff_argnums
            if self.window is None:
                attn = flash_attention(q, k, v, True, bq, bk,
                                       self.flash_interpret, self.attention_scale)
            else:
                # blocks the model does not state are the kernels' to choose
                # from the window
                attn = flash_attention(q, k, v, True, self.block_q,
                                       self.block_k, self.flash_interpret,
                                       self.attention_scale, self.window)
        elif self.window is not None:
            attn = causal_attention(q, k, v, scale=self.attention_scale,
                                    window=self.window)
        else:
            # the keyword only where a scale is stated: the benchmark's lm217m
            # reference swaps this function for one that takes none
            attn = (causal_attention(q, k, v) if self.attention_scale is None
                    else causal_attention(q, k, v, scale=self.attention_scale))
        form = gate_form(self.attn_gate)
        if form is not None:
            from ..metrics import record_attn_gate_width

            record_attn_gate_width(self.heads if form == "head" else width)
        if form == "head":
            with jax.named_scope(device_names.ATTN_GATE):
                gate = nn.sigmoid(nn.Dense(self.heads, use_bias=False,
                                           dtype=self.dtype, name="gate_proj")(h))
                attn = attn * gate[..., None]
        attn = attn.reshape(b, t, width)
        if form == "element":
            # arXiv:2505.06708's gate: sigmoid(h Wg) an element of the
            # output, its projection as wide as q's and counted with q's
            with jax.named_scope(device_names.ATTN_PROJ):
                gate = nn.Dense(width, use_bias=False, dtype=self.dtype,
                                name="gate_proj")(h)
            with jax.named_scope(device_names.ATTN_GATE):
                attn = (attn.astype(jnp.float32) * nn.sigmoid(
                    gate.astype(jnp.float32))).astype(self.dtype)
        with jax.named_scope(device_names.ATTN_PROJ):
            return nn.Dense(self.dim, use_bias=False, dtype=self.dtype, name="o_proj")(attn)

    def _selected_attention(self, h, positions):
        """Grouped-query attention of the normed ``h`` over a learned
        selection (docs/sparse-attention.md), through o_proj. The indexer
        reads ``stop_gradient(h)``: queries ``index_q`` (``index_heads`` x
        ``index_dim``), ONE key head ``index_k`` under a LayerNorm with bias,
        both turned by the temporal positions at ``rope_theta``, and a weight
        a head and token ``index_w`` x ``index_heads^-0.5 index_dim^-0.5``.
        The selection (``ops.sparse_attention.select``) carries no gradient
        and is saved across a recomputation by name; every head attends over
        it (``ops.flash_attention.selected_attention``); the alignment loss
        (``ops.sparse_attention.align_loss``) is sown as ``dsa_align_loss``
        and is the ONLY source of the indexer's gradients, as the
        language-model loss is of every other parameter's. Also sown:
        ``dsa_words``, the selection as bits, and ``dsa_selected_pairs`` and
        ``dsa_live_block_steps``, the layer's census from the data."""
        from ..ops import sparse_attention as dsa
        from ..ops.flash_attention import _plan, selected_attention

        sp, heads = self.sparse, self.heads
        if (self.attention != "flash" or self.sp_axis is not None
                or self.window is not None or gate_form(self.attn_gate)
                or self.qk_norm):
            raise ValueError(
                "sparse attention runs through the flash kernels on one "
                "chip: attention='flash', no sp_axis, window, gate or "
                "whole-projection qk_norm")
        head_dim = (self.dim // heads if self.head_dim is None
                    else self.head_dim)
        kvh = heads if self.kv_heads is None else self.kv_heads
        b, t = h.shape[0], h.shape[1]
        time = positions[0] if positions.ndim == 3 else positions

        def dense(width, name):
            return nn.Dense(width, use_bias=False, dtype=self.dtype, name=name)

        with jax.named_scope(device_names.ATTN_PROJ):
            q = dense(heads * head_dim, "q_proj")(h).reshape(b, t, heads, head_dim)
            k, v = (x.reshape(b, t, kvh, head_dim) for x in jnp.split(
                dense(2 * kvh * head_dim, "kv_proj")(h), 2, axis=-1))
        if self.qk_head_norm:
            q = nn.RMSNorm(epsilon=self.rms_norm_eps, dtype=self.dtype,
                           name="q_head_norm")(q)
            k = nn.RMSNorm(epsilon=self.rms_norm_eps, dtype=self.dtype,
                           name="k_head_norm")(k)
        if self.rotary is not None:
            with jax.named_scope(device_names.ATTN_ROPE):
                q, k = (_rope_scheme(x, positions, self.rotary) for x in (q, k))
        elif self.rope:
            q, k = (_rope(x, time, self.rope_theta, self.rope_interleave)
                    for x in (q, k))
        with jax.named_scope(device_names.DSA_INDEXER):
            hd = jax.lax.stop_gradient(h)
            # a scheme's angles are float64 on the host before they are
            # float32: at base 1e7 a float32 power on the device is a few
            # 1e-4 rad off by position 4,096, which moves the selection
            turn = RotaryScheme(theta=self.rope_theta)
            qi = _rope_scheme(dense(sp.index_heads * sp.index_dim, "index_q")(
                hd).reshape(b, t, sp.index_heads, sp.index_dim), time, turn)
            ki = nn.LayerNorm(epsilon=1e-6, dtype=self.dtype,
                              name="index_k_norm")(
                dense(sp.index_dim, "index_k")(hd))
            ki = _rope_scheme(ki[:, :, None, :], time, turn)[:, :, 0]
            w = dense(sp.index_heads, "index_w")(hd).astype(jnp.float32) * (
                sp.index_heads ** -0.5 * sp.index_dim ** -0.5)
        words, lse_i = dsa.select(qi, ki, w, sp.topk, sp.kv_chunk, sp.q_chunk,
                                  self.flash_interpret)
        # positional: custom_vjp nondiff_argnums
        attn, lse = selected_attention(
            q, k, v, words, self.block_q, self.block_k, self.flash_interpret,
            self.attention_scale, sp.kv_chunk)
        self.sow("intermediates", "dsa_align_loss", dsa.align_loss(
            *map(jax.lax.stop_gradient, (q, k, lse)), qi, ki, w, words, lse_i,
            self.attention_scale, sp.kv_chunk, self.flash_interpret))
        pairs, live = dsa.census(words, *_plan(
            t, self.block_q, self.block_k, self.flash_interpret, None)[:2],
            sp.kv_chunk)
        self.sow("intermediates", "dsa_words", words)
        self.sow("intermediates", "dsa_selected_pairs", pairs)
        self.sow("intermediates", "dsa_live_block_steps", live)
        with jax.named_scope(device_names.ATTN_PROJ):
            return dense(self.dim, "o_proj")(attn.reshape(b, t, heads * head_dim))

    def _flash_blocks(self):
        """(block_q, block_k) for the flash kernels: the fields, or the
        kernels' own defaults."""
        from ..ops.flash_attention import DEFAULT_BLOCK_K, DEFAULT_BLOCK_Q

        return (self.block_q if self.block_q is not None else DEFAULT_BLOCK_Q,
                self.block_k if self.block_k is not None else DEFAULT_BLOCK_K)

    def _latent_attention(self, h, positions):
        """Latent attention (MLA, arXiv:2412.19437 §2.1.1, q without a latent)
        of the normed ``h``, through o_proj: keys and values come out of a
        normed latent of ``kv_rank``, the rotary part of the key is ONE head
        that every query head shares, and q | k have head size ``qk_nope +
        qk_rope`` where v has ``v``. q, k and v reach the flash kernels in the
        parts the projections wrote: ``q_proj``'s output, head by head, its
        rotary lanes turned in place (:func:`_rope`'s ``lead``);
        ``kv_b_proj``'s output whole, ``[k_nope | v]`` a head
        (``flash_attention``'s ``v=None``, where ``qk_nope`` is a whole number
        of ``v``; else split); and the shared rotary key as ONE head,
        ``k_shared``, which the kernels read through an index map as the
        grouped-query path reads a shared head and whose gradient they sum
        over the heads. No part of q alone and no k of ``qk_nope + qk_rope``
        lanes is built (the dense branch splits kv and assembles k).
        ``rope=False`` (``mla_use_nope``): neither rotary part is turned."""
        m, heads = self.mla, self.heads
        if self.sp_axis is not None or self.kv_heads not in (None, heads):
            raise ValueError("latent attention is multi-head on one chip: "
                             "no sp_axis, no kv_heads")
        b, t = h.shape[0], h.shape[1]

        def dense(width, name):
            return nn.Dense(width, use_bias=False, dtype=self.dtype, name=name)

        with jax.named_scope(device_names.MLA_PROJ):
            q = dense(heads * (m.qk_nope + m.qk_rope), "q_proj")(h)
            latent, k_rope = jnp.split(
                dense(m.kv_rank + m.qk_rope, "kv_a_proj")(h), [m.kv_rank], axis=-1)
            latent = nn.RMSNorm(epsilon=self.rms_norm_eps, dtype=self.dtype,
                                name="kv_a_norm")(latent)
            kv = dense(heads * (m.qk_nope + m.v), "kv_b_proj")(latent)
        with jax.named_scope(device_names.MLA_ROPE):
            q = q.reshape(b, t, heads, m.qk_nope + m.qk_rope)
            kv = kv.reshape(b, t, heads, m.qk_nope + m.v)
            k_rope = k_rope.reshape(b, t, 1, m.qk_rope)
            if self.rope:   # q's trailing lanes in place; the one key head
                q = _rope(q, positions, self.rope_theta,
                          self.rope_interleave, lead=m.qk_nope)
                k_rope = _rope(k_rope, positions, self.rope_theta,
                               self.rope_interleave)
        if self.attention == "flash" and m.qk_nope % m.v == 0:
            # [k_nope | v] goes to the kernels as kv_b_proj wrote it: their
            # index maps read each by its lane block
            k_nope, v = kv, None
        else:
            with jax.named_scope(device_names.MLA_ROPE):
                k_nope, v = jnp.split(kv, [m.qk_nope], axis=-1)
        if self.attention == "flash":
            from ..ops.flash_attention import flash_attention

            # positional: custom_vjp nondiff_argnums
            attn = flash_attention(q, k_nope, v, True, *self._flash_blocks(),
                                   self.flash_interpret, self.attention_scale,
                                   None, k_rope)
        else:
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(k_rope, (b, t, heads, m.qk_rope))], axis=-1)
            attn = causal_attention(q, k, v, scale=self.attention_scale)
        with jax.named_scope(device_names.MLA_PROJ):
            return dense(self.dim, "o_proj")(attn.reshape(b, t, heads * m.v))


# layer_types' names for a layer that is ONE sub-layer -> Block.sublayers
_ONE_SUBLAYER = {"mamba_only": "mixer", "attention_only": "mixer",
                 "experts_only": "mlp"}


class TransformerLM(nn.Module):
    # TPU sizing note: prefer
    # head_dim = dim // heads >= 128 where the architecture is yours to
    # choose. The MXU contracts 128 lanes per pass, so head_dim 64 runs every
    # attention matmul at half width. Round 3 (the kernels of that time)
    # measured a 33% tokens/sec swing at dim 1024 between heads=16 (hd 64) and
    # heads=8 (hd 128), identical FLOPs and params. Re-read with PR 25's
    # kernels (PERF.md §5, PR 30, one v5e chip at 16,384 tokens): at head_dim
    # 64 (32 query heads over 8, granite4h_long_1chip) the forward, dq and dkv
    # kernels run at 38 / 39 / 37% of the bf16 peak on their needed products;
    # at head_dim 128 (lm217m_long_1chip) at 68 / 84 / 76%. Still half.
    vocab: int = 32000
    dim: int = 512
    heads: int = 8
    layers: int = 6
    mlp_ratio: int = 4
    dtype: Any = jnp.bfloat16
    sp_axis: Optional[str] = None
    # >0 turns every `moe_every`-th block's MLP into a mixture of this many
    # experts (models/moe.py): OLMoE's dropless SwiGLU experts of width
    # moe_hidden, moe_top_k of them a token (MoEMLP raises a ValueError
    # unless 0 < moe_top_k <= moe_experts), whose auxiliary losses the caller
    # reads with models.moe.aux_losses. ep_param_specs names the expert
    # tensors for a mesh that shards them.
    moe_experts: int = 0
    moe_every: int = 2
    moe_top_k: int = 0
    moe_hidden: Optional[int] = None
    # OLMoE's QK-norm: RMSNorm over the whole projected q and k (all heads),
    # before the split into heads and RoPE.
    qk_norm: bool = False
    rms_norm_eps: float = 1e-6      # every RMSNorm of the model
    # "flash" runs attention through the pallas fused kernel (O(T*D) HBM
    # traffic; trains at sequence lengths where the dense schedule cannot
    # even compile — measured on v5e: seq 8192 dense OOMs the compiler,
    # flash runs). Sequence length must tile into 128-blocks. The kernels
    # compile for the TPU only: a flash model on a machine without one
    # raises unless flash_interpret asks for the interpreter. Combined
    # with sp_axis it selects ring_flash_attention: ring schedule between
    # chips, fused flash blocks within each chip.
    attention: str = "dense"
    # kv_heads < heads enables grouped-query attention: one kv head serves
    # heads//kv_heads query heads. The flash kernels alias the shared head
    # (no replication in HBM), and ring_flash rotates only the small kv
    # blocks over ICI.
    kv_heads: Optional[int] = None
    # Rematerialize each block in the backward pass (jax.checkpoint): trade
    # one extra forward of FLOPs for O(layers) less activation HBM — the
    # knob that buys deeper models / longer sequences when activations,
    # not weights, are the memory ceiling. Composes with flash and sp.
    remat: bool = False
    # flash kernel tile sizes (None = ops/flash_attention.py defaults;
    # sweep per sequence length with transformer_benchmark --sweep-blocks)
    block_q: Optional[int] = None
    block_k: Optional[int] = None
    # Pallas interpreter for the flash kernels (CPU tests); see Block.
    flash_interpret: bool = False
    # dtype of the lm_head matmul AND the stored logits. f32 (default) is
    # the conservative choice; bf16 halves the logits pipeline's HBM
    # traffic (B*T*vocab bytes through head matmul epilogue, reshape,
    # softmax-CE and its backward — ~10% of the 4k batch-1 step on an
    # earlier installation). With bf16, upcast to f32 BEFORE the
    # cross entropy (the convert fuses into the CE read, costing no HBM):
    # the remaining numerics change is the one-time bf16 rounding of the
    # logit values themselves. Kernel params stay f32 either way.
    logits_dtype: Any = jnp.float32
    # A hybrid model (Granite 4.0-H: docs/mamba-hybrid.md), each as the
    # model's own configuration states it. layer_types: one of "attention" /
    # "mamba" a layer (len == layers; None: attention everywhere), "mamba"
    # putting a Mamba-2 mixer of the sizes in ``mamba`` (models/mamba.py) in
    # attention's place. mlp_hidden: a SwiGLU MLP of that width in every
    # block in place of the ungated GELU one. rope=False: no rotary
    # embedding ("nope"). tie_embeddings: the head is the embedding's
    # transpose, one leaf that receives both gradients (no lm_head leaf).
    # The four multipliers: the embedding's output times
    # embedding_multiplier, attention scores times attention_multiplier in
    # place of head_dim ** -0.5 (handed to the flash kernels as their
    # softmax scale), every residual branch times residual_multiplier,
    # logits divided by logits_scaling — with return_hidden the hidden states
    # come back already divided by it, so that ``hidden @ head`` is the logits
    # for chunked_lm_loss, whose ``head`` is then ``embedding.T``.
    layer_types: Optional[tuple] = None
    mamba: Optional[Mamba2Dims] = None
    mlp_hidden: Optional[int] = None
    rope: bool = True
    tie_embeddings: bool = False
    embedding_multiplier: float = 1.0
    attention_multiplier: Optional[float] = None
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    # A DeepSeek-V3-family model (kanana-2-30b-a3b: docs/mla-moe.md), each as
    # the model's own configuration states it. mla: latent attention of these
    # sizes in every attention layer. rope_theta, rope_interleave: the rotary
    # base, and pairs (2i, 2i + 1) in place of (i, i + half). first_k_dense:
    # the first k layers take the dense MLP (mlp_hidden, or the GELU one) and
    # every later one the experts; it is DeepSeek's pattern, counted from the
    # first layer, where moe_every counts from a period's last: set together
    # they would state two patterns, so first_k_dense > 0 wants moe_every = 1
    # (every layer after the dense ones) and raises otherwise. moe_router
    # "sigmoid": scores by sigmoid, the choice by score + bias, the chosen
    # scores renormalised and times moe_route_scale (models/moe.py; the bias
    # is the collection ``moe_bias``, moved by the caller's step).
    # moe_shared_hidden: a shared SwiGLU expert of that width beside the
    # routed ones. moe_held = (first, count): this rank holds those of the
    # moe_experts the router chooses among (one expert-parallel rank's share).
    mla: Optional[LatentDims] = None
    rope_theta: float = 10000.0
    rope_interleave: bool = False
    first_k_dense: int = 0
    moe_router: str = "softmax"
    moe_route_scale: float = 1.0
    moe_shared_hidden: int = 0
    moe_held: Optional[tuple] = None
    # A window / full hybrid (Laguna-XS.2: docs/window-attention.md), each as
    # the model's own configuration states it. layer_types may also name
    # "full_attention" / "sliding_attention": causal attention over every
    # earlier key, or over the sliding_window last ones (the query's own
    # included; through the flash kernels' ``window``), each kind with a
    # rotary scheme of its own (full_rotary, sliding_rotary: a RotaryScheme;
    # None: rope_theta's plain one). head_dim: a head's size where it is not
    # dim // heads; q and o are then heads x head_dim wide. heads_per_layer:
    # the query heads of each layer (len == layers; None: heads everywhere)
    # over the same kv_heads. attn_gate: a sigmoid gate on softmax
    # attention's output before o_proj, in the form the model's own
    # configuration states: "head" (or True; Laguna's) multiplies the output
    # of head a by sigmoid(h Wg)_a, one number a head and token (Wg dim ->
    # heads); "element" (Solar-Open2's ``use_gqa_gate``; arXiv:2505.06708)
    # multiplies every element by its own, sigmoid(h Wg) with Wg dim ->
    # heads x head_dim. It belongs to the layers that run softmax attention:
    # a "kda", "mamba" or one-sub-layer expert layer of the same model takes
    # none, and a model with no such layer, or with latent or sparse
    # attention, that states one raises.
    head_dim: Optional[int] = None
    heads_per_layer: Optional[tuple] = None
    sliding_window: Optional[int] = None
    full_rotary: Optional[RotaryScheme] = None
    sliding_rotary: Optional[RotaryScheme] = None
    attn_gate: Any = False
    # A Nemotron-H-family hybrid (Nemotron-3-Super: docs/latent-moe.md), each
    # as the model's own configuration states it. layer_types may also name
    # "mamba_only" / "attention_only" / "experts_only" (the pattern's M, * and
    # E): a layer that is ONE sub-layer, x + f(RMSNorm x), f a Mamba-2 mixer,
    # an attention or the experts, with no second half; an "experts_only"
    # layer takes the experts whatever moe_every says, and needs moe_experts
    # > 0. moe_activation "relu2": experts (and the shared expert) without a
    # gate, down(relu(up x)^2). moe_latent > 0: the routed experts live in a
    # latent of that width (models/moe.py). mtp_layer_types: a
    # multi-token-prediction module of depth 1 (arXiv:2412.19437 §2.2) whose
    # blocks are of these kinds: h' = W [RMSNorm(h_t) ; RMSNorm(E[x_{t+1}])]
    # (2 dim -> dim) with h the main model's last residual stream (before its
    # final norm) and E the model's OWN embedding, through the blocks, a
    # final norm. The call then returns a PAIR, the main model's result and
    # the module's (hidden states with return_hidden, else logits through
    # the SAME head), position t of the module's standing for x_{t+2}:
    # ``lm_loss_with_mtp`` takes both losses.
    moe_activation: str = "swiglu"
    moe_latent: int = 0
    mtp_layer_types: Optional[tuple] = None
    # A sparse-attention mixture of experts (Keye-VL-2.0-30B-A3B's language
    # model: docs/sparse-attention.md), each as the model's own configuration
    # states it. sparse: every attention layer scores all earlier tokens with
    # an indexer, keeps each query's ``topk`` best and attends over those
    # (``sa_config``); the layers then sow ``dsa_align_loss``, which
    # ``models.transformer.align_losses`` sums for the caller's loss function
    # as ``models.moe.aux_losses`` sums the routers'. qk_head_norm: RMSNorm
    # with a weight of head_dim over EACH head of q and of k, before the
    # rotary embedding (Qwen3's; ``qk_norm`` is OLMoE's, over the whole
    # projection). rotary: a RotaryScheme for "attention" layers, whose
    # ``sections`` take positions in three streams ``(3, B, T)``
    # (``mrope_section``). moe_norm_topk: the softmax router's chosen
    # probabilities divided by their sum (``norm_topk_prob``).
    sparse: Optional[SparseDims] = None
    qk_head_norm: bool = False
    rotary: Optional[RotaryScheme] = None
    moe_norm_topk: bool = False
    # A linear-attention hybrid (Kimi-Linear-48B-A3B: docs/linear-attention.md),
    # as the model's own configuration states it. layer_types may also name
    # "kda": a Kimi Delta Attention mixer of the sizes in ``kda``
    # (models/kda.py: a gated delta rule with a decay a channel) in
    # attention's place; with ``mla`` set its "full_attention" layers are
    # latent attention, and ``rope=False`` (``mla_use_nope``) turns neither
    # rotary part: no position information anywhere in the model. Without
    # ``mla`` (Solar-Open2-250B) they are grouped-query softmax attention of
    # ``heads`` over ``kv_heads`` of ``head_dim``, gated as ``attn_gate``
    # says; ``kda.allow_neg_eigval`` (``kda_allow_neg_eigval``) doubles the
    # delta rule's beta. ``heads`` / ``kv_heads`` / ``kda.heads`` are what
    # THIS rank holds where a layer's heads are cut over tensor ranks.
    kda: Optional[KDADims] = None
    # A short-convolution hybrid (LFM2-24B-A2B: docs/short-conv.md), each as
    # the model's own configuration states it. layer_types may also name
    # "conv": a gated short-convolution mixer of the sizes in ``conv``
    # (models/short_conv.py: one projection dim -> 3 dim, a product, a causal
    # depthwise convolution of ``conv_L_cache`` taps with no activation, a
    # second product, an output projection) in attention's place; its
    # "full_attention" layers are grouped-query softmax attention with
    # ``qk_head_norm`` before the rotation at ``rope_theta``. ``first_k_dense``
    # counts layers of any kind: a dense layer's mixer may be a "conv".
    # moe_route_eps: what the sigmoid router adds to the sum of the chosen
    # scores before it divides by it (LFM2's released code: 1e-6; the default
    # is DeepSeek-V3's 1e-20).
    conv: Optional[ShortConvDims] = None
    moe_route_eps: float = 1e-20
    # What a delta-rule hybrid of the OLMo family's configuration states
    # (Olmo-Hybrid-7B: ``linear_num_key_heads``, ``linear_key_head_dim``,
    # ``linear_value_head_dim``, ``linear_conv_kernel_dim``,
    # ``linear_allow_neg_eigval``). layer_types may also name
    # "linear_attention": a Gated DeltaNet mixer of the sizes in ``gdn``
    # (models/gdn.py: a gated delta rule with ONE decay a head, keys and
    # values of different widths, a silu-gated head norm) in attention's
    # place. norm_after: OLMo 2's reordered norm, ``x + norm(f(x))`` for both
    # halves of every layer with no norm before ``f`` (dense MLP halves only);
    # its "full_attention" layers are ``qk_norm`` attention with ``rope``
    # as stated.
    gdn: Optional[GDNDims] = None
    norm_after: bool = False
    # A looped model (Ouro-2.6B: docs/looped.md), each as the model's own
    # configuration states it. passes (``total_ut_steps``): the residual
    # stream goes through the ONE stack of ``layers`` blocks that many times,
    # every pass over the same parameters (one ``block_{i}`` leaf set, whose
    # gradient is the sum of the passes'); the model's one final RMSNorm
    # closes EVERY pass, and its output is what enters the next. With
    # ``remat`` each block APPLICATION is recomputed on its own (layers x
    # passes saved streams). exit_gate: a one-number early-exit gate
    # ``x w_e + b_e`` (leaf ``exit_gate``, float32) reads every pass's normed
    # stream. With ``passes`` > 1 or ``exit_gate`` the call returns a PAIR:
    # the passes' results stacked on a leading axis ((passes, B, T, dim)
    # hidden states with return_hidden, else (passes, B, T, vocab) logits
    # through the SAME head) and the gates' logits ((passes, B, T) float32;
    # None without a gate), for ``loop_lm_loss``, which takes the loss
    # expected over the exit distribution the gates define. sandwich_norm: a
    # norm on BOTH sides of each half, ``x + norm(f(norm(x)))``, four RMSNorms
    # a layer (dense MLP halves only), where ``norm_after`` has the second
    # alone and every other model the first.
    passes: int = 1
    exit_gate: bool = False
    sandwich_norm: bool = False

    @nn.compact
    def __call__(self, tokens, positions=None, return_hidden: bool = False):
        if positions is None:
            positions = jnp.arange(tokens.shape[1])[None, :]
        kinds = (("attention",) * self.layers if self.layer_types is None
                 else tuple(self.layer_types))
        mtp_kinds = tuple(self.mtp_layer_types or ())
        if len(kinds) != self.layers or set(kinds + mtp_kinds) - {
                "attention", "mamba", "full_attention", "sliding_attention",
                "kda", "conv", "linear_attention", *_ONE_SUBLAYER}:
            raise ValueError(
                f"layer_types {kinds} (mtp_layer_types {mtp_kinds}) must name "
                f"'attention', 'mamba', 'full_attention', 'sliding_attention', "
                f"'kda', 'conv', 'linear_attention' or, for a layer that is one "
                f"sub-layer, 'mamba_only', "
                f"'attention_only' or 'experts_only' for each of the "
                f"{self.layers} layers")
        if {"mamba", "mamba_only"} & set(kinds + mtp_kinds) and self.mamba is None:
            raise ValueError("a 'mamba' layer needs the mixer's sizes (mamba=)")
        if "kda" in kinds + mtp_kinds and self.kda is None:
            raise ValueError("a 'kda' layer needs the mixer's sizes (kda=)")
        if "conv" in kinds + mtp_kinds and self.conv is None:
            raise ValueError("a 'conv' layer needs the mixer's sizes (conv=)")
        if "linear_attention" in kinds + mtp_kinds and self.gdn is None:
            raise ValueError("a 'linear_attention' layer needs the mixer's "
                             "sizes (gdn=)")
        if "experts_only" in kinds + mtp_kinds and self.moe_experts <= 0:
            raise ValueError("an 'experts_only' layer needs its experts "
                             "(moe_experts=, moe_top_k=)")
        if self.mlp_hidden is not None and not set(kinds) - set(_ONE_SUBLAYER):
            raise ValueError(
                f"mlp_hidden={self.mlp_hidden} but no layer of {kinds} has a "
                f"dense MLP half")
        if mtp_kinds and self.tie_embeddings:
            raise ValueError("the multi-token-prediction module shares an "
                             "untied head: tie_embeddings must be False")
        softmax_kinds = {"attention", "full_attention", "sliding_attention",
                         "attention_only"}
        if gate_form(self.attn_gate) is not None and (
                self.mla is not None or not softmax_kinds & set(kinds + mtp_kinds)):
            raise ValueError(
                f"attn_gate={self.attn_gate!r} gates softmax attention's "
                f"output, and no layer of {kinds + mtp_kinds} "
                f"{'(latent attention: mla=) ' if self.mla is not None else ''}"
                f"runs it")
        if "sliding_attention" in kinds and self.sliding_window is None:
            raise ValueError("a 'sliding_attention' layer needs its window "
                             "(sliding_window=)")
        heads = ((self.heads,) * self.layers if self.heads_per_layer is None
                 else tuple(self.heads_per_layer))
        if len(heads) != self.layers:
            raise ValueError(f"heads_per_layer {heads} must state the query "
                             f"heads of each of the {self.layers} layers")
        rotary = {"full_attention": self.full_rotary,
                  "sliding_attention": self.sliding_rotary}
        if self.first_k_dense and (self.moe_experts <= 0 or self.moe_every != 1):
            raise ValueError(
                f"first_k_dense={self.first_k_dense} states dense layers "
                f"before experts in EVERY later layer: it needs moe_experts > 0 "
                f"and moe_every=1, not {self.moe_experts} and {self.moe_every}")
        looped = self.passes > 1 or self.exit_gate
        if self.passes < 1:
            raise ValueError(f"passes {self.passes}: the stack runs at least "
                             f"once")
        if looped and (mtp_kinds or self.logits_scaling != 1.0
                       or self.tie_embeddings):
            raise ValueError(
                "a looped model (passes > 1 or exit_gate) hands every pass's "
                "normed stream on as it is, to an untied head: no "
                "multi-token-prediction module, logits_scaling or "
                "tie_embeddings")
        embed = nn.Embed(self.vocab, self.dim, dtype=self.dtype, name="embed")
        with jax.named_scope(device_names.EMBED):
            x = embed(tokens)
            if self.embedding_multiplier != 1.0:
                x = x * jnp.asarray(self.embedding_multiplier, x.dtype)
        block_cls = Block
        if self.remat:
            # What the routers chose and attention's selection are saved,
            # never recomputed (ops/moe.py, ops/sparse_attention.py): the
            # chosen experts and, of a sigmoid router, the scores at them and
            # the weights, (N, top_k) each, from which its backward is formed
            # - so a recomputed expert layer runs no router product. So are
            # the alignment loss's gradients, which its forward pass produced.
            saved = (list(ROUTER_SAVED) if self.moe_experts > 0 else []) + (
                [SELECTED, ALIGN_GRADS] if self.sparse is not None else [])
            block_cls = nn.remat(
                Block, policy=save_names(*saved) if saved else None)

        def block(kind, name, heads, second_is_experts):
            """One layer of ``kind``; ``second_is_experts``: whether a layer
            with both halves takes the experts for its second."""
            sublayers = _ONE_SUBLAYER.get(kind, "both")
            experts = (self.moe_experts if sublayers == "mlp"
                       or (sublayers == "both" and second_is_experts) else 0)
            return block_cls(
                dim=self.dim,
                heads=heads,
                mlp_ratio=self.mlp_ratio,
                dtype=self.dtype,
                sp_axis=self.sp_axis,
                attention=self.attention,
                kv_heads=self.kv_heads,
                block_q=self.block_q,
                block_k=self.block_k,
                flash_interpret=self.flash_interpret,
                moe_experts=experts,
                moe_top_k=self.moe_top_k,
                moe_hidden=self.moe_hidden,
                qk_norm=self.qk_norm,
                rms_norm_eps=self.rms_norm_eps,
                mamba=(self.mamba if kind in ("mamba", "mamba_only")
                       else None),
                mlp_hidden=self.mlp_hidden if sublayers == "both" else None,
                rope=self.rope,
                attention_scale=self.attention_multiplier,
                residual_scale=self.residual_multiplier,
                mla=(self.mla if sublayers != "mlp"
                     and kind not in ("kda", "conv", "linear_attention")
                     else None),
                rope_theta=self.rope_theta,
                rope_interleave=self.rope_interleave,
                moe_router=self.moe_router,
                moe_route_scale=self.moe_route_scale,
                moe_shared_hidden=self.moe_shared_hidden if experts else 0,
                moe_held=self.moe_held,
                head_dim=self.head_dim,
                window=(self.sliding_window if kind == "sliding_attention"
                        else None),
                rotary=rotary.get(kind, self.rotary),
                attn_gate=self.attn_gate if kind in softmax_kinds else False,
                sublayers=sublayers,
                moe_activation=self.moe_activation,
                moe_latent=self.moe_latent,
                sparse=self.sparse if sublayers != "mlp" else None,
                qk_head_norm=self.qk_head_norm,
                moe_norm_topk=self.moe_norm_topk,
                kda=self.kda if kind == "kda" else None,
                conv=self.conv if kind == "conv" else None,
                moe_route_eps=self.moe_route_eps,
                gdn=self.gdn if kind == "linear_attention" else None,
                norm_after=self.norm_after,
                sandwich_norm=self.sandwich_norm,
                name=name,
            )

        def stack():
            """The model's layers, in order; built where they are called (a
            looped model builds them inside its scanned pass)."""
            return [block(kinds[i], f"block_{i}", heads[i],
                          self.moe_experts > 0
                          and i % self.moe_every == self.moe_every - 1
                          and i >= self.first_k_dense)
                    for i in range(self.layers)]

        if looped:
            return self._loop(stack, x, positions, return_hidden)
        for layer in stack():
            x = layer(x, positions)
        if mtp_kinds:
            with jax.named_scope(device_names.MTP):
                following = embed(jnp.roll(tokens, -1, axis=1))
                if self.embedding_multiplier != 1.0:
                    following = following * jnp.asarray(
                        self.embedding_multiplier, x.dtype)
                y = nn.Dense(self.dim, use_bias=False, dtype=self.dtype,
                             name="mtp_proj")(jnp.concatenate([
                                 nn.RMSNorm(epsilon=self.rms_norm_eps,
                                            dtype=self.dtype, name=name)(part)
                                 for name, part in (
                                     ("mtp_hidden_norm", x),
                                     ("mtp_embed_norm", following))], axis=-1))
            for j, kind in enumerate(mtp_kinds):
                y = block(kind, f"mtp_block_{j}", self.heads, False)(
                    y, positions)
            with jax.named_scope(device_names.MTP):
                y = nn.RMSNorm(epsilon=self.rms_norm_eps, dtype=self.dtype,
                               name="mtp_norm")(y)
        with jax.named_scope(device_names.NORM_ADD):
            x = nn.RMSNorm(epsilon=self.rms_norm_eps, dtype=self.dtype)(x)
            if self.logits_scaling != 1.0:
                x = x / jnp.asarray(self.logits_scaling, x.dtype)
        if self.logits_scaling != 1.0 and mtp_kinds:
            y = y / jnp.asarray(self.logits_scaling, y.dtype)
        if self.tie_embeddings:
            if return_hidden:
                return x
            with jax.named_scope(device_names.LM_HEAD):
                return jnp.dot(x.astype(self.logits_dtype),
                               embed.embedding.T.astype(self.logits_dtype))
        head = nn.Dense(self.vocab, use_bias=False, dtype=self.logits_dtype,
                        name="lm_head")
        if return_hidden:
            # Long-sequence loss path: the (B, T, vocab) f32 logits dwarf
            # every other activation past ~16k tokens (vocab 32k -> 4 GB at
            # T=32k). Return the normed hidden states and compute the loss
            # in sequence chunks with chunked_lm_loss.
            if self.is_initializing():
                head(x[:, :1])  # param tree must not depend on the flag
            return (x, y) if mtp_kinds else x
        with jax.named_scope(device_names.LM_HEAD):
            logits = head(x)    # the main head's; the module's pass has no name
        return (logits, head(y)) if mtp_kinds else logits

    def _loop(self, stack, x, positions, return_hidden):
        """``passes`` passes of ``x`` through ``stack()``, the same parameters
        each time, each closed by the model's ONE final norm, whose output is
        read by the exit gate and the head and enters the next pass. Returns
        (the passes' normed streams or their logits, stacked; the gates'
        logits or None). The pass is the body of ONE ``lax.scan`` over the
        passes (``nn.scan`` with the parameters broadcast): traced, lowered
        and compiled once whatever ``passes`` is, and under ``remat`` each
        block application of each pass its own recomputation. An unrolled
        trace of the same 32 applications took 0.6% LONGER a step on the v5e
        and 2.6 times as long to compile (PERF.md section 6, PR 67)."""
        from ..metrics import record_loop_plan

        record_loop_plan(self.passes, self.layers * self.passes)

        def one_pass(mdl, x, _):
            # the stack, the final norm and the gate are built INSIDE the
            # scanned function, under the scan's own scope; their parameters
            # are broadcast over the passes
            for layer in stack():
                x = layer(x, positions)
            with jax.named_scope(device_names.NORM_ADD):
                x = nn.RMSNorm(epsilon=self.rms_norm_eps, dtype=self.dtype,
                               name="RMSNorm_0")(x)
            if not self.exit_gate:
                return x, (x, None)
            with jax.named_scope(device_names.LOOP_EXIT):
                gate = nn.Dense(1, dtype=jnp.float32, name="exit_gate")(x)
            return x, (x, gate[..., 0])

        x, (hidden, gates) = nn.scan(
            one_pass, variable_broadcast="params",
            split_rngs={"params": False}, length=self.passes)(self, x, None)
        head = nn.Dense(self.vocab, use_bias=False, dtype=self.logits_dtype,
                        name="lm_head")
        if return_hidden:
            if self.is_initializing():
                head(x[:, :1])  # param tree must not depend on the flag
            return hidden, gates
        with jax.named_scope(device_names.LM_HEAD):
            return head(hidden), gates


def align_losses(intermediates):
    """(alignment loss, selected pairs, live block steps), each summed over
    the sparse-attention layers found in a model's ``intermediates``
    collection; zeros where there is none."""
    from .moe import sown_sums

    return tuple(sown_sums(intermediates, (
        "dsa_align_loss", "dsa_selected_pairs", "dsa_live_block_steps")
    ).values())


def chunked_lm_loss(hidden, head_kernel, targets, chunk: int = 2048,
                    weights=None):
    """Next-token cross entropy without ever materializing the full
    (B, T, vocab) logits: the lm_head + softmax-CE run over sequence chunks,
    and under ``jax.grad`` each chunk's gradients are formed in the same
    loop iteration that computes its logits, so no logits are saved and
    none are computed a second time (three vocabulary products a chunk:
    logits, d-hidden, d-kernel; a call that is not differentiated issues
    the first alone).

    Use with ``model.apply(..., return_hidden=True)``; ``head_kernel`` is
    ``params["lm_head"]["kernel"]``. Peak extra memory is at most one chunk's
    logits and their gradient (rows·vocab f32 each, rows = B·chunk) plus,
    when differentiated, the f32 (d, vocab) accumulator of the kernel's
    gradient — the difference between OOM and training at 32k+ tokens
    with a 32k vocab. The loss is the mean over every position; logits,
    softmax and the kernel's gradient are float32 whatever ``hidden``'s
    dtype, and the three products follow ``jax.default_matmul_precision``
    as a plain ``@`` does.

    ``weights`` (float32, ``hidden``'s shape without its last axis): a weight
    a ROW, which is data and takes a gradient: the loss is then ``(1 / N)
    sum_rows weights[row] CE[row]`` with N = B·T. ``hidden`` may then carry a
    leading axis of PASSES, ``(P, B, T, d)`` under weights ``(P, B, T)``
    against the one ``targets`` (B, T): the readings of one head by the P
    passes of a looped model (``TransformerLM.passes``), summed over the
    passes, in ONE loop over (pass, chunk) that holds one accumulator of the
    kernel's gradient. Weights of 1 on a ``(B, T, d)`` ``hidden`` give the
    unweighted loss and its gradients, bit for bit. Without ``weights`` the
    loop traces as it did before there were any.

    The loop takes a chunk of every sequence as ONE block of rows,
    ``(B·chunk, d)``, not ``(B, chunk, d)``: handed 2-D logits the TPU
    compiler fuses the row max into the product at every shape the benchmark
    has, where 3-D logits of 2 x 2048 x 8192 got a softmax fusion that wrote
    ``logits - max`` back to HBM (PR 66).

    The gradients come from a ``jax.custom_vjp``: reverse mode only, once.
    Its forward pass forms the loss AND every gradient for a cotangent of 1
    (the weights sit INSIDE the row sums of ``d_kernel = sum_rows w h
    (softmax - hit) / N``, so they are an argument of the loop, not a
    rescaling after it); the gradient of the weights is the per-row cross
    entropies over N, which the same loop hands back. Forward mode (``jvp``
    / ``jacfwd`` / ``linearize``) and second derivatives (``hessian``)
    through this loss are still not available, weighted or not; nothing in
    horovod_tpu, benchmarks/ or examples/ takes one.

    A device profile shows the loop under ``hvd_lm_head``.
    """
    with jax.named_scope(device_names.LM_HEAD):
        return _checked_lm_loss(hidden, head_kernel, targets, chunk, weights)


def _checked_lm_loss(hidden, head_kernel, targets, chunk, weights=None):
    """:func:`chunked_lm_loss` under no name of its own: its caller's."""
    t = hidden.shape[-2]
    if chunk <= 0:
        raise ValueError(f"loss chunk must be positive, got {chunk}")
    chunk = min(chunk, t)
    if t % chunk:
        raise ValueError(f"sequence {t} not divisible by loss chunk {chunk}")
    if weights is None:
        if hidden.ndim != 3:
            raise ValueError(f"hidden states {hidden.shape} with a leading "
                             f"axis of passes need their weights")
        return _chunked_lm_loss(hidden, head_kernel, targets, chunk)
    if (weights.shape != hidden.shape[:-1] or hidden.ndim not in (3, 4)
            or hidden.shape[-3:-1] != targets.shape):
        raise ValueError(
            f"weights {weights.shape} must be a number a row of the hidden "
            f"states {hidden.shape}, (B, T, d) or (P, B, T, d) against "
            f"targets {targets.shape}")
    if hidden.ndim == 3:
        hidden, weights = hidden[None], weights[None]
    return _weighted_lm_loss(hidden, head_kernel, targets,
                             weights.astype(jnp.float32), chunk)


def lm_loss_with_mtp(hidden, mtp_hidden, head_kernel, tokens,
                     mtp_weight: float, chunk: int = 2048):
    """``(L_main + mtp_weight * L_mtp, (L_main, L_mtp))`` of a model with a
    multi-token-prediction module (``TransformerLM.mtp_layer_types``; both
    hidden states from ``return_hidden=True``): the next-token loss of the
    main model and the module's loss against the token AFTER the next, each
    a :func:`chunked_lm_loss` over the SAME head, whose gradient is then the
    sum of both passes'. Targets wrap round the row's end, as every loss of
    this repo's; the main pass goes by ``hvd_lm_head``, the module's by
    ``hvd_mtp``."""
    main = chunked_lm_loss(hidden, head_kernel, jnp.roll(tokens, -1, axis=1),
                           chunk)
    with jax.named_scope(device_names.MTP):
        mtp = _checked_lm_loss(mtp_hidden, head_kernel,
                               jnp.roll(tokens, -2, axis=1), chunk)
    return main + mtp_weight * mtp, (main, mtp)


def exit_log_distribution(gate_logits):
    """``log p`` (P, ...) float32 of a looped model's exit distribution from
    its gates' logits (P, ...): with ``lambda_t = sigmoid(gate_logits[t])``,
    ``p_t = lambda_t prod_{j<t} (1 - lambda_j)`` for t < P and ``p_P =
    prod_{j<P} (1 - lambda_j)``: the last pass takes what is left, and its
    own gate is read by nothing (no gradient reaches it from here). In logs,
    ``log lambda = -softplus(-g)`` and ``log (1 - lambda) = -softplus(g)``:
    finite for every finite gate, so that ``p log p`` has no 0 x inf."""
    g = gate_logits.astype(jnp.float32)
    g, none = g[:-1], jnp.zeros_like(g[:1])
    stay = -jax.nn.softplus(g)                          # log (1 - lambda_j)
    stayed = jnp.concatenate([none, jnp.cumsum(stay, 0)])
    leave = jnp.concatenate([-jax.nn.softplus(-g), none])
    return stayed + leave


def loop_lm_loss(hidden, gate_logits, head_kernel, targets, beta: float,
                 chunk: int = 2048):
    """``(L, parts)`` of a looped model (``TransformerLM.passes`` with
    ``exit_gate``; ``hidden`` (P, B, T, d) and ``gate_logits`` (P, B, T) from
    ``return_hidden=True``): the next-token loss EXPECTED over the exit
    distribution a token that the gates define
    (:func:`exit_log_distribution`), less ``beta`` times that distribution's
    entropy (arXiv:2510.25741's entropy-regularised objective): ``L = mean
    over tokens of [sum_t p_t CE_t - beta H(p)]``, ``H(p) = -sum_t p_t log
    p_t``. The P readings of the one head are ONE weighted
    :func:`chunked_lm_loss` with ``p`` as its weights: the gates receive the
    per-token cross entropies as their gradient through it. ``parts``:
    ``expected`` (the first term), ``entropy`` (the mean ``H``) and
    ``exit_mass`` ((P,): the mean ``p_t`` over the tokens). The gates, the
    distribution and the entropy are float32 and go by ``hvd_loop_exit``, the
    head's passes and their loss by ``hvd_lm_head``."""
    with jax.named_scope(device_names.LOOP_EXIT):
        log_p = exit_log_distribution(gate_logits)
        p = jnp.exp(log_p)
    expected = chunked_lm_loss(hidden, head_kernel, targets, chunk, weights=p)
    with jax.named_scope(device_names.LOOP_EXIT):
        entropy = -jnp.mean(jnp.sum(p * log_p, axis=0))
        mass = jax.lax.stop_gradient(jnp.mean(p, axis=tuple(range(1, p.ndim))))
    return expected - beta * entropy, {"expected": expected,
                                       "entropy": entropy, "exit_mass": mass}


def _loss_chunks(hidden, targets, chunk, weights=None):
    """``(n, b * chunk, d)`` hidden states and ``(n, b * chunk)`` targets:
    iteration ``i`` holds tokens ``[i * chunk, (i + 1) * chunk)`` of every
    sequence, as one block of rows. With ``weights`` (P, b, t) under hidden
    states (P, b, t, d): ``P * n`` iterations, pass by pass, each pass
    against the same targets, and the weights a row as a third array."""
    b, t = targets.shape
    n = t // chunk

    def rows(x):
        return (x.reshape(b, n, chunk, *x.shape[2:]).swapaxes(0, 1)
                .reshape(n, b * chunk, *x.shape[2:]))

    if weights is None:
        return rows(hidden), rows(targets)

    def passes(x):
        return jax.vmap(rows)(x).reshape(-1, b * chunk, *x.shape[3:])

    return (passes(hidden), jnp.tile(rows(targets), (hidden.shape[0], 1)),
            passes(weights))


def _loss_alone(hidden, head_kernel, targets, chunk, weights=None):
    """The loss where nobody asks for its gradients: one vocabulary product
    a chunk (and pass), no accumulator."""
    import optax

    from ..metrics import record_chunked_loss_plan

    record_chunked_loss_plan(1)

    def one(block):
        hc, tc, *wc = block
        logits = hc.astype(jnp.float32) @ head_kernel    # (rows, vocab)
        ce = optax.softmax_cross_entropy_with_integer_labels(logits, tc)
        return (wc[0] * ce).mean() if wc else ce.mean()

    losses = jax.lax.map(one, _loss_chunks(hidden, targets, chunk, weights))
    if weights is None:
        return losses.mean()
    return losses.reshape(hidden.shape[0], -1).mean(1).sum()


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _chunked_lm_loss(hidden, head_kernel, targets, chunk):
    return _loss_alone(hidden, head_kernel, targets, chunk)


def _loss_and_gradients(hidden, head_kernel, targets, chunk, weights=None):
    """The loss AND its gradients (for a cotangent of 1) from one scan over
    blocks of rows: ``(loss, (d_hidden, d_kernel))``, and with ``weights``
    (hidden states (P, b, t, d)) ``(loss, (d_hidden, d_kernel, d_weights))``,
    the weight a row inside both products' row sums and the per-row cross
    entropies over ``b * t`` handed back as the weights' gradient."""
    from ..metrics import record_chunked_loss_plan

    record_chunked_loss_plan(3)
    b, t = targets.shape
    d = hidden.shape[-1]
    vocab = head_kernel.shape[-1]

    def one(d_kernel, block):
        hc, tc, *wc = block
        hf = hc.astype(jnp.float32)
        logits = hf @ head_kernel                        # (rows, vocab)
        shifted = logits - logits.max(-1, keepdims=True)
        e = jnp.exp(shifted)
        z = e.sum(-1, keepdims=True)
        hit = jax.lax.broadcasted_iota(tc.dtype, shifted.shape, 1) == tc[:, None]
        ce = jnp.log(z[:, 0]) - jnp.where(hit, shifted, 0.0).sum(-1)
        loss = (wc[0] * ce).mean() if wc else ce.mean()
        dlogits = e / z - hit
        if wc:
            dlogits = dlogits * wc[0][:, None]
        dlogits = dlogits / (b * t)
        d_hidden = (dlogits @ head_kernel.T).astype(hidden.dtype)
        d_kernel = d_kernel + jnp.tensordot(hf, dlogits, ((0,), (0,)))
        if wc:
            return d_kernel, (loss, d_hidden, ce / (b * t))
        return d_kernel, (loss, d_hidden)

    d_kernel, (losses, d_hidden, *d_weights) = jax.lax.scan(
        one, jnp.zeros((d, vocab), jnp.float32),
        _loss_chunks(hidden, targets, chunk, weights))
    d_kernel = d_kernel.astype(head_kernel.dtype)
    if weights is None:
        d_hidden = d_hidden.reshape(-1, b, chunk, d).swapaxes(0, 1)
        return losses.mean(), (d_hidden.reshape(b, t, d), d_kernel)

    def unchunked(x):       # (P * n, b * chunk, ...) -> (P, b, t, ...)
        x = x.reshape(hidden.shape[0], -1, b, chunk, *x.shape[2:])
        return x.swapaxes(1, 2).reshape(hidden.shape[0], b, t, *x.shape[4:])

    return (losses.reshape(hidden.shape[0], -1).mean(1).sum(),
            (unchunked(d_hidden), d_kernel, unchunked(d_weights[0])))


def _chunked_lm_loss_bwd(chunk, gradients, g):
    d_hidden, d_kernel = gradients
    return ((g * d_hidden).astype(d_hidden.dtype),
            (g * d_kernel).astype(d_kernel.dtype), None)


# the residuals are the gradients themselves
_chunked_lm_loss.defvjp(_loss_and_gradients, _chunked_lm_loss_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _weighted_lm_loss(hidden, head_kernel, targets, weights, chunk):
    """``(1 / (b t)) sum_{pass, row} weights CE`` of hidden states (P, b, t,
    d) under weights (P, b, t)."""
    return _loss_alone(hidden, head_kernel, targets, chunk, weights)


def _weighted_lm_loss_fwd(hidden, head_kernel, targets, weights, chunk):
    return _loss_and_gradients(hidden, head_kernel, targets, chunk, weights)


def _weighted_lm_loss_bwd(chunk, gradients, g):
    d_hidden, d_kernel, d_weights = gradients
    return ((g * d_hidden).astype(d_hidden.dtype),
            (g * d_kernel).astype(d_kernel.dtype), None, g * d_weights)


_weighted_lm_loss.defvjp(_weighted_lm_loss_fwd, _weighted_lm_loss_bwd)


def tp_param_specs(params, tp_axis: str = "tp"):
    """PartitionSpecs for tensor parallelism: shard QKV/MLP-in kernels on the
    output dim, O-proj/MLP-out on the input dim, replicate the rest. Used as
    jit in_shardings so XLA inserts the single per-block psum."""
    from jax.sharding import PartitionSpec as P

    def spec(path, leaf):
        names = [getattr(p, "key", getattr(p, "name", "")) for p in path]
        joined = "/".join(str(n) for n in names)
        if leaf.ndim == 2:
            # the gate is one column a query head, or one an element of
            # the heads' output: either way sharded with q_proj's
            if ("qkv" in joined or "q_proj" in joined or "kv_proj" in joined
                    or "gate_proj" in joined or "mlp_in" in joined):
                return P(None, tp_axis)
            if "o_proj" in joined or "mlp_out" in joined or "lm_head" in joined:
                return P(tp_axis, None)
        return P()

    return jax.tree_util.tree_map_with_path(spec, params)
