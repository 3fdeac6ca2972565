"""LFM2-24B-A2B (Hugging Face ``LiquidAI/LFM2-24B-A2B``, ``model_type``
``lfm2_moe``) in plain ``jax.numpy``: forward, loss, gradients and the
router-bias rule of ONE expert-parallel rank's share. The equations' public
sources: the model's ``config.json`` and the released ``lfm2_moe`` modelling
code (the gated short convolution, the head norms before the rotation, the
router's ``+ 1e-6``); the selection bias and its rule as arXiv:2412.19437
§2.1.2 / §4.2 define them.

Everything is float32 under ``jax.default_matmul_precision("highest")``; the
convolution is K shifted products, attention a masked softmax a head, each
held expert is applied to EVERY token and masked by ``chosen`` - no kernel,
no sort, no flax, nothing from ``horovod_tpu`` and nothing from another
reference.

Hidden states ``x`` (B, T, D); no matrix or convolution has a bias; ``rms``
has eps ``cfg["eps"]`` and a learned weight. The weights handed in are the
rank's share: every mixer, norm, dense layer and router whole, the experts
``[first, first + count)`` (``cfg["held"]``) of each expert layer, some rows
of the vocabulary (the table is the head too).

* ``x = E[tokens]``.
* A ``"conv"`` layer (``cfg["kinds"]``), ``h = rms(x)``: ``[B | C | X] = h
  W_in`` (D -> 3 D, three runs of D in that order); ``u = B * X``; ``c_t =
  sum_j taps[j] * u[t - (K - 1) + j]`` a channel (``taps`` (K, D), zeros
  before the row's start, no activation); ``x = x + (C * c) W_out``.
* An ``"attention"`` layer, H query heads over Hkv key/value heads of d:
  ``q = h Wq``, ``[k | v] = h Wkv``; q and k each through ``rms`` over a
  head's d with ONE learned weight of d; then the rotation, pairs
  ``(i, i + d / 2)``, angle ``t * theta^(-2 i / d)``, all d dimensions;
  scores ``q k^T * d^-0.5``, causal, softmax; ``x = x + (P v) Wo``.
* A layer's second half, ``h2 = rms(x)``: a dense SwiGLU ``x = x + W2(silu(W1
  h2) * W3 h2)`` where the layer has no router; else ``s = sigmoid(h2 Wr)``
  (T, E); ``chosen`` = the ``top_k`` largest of ``s + b``; ``w = s[chosen] /
  (sum + route_eps)``; ``x = x + sum_{e chosen AND held} w_e W2_e(silu(W1_e
  h2) * W3_e h2)``. ``w`` is normalised over all ``top_k`` whoever holds them.
  ``b`` receives no gradient.
* Final ``rms``; logits = ``x E^T`` over the rows handed in. Loss = mean
  next-token cross entropy.
* After a step, per expert layer: ``b_e += rate * sign(mean(c) - c_e)``.

Departures from the published description, each at its line below:

* k and v come out of ONE matrix ``Wkv`` (the published two, side by side);
* the target of the last position is the first token (``roll`` by one);
* the chosen are found by a threshold at the ``top_k``-th largest ``s + b``
  (a mask), not by indices; the bias's RULE is DeepSeek-V3's, which
  ``config.json`` does not name (the file's ``assumed``);
* a head's attention and each expert's term are wrapped in
  ``jax.checkpoint``.
"""

import jax
import jax.numpy as jnp

F32 = jnp.float32


def rms(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def gated_conv(bcx, taps):
    """``C * conv(B * X)`` for ``bcx`` (B, T, 3 D) = ``[B | C | X]``: K shifted
    products, zeros before the row's start, no activation."""
    t, k = bcx.shape[1], taps.shape[0]
    b, c, x = jnp.split(bcx, 3, axis=-1)
    u = jnp.pad(b * x, ((0, 0), (k - 1, 0), (0, 0)))     # zeros before the row
    return c * sum(taps[j] * u[:, j:j + t] for j in range(k))


def short_conv(layer, h, cfg):
    """The doubly gated short convolution between its two projections."""
    return gated_conv(h @ layer["w_in"], layer["taps"]) @ layer["w_out"]


def rotate(x, theta):
    """x (B, T, H, d): pairs (i, i + d / 2) turned by t * theta^(-2 i / d)."""
    t, half = x.shape[1], x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    angles = jnp.arange(t, dtype=F32)[:, None] * freqs          # (T, half)
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    lo, hi = x[..., :half], x[..., half:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], axis=-1)


@jax.checkpoint     # departure: bookkeeping, so that 32 heads' scores fit
def one_head(q, k, v):
    """q, k, v (B, T, d) of one query head: a masked softmax."""
    t, d = q.shape[1], q.shape[2]
    s = jnp.einsum("bqd,bkd->bqk", q, k) * d ** -0.5
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v)


def attention(layer, h, cfg):
    """Grouped-query softmax attention, q and k normed a head, then turned."""
    b, t, _ = h.shape
    heads, kv_heads, d = cfg["heads"], cfg["kv_heads"], cfg["head_dim"]
    q = (h @ layer["wq"]).reshape(b, t, heads, d)
    # departure: k and v out of one matrix, the published two side by side
    kv = (h @ layer["wkv"]).reshape(b, t, 2, kv_heads, d)
    k, v = kv[:, :, 0], kv[:, :, 1]
    q = rotate(rms(q, layer["q_norm"], cfg["eps"]), cfg["theta"])
    k = rotate(rms(k, layer["k_norm"], cfg["eps"]), cfg["theta"])
    # a head after another: one key/value head serves heads / kv_heads
    qh, kh, vh = (jnp.repeat(jnp.moveaxis(x, 2, 0), heads // x.shape[2], axis=0)
                  for x in (q, k, v))
    a = jax.lax.map(lambda qkv: one_head(*qkv), (qh, kh, vh))   # (H, B, T, d)
    return jnp.moveaxis(a, 0, 2).reshape(b, t, heads * d) @ layer["wo"]


def swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


@jax.checkpoint     # departure: bookkeeping, so that 8 experts' terms fit
def expert_term(h2, weight, w_gate, w_up, w_down):
    """One expert on EVERY row, times that row's weight for it (0 where the
    expert was not chosen)."""
    return weight[:, None] * swiglu(h2, w_gate, w_up, w_down)


def route(h2, router, bias, cfg, forced=None):
    """(weights (N, E), 0 where not chosen; chosen (N, E); scores (N, E)).
    ``forced`` (N, E), where given, IS the chosen set: a caller whose own
    choice differs on a token whose ``top_k``-th and next ``s + b`` are tied
    within rounding compares the rest of the arithmetic under its choice. A
    pair ``(use, forced)`` takes the set only where the (traced) ``use`` is
    true, so that one compiled program serves both."""
    n_experts, top_k = router.shape[1], cfg["top_k"]
    s = jax.nn.sigmoid(h2 @ router)
    biased = s + jax.lax.stop_gradient(bias)
    # departure: a threshold at the top_k-th largest, not top_k's indices
    kth = jnp.sort(biased, axis=-1)[:, n_experts - top_k]
    chosen = biased >= kth[:, None]                     # (N, E), top_k a row
    if forced is not None:
        use, forced = forced if isinstance(forced, tuple) else (True, forced)
        chosen = jnp.where(use, forced, chosen)
    w = jnp.where(chosen, s, 0.0)
    w = cfg["route_scale"] * w / (jnp.sum(w, axis=-1, keepdims=True)
                                  + cfg["route_eps"])
    return w, chosen, s


def experts(layer, bias, h2, cfg, forced=None):
    """h2: (N, D). The held experts' part of the routed sum, an expert after
    another (a loop with a mask, no sort). Returns (y, router stats)."""
    weights, chosen, scores = route(h2, layer["router"], bias, cfg, forced)
    first, count = cfg["held"]
    y = sum(expert_term(h2, weights[:, first + e], layer["w_gate"][e],
                        layer["w_up"][e], layer["w_down"][e])
            for e in range(count))
    return y, {"chosen": chosen, "scores": scores,
               "counts": jnp.sum(chosen, axis=0, dtype=jnp.int32)}


def forward(params, biases, tokens, cfg, forced=None):
    """tokens: (B, T) ints; ``biases``: one (E,) per EXPERT layer, in order;
    ``forced``: None, or one chosen set (B T, E), or one pair ``(use, set)``,
    per expert layer (:func:`route`).
    Returns (logits (B, T, V), [stats of each expert layer])."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens]
        stats = []
        n_expert_layers = sum("router" in layer for layer in params["layers"])
        sets = iter(forced if forced is not None else [None] * n_expert_layers)
        biases = iter(biases)
        for kind, layer in zip(cfg["kinds"], params["layers"]):
            mixer = {"conv": short_conv, "attention": attention}[kind]
            x = x + mixer(layer, rms(x, layer["attn_norm"], cfg["eps"]), cfg)
            h2 = rms(x, layer["mlp_norm"], cfg["eps"])
            if "router" not in layer:       # a leading dense layer
                x = x + swiglu(h2, layer["w_gate"], layer["w_up"],
                               layer["w_down"])
                continue
            y, s = experts(layer, next(biases), h2.reshape(-1, h2.shape[-1]),
                           cfg, next(sets))
            x = x + y.reshape(x.shape)
            stats.append(s)
        # the head is the embedding: one table, both gradients
        return rms(x, params["final_norm"], cfg["eps"]) @ params["embed"].T, stats


def loss_parts(params, biases, tokens, cfg, forced=None):
    """(loss, {"logits", "chosen", "counts", "scores"}): the mean next-token
    cross entropy, and per expert layer what the router did."""
    logits, stats = forward(params, biases, tokens, cfg, forced)
    targets = jnp.roll(tokens, -1, axis=1)     # departure: the row wraps round
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(nll), {"logits": logits,
                           **{k: [s[k] for s in stats]
                              for k in ("chosen", "counts", "scores")}}


def bias_update(bias, counts, rate):
    """``b_e += rate * sign(mean(c) - c_e)``: an expert with more than the
    mean load is chosen less from the next step on."""
    counts = counts.astype(F32)
    return bias + rate * jnp.sign(jnp.mean(counts) - counts)
