"""Laguna-XS.2 (Hugging Face ``poolside/Laguna-XS.2``, ``model_type``
``laguna``) in plain ``jax.numpy``: forward, loss and gradients.

Everything is float32 under ``jax.default_matmul_precision("highest")``;
attention is a masked T x T softmax, one head at a time; each expert is
applied to EVERY token and masked by ``chosen`` - no sort, no gather into
expert order, no kernel, no cache, no flax, nothing from ``horovod_tpu``.
``benchmarks/reference/laguna.py`` is a copy of this file (a test holds the two
to the same outputs), so that the benchmark brings its own.

Hidden states ``x`` (B, T, D); no matrix has a bias; ``rms`` has eps
``cfg["eps"]`` and a learned weight. Layer ``l`` is of kind
``cfg["kinds"][l]`` (``"full_attention"`` or ``"sliding_attention"``) and has
``cfg["heads"][l]`` query heads over ``cfg["kv_heads"]`` key/value heads of
``cfg["head_dim"]``, query head ``a`` reading key/value head ``a // group``.

* ``x = E[tokens]``.
* Every layer: ``h = rms(x)``; ``q = h Wq`` -> (T, H_l, hd), ``k = h Wk``,
  ``v = h Wv`` -> (T, Hkv, hd). Rotary on q and k, pairs (i, i + half) of the
  dimensions that turn (:func:`rotary`): on a sliding layer all ``hd`` at
  ``cfg["rope_sliding"]["theta"]``; on a full layer the FIRST
  ``cfg["rope_full"]["dims"]`` with YaRN's blended frequencies
  (:func:`yarn_inv_freq`, what ``transformers``' ``_compute_yarn_parameters``
  computes over those dimensions), cos and sin times ``attention_factor``, the
  other dimensions passing through. Scores ``q k^T hd^-0.5``, causal; on a
  sliding layer the query at p sees the keys ``p - window < j <= p``; softmax;
  ``o = P v``. Gate: ``g = sigmoid(h Wg)`` (T, H_l), head a's output times
  ``g_a``. ``x = x + concat(g_a o_a) Wo``.
* Dense layers (those without a ``router``): ``h2 = rms(x)``;
  ``x = x + Wd (silu(Wg' h2) * Wu h2)``.
* Expert layers: ``h2 = rms(x)``; ``s = sigmoid(h2 Wr)`` (T, E); ``chosen`` =
  the ``top_k`` largest of ``s``; ``w = s[chosen]``,
  ``w = route_scale * w / (sum(w) + 1e-20)``;
  ``x = x + sum_{e chosen AND held} w_e down_e(silu(gate_e h2) * up_e h2)
  + down_s(silu(gate_s h2) * up_s h2)``. ``held = (first, count)``: the
  weights handed in are those of experts ``[first, first + count)`` alone;
  ``w`` is still normalised over all ``top_k``.
* Final ``rms``; logits = ``x H`` over the rows of the vocabulary handed in.
  Loss = mean next-token cross entropy.

What the published ``config.json`` alone does not fix, and how it is settled
here (the configuration file's ``assumed`` says the same):

1. The gate is one number a HEAD and token (``gating: true``; the sibling
   Laguna-S-2.1's config states ``gating: "per-head"``).
2. Scores by sigmoid, the chosen ones renormalised (the sibling states
   ``norm_topk_prob: true``) and times ``moe_routed_scaling_factor`` 2.5, over
   all the experts in one group, no bias in the choice and no auxiliary loss
   (the config names no scoring function, no ``topk_method``, no coefficient).
3. No QK-norm, no gate on the shared expert (no key for either).
4. The window holds ``sliding_window`` keys, the query's own included
   (Hugging Face's sliding-window mask).
5. Rotary pairs (i, i + half), YaRN with ``truncate`` at its default (true).

Departures from what Hugging Face's decoder models do, each with its reason:

* The target of the last position is the first token (``roll`` by one), the
  convention of this repo's other language-model losses; a data loader would
  mask it.
* The chosen are found by a threshold at the ``top_k``-th largest score (a
  mask), not by ``top_k``'s indices; equal with probability 1 on continuous
  values.
* Each head's attention and each expert's term are wrapped in
  ``jax.checkpoint``: the same arithmetic, recomputed in the backward pass, so
  that 64 heads of 2048 x 2048 scores fit the chip beside the training state.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def rms(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def yarn_inv_freq(dims, theta, factor, original_max, beta_fast, beta_slow):
    """(dims / 2,) angles a position: ``f_i = theta^(-2i/dims)`` divided by
    ``factor`` (interpolated) where pair i turns less than ``beta_slow``
    times over ``original_max`` positions, as it is (extrapolated) where it
    turns more than ``beta_fast`` times, a linear blend between."""
    f = theta ** (-np.arange(0, dims, 2, dtype=np.float64) / dims)

    def turns(n):       # the pair that turns n times in original_max positions
        return dims * math.log(original_max / (n * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(turns(beta_fast)), 0)
    high = min(math.ceil(turns(beta_slow)), dims - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dims // 2) - low) / (high - low), 0.0, 1.0)
    return (f / factor) * ramp + f * (1.0 - ramp)


def rotary(x, rope):
    """x: (B, T, H, hd). The first ``rope["dims"]`` dimensions (all without
    the key) turn, position t turning the pair (i, i + dims / 2) by
    ``t * inv_freq_i``; cos and sin are multiplied by
    ``rope["attention_factor"]`` (1 without the key)."""
    dims = rope.get("dims", x.shape[-1])
    half = dims // 2
    if "factor" in rope:
        inv_freq = yarn_inv_freq(dims, rope["theta"], rope["factor"],
                                 rope["original_max"], rope["beta_fast"],
                                 rope["beta_slow"])
    else:
        inv_freq = rope["theta"] ** (-np.arange(0, dims, 2, dtype=np.float64)
                                     / dims)
    angles = (jnp.arange(x.shape[1], dtype=F32)[:, None]
              * jnp.asarray(inv_freq, F32))                         # (T, half)
    scale = rope.get("attention_factor", 1.0)
    cos = (jnp.cos(angles) * scale)[:, None, :]
    sin = (jnp.sin(angles) * scale)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:dims], x[..., dims:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def attention(layer, h, cfg, kind, heads):
    b, t, _ = h.shape
    kv_heads, hd = cfg["kv_heads"], cfg["head_dim"]
    rope = cfg["rope_full" if kind == "full_attention" else "rope_sliding"]
    q = rotary((h @ layer["wq"]).reshape(b, t, heads, hd), rope)
    k = rotary((h @ layer["wk"]).reshape(b, t, kv_heads, hd), rope)
    v = (h @ layer["wv"]).reshape(b, t, kv_heads, hd)
    gate = jax.nn.sigmoid(h @ layer["wg"])                       # (B, T, H)
    pos = jnp.arange(t)
    seen = pos[:, None] >= pos[None, :]
    if kind == "sliding_attention":
        seen &= pos[None, :] > pos[:, None] - cfg["window"]

    @jax.checkpoint
    def one_head(qkv):
        qa, ka, va = qkv                                         # (B, T, hd)
        s = jnp.einsum("bqd,bkd->bqk", qa, ka) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bqk,bkd->bqd", p, va)

    def by_head(x, repeat=1):       # (B, T, n, hd) -> (n * repeat, B, T, hd)
        return jnp.repeat(jnp.moveaxis(x, 2, 0), repeat, axis=0)

    group = heads // kv_heads
    o = jax.lax.map(one_head, (by_head(q), by_head(k, group), by_head(v, group)))
    o = jnp.moveaxis(o, 0, 2) * gate[..., None]                  # (B, T, H, hd)
    return o.reshape(b, t, heads * hd) @ layer["wo"]


def swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


@jax.checkpoint
def expert_term(h2, weight, w_gate, w_up, w_down):
    """One expert on EVERY row, times that row's weight for it (0 where the
    expert was not chosen)."""
    return weight[:, None] * swiglu(h2, w_gate, w_up, w_down)


def route(h2, router, cfg, forced=None):
    """(weights (N, E), 0 where not chosen; chosen (N, E); scores (N, E)).
    ``forced`` (N, E), where given, IS the chosen set: a caller whose own
    choice differs on a token whose ``top_k``-th and next score are tied
    within rounding compares the rest of the arithmetic under its choice."""
    n_experts, top_k = router.shape[1], cfg["top_k"]
    s = jax.nn.sigmoid(h2 @ router)
    kth = jnp.sort(s, axis=-1)[:, n_experts - top_k]
    chosen = s >= kth[:, None]                          # (N, E), top_k a row
    if forced is not None:
        chosen = forced
    w = jnp.where(chosen, s, 0.0)
    w = cfg["route_scale"] * w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w, chosen, s


def experts(layer, h2, cfg, forced=None):
    """h2: (N, D). The held experts' part of the routed sum plus the shared
    expert. Returns (y, router stats)."""
    first, count = cfg["held"]
    weights, chosen, scores = route(h2, layer["router"], cfg, forced)

    def step(y, per_expert):
        weight, w_gate, w_up, w_down = per_expert
        return y + expert_term(h2, weight, w_gate, w_up, w_down), None

    y, _ = jax.lax.scan(step, jnp.zeros_like(h2),
                        (weights.T[first:first + count], layer["w_gate"],
                         layer["w_up"], layer["w_down"]))
    y = y + swiglu(h2, layer["s_gate"], layer["s_up"], layer["s_down"])
    return y, {"chosen": chosen, "scores": scores,
               "counts": jnp.sum(chosen, axis=0, dtype=jnp.int32)}


def forward(params, tokens, cfg, forced=None):
    """tokens: (B, T) ints; ``forced``: None, or one chosen set (B T, E) per
    expert layer (:func:`route`). Returns (logits (B, T, V), [stats of each
    expert layer])."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens]
        stats = []
        forced = iter(forced if forced is not None
                      else [None] * len(params["layers"]))
        for i, (layer, kind, heads) in enumerate(zip(
                params["layers"], cfg["kinds"], cfg["heads"])):
            if layer["wq"].shape[1] != heads * cfg["head_dim"]:
                # parameters of a model with other head counts are not this
                # model's: said, and not run
                raise AssertionError(
                    f"layer {i}: wq is {layer['wq'].shape[1]} wide, {heads} "
                    f"query heads of {cfg['head_dim']} are "
                    f"{heads * cfg['head_dim']}")
            x = x + attention(layer, rms(x, layer["attn_norm"], cfg["eps"]),
                              cfg, kind, heads)
            h2 = rms(x, layer["mlp_norm"], cfg["eps"])
            if "router" in layer:
                y, s = experts(layer, h2.reshape(-1, h2.shape[-1]), cfg,
                               next(forced))
                x = x + y.reshape(x.shape)
                stats.append(s)
            else:
                x = x + swiglu(h2, layer["w_gate"], layer["w_up"],
                               layer["w_down"])
        return rms(x, params["final_norm"], cfg["eps"]) @ params["head"], stats


def loss_parts(params, tokens, cfg, forced=None):
    """(loss, {"logits", "chosen", "counts", "scores"}): the mean next-token
    cross entropy, and per expert layer what the router did."""
    logits, stats = forward(params, tokens, cfg, forced)
    targets = jnp.roll(tokens, -1, axis=1)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(nll), {"logits": logits,
                           **{k: [s[k] for s in stats]
                              for k in ("chosen", "counts", "scores")}}


def loss_and_grads(params, tokens, cfg, forced=None):
    """((loss, parts), gradients of the loss with respect to ``params``)."""
    return jax.jit(lambda p, t, f: jax.value_and_grad(
        loss_parts, has_aux=True)(p, t, cfg, f))(params, tokens, forced)


def init_params(key, cfg, scale=0.02):
    """Seeded float32 parameters in this file's own layout (normal, ``scale``;
    norm weights around 1 so that their gradients are exercised): one layer
    for each of ``cfg["kinds"]``, the first ``cfg["first_k_dense"]`` with a
    dense MLP, the rest expert layers holding ``cfg["held"][1]`` experts'
    weights."""
    d, hd, kv, e = (cfg[k] for k in ("hidden", "head_dim", "kv_heads",
                                     "experts"))
    count, vocab = cfg["held"][1], cfg["vocab"]
    keys = iter(jax.random.split(key, 3 + 16 * len(cfg["kinds"])))

    def normal(shape, mean=0.0):
        return mean + scale * jax.random.normal(next(keys), shape, F32)

    def layer(i, heads):
        out = {"attn_norm": normal((d,), 1.0), "mlp_norm": normal((d,), 1.0),
               "wq": normal((d, heads * hd)), "wk": normal((d, kv * hd)),
               "wv": normal((d, kv * hd)), "wg": normal((d, heads)),
               "wo": normal((heads * hd, d))}
        if i < cfg["first_k_dense"]:
            w = cfg["dense_width"]
            out.update(w_gate=normal((d, w)), w_up=normal((d, w)),
                       w_down=normal((w, d)))
        else:
            w, ws = cfg["expert_width"], cfg["shared_width"]
            out.update(router=normal((d, e)),
                       w_gate=normal((count, d, w)), w_up=normal((count, d, w)),
                       w_down=normal((count, w, d)), s_gate=normal((d, ws)),
                       s_up=normal((d, ws)), s_down=normal((ws, d)))
        return out

    return {"embed": normal((vocab, d)),
            "layers": [layer(i, h) for i, h in enumerate(cfg["heads"])],
            "final_norm": normal((d,), 1.0), "head": normal((d, vocab))}
