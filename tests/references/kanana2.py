"""kanana-2-30b-a3b (Hugging Face ``kakaocorp/kanana-2-30b-a3b-instruct-2601``,
``model_type`` ``deepseek_v3``; the equations are DeepSeek-V3's,
arXiv:2412.19437 §2.1, with ``q_lora_rank`` null and one routing group) in
plain ``jax.numpy``: forward, loss, gradients and the router-bias rule.

Everything is float32 under ``jax.default_matmul_precision("highest")``; each
expert is applied to EVERY token and masked by ``chosen`` - no sort, no gather
into expert order, no kernel, no flax, nothing from ``horovod_tpu``.
``benchmarks/reference/kanana2.py`` is a copy of this file (a test holds the
two to the same outputs), so that the benchmark brings its own.

Hidden states ``x`` (B, T, D); no matrix has a bias; ``rms`` has eps
``cfg["eps"]`` and a learned weight.

* ``x = E[tokens]``.
* Every layer, latent attention: ``h = rms(x)``. ``q = h Wq`` -> (T, H, 192),
  per head ``q_n`` (128) | ``q_r`` (64). ``[c | k_r] = h Wkva`` (512 | 64);
  ``c = rms(c)``. ``c Wkvb`` -> (T, H, 256), per head ``k_n`` (128) | ``v``
  (128). Rotary embedding on ``q_r`` (each head) and ``k_r`` (ONE head shared
  by all): INTERLEAVED, components (2i, 2i + 1) a pair turned by
  ``pos * theta^(-2i/64)``. ``k = [k_n | k_r]``, ``k_r`` repeated per head;
  scores ``q k^T * 192^-0.5``, causal, softmax; ``o = P v``;
  ``x = x + reshape(o) Wo``.
* Dense layers (the first ``first_k_dense``): ``h2 = rms(x)``;
  ``x = x + Wd (silu(Wg h2) * Wu h2)``.
* Expert layers: ``h2 = rms(x)``; ``s = sigmoid(h2 Wr)`` (T, E); ``chosen`` =
  the ``top_k`` largest of ``s + b``; ``w = s[chosen]``,
  ``w = route_scale * w / (sum(w) + 1e-20)``;
  ``x = x + sum_{e chosen AND held} w_e down_e(silu(gate_e h2) * up_e h2)
  + down_s(silu(gate_s h2) * up_s h2)``. ``held = (first, count)``: the
  weights handed in are those of experts ``[first, first + count)`` alone;
  ``w`` is still normalised over all ``top_k``. ``b`` receives no gradient.
* Final ``rms``; logits = ``x H`` over the rows of the vocabulary handed in.
  Loss = mean next-token cross entropy.
* After a step, per expert layer: ``c_e`` = pairs routed to expert ``e`` (all
  E); ``b_e += rate * sign(mean(c) - c_e)`` (§2.1.2; :func:`bias_update`).

Departures from the published code, each with its reason:

* Hugging Face's ``DeepseekV3`` de-interleaves the rotary part and rotates
  halves; this rotates the interleaved pairs where they lie: the same scores
  (q and k are permuted alike).
* The target of the last position is the first token (``roll`` by one), the
  convention of this repo's other language-model losses; a data loader would
  mask it.
* The chosen are found by a threshold at the ``top_k``-th largest ``s + b``
  (a mask), not by ``top_k``'s indices; equal with probability 1 on
  continuous values.
* No sequence-wise auxiliary loss (§2.1.2's complementary term): the
  catalog's ``config.json`` has no training key, and Hugging Face computes
  none.
* Each expert's term is wrapped in ``jax.checkpoint``: the same arithmetic,
  recomputed in the backward pass.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32


def rms(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def rope_interleaved(x, theta):
    """x: (B, T, H, Dr); position t turns the pair (2i, 2i + 1) by
    t * theta^(-2i/Dr)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    angles = jnp.arange(x.shape[1], dtype=F32)[:, None] * freqs      # (T, half)
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def attention(layer, h, cfg):
    b, t, _ = h.shape
    heads, nope, rope, v_dim = (cfg[k] for k in ("heads", "qk_nope", "qk_rope",
                                                  "v_dim"))
    q = (h @ layer["wq"]).reshape(b, t, heads, nope + rope)
    q_n, q_r = q[..., :nope], q[..., nope:]
    kva = h @ layer["wkva"]
    c, k_r = kva[..., :cfg["kv_rank"]], kva[..., cfg["kv_rank"]:]
    kv = (rms(c, layer["kv_norm"], cfg["eps"]) @ layer["wkvb"]).reshape(
        b, t, heads, nope + v_dim)
    k_n, v = kv[..., :nope], kv[..., nope:]
    q_r = rope_interleaved(q_r, cfg["rope_theta"])
    k_r = rope_interleaved(k_r[:, :, None, :], cfg["rope_theta"])
    q = jnp.concatenate([q_n, q_r], axis=-1)
    k = jnp.concatenate([k_n, jnp.repeat(k_r, heads, axis=2)], axis=-1)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (nope + rope) ** -0.5
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(b, t, -1) @ layer["wo"]


def swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


@jax.checkpoint
def expert_term(h2, weight, w_gate, w_up, w_down):
    """One expert on EVERY row, times that row's weight for it (0 where the
    expert was not chosen)."""
    return weight[:, None] * swiglu(h2, w_gate, w_up, w_down)


def route(h2, router, bias, cfg, forced=None):
    """(weights (N, E), 0 where not chosen; chosen (N, E); scores (N, E)).
    ``forced`` (N, E), where given, IS the chosen set: a caller whose own
    choice differs on a token whose ``top_k``-th and next ``s + b`` are tied
    within rounding compares the rest of the arithmetic under its choice."""
    n_experts, top_k = router.shape[1], cfg["top_k"]
    s = jax.nn.sigmoid(h2 @ router)
    biased = s + jax.lax.stop_gradient(bias)
    kth = jnp.sort(biased, axis=-1)[:, n_experts - top_k]
    chosen = biased >= kth[:, None]                     # (N, E), top_k a row
    if forced is not None:
        chosen = forced
    w = jnp.where(chosen, s, 0.0)
    w = cfg["route_scale"] * w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w, chosen, s


def experts(layer, bias, h2, cfg, forced=None):
    """h2: (N, D). The held experts' part of the routed sum plus the shared
    expert. Returns (y, router stats)."""
    first, count = cfg["held"]
    weights, chosen, scores = route(h2, layer["router"], bias, cfg, forced)

    def step(y, per_expert):
        weight, w_gate, w_up, w_down = per_expert
        return y + expert_term(h2, weight, w_gate, w_up, w_down), None

    y, _ = jax.lax.scan(step, jnp.zeros_like(h2),
                        (weights.T[first:first + count], layer["w_gate"],
                         layer["w_up"], layer["w_down"]))
    y = y + swiglu(h2, layer["s_gate"], layer["s_up"], layer["s_down"])
    return y, {"chosen": chosen, "scores": scores,
               "counts": jnp.sum(chosen, axis=0, dtype=jnp.int32)}


def forward(params, biases, tokens, cfg, forced=None):
    """tokens: (B, T) ints; ``biases``: one (E,) per expert layer, in order;
    ``forced``: None, or one chosen set (B T, E) per expert layer (:func:`route`).
    Returns (logits (B, T, V), [stats of each expert layer])."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens]
        stats, biases = [], iter(biases)
        forced = iter(forced if forced is not None
                      else [None] * len(params["layers"]))
        for layer in params["layers"]:
            x = x + attention(layer, rms(x, layer["attn_norm"], cfg["eps"]), cfg)
            h2 = rms(x, layer["mlp_norm"], cfg["eps"])
            if "router" in layer:
                y, s = experts(layer, next(biases),
                               h2.reshape(-1, h2.shape[-1]), cfg, next(forced))
                x = x + y.reshape(x.shape)
                stats.append(s)
            else:
                x = x + swiglu(h2, layer["w_gate"], layer["w_up"],
                               layer["w_down"])
        return rms(x, params["final_norm"], cfg["eps"]) @ params["head"], stats


def loss_parts(params, biases, tokens, cfg, forced=None):
    """(loss, {"logits", "chosen", "counts", "scores"}): the mean next-token
    cross entropy, and per expert layer what the router did."""
    logits, stats = forward(params, biases, tokens, cfg, forced)
    targets = jnp.roll(tokens, -1, axis=1)
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


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _loss_and_grads(params, biases, tokens, cfg_items):
    return jax.value_and_grad(loss_parts, has_aux=True)(
        params, biases, tokens, dict(cfg_items))


def loss_and_grads(params, biases, tokens, cfg):
    """((loss, parts), gradients of the loss with respect to ``params``)."""
    return _loss_and_grads(params, biases, tokens, tuple(sorted(cfg.items())))


def init_params(key, cfg, layers, scale=0.02):
    """Seeded float32 parameters in this file's own layout (normal, ``scale``;
    norm weights around 1 so that their gradients are exercised) and zero
    biases: the first ``cfg["first_k_dense"]`` of ``layers`` dense, the rest
    expert layers holding ``cfg["held"][1]`` experts' weights."""
    d, heads, e = cfg["hidden"], cfg["heads"], cfg["experts"]
    nope, rope, v_dim, rank = (cfg[k] for k in ("qk_nope", "qk_rope", "v_dim",
                                                 "kv_rank"))
    count, vocab = cfg["held"][1], cfg["vocab"]
    keys = iter(jax.random.split(key, 3 + 16 * layers))

    def normal(shape, mean=0.0):
        return mean + scale * jax.random.normal(next(keys), shape, F32)

    def layer(i):
        out = {"attn_norm": normal((d,), 1.0), "mlp_norm": normal((d,), 1.0),
               "wq": normal((d, heads * (nope + rope))),
               "wkva": normal((d, rank + rope)), "kv_norm": normal((rank,), 1.0),
               "wkvb": normal((rank, heads * (nope + v_dim))),
               "wo": normal((heads * v_dim, d))}
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

    params = {"embed": normal((vocab, d)),
              "layers": [layer(i) for i in range(layers)],
              "final_norm": normal((d,), 1.0), "head": normal((d, vocab))}
    biases = [jnp.zeros((e,), F32)
              for _ in range(layers - cfg["first_k_dense"])]
    return params, biases
