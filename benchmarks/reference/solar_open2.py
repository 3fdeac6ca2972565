"""Solar-Open2-250B (Hugging Face ``upstage/Solar-Open2-250B``, ``model_type``
``solar_open2``) in plain ``jax.numpy``: forward, loss, gradients and the
router-bias rule of ONE tensor-parallel / expert-parallel rank's share. The
equations' public sources: Kimi Delta Attention as the Kimi Linear report
defines it (arXiv:2510.26692, the section on KDA), with beta doubled
(``kda_allow_neg_eigval``); the sigmoid gate an element of softmax attention's
output before ``o_proj`` (arXiv:2505.06708); sigmoid scores, a selection bias
and the chosen weights renormalised (arXiv:2412.19437 §2.1.2).

Everything is float32 under ``jax.default_matmul_precision("highest")``; the
delta rule is a step a TOKEN (``lax.scan``), attention a masked softmax a
head, each held expert is applied to EVERY token and masked by ``chosen`` -
no chunked form, no sort, no kernel, no flax, nothing from ``horovod_tpu`` and
nothing from another reference.

Hidden states ``x`` (B, T, D); no matrix has a bias; ``rms`` has eps
``cfg["eps"]`` and a learned weight; there is NO position information
anywhere (``use_rope`` false): the recurrence and the causal mask order the
tokens. The weights handed in are the rank's share: ``H`` heads of each mixer
(``cfg["kda_heads"]``, ``cfg["heads"]`` over ``cfg["kv_heads"]``), some of the
shared expert's columns, the experts ``[first, first + count)``
(``cfg["held"]``), some rows of the vocabulary. Every equation is the
published one at the heads handed in; ``o_proj`` and the shared expert's down
projection then give the rank's PARTIAL sums, and they go on as they are.

* ``x = E[tokens]``.
* A ``"gqa"`` layer (``cfg["kinds"]``), ``h = rms(x)``: ``q = h Wq`` (D ->
  H x d), ``[k | v] = h Wkv`` (D -> 2 x Hkv x d; one key/value head serves
  H / Hkv query heads), ``z = h Wg`` (D -> H x d); no rotation, no q/k norm;
  scores ``q k^T * d^-0.5``, causal, softmax; ``a = P v``;
  ``x = x + (a * sigmoid(z)) Wo``.
* A ``"kda"`` layer, H heads of d: ``q~ = h Wq``, ``k~ = h Wk``, ``v~ = h Wv``
  (D -> H d each); each through its OWN causal depthwise convolution
  (``taps`` (K, H d): ``sum_j taps[j] x[t - (K - 1) + j]``, zeros before the
  row) and then SiLU. Per head ``q = q / sqrt(|q|^2 + 1e-6) * d^-0.5``,
  ``k = k / sqrt(|k|^2 + 1e-6)``. ``g = -exp(a_log_head) * softplus((h Wf_a)
  Wf_b + dt_bias)`` (D -> d -> H d), ``alpha = exp(g)``; ``beta = 2 sigmoid(h
  Wb)`` (D -> H). Per head, ``S_0 = 0`` (d x d, key x value):
  ``S' = Diag(alpha_t) S_{t-1}``; ``S_t = S' + beta_t k_t (v_t - S'^T k_t)^T``;
  ``o_t = S_t^T q_t``. Then ``o = rms_head(o) * o_norm`` (ONE weight of d
  shared by the heads) ``* sigmoid((h Wg_a) Wg_b)``; ``x = x + concat(o) Wo``.
* Every layer's second half: ``s = sigmoid(h2 Wr)`` (T, E); ``chosen`` = the
  ``top_k`` largest of ``s + b``; ``w = s[chosen] / (sum + 1e-20)``;
  ``x = x + sum_{e chosen AND held} w_e down_e(silu(gate_e h2) * up_e h2)
  + down_s(silu(gate_s h2) * up_s h2)``. ``w`` is normalised over all
  ``top_k`` whoever holds them. ``b`` receives no gradient.
* Final ``rms``; logits = ``x H`` over the rows of the vocabulary handed in.
  Loss = mean next-token cross entropy.
* After a step, per expert layer: ``b_e += rate * sign(mean(c) - c_e)``.

Departures from the published description, each at its line below:

* the released code calls a chunked kernel (chunk 64) in training; this is
  the recurrence it computes, in checkpointed blocks of ``SCAN_BLOCK`` steps;
* k and v come out of ONE matrix ``Wkv`` (the published two, side by side);
* the target of the last position is the first token (``roll`` by one);
* the chosen are found by a threshold at the ``top_k``-th largest ``s + b``
  (a mask), not by indices; the bias and its rule are DeepSeek-V3's, which
  ``config.json`` does not name (the file's ``assumed``);
* each expert's term is wrapped in ``jax.checkpoint``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
SCAN_BLOCK = 64     # steps of the delta rule a checkpointed block holds


def rms(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def conv_silu(x, taps):
    """x: (B, T, C); taps: (K, C). Causal, depthwise, no bias, then SiLU."""
    k, t = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return jax.nn.silu(sum(taps[j] * padded[:, j:j + t] for j in range(k)))


def unit(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def delta_rule(q, k, v, g, beta, block=SCAN_BLOCK):
    """The gated delta rule, a step a token. q, k, g: (B, T, H, K); v: (B, T,
    H, V); beta: (B, T, H), in (0, 2). Returns o (B, T, H, V)."""
    b, t, h, dk = k.shape
    # departure: the scan runs in checkpointed blocks so that its gradient
    # fits; bookkeeping, the same arithmetic
    block = max(n for n in range(1, min(block, t) + 1) if t % n == 0)

    def step(state, x):
        qt, kt, vt, gt, bt = x
        state = jnp.exp(gt)[..., None] * state               # Diag(alpha) S
        seen = jnp.einsum("bhkv,bhk->bhv", state, kt)         # S'^T k
        state = state + (bt[..., None] * kt)[..., None] * (vt - seen)[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, qt)

    @jax.checkpoint
    def steps(state, xs):
        return jax.lax.scan(step, state, xs)

    def time_first(x):      # (B, T, ...) -> (T / block, block, B, ...)
        x = jnp.moveaxis(x, 1, 0)
        return x.reshape(t // block, block, *x.shape[1:])

    _, o = jax.lax.scan(steps, jnp.zeros((b, h, dk, v.shape[-1]), F32),
                        tuple(time_first(x) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape(t, *o.shape[2:]), 0, 1)


def kda(layer, h, cfg):
    b, t, _ = h.shape
    heads, d = cfg["kda_heads"], cfg["head_dim"]

    def head(x):
        return x.reshape(b, t, heads, d)

    q = head(conv_silu(h @ layer["wq"], layer["conv_q"]))
    k = head(conv_silu(h @ layer["wk"], layer["conv_k"]))
    v = head(conv_silu(h @ layer["wv"], layer["conv_v"]))
    q, k = unit(q) * d ** -0.5, unit(k)
    g = -jnp.exp(layer["a_log"])[:, None] * head(jax.nn.softplus(
        (h @ layer["wf_a"]) @ layer["wf_b"] + layer["dt_bias"]))
    beta = 2.0 * jax.nn.sigmoid(h @ layer["wb"])    # kda_allow_neg_eigval
    o = delta_rule(q, k, v, g, beta)
    o = rms(o, layer["o_norm"], cfg["eps"])
    gate = jax.nn.sigmoid((h @ layer["wg_a"]) @ layer["wg_b"])
    return (o.reshape(b, t, heads * d) * gate) @ layer["wo"]


def attention(layer, h, cfg):
    """Grouped-query softmax attention, its output gated an element."""
    b, t, _ = h.shape
    heads, kv_heads, d = cfg["heads"], cfg["kv_heads"], cfg["head_dim"]
    q = (h @ layer["wq"]).reshape(b, t, heads, d)
    # departure: k and v out of one matrix, the published two side by side
    kv = (h @ layer["wkv"]).reshape(b, t, 2, kv_heads, d)
    k, v = (jnp.repeat(kv[:, :, n], heads // kv_heads, axis=2)
            for n in range(2))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * d ** -0.5
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    a = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(b, t, heads * d)
    return (a * jax.nn.sigmoid(h @ layer["wg"])) @ layer["wo"]


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
    w = cfg["route_scale"] * w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w, chosen, s


def routed(layer, weights, h2, cfg):
    """The held experts' part of the routed sum, an expert after another.
    h2: (N, D)."""
    first, count = cfg["held"]
    return sum(expert_term(h2, weights[:, first + e], layer["w_gate"][e],
                           layer["w_up"][e], layer["w_down"][e])
               for e in range(count))


def experts(layer, bias, h2, cfg, forced=None):
    """h2: (N, D). The held experts' part of the routed sum plus the shared
    expert's held columns. Returns (y, router stats)."""
    weights, chosen, scores = route(h2, layer["router"], bias, cfg, forced)
    y = routed(layer, weights, h2, cfg) + swiglu(
        h2, layer["s_gate"], layer["s_up"], layer["s_down"])
    return y, {"chosen": chosen, "scores": scores,
               "counts": jnp.sum(chosen, axis=0, dtype=jnp.int32)}


def forward(params, biases, tokens, cfg, forced=None):
    """tokens: (B, T) ints; ``biases``: one (E,) per layer, in order;
    ``forced``: None, or one chosen set (B T, E), or one pair ``(use, set)``,
    per layer (:func:`route`).
    Returns (logits (B, T, V), [stats of each layer's experts])."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens]
        stats = []
        forced = forced if forced is not None else [None] * len(params["layers"])
        for kind, layer, bias, sets in zip(cfg["kinds"], params["layers"],
                                           biases, forced):
            mixer = {"kda": kda, "gqa": attention}[kind]
            x = x + mixer(layer, rms(x, layer["attn_norm"], cfg["eps"]), cfg)
            h2 = rms(x, layer["mlp_norm"], cfg["eps"])
            y, s = experts(layer, bias, h2.reshape(-1, h2.shape[-1]), cfg, sets)
            x = x + y.reshape(x.shape)
            stats.append(s)
        return rms(x, params["final_norm"], cfg["eps"]) @ params["head"], stats


def loss_parts(params, biases, tokens, cfg, forced=None):
    """(loss, {"logits", "chosen", "counts", "scores"}): the mean next-token
    cross entropy, and per layer what the router did."""
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
