"""Kimi-Linear-48B-A3B-Instruct (Hugging Face
``moonshotai/Kimi-Linear-48B-A3B-Instruct``, ``model_type`` ``kimi_linear``;
Kimi Delta Attention as the Kimi Linear report, arXiv:2510.26692, defines it
in the section on KDA, and as the released modelling code computes it) in
plain ``jax.numpy``: forward, loss, gradients and the router-bias rule.

Everything is float32 under ``jax.default_matmul_precision("highest")``; the
delta rule is a step a TOKEN (``lax.scan``), each expert is applied to EVERY
token and masked by ``chosen`` - no chunked form, no sort, no kernel, no flax,
nothing from ``horovod_tpu``.

Hidden states ``x`` (B, T, D); no matrix has a bias; ``rms`` has eps
``cfg["eps"]`` and a learned weight; there is NO position information
anywhere (``mla_use_nope``): the recurrence and the causal mask order the
tokens.

* ``x = E[tokens]``.
* A ``"kda"`` layer (``cfg["kinds"]``), ``h = rms(x)``, H heads of d:
  ``q~ = h Wq``, ``k~ = h Wk``, ``v~ = h Wv`` (D -> H d each); each through
  its OWN causal depthwise convolution (``taps`` (K, H d):
  ``sum_j taps[j] x[t - (K - 1) + j]``, zeros before the row) and then SiLU.
  Per head ``q = q / sqrt(|q|^2 + 1e-6) * d^-0.5``, ``k = k / sqrt(|k|^2 +
  1e-6)``. ``g = -exp(a_log_head) * softplus((h Wf_a) Wf_b + dt_bias)``
  (D -> d -> H d), ``alpha = exp(g)``; ``beta = sigmoid(h Wb)`` (D -> H).
  Per head, ``S_0 = 0`` (d x d, key x value):
  ``S' = Diag(alpha_t) S_{t-1}``; ``S_t = S' + beta_t k_t (v_t - S'^T k_t)^T``;
  ``o_t = S_t^T q_t``. Then ``o = rms_head(o) * o_norm`` (ONE weight of d
  shared by the heads, eps ``cfg["eps"]``) ``* sigmoid((h Wg_a) Wg_b)``;
  ``x = x + concat(o) Wo``.
* A ``"full"`` layer, latent attention with no rotary embedding:
  ``q = h Wq`` -> (T, H, 192); ``[c | k_s] = h Wkva`` (512 | 64);
  ``c = rms(c)``; ``c Wkvb`` -> (T, H, 256), per head ``k_n`` (128) | ``v``
  (128); ``k = [k_n | k_s]``, ``k_s`` ONE head repeated for all; scores
  ``q k^T * 192^-0.5``, causal, softmax; ``x = x + reshape(P v) Wo``.
* Dense layers (the first ``first_k_dense``): ``h2 = rms(x)``;
  ``x = x + Wd (silu(Wg h2) * Wu h2)``.
* Expert layers: ``s = sigmoid(h2 Wr)`` (T, E); ``chosen`` = the ``top_k``
  largest of ``s + b``; ``w = route_scale * s[chosen] / (sum + 1e-20)``;
  ``x = x + sum_{e chosen AND held} w_e down_e(silu(gate_e h2) * up_e h2)
  + down_s(silu(gate_s h2) * up_s h2)``. ``held = (first, count)``: the
  weights handed in are those of experts ``[first, first + count)`` alone;
  ``w`` is still normalised over all ``top_k``. ``b`` receives no gradient.
* Final ``rms``; logits = ``x H`` over the rows of the vocabulary handed in.
  Loss = mean next-token cross entropy.
* After a step, per expert layer: ``b_e += rate * sign(mean(c) - c_e)``.

Departures from the published code, each with its reason:

* The released code calls a chunked kernel (chunk 64) in training and a
  fused recurrent one in decoding; this is the recurrence both compute.
* The scan over tokens runs in blocks of ``SCAN_BLOCK`` steps under
  ``jax.checkpoint`` so that its gradient fits: bookkeeping, the same
  arithmetic.
* The published latent attention splits q and k into a "nope" and a "rope"
  part and, with ``mla_use_nope``, turns neither: this never splits them.
* The target of the last position is the first token (``roll`` by one), the
  convention of this repo's other language-model losses.
* The chosen are found by a threshold at the ``top_k``-th largest ``s + b``
  (a mask), not by ``top_k``'s indices; equal with probability 1 on
  continuous values. The router's bias and its rule are DeepSeek-V3's
  (arXiv:2412.19437 §2.1.2): ``config.json`` names neither (the file's
  ``assumed``).
* Each expert's term is wrapped in ``jax.checkpoint``.
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
    H, V); beta: (B, T, H). Returns o (B, T, H, V)."""
    b, t, h, dk = k.shape
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
    heads, d = cfg["heads"], cfg["kda_head_dim"]

    def head(x):
        return x.reshape(b, t, heads, d)

    q = head(conv_silu(h @ layer["wq"], layer["conv_q"]))
    k = head(conv_silu(h @ layer["wk"], layer["conv_k"]))
    v = head(conv_silu(h @ layer["wv"], layer["conv_v"]))
    q, k = unit(q) * d ** -0.5, unit(k)
    g = -jnp.exp(layer["a_log"])[:, None] * head(jax.nn.softplus(
        (h @ layer["wf_a"]) @ layer["wf_b"] + layer["dt_bias"]))
    beta = jax.nn.sigmoid(h @ layer["wb"])
    o = delta_rule(q, k, v, g, beta)
    o = rms(o, layer["o_norm"], cfg["eps"])
    gate = jax.nn.sigmoid((h @ layer["wg_a"]) @ layer["wg_b"])
    return (o.reshape(b, t, heads * d) * gate) @ layer["wo"]


def attention(layer, h, cfg):
    b, t, _ = h.shape
    heads, d_qk, v_dim = (cfg["heads"], cfg["qk_nope"] + cfg["qk_rope"],
                          cfg["v_dim"])
    nope = cfg["qk_nope"]
    q = (h @ layer["wq"]).reshape(b, t, heads, d_qk)
    kva = h @ layer["wkva"]
    c, k_s = kva[..., :cfg["kv_rank"]], kva[..., cfg["kv_rank"]:]
    kv = (rms(c, layer["kv_norm"], cfg["eps"]) @ layer["wkvb"]).reshape(
        b, t, heads, nope + v_dim)
    k_n, v = kv[..., :nope], kv[..., nope:]
    k = jnp.concatenate(
        [k_n, jnp.repeat(k_s[:, :, None, :], heads, axis=2)], axis=-1)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * d_qk ** -0.5
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
    within rounding compares the rest of the arithmetic under its choice. A
    pair ``(use, forced)`` takes the set only where the (traced) ``use`` is
    true, so that one compiled program serves both."""
    n_experts, top_k = router.shape[1], cfg["top_k"]
    s = jax.nn.sigmoid(h2 @ router)
    biased = s + jax.lax.stop_gradient(bias)
    kth = jnp.sort(biased, axis=-1)[:, n_experts - top_k]
    chosen = biased >= kth[:, None]                     # (N, E), top_k a row
    if forced is not None:
        use, forced = forced if isinstance(forced, tuple) else (True, forced)
        chosen = jnp.where(use, forced, chosen)
    w = jnp.where(chosen, s, 0.0)
    w = cfg["route_scale"] * w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w, chosen, s


def routed(layer, weights, h2, cfg):
    """The held experts' part of the routed sum. h2: (N, D)."""
    first, count = cfg["held"]

    def step(y, per_expert):
        weight, w_gate, w_up, w_down = per_expert
        return y + expert_term(h2, weight, w_gate, w_up, w_down), None

    return jax.lax.scan(step, jnp.zeros_like(h2),
                        (weights.T[first:first + count], layer["w_gate"],
                         layer["w_up"], layer["w_down"]))[0]


def experts(layer, bias, h2, cfg, forced=None):
    """h2: (N, D). The held experts' part of the routed sum plus the shared
    expert. Returns (y, router stats)."""
    weights, chosen, scores = route(h2, layer["router"], bias, cfg, forced)
    y = routed(layer, weights, h2, cfg) + swiglu(
        h2, layer["s_gate"], layer["s_up"], layer["s_down"])
    return y, {"chosen": chosen, "scores": scores,
               "counts": jnp.sum(chosen, axis=0, dtype=jnp.int32)}


def forward(params, biases, tokens, cfg, forced=None):
    """tokens: (B, T) ints; ``biases``: one (E,) per expert layer, in order;
    ``forced``: None, or one chosen set (B T, E), or one pair ``(use, set)``,
    per expert layer (:func:`route`).
    Returns (logits (B, T, V), [stats of each expert layer])."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens]
        stats, biases = [], iter(biases)
        forced = iter(forced if forced is not None
                      else [None] * len(params["layers"]))
        for kind, layer in zip(cfg["kinds"], params["layers"]):
            mixer = {"kda": kda, "full": attention}[kind]
            x = x + mixer(layer, rms(x, layer["attn_norm"], cfg["eps"]), cfg)
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
