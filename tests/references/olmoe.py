"""OLMoE (arXiv:2409.02060; Hugging Face ``allenai/OLMoE-1B-7B-0125-Instruct``)
in plain ``jax.numpy``: forward, loss with both auxiliary terms, gradients.

Everything is float32 under ``jax.default_matmul_precision("highest")``; the
experts are a loop over all of them with a (token, expert) mask - no sort, no
gather into expert order, no kernel, no flax, nothing from ``horovod_tpu``.
``benchmarks/reference/olmoe.py`` is a copy of this file (a test holds the two
to the same outputs), so that the benchmark brings its own.

Per layer, for hidden states ``x`` (B, T, D), no bias anywhere:

* ``h = rms(x)``; ``q = rms_q(h Wq)``, ``k = rms_k(h Wk)`` with the norm over
  ALL heads x head_dim projected features and a weight of that length;
  ``v = h Wv``; heads split; RoPE (half-split rotation) on q and k; causal
  softmax attention scaled by head_dim^-0.5; ``x = x + attn Wo``.
* ``h2 = rms(x)``; ``r = h2 Wr``; ``p = softmax(r)``; the ``top_k`` largest
  ``p`` are the weights, NOT renormalised (``norm_topk_prob`` false);
  ``y = sum_e [e chosen] p_e Wdown_e(silu(Wgate_e h2) * Wup_e h2)``;
  ``x = x + y``. Every chosen pair contributes: there is no capacity.
* Final ``rms``, untied head. Loss = mean next-token cross entropy
  + ``lb_coef`` x sum over layers of ``E sum_e f_e P_e``
  + ``z_coef`` x sum over layers of ``mean(logsumexp(r)^2)``,
  with ``f_e`` the share of the N x top_k assignments that went to expert e
  and ``P_e`` the mean of ``p_e``, over the rows handed in (one rank's rows).

Departures from the published code, each with its reason:

* ``f_e`` is a share of the N x top_k assignments (it sums to 1), as the
  paper writes the loss; Hugging Face's ``load_balancing_loss_func`` divides
  the counts by N alone, which is this times ``top_k``. The coefficient
  (0.01) is the paper's, used with the paper's formula.
* The target of the last position is the first token (``roll`` by one), the
  convention of this repo's other language-model losses; a data loader would
  mask it.
* The 8 chosen are found by a threshold at the 8th largest ``p`` (a mask),
  not by ``top_k``'s indices; equal with probability 1 on continuous values.
* Each expert's term is wrapped in ``jax.checkpoint``: the same arithmetic,
  recomputed in the backward pass, so that 64 experts' intermediates are not
  all held at once at the published widths.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32


def rms(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def rope(x, theta):
    """x: (B, T, H, Dh); position t rotates the pair (i, i + Dh/2) by
    t * theta^(-2i/Dh)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    angles = jnp.arange(x.shape[1], dtype=F32)[:, None] * freqs      # (T, half)
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(layer, h, cfg):
    b, t, _ = h.shape
    heads, eps = cfg["heads"], cfg["eps"]
    q = rms(h @ layer["wq"], layer["q_norm"], eps)
    k = rms(h @ layer["wk"], layer["k_norm"], eps)
    v = h @ layer["wv"]
    q, k, v = (a.reshape(b, t, heads, -1) for a in (q, k, v))
    q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(b, t, -1) @ layer["wo"]


@jax.checkpoint
def expert_term(h2, weight, w_gate, w_up, w_down):
    """One expert on EVERY row, times that row's weight for it (0 where the
    expert was not chosen)."""
    return weight[:, None] * ((jax.nn.silu(h2 @ w_gate) * (h2 @ w_up)) @ w_down)


def experts(layer, h2, cfg):
    """h2: (N, D). Returns (y, router stats)."""
    n_experts, top_k = layer["router"].shape[1], cfg["top_k"]
    r = h2 @ layer["router"]
    p = jax.nn.softmax(r, axis=-1)
    kth = jnp.sort(p, axis=-1)[:, n_experts - top_k]
    chosen = p >= kth[:, None]                          # (N, E), top_k a row
    weights = jnp.where(chosen, p, 0.0)

    def step(y, per_expert):
        weight, w_gate, w_up, w_down = per_expert
        return y + expert_term(h2, weight, w_gate, w_up, w_down), None

    y, _ = jax.lax.scan(step, jnp.zeros_like(h2),
                        (weights.T, layer["w_gate"], layer["w_up"],
                         layer["w_down"]))
    share = jnp.mean(chosen.astype(F32), axis=0) / top_k    # sums to 1
    return y, {
        "lb": n_experts * jnp.sum(share * jnp.mean(p, axis=0)),
        "z": jnp.mean(jax.nn.logsumexp(r, axis=-1) ** 2),
        "router_logits": r, "chosen": chosen,
    }


def forward(params, tokens, cfg):
    """tokens: (B, T) ints. Returns (logits (B, T, V), [per-layer stats])."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens]
        stats = []
        for layer in params["layers"]:
            x = x + attention(layer, rms(x, layer["attn_norm"], cfg["eps"]), cfg)
            h2 = rms(x, layer["mlp_norm"], cfg["eps"])
            y, s = experts(layer, h2.reshape(-1, h2.shape[-1]), cfg)
            x = x + y.reshape(x.shape)
            stats.append(s)
        return rms(x, params["final_norm"], cfg["eps"]) @ params["head"], stats


def loss_parts(params, tokens, cfg, weights=None):
    """(total, {"ce", "lb", "z", "logits", "chosen", "router_logits"});
    ``lb`` and ``z`` are sums over layers, before their coefficients.
    ``weights`` (B, T), where given, weigh the positions of the cross entropy
    (a loss mask); the auxiliary terms are over all rows."""
    logits, stats = forward(params, tokens, cfg)
    targets = jnp.roll(tokens, -1, axis=1)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    ce = (jnp.mean(nll) if weights is None
          else jnp.sum(nll * weights) / jnp.sum(weights))
    lb, z = (sum(s[k] for s in stats) for k in ("lb", "z"))
    total = ce + cfg["lb_coef"] * lb + cfg["z_coef"] * z
    return total, {"ce": ce, "lb": lb, "z": z, "logits": logits,
                   "chosen": [s["chosen"] for s in stats],
                   "router_logits": [s["router_logits"] for s in stats]}


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _loss_and_grads(params, tokens, cfg_items):
    return jax.value_and_grad(loss_parts, has_aux=True)(
        params, tokens, dict(cfg_items))


def loss_and_grads(params, tokens, cfg):
    """((total, parts), gradients of total with respect to ``params``)."""
    return _loss_and_grads(params, tokens, tuple(sorted(cfg.items())))


def init_params(key, cfg, layers=1, scale=0.02):
    """Seeded float32 parameters in this file's own layout (normal, ``scale``;
    norm weights around 1 so that their gradients are exercised)."""
    d, e, w, v = cfg["hidden"], cfg["experts"], cfg["expert_width"], cfg["vocab"]
    keys = iter(jax.random.split(key, 3 + 12 * layers))

    def normal(shape, mean=0.0):
        return mean + scale * jax.random.normal(next(keys), shape, F32)

    return {
        "embed": normal((v, d)),
        "layers": [{
            "attn_norm": normal((d,), 1.0), "mlp_norm": normal((d,), 1.0),
            "q_norm": normal((d,), 1.0), "k_norm": normal((d,), 1.0),
            "wq": normal((d, d)), "wk": normal((d, d)), "wv": normal((d, d)),
            "wo": normal((d, d)), "router": normal((d, e)),
            "w_gate": normal((e, d, w)), "w_up": normal((e, d, w)),
            "w_down": normal((e, w, d)),
        } for _ in range(layers)],
        "final_norm": normal((d,), 1.0),
        "head": normal((d, v)),
    }
