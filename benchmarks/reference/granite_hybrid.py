"""Granite 4.0-H (Hugging Face ``ibm-granite/granite-4.0-h-micro``, model type
``granitemoehybrid`` with no experts; the Mamba-2 layer of Dao & Gu,
arXiv:2405.21060 §6-7) in plain ``jax.numpy``: forward, loss, gradients.

Everything is float32 under ``jax.default_matmul_precision("highest")``; the
state-space layer is computed by its definition, a step per token - no
chunks, no kernel, no flax, nothing from ``horovod_tpu``.
``benchmarks/reference/granite_hybrid.py`` is a copy of this file (a test
holds the two to the same outputs), so that the benchmark brings its own.

Hidden states ``x`` (B, T, D); no matrix has a bias. ``x = emb_mult * E[tokens]``,
then per layer, by ``cfg["layer_types"]``:

* ``"mamba"``: ``h = rms(x)``; ``[z | xBC | dt] = h W_in`` (widths H*P |
  H*P + 2*G*N | H); ``xBC = silu(conv(xBC))`` with ``conv`` causal and
  depthwise, ``out[t, c] = b[c] + sum_j w[j, c] * xBC[t - (K-1) + j, c]``, zeros
  before the row's start; ``xBC`` splits into ``u`` (H heads x P), ``B`` and
  ``C`` (G groups x N each). Per head: ``dt_t = softplus(dt_t + dt_bias)``,
  ``a_t = exp(dt_t * A)`` with ``A = -exp(A_log)``, ``S_0 = 0``,
  ``S_t = a_t S_{t-1} + dt_t u_t B_t^T``, ``y_t = S_t C_t + D u_t``
  (:func:`ssm_recurrence`). Then ``y = rms(y * silu(z))`` over each group's
  H*P/G features (the GATE FIRST, then the norm) and
  ``x = x + res_mult * (y W_out)``.
* ``"attention"``: ``h = rms(x)``; ``q = h Wq`` (heads x Dh), ``k = h Wk``,
  ``v = h Wv`` (kv_heads x Dh); NO rotary embedding; causal softmax attention
  with scores scaled by ``attn_mult`` (not Dh^-0.5), query head ``i`` reading
  key/value head ``i // (heads / kv_heads)``; ``x = x + res_mult * (attn Wo)``.
* every layer then: ``h2 = rms(x)``;
  ``x = x + res_mult * (W_down (silu(W_gate h2) * W_up h2))``.

Final ``rms``; ``logits = (x E^T) / logits_scaling`` with the SAME ``E``. Loss
= mean next-token cross entropy (or a weighted mean).

:func:`ssm_quadratic` is the second form of the state-space layer,
``y = (L o C B^T)(dt u) + D u`` with ``L[t, s] = exp(sum_{s < r <= t} dt_r A)``
for ``s <= t`` and 0 above: the same numbers without a saved state per token
(the recurrence's gradient would hold 2 MiB a token a layer at the published
widths). ``forward(..., ssm=ssm_quadratic)`` takes gradients through it; a
test holds the two forms to each other.

Departures from the published code, each with its reason:

* The target of the last position is the first token (``roll`` by one), the
  convention of this repo's other language-model losses; a data loader would
  mask it.
* The convolution's weight is stored (K, C), the transpose of torch's
  ``Conv1d`` (C, 1, K): the same numbers, laid out for broadcasting.
* Hugging Face clamps ``dt`` to ``time_step_limit`` = (0, inf) after the
  softplus: no change to any value, left out.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def rms(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def causal_conv(x, w, b):
    """x: (B, T, C); w: (K, C); b: (C,)."""
    k, t = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return b + sum(w[j] * padded[:, j:j + t] for j in range(k))


def ssm_recurrence(u, dt, A, B, C, D):
    """The definition, a step per token. u: (b, T, H, P); dt: (b, T, H),
    positive; A: (H,), negative; B, C: (b, T, G, N); D: (H,). Head ``h`` reads
    group ``h // (H / G)``. Returns y (b, T, H, P)."""
    b, t, h, p = u.shape
    g = B.shape[2]
    B, C = (jnp.repeat(x, h // g, axis=2) for x in (B, C))       # (b,T,H,N)

    def step(state, now):
        u_t, dt_t, b_t, c_t = now
        a_t = jnp.exp(dt_t * A)                                  # (b,H)
        state = (a_t[..., None, None] * state
                 + (dt_t[..., None] * u_t)[..., None] * b_t[:, :, None, :])
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t) + D[:, None] * u_t

    _, y = jax.lax.scan(
        step, jnp.zeros((b, h, p, B.shape[-1]), F32),
        tuple(jnp.moveaxis(x, 1, 0) for x in (u, dt, B, C)))
    return jnp.moveaxis(y, 0, 1)


def ssm_quadratic(u, dt, A, B, C, D):
    """The same ``y`` as one masked (T x T) matrix a head."""
    b, t, h, p = u.shape
    g = B.shape[2]
    B, C = (jnp.repeat(x, h // g, axis=2) for x in (B, C))
    cum = jnp.cumsum(dt * A, axis=1)                             # (b,T,H)
    seg = cum[:, :, None, :] - cum[:, None, :, :]                # (b,t,s,H)
    lower = jnp.tril(jnp.ones((t, t), bool))[None, :, :, None]
    decay = jnp.exp(jnp.where(lower, seg, -jnp.inf))
    scores = jnp.einsum("bthn,bshn->btsh", C, B) * decay
    return (jnp.einsum("btsh,bshp->bthp", scores, dt[..., None] * u)
            + D[:, None] * u)


def mamba_mixer(h, layer, cfg, ssm):
    b, t, _ = h.shape
    heads, p, g, n = (cfg[k] for k in ("mamba_heads", "mamba_head_dim",
                                       "mamba_groups", "mamba_state"))
    inner = heads * p
    zxbcdt = h @ layer["w_in"]
    z, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * g * n], axis=-1)
    xbc = jax.nn.silu(causal_conv(xbc, layer["conv_w"], layer["conv_b"]))
    u, B, C = jnp.split(xbc, [inner, inner + g * n], axis=-1)
    dt = jax.nn.softplus(dt + layer["dt_bias"])
    y = ssm(u.reshape(b, t, heads, p), dt, -jnp.exp(layer["A_log"]),
            B.reshape(b, t, g, n), C.reshape(b, t, g, n), layer["D"])
    gated = (y.reshape(b, t, inner) * jax.nn.silu(z)).reshape(b, t, g, inner // g)
    normed = rms(gated, layer["gate_norm"].reshape(g, inner // g), cfg["eps"])
    return normed.reshape(b, t, inner) @ layer["w_out"]


def attention(h, layer, cfg):
    b, t, _ = h.shape
    heads, kv = cfg["heads"], cfg["kv_heads"]
    q = (h @ layer["wq"]).reshape(b, t, heads, -1)
    k = (h @ layer["wk"]).reshape(b, t, kv, -1)
    v = (h @ layer["wv"]).reshape(b, t, kv, -1)
    k, v = (jnp.repeat(x, heads // kv, axis=2) for x in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * cfg["attn_mult"]
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, t, -1) @ layer["wo"]


def hidden_states(params, tokens, cfg, ssm=ssm_recurrence):
    """The final normed hidden states (B, T, D)."""
    x = cfg["emb_mult"] * params["embed"][tokens]
    for kind, layer in zip(cfg["layer_types"], params["layers"], strict=True):
        h = rms(x, layer["norm"], cfg["eps"])
        mixed = (mamba_mixer(h, layer, cfg, ssm) if kind == "mamba"
                 else attention(h, layer, cfg))
        x = x + cfg["res_mult"] * mixed
        h2 = rms(x, layer["mlp_norm"], cfg["eps"])
        mlp = (jax.nn.silu(h2 @ layer["w_gate"]) * (h2 @ layer["w_up"])) @ layer["w_down"]
        x = x + cfg["res_mult"] * mlp
    return rms(x, params["final_norm"], cfg["eps"])


def forward(params, tokens, cfg, ssm=ssm_recurrence):
    """Logits (B, T, vocab), float32 at ``highest``."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(lambda x: x.astype(F32), params)
        x = hidden_states(params, tokens, cfg, ssm)
        return (x @ params["embed"].T) / cfg["logits_scaling"]


def loss(params, tokens, cfg, ssm=ssm_recurrence, weights=None):
    """Mean next-token cross entropy on float32 logits (weighted by
    ``weights`` (B, T) where given); returns (loss, logits)."""
    logits = forward(params, tokens, cfg, ssm)
    targets = jnp.roll(tokens, -1, axis=1)
    nll = (jax.nn.logsumexp(logits, axis=-1)
           - jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0])
    if weights is None:
        return jnp.mean(nll), logits
    return jnp.sum(nll * weights) / jnp.sum(weights), logits


def loss_and_grads(params, tokens, cfg, ssm=ssm_quadratic):
    """((loss, logits), gradients of the loss in ``params``' layout); through
    the quadratic form by default, which saves no state per token."""
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            lambda p: loss(p, tokens, cfg, ssm), has_aux=True)(params)
