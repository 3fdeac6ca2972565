"""Olmo-Hybrid-7B (Hugging Face ``allenai/Olmo-Hybrid-7B``, ``model_type``
``olmo_hybrid``; the linear layers are Gated DeltaNet's, arXiv:2412.06464,
with beta in (0, 2), arXiv:2411.12537; block and attention are OLMo 2's,
arXiv:2501.00656 §3) in plain ``jax.numpy``: forward, loss and gradients.

Everything is float32 under ``jax.default_matmul_precision("highest")``; the
delta rule is a step a TOKEN (``lax.scan``) - no chunked form, no kernel, no
flax, nothing from ``horovod_tpu``.

Hidden states ``x`` (B, T, D); no matrix has a bias; ``rms`` has eps
``cfg["eps"]`` and a learned weight; there is NO position information
anywhere (``rope_theta`` null): the recurrence, the convolutions and the
causal mask order the tokens.

* ``x = E[tokens]``.
* Every layer, OLMo 2's reordered norm: ``x = x + rms(mixer(x))``;
  ``x = x + rms(Wd (silu(Wg x) * Wu x))``. No norm before either half.
* A ``"linear_attention"`` layer (``cfg["kinds"]``), H heads with keys of dk
  and values of dv: ``q~ = x Wq``, ``k~ = x Wk`` (D -> H dk), ``v~ = x Wv``
  (D -> H dv); each through its OWN causal depthwise convolution (``taps``
  (K, C): ``sum_j taps[j] x[t - (K - 1) + j]``, zeros before the row) and
  then SiLU. Per head ``q = q / sqrt(|q|^2 + 1e-6) * dk^-0.5``, ``k = k /
  sqrt(|k|^2 + 1e-6)``. ``g = -exp(a_log) * softplus(x Wa + dt_bias)`` (D ->
  H: ONE number a head and position), ``alpha = exp(g)``; ``beta = 2
  sigmoid(x Wb)`` (D -> H; the 2 is ``cfg["neg_eigval"]``). Per head, ``S_0 =
  0`` (dk x dv): ``S' = alpha_t S_{t-1}``; ``S_t = S' + beta_t k_t (v_t -
  S'^T k_t)^T``; ``o_t = S_t^T q_t``. Then ``o = rms_head(o) * o_norm`` (ONE
  weight of dv shared by the heads) ``* silu(x Wg)`` (D -> H dv);
  out ``= concat(o) Wo``.
* A ``"full_attention"`` layer: ``q, k, v = x Wq, x Wk, x Wv`` (D -> H d
  each); ``q = rms(q)``, ``k = rms(k)`` over the WHOLE projection handed in,
  each with a weight of that length; no rotation; scores ``q k^T * d^-0.5``,
  causal, softmax; out ``= concat(P v) Wo``.
* Final ``rms``; logits = ``x H`` over the rows of the vocabulary handed in.
  Loss = mean next-token cross entropy.

The weights handed in are one tensor-parallel rank's: its heads of every
mixer, its columns of the MLP, and the QK-norm's statistic over the channels
it holds. ``o``-projections and the MLP's down projection give that rank's
partial sums, which go into the half's norm as they are: nothing stands in
for the other rank (the configuration file's ``cut``).

Departures from the published code, each with its reason:

* The released code calls a chunked kernel in training and a fused
  recurrent one in decoding; this is the recurrence both compute.
* The scan over tokens runs in blocks of ``SCAN_BLOCK`` steps under
  ``jax.checkpoint`` so that its gradient fits: bookkeeping, the same
  arithmetic.
* The target of the last position is the first token (``roll`` by one), the
  convention of this repo's other language-model losses.
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
    """The gated delta rule with one decay a head, a step a token. q, k: (B,
    T, H, K); v: (B, T, H, V); g, beta: (B, T, H). Returns o (B, T, H, V)."""
    b, t, h, dk = k.shape
    block = max(n for n in range(1, min(block, t) + 1) if t % n == 0)

    def step(state, x):
        qt, kt, vt, gt, bt = x
        state = jnp.exp(gt)[..., None, None] * state          # alpha S
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


def linear_attention(layer, x, cfg):
    b, t, _ = x.shape
    heads, dk, dv = cfg["linear_heads"], cfg["key_dim"], cfg["value_dim"]
    q = conv_silu(x @ layer["wq"], layer["conv_q"]).reshape(b, t, heads, dk)
    k = conv_silu(x @ layer["wk"], layer["conv_k"]).reshape(b, t, heads, dk)
    v = conv_silu(x @ layer["wv"], layer["conv_v"]).reshape(b, t, heads, dv)
    q, k = unit(q) * dk ** -0.5, unit(k)
    g = -jnp.exp(layer["a_log"]) * jax.nn.softplus(
        x @ layer["wa"] + layer["dt_bias"])
    beta = jax.nn.sigmoid(x @ layer["wb"]) * (2.0 if cfg["neg_eigval"] else 1.0)
    o = rms(delta_rule(q, k, v, g, beta), layer["o_norm"], cfg["eps"])
    gate = jax.nn.silu(x @ layer["wg"])
    return (o.reshape(b, t, heads * dv) * gate) @ layer["wo"]


def full_attention(layer, x, cfg):
    b, t, _ = x.shape
    heads, d = cfg["heads"], cfg["head_dim"]
    q = rms(x @ layer["wq"], layer["q_norm"], cfg["eps"]).reshape(b, t, heads, d)
    k = rms(x @ layer["wk"], layer["k_norm"], cfg["eps"]).reshape(b, t, heads, d)
    v = (x @ layer["wv"]).reshape(b, t, heads, d)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * d ** -0.5
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(b, t, -1) @ layer["wo"]


def swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


MIXERS = {"linear_attention": linear_attention,
          "full_attention": full_attention}


def forward(params, tokens, cfg):
    """tokens: (B, T) ints. Returns logits (B, T, V)."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens]
        for kind, layer in zip(cfg["kinds"], params["layers"]):
            x = x + rms(MIXERS[kind](layer, x, cfg), layer["attn_norm"],
                        cfg["eps"])
            x = x + rms(swiglu(x, layer["w_gate"], layer["w_up"],
                               layer["w_down"]), layer["mlp_norm"], cfg["eps"])
        return rms(x, params["final_norm"], cfg["eps"]) @ params["head"]


def loss_parts(params, tokens, cfg):
    """(loss, logits): the mean next-token cross entropy."""
    logits = forward(params, tokens, cfg)
    targets = jnp.roll(tokens, -1, axis=1)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(nll), logits
