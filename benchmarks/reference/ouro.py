"""Ouro-2.6B (Hugging Face ``ByteDance/Ouro-2.6B``, ``model_type`` ``ouro``;
arXiv:2510.25741, "Scaling Latent Reasoning via Looped Language Models") in
plain ``jax.numpy``: forward, the expected loss over the exit distribution
with its entropy term, and gradients.

Everything is float32 under ``jax.default_matmul_precision("highest")``;
attention is the dense ``T x T`` form - no kernel, no chunked loss, no
``custom_vjp``, no flax, nothing from ``horovod_tpu``. The loop is four
Python passes over ONE parameter tree: ``jax.grad`` sums a shared leaf's
gradient over the passes by itself.

Hidden states ``x`` (B, T, D); no projection has a bias; ``rms`` has eps
``cfg["eps"]`` and a learned weight.

* ``x_0 = E[tokens]``.
* One layer, the sandwich norm (four RMSNorms): ``x = x + rms(Attn(rms(x)))``;
  ``x = x + rms(Wd (silu(Wg h) * Wu h))`` with ``h = rms(x)``.
* ``Attn``: ``q, k, v = h Wq, h Wk, h Wv`` (D -> H d each); q and k rotated a
  head at base ``cfg["theta"]`` on pairs ``(i, i + d / 2)``; scores ``q k^T *
  d^-0.5``, causal over every earlier key, softmax; out ``= concat(P v) Wo``.
* The loop, passes ``t = 1 .. cfg["passes"]`` over the SAME layers: ``x^(t) =
  rms_f(Stack(x^(t-1)))``, ``x^(0) = x_0``: the model's ONE final norm closes
  every pass and its output enters the next. After pass t: ``logits_t =
  x^(t) H`` and ``lambda_t = sigmoid(x^(t) w_e + b_e)``.
* The exit distribution a token: ``p_t = lambda_t prod_{j<t} (1 - lambda_j)``
  for ``t < T``; ``p_T = prod_{j<T} (1 - lambda_j)``. ``lambda_T`` is
  computed and read by nothing.
* ``L = mean over tokens of [sum_t p_t CE_t - beta H(p)]``, ``CE_t`` the
  next-token cross entropy of ``logits_t``, ``H(p) = -sum_t p_t log p_t``.

Departures from the published code, each with its reason:

* The released code is the inference path (it stops at the first pass whose
  cumulated exit mass reaches ``early_exit_threshold``; at the published 1
  every token runs every pass): this is the training objective of the
  paper's first stage over all passes.
* The layers handed in are the configuration's cut (the model's first
  ``layers``); nothing stands in for the absent ones.
* The target of the last position is the first token (``roll`` by one), the
  convention of this repo's other language-model losses.
* Every layer application and every pass's reading run under
  ``jax.checkpoint``, so that the gradient of 32 applications at 2,048 tokens
  fits beside the trainer's state: bookkeeping, the same arithmetic.
* ``cfg["scan_passes"]`` (the benchmark's check on the chip sets it) runs
  the passes as the steps of a ``lax.scan`` whose body is the one Python
  pass: unrolled, the float32 program of 32 applications and their backward
  is 1.16 GiB of CODE for a v5e and takes minutes to compile; scanned it is a
  quarter of both. The same arithmetic in the same order
  (``tests/benchmark/test_benchmark_ouro.py`` holds the two forms equal).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def rms(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def rotate(x, theta):
    """(B, T, H, d): pairs ``(i, i + d / 2)`` turned by ``position *
    theta^(-2 i / d)``."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    angles = jnp.arange(x.shape[1], dtype=F32)[:, None] * freqs     # (T, half)
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    lo, hi = x[..., :half], x[..., half:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], -1)


def attention(layer, h, cfg):
    b, t, _ = h.shape
    shape = (b, t, cfg["heads"], cfg["head_dim"])
    q = rotate((h @ layer["wq"]).reshape(shape), cfg["theta"])
    k = rotate((h @ layer["wk"]).reshape(shape), cfg["theta"])
    v = (h @ layer["wv"]).reshape(shape)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * cfg["head_dim"] ** -0.5
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(b, t, -1) @ layer["wo"]


def swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def layer_forward(layer, x, cfg):
    eps = cfg["eps"]
    x = x + rms(attention(layer, rms(x, layer["attn_in_norm"], eps), cfg),
                layer["attn_out_norm"], eps)
    return x + rms(swiglu(rms(x, layer["mlp_in_norm"], eps), layer["w_gate"],
                          layer["w_up"], layer["w_down"]),
                   layer["mlp_out_norm"], eps)


def read(params, x, targets):
    """One pass's reading of the normed stream: ``(logits, lambda, CE)``."""
    logits = x @ params["head"]
    gate = jax.nn.sigmoid(x @ params["gate_w"] + params["gate_b"][0])
    ce = -jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                              targets[..., None], axis=-1)[..., 0]
    return logits, gate, ce


def forward(params, tokens, cfg):
    """``(logits (P, B, T, V), gates (P, B, T), CE (P, B, T))``: every pass's
    reading of the one head, every pass's ``lambda``, and every pass's
    next-token cross entropy a position. The passes are a Python loop over
    the one parameter tree; with ``cfg["scan_passes"]`` the SAME pass is the
    body of a ``lax.scan`` of ``cfg["passes"]`` steps instead (the program a
    quarter as large: see the departures above)."""
    x = params["embed"][tokens].astype(F32)
    targets = jnp.roll(tokens, -1, axis=1)
    one_layer = jax.checkpoint(lambda layer, x: layer_forward(layer, x, cfg))

    def one_pass(x, _=None):
        for layer in params["layers"]:
            x = one_layer(layer, x)
        x = rms(x, params["final_norm"], cfg["eps"])
        return x, jax.checkpoint(read)(params, x, targets)

    if cfg.get("scan_passes"):
        return jax.lax.scan(one_pass, x, None, length=cfg["passes"])[1]
    readings = []
    for _ in range(cfg["passes"]):
        x, reading = one_pass(x)
        readings.append(reading)
    return tuple(jnp.stack(part) for part in zip(*readings))


def exit_distribution(gates):
    """(P, ...) from the passes' ``lambda`` (P, ...)."""
    stayed = jnp.ones_like(gates[0])
    p = []
    for t in range(gates.shape[0] - 1):
        p.append(gates[t] * stayed)
        stayed = stayed * (1.0 - gates[t])
    return jnp.stack(p + [stayed])


def loss_parts(params, tokens, cfg):
    """``(L, (logits, gates, p))``."""
    logits, gates, ce = forward(params, tokens, cfg)
    p = exit_distribution(gates)
    entropy = -jnp.sum(p * jnp.log(p), axis=0)
    loss = jnp.mean(jnp.sum(p * ce, axis=0) - cfg["beta"] * entropy)
    return loss, (logits, gates, p)


def loss_and_grads(params, tokens, cfg):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss_parts, has_aux=True)(params, tokens, cfg)
