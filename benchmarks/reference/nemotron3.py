"""Nemotron-3-Super-120B-A12B (Hugging Face
``nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16``, ``model_type``
``nemotron_h``; the Mamba-2 layer of Dao & Gu, arXiv:2405.21060 §6-7; routing
and multi-token prediction as DeepSeek-V3's, arXiv:2412.19437 §2.1.2 / §2.2)
in plain ``jax.numpy``: forward, both loss terms, gradients and the
router-bias rule, for one tensor- / expert-parallel rank's share.

Everything is float32 under ``jax.default_matmul_precision("highest")``; the
state-space layer is computed by its definition, a step per token; each held
expert is applied to EVERY token and masked by ``chosen`` - no chunks, no sort,
no gather into expert order, no kernel, no flax, nothing from ``horovod_tpu``.
``benchmarks/reference/nemotron3.py`` is a copy of this file (a test holds the
two to the same outputs), so that the benchmark brings its own.

Hidden states ``x`` (B, T, D); no matrix has a bias, the convolution has one;
``rms`` has eps ``cfg["eps"]`` and a learned weight. ``x = E[tokens]``, then
per layer, by the letters of ``cfg["layer_types"]`` (the published
``hybrid_override_pattern``), every layer ONE sub-layer ``x = x + f(rms(x))``:

* ``"M"``: ``[z | xBC | dt] = h W_in`` (widths H*P | H*P + 2*G*N | H);
  ``xBC = silu(conv(xBC))``, causal and depthwise with bias; ``xBC`` splits
  into ``u`` (H heads x P), ``B`` and ``C`` (G groups x N each). Per head:
  ``dt_t = softplus(dt_t + dt_bias)``, ``a_t = exp(dt_t * A)`` with ``A =
  -exp(A_log)``, ``S_t = a_t S_{t-1} + dt_t u_t B_t^T``, ``y_t = S_t C_t + D
  u_t`` (:func:`ssm_recurrence`); ``y = rms(y * silu(z))`` over each group's
  H*P/G features (the GATE FIRST); ``f = y W_out``.
* ``"*"``: ``q = h Wq`` (heads x Dh), ``k = h Wk``, ``v = h Wv`` (kv_heads x
  Dh); NO rotary embedding; causal softmax attention, scores times
  ``Dh^-0.5``, query head ``i`` reading key/value head ``i // (heads /
  kv_heads)``; ``f = attn Wo``.
* ``"E"`` (LatentMoE): ``s = sigmoid(h Wr)`` (T, E) from the FULL hidden
  state; ``chosen`` = the ``top_k`` largest of ``s + b``; ``w = s[chosen]``,
  ``w = route_scale * w / (sum(w) + 1e-20)``; ``l = h W_fc1`` (the latent);
  ``r = sum_{e chosen AND held} w_e down_e(relu(up_e l)^2)``;
  ``f = r W_fc2 + W_sd relu(W_su h)^2`` (the shared expert reads ``h``, not
  the latent). ``held = (first, count)``: the weights handed in are those of
  experts ``[first, first + count)`` alone; ``w`` is still normalised over
  all ``top_k``. ``b`` receives no gradient.
* Final ``rms``; logits = ``x H`` over the rows of the vocabulary handed in.
* Multi-token prediction, depth 1 (``params["mtp"]``): with ``x`` the main
  model's last residual stream (before its final ``rms``),
  ``h' = W_eh [rms(x_t) ; rms(E[token_{t+1}])]`` (2D -> D), through the layers
  ``cfg["mtp_layer_types"]`` (each as above, with parameters of its own), a
  final ``rms``, then the SAME ``H``: position t predicts token t + 2.
* Loss = ``L_main + mtp_weight * L_mtp``, each a mean cross entropy.
* After a step, per expert layer (the module's too): ``c_e`` = pairs routed
  to expert ``e`` (all E); ``b_e += rate * sign(mean(c) - c_e)``
  (:func:`bias_update`).

A rank's share of the heads is a smaller model of the same equations: 16 of
128 Mamba heads with 1 of 8 groups, 4 of 32 query heads over 1 of 2 key/value
heads, 672 of 5,376 shared-expert columns. What ``W_out``, ``Wo`` and ``W_sd``
then give is the rank's PARTIAL sum; nothing here stands in for the other
ranks' parts.

:func:`ssm_quadratic` is the second form of the state-space layer,
``y = (L o C B^T)(dt u) + D u``: the same numbers without a saved state per
token. ``loss_parts(..., ssm=ssm_quadratic)`` takes gradients through it; a
test holds the two forms to each other.

Departures from the published code, each with its reason:

* Targets wrap round the row's end (``roll`` by one, and by two for the
  module), the convention of this repo's other losses; a data loader would
  mask them.
* The convolution's weight is stored (K, C), the transpose of torch's
  ``Conv1d`` (C, 1, K): the same numbers, laid out for broadcasting.
* Hugging Face clamps ``dt`` to ``time_step_limit`` = (0, inf) after the
  softplus: no change to any value, left out.
* The chosen are found by a threshold at the ``top_k``-th largest ``s + b``
  (a mask), not by ``top_k``'s indices; equal with probability 1 on
  continuous values. ``forced``, where given, IS the chosen set.
* ``config.json`` has ``rope_theta`` and ``partial_rotary_factor``; the
  ``nemotron_h`` modelling code applies no rotary embedding: none here.
* Each expert's term is wrapped in ``jax.checkpoint``: the same arithmetic,
  recomputed in the backward pass.
* The module reads the residual stream BEFORE the main model's final norm
  (it has a norm of its own for it), and its weight 0.3 in the loss is
  arXiv:2412.19437 §4.2's first value: ``config.json`` is silent on both.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def rms(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def relu2(x):
    return jnp.square(jax.nn.relu(x))


# ------------------------------------------------------------- "M": Mamba-2

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


# ----------------------------------------------------------- "*": attention

def attention(h, layer, cfg):
    b, t, _ = h.shape
    heads, kv = cfg["heads"], cfg["kv_heads"]
    q = (h @ layer["wq"]).reshape(b, t, heads, -1)
    k = (h @ layer["wk"]).reshape(b, t, kv, -1)
    v = (h @ layer["wv"]).reshape(b, t, kv, -1)
    k, v = (jnp.repeat(x, heads // kv, axis=2) for x in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, t, -1) @ layer["wo"]


# ------------------------------------------------------------ "E": LatentMoE

@jax.checkpoint
def expert_term(latent, weight, w_up, w_down):
    """One expert on EVERY row of the latent, times that row's weight for it
    (0 where the expert was not chosen)."""
    return weight[:, None] * (relu2(latent @ w_up) @ w_down)


def route(h, router, bias, cfg, forced=None):
    """(weights (N, E), 0 where not chosen; chosen (N, E); the router's own
    choice (N, E)). ``forced`` (N, E), where given, IS the chosen set: a
    caller whose own choice differs on a token whose ``top_k``-th and next
    ``s + b`` are tied within rounding compares the rest of the arithmetic
    under its choice, and reads in the third what this router would have
    picked from the same input."""
    n_experts, top_k = router.shape[1], cfg["top_k"]
    s = jax.nn.sigmoid(h @ router)
    biased = s + jax.lax.stop_gradient(bias)
    kth = jnp.sort(biased, axis=-1)[:, n_experts - top_k]
    own = biased >= kth[:, None]                        # (N, E), top_k a row
    chosen = own if forced is None else forced
    w = jnp.where(chosen, s, 0.0)
    return (cfg["route_scale"] * w / (jnp.sum(w, axis=-1, keepdims=True)
                                      + 1e-20), chosen, own)


def latent_experts(h, layer, bias, cfg, forced=None):
    """h: (N, D). The held experts' part of the routed sum, through the
    latent, plus the shared expert. Returns (f(h), router stats)."""
    first, count = cfg["held"]
    weights, chosen, own = route(h, layer["router"], bias, cfg, forced)
    latent = h @ layer["w_fc1"]

    def step(r, per_expert):
        return r + expert_term(latent, *per_expert), None

    r, _ = jax.lax.scan(step, jnp.zeros_like(latent),
                        (weights.T[first:first + count], layer["w_up"],
                         layer["w_down"]))
    y = r @ layer["w_fc2"] + relu2(h @ layer["s_up"]) @ layer["s_down"]
    return y, {"chosen": chosen, "own_chosen": own,
               "counts": jnp.sum(chosen, axis=0, dtype=jnp.int32)}


# ------------------------------------------------------------------ the model

def _layers(x, kinds, layers, biases, forced, cfg, ssm, stats):
    """``x`` through ``layers`` of ``kinds``; ``biases`` and ``forced`` are
    iterators over the expert layers', in order."""
    for kind, layer in zip(kinds, layers, strict=True):
        h = rms(x, layer["norm"], cfg["eps"])
        if kind == "M":
            x = x + mamba_mixer(h, layer, cfg, ssm)
        elif kind == "*":
            x = x + attention(h, layer, cfg)
        elif kind == "E":
            y, s = latent_experts(h.reshape(-1, h.shape[-1]), layer,
                                  next(biases), cfg, next(forced))
            x = x + y.reshape(x.shape)
            stats.append(s)
        else:
            raise ValueError(f"layer kind {kind!r}: 'M', '*' or 'E'")
    return x


def forward(params, biases, tokens, cfg, ssm=ssm_recurrence, forced=None):
    """tokens: (B, T) ints; ``biases``: one (E,) per expert layer, the main
    model's in order, then the module's; ``forced``: None, or one chosen set
    (B T, E) per expert layer (:func:`route`). Returns (logits (B, T, V),
    the module's logits (B, T, V), [stats of each expert layer])."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(lambda p: p.astype(F32), params)
        n_expert_layers = (cfg["layer_types"] + cfg["mtp_layer_types"]).count("E")
        stats, biases = [], iter(biases)
        forced = iter(forced if forced is not None
                      else [None] * n_expert_layers)
        x = _layers(params["embed"][tokens], cfg["layer_types"],
                    params["layers"], biases, forced, cfg, ssm, stats)
        logits = rms(x, params["final_norm"], cfg["eps"]) @ params["head"]
        mtp = params["mtp"]
        following = params["embed"][jnp.roll(tokens, -1, axis=1)]
        y = jnp.concatenate([rms(x, mtp["hidden_norm"], cfg["eps"]),
                             rms(following, mtp["embed_norm"], cfg["eps"])],
                            axis=-1) @ mtp["proj"]
        y = _layers(y, cfg["mtp_layer_types"], mtp["layers"], biases, forced,
                    cfg, ssm, stats)
        return (logits, rms(y, mtp["norm"], cfg["eps"]) @ params["head"],
                stats)


def cross_entropy(logits, targets):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None],
                                         axis=-1)[..., 0])


def loss_parts(params, biases, tokens, cfg, ssm=ssm_recurrence, forced=None):
    """(``L_main + mtp_weight * L_mtp``, {"main", "mtp", "logits",
    "mtp_logits", "chosen", "own_chosen", "counts"}): both loss terms, both
    sets of logits, and per expert layer what the router did."""
    logits, mtp_logits, stats = forward(params, biases, tokens, cfg, ssm, forced)
    main = cross_entropy(logits, jnp.roll(tokens, -1, axis=1))
    mtp = cross_entropy(mtp_logits, jnp.roll(tokens, -2, axis=1))
    return main + cfg["mtp_weight"] * mtp, {
        "main": main, "mtp": mtp, "logits": logits, "mtp_logits": mtp_logits,
        **{k: [s[k] for s in stats]
           for k in ("chosen", "own_chosen", "counts")}}


def loss_and_grads(params, biases, tokens, cfg, ssm=ssm_quadratic, forced=None):
    """((loss, parts), gradients of the loss in ``params``' layout); through
    the quadratic form by default, which saves no state per token."""
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            lambda p: loss_parts(p, biases, tokens, cfg, ssm, forced),
            has_aux=True)(params)


def bias_update(bias, counts, rate):
    """``b_e += rate * sign(mean(c) - c_e)``: an expert with more than the
    mean load is chosen less from the next step on."""
    counts = counts.astype(F32)
    return bias + rate * jnp.sign(jnp.mean(counts) - counts)


def init_params(key, cfg, scale=0.02):
    """Seeded float32 parameters in this file's own layout (normal, ``scale``;
    norm weights around 1 so that their gradients are exercised; ``A_log``,
    ``dt_bias`` and ``D`` in Mamba-2's ranges) and zero biases, for the sizes
    of ``cfg`` (besides the forward's keys: ``hidden``, ``vocab``,
    ``experts``, ``latent``, ``expert_width``, ``shared_width``, ``conv``):
    the expert layers hold ``cfg["held"][1]`` experts' weights."""
    d, vocab = cfg["hidden"], cfg["vocab"]
    kinds = cfg["layer_types"] + cfg["mtp_layer_types"]
    keys = iter(jax.random.split(key, 8 + 12 * len(kinds)))

    def normal(shape, mean=0.0):
        return mean + scale * jax.random.normal(next(keys), shape, F32)

    def layer(kind):
        out = {"norm": normal((d,), 1.0)}
        if kind == "M":
            h, p, g, n = (cfg[k] for k in ("mamba_heads", "mamba_head_dim",
                                           "mamba_groups", "mamba_state"))
            inner, channels = h * p, h * p + 2 * g * n
            out.update(
                w_in=normal((d, inner + channels + h)),
                conv_w=normal((cfg["conv"], channels)) * 10,
                conv_b=normal((channels,)),
                dt_bias=jnp.log(jnp.expm1(jnp.exp(jax.random.uniform(
                    next(keys), (h,), F32, jnp.log(1e-3), jnp.log(1e-1))))),
                A_log=jnp.log(jax.random.uniform(next(keys), (h,), F32, 1.0,
                                                 16.0)),
                D=normal((h,), 1.0), gate_norm=normal((inner,), 1.0),
                w_out=normal((inner, d)))
        elif kind == "*":
            heads, kv, dh = cfg["heads"], cfg["kv_heads"], cfg["head_dim"]
            out.update(wq=normal((d, heads * dh)), wk=normal((d, kv * dh)),
                       wv=normal((d, kv * dh)), wo=normal((heads * dh, d)))
        else:
            e, count = cfg["experts"], cfg["held"][1]
            lat, w, ws = (cfg[k] for k in ("latent", "expert_width",
                                           "shared_width"))
            out.update(router=normal((d, e)), w_fc1=normal((d, lat)),
                       w_up=normal((count, lat, w)),
                       w_down=normal((count, w, lat)), w_fc2=normal((lat, d)),
                       s_up=normal((d, ws)), s_down=normal((ws, d)))
        return out

    params = {"embed": normal((vocab, d)),
              "layers": [layer(k) for k in cfg["layer_types"]],
              "final_norm": normal((d,), 1.0), "head": normal((d, vocab)),
              "mtp": {"hidden_norm": normal((d,), 1.0),
                      "embed_norm": normal((d,), 1.0),
                      "proj": normal((2 * d, d)),
                      "layers": [layer(k) for k in cfg["mtp_layer_types"]],
                      "norm": normal((d,), 1.0)}}
    return params, [jnp.zeros((cfg["experts"],), F32)
                    for _ in range(kinds.count("E"))]
