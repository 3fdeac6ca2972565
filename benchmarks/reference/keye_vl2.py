"""Keye-VL-2.0-30B-A3B's language model (Hugging Face
``Kwai-Keye/Keye-VL-2.0-30B-A3B``, ``model_type`` ``KeyeVL2``) in plain
``jax.numpy``: forward, the three loss terms and gradients.

Everything is float32 under ``jax.default_matmul_precision("highest")``; the
indexer's (T x T) scores and every head's (T x T) attention exist whole, the
selection is ``lax.top_k``, each expert is applied to EVERY token and masked
by ``chosen`` - no tile, no packed mask, no sort, no kernel, no flax, nothing
from ``horovod_tpu``.

Hidden states ``x`` (B, T, D); no matrix has a bias; ``rms`` has eps
``cfg["eps"]`` and a learned weight. ``positions`` (3, B, T): the temporal,
height and width streams (a text row's are equal). Every layer:

* ``h = rms(x)``; ``q = h Wq`` -> (T, H, hd), ``k = h Wk``, ``v = h Wv`` ->
  (T, Hkv, hd); ``rms`` with a weight of ``hd`` over EACH head of q and of k;
  rotary at ``cfg["theta"]`` over all ``hd`` dimensions, pairs ``(i, i + hd /
  2)``, pair ``i`` turned by the stream of its section (``cfg["sections"]``:
  the first 16 pairs temporal, the next 24 height, the last 24 width).
* Indexer, on ``hd = stop_gradient(h)``: ``qI = rot(hd WqI)`` -> (T, Hi, di),
  ``kI = rot(layer_norm(hd WkI))`` -> (T, di), ONE head that all ``Hi`` share
  (LayerNorm with weight and bias, eps 1e-6; both rotated over all ``di``
  dimensions by the temporal stream), ``w = hd WwI x Hi^-0.5 di^-0.5``;
  ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])``.
* ``S_t`` = the ``topk`` largest ``I[t, s]`` over ``s <= t`` (all of them
  while ``t < topk``), ``lax.top_k``: ties to the lower position. No gradient.
* Head ``a`` over key/value head ``a // group``: ``P[t, a, .] = softmax over
  S_t of q_a . k x hd^-0.5``; ``o = P v``; ``x = x + concat(o) Wo``.
* ``L_I = mean_t sum_{s in S_t} p (ln p - ln r)`` with ``p = stop_gradient(
  mean_a P)`` and ``r = softmax over S_t of I``.
* ``h2 = rms(x)``; ``probs = softmax(h2 Wr)`` (E); ``chosen`` = the ``top_k``
  largest; ``w = probs[chosen] / sum(probs[chosen])``; ``x = x + sum_{e chosen
  AND held} w_e down_e(silu(gate_e h2) * up_e h2)``. ``held = (first,
  count)``: the weights handed in are those of experts ``[first, first +
  count)`` alone. ``L_B = E sum_e f_e P_e``, ``f_e`` the share of the N x top_k
  assignments on expert e (no gradient), ``P_e`` the mean probability.

Final ``rms``; logits = ``x H`` over the rows of the vocabulary handed in.
Loss = mean next-token cross entropy + ``cfg["align_weight"]`` x sum of
``L_I`` + ``cfg["balance_weight"]`` x sum of ``L_B`` over the layers.

What the published ``config.json`` does not fix, and how it is settled here
(the configuration file's ``assumed`` says the same): per-head RMSNorm on q
and k (the Qwen3-MoE class's attention); the indexer reads the normed hidden
state; LayerNorm with bias on its key and the weights' scale, as
DeepSeek-V3.2's released indexer; its rotary over all 64 dimensions by the
temporal stream; selection per query token; no FP8, no Hadamard rotation.

Departures from what Hugging Face's decoder models do, each with its reason:

* The target of the last position is the first token (``roll`` by one), the
  convention of this repo's other language-model losses.
* The chosen experts are found by a threshold at the ``top_k``-th largest
  probability (a mask), not by ``top_k``'s indices; equal with probability 1
  on continuous values. The selection IS ``lax.top_k``'s indices, scattered
  into a mask.
* ``selection(forced=)`` and ``route(forced=)``: a caller whose own choice
  differs on a float32 tie compares the rest of the arithmetic under its
  choice.
* Heads, indexer heads and experts are scanned under ``jax.checkpoint``: the
  same arithmetic, recomputed in the backward pass, so that 32 heads of 4096 x
  4096 scores fit the chip beside the parameters.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def rms(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def layer_norm(x, weight, bias, eps=1e-6):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * weight + bias


def rotary(x, positions, theta, sections=None):
    """x: (B, T, H, hd); pairs (i, i + hd / 2). ``positions`` (3, B, T); pair
    ``i`` is turned by ``positions[stream(i)] * theta^(-2i/hd)``, the stream
    by ``sections`` (None: the first, temporal, for every pair)."""
    half = x.shape[-1] // 2
    inv_freq = jnp.asarray(theta ** (-np.arange(0, 2 * half, 2, dtype=np.float64)
                                     / (2 * half)), F32)
    stream = (np.zeros(half, np.int64) if sections is None
              else np.repeat(np.arange(3), sections))
    pos = jnp.moveaxis(positions.astype(F32), 0, -1)[..., stream]  # (B, T, half)
    angles = pos * inv_freq
    cos, sin = jnp.cos(angles)[:, :, None, :], jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def index_scores(q_index, k_index, weights):
    """(B, T, T): ``sum_j w[t, j] relu(qI[t, j] . kI[s])``, a head at a time."""
    @jax.checkpoint
    def one_head(total, head):
        qj, wj = head                               # (B, T, di), (B, T)
        z = jnp.einsum("btd,bsd->bts", qj, k_index)
        return total + wj[..., None] * jax.nn.relu(z), None

    b, t = weights.shape[:2]
    total, _ = jax.lax.scan(one_head, jnp.zeros((b, t, t), F32),
                            (jnp.moveaxis(q_index, 2, 0),
                             jnp.moveaxis(weights, 2, 0)))
    return total


def selection(scores, topk, forced=None):
    """(B, T, T) booleans: of each query ``t`` the ``topk`` keys ``s <= t``
    with the largest score (``lax.top_k``: ties to the lower position), all
    of them while ``t < topk``. ``forced``, where given, IS the selection."""
    if forced is not None:
        return forced
    b, t, _ = scores.shape
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    _, best = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), min(topk, t))
    picked = jnp.zeros((b, t, t), bool).at[
        jnp.arange(b)[:, None, None], jnp.arange(t)[None, :, None], best].set(True)
    return picked & causal


def attention(layer, h, positions, cfg, forced=None):
    """-> (the layer's output through Wo, alignment loss, selection)."""
    b, t, _ = h.shape
    heads, kv_heads, hd = cfg["heads"], cfg["kv_heads"], cfg["head_dim"]
    hi, di = cfg["index_heads"], cfg["index_dim"]
    group = heads // kv_heads
    q = rms((h @ layer["wq"]).reshape(b, t, heads, hd), layer["q_norm"], cfg["eps"])
    k = rms((h @ layer["wk"]).reshape(b, t, kv_heads, hd), layer["k_norm"],
            cfg["eps"])
    q, k = (rotary(x, positions, cfg["theta"], cfg["sections"]) for x in (q, k))
    v = (h @ layer["wv"]).reshape(b, t, kv_heads, hd)

    hd_ = jax.lax.stop_gradient(h)
    q_index = rotary((hd_ @ layer["index_q"]).reshape(b, t, hi, di), positions,
                     cfg["theta"])
    k_index = rotary(layer_norm(hd_ @ layer["index_k"], layer["index_k_norm_w"],
                                layer["index_k_norm_b"])[:, :, None, :],
                     positions, cfg["theta"])[:, :, 0]
    weights = (hd_ @ layer["index_w"]) * (hi ** -0.5 * di ** -0.5)
    scores = index_scores(q_index, k_index, weights)
    sel = selection(jax.lax.stop_gradient(scores), cfg["topk"], forced)

    def probabilities(qa, ka):                                  # (B, T, hd)
        s = jnp.einsum("bqd,bkd->bqk", qa, ka) * hd ** -0.5
        return jax.nn.softmax(jnp.where(sel, s, -jnp.inf), axis=-1)

    @jax.checkpoint
    def one_head(qkv):
        qa, ka, va = qkv
        return jnp.einsum("bqk,bkd->bqd", probabilities(qa, ka), va)

    def by_head(x, repeat=1):       # (B, T, n, hd) -> (n * repeat, B, T, hd)
        return jnp.repeat(jnp.moveaxis(x, 2, 0), repeat, axis=0)

    o = jax.lax.map(one_head, (by_head(q), by_head(k, group), by_head(v, group)))
    out = jnp.moveaxis(o, 0, 2).reshape(b, t, heads * hd) @ layer["wo"]

    # the alignment loss: p is a constant of it
    mean_p, _ = jax.lax.scan(
        lambda total, qk: (total + probabilities(*qk) / heads, None),
        jnp.zeros((b, t, t), F32),
        jax.lax.stop_gradient((by_head(q), by_head(k, group))))
    log_r = jax.nn.log_softmax(jnp.where(sel, scores, -jnp.inf), axis=-1)
    kept = sel & (mean_p > 0)
    terms = jnp.where(kept, mean_p * (jnp.log(jnp.where(kept, mean_p, 1.0))
                                      - jnp.where(kept, log_r, 0.0)), 0.0)
    return out, jnp.mean(jnp.sum(terms, axis=-1)), sel


def swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


@jax.checkpoint
def expert_term(h2, weight, w_gate, w_up, w_down):
    """One expert on EVERY row, times that row's weight for it (0 where the
    expert was not chosen)."""
    return weight[:, None] * swiglu(h2, w_gate, w_up, w_down)


def route(h2, router, cfg, forced=None):
    """(weights (N, E), 0 where not chosen; chosen (N, E); probs (N, E)).
    ``forced`` (N, E), where given, IS the chosen set."""
    n_experts, top_k = router.shape[1], cfg["top_k"]
    probs = jax.nn.softmax(h2 @ router, axis=-1)
    kth = jnp.sort(probs, axis=-1)[:, n_experts - top_k]
    chosen = probs >= kth[:, None]                      # (N, E), top_k a row
    if forced is not None:
        chosen = forced
    w = jnp.where(chosen, probs, 0.0)
    return w / jnp.sum(w, axis=-1, keepdims=True), chosen, probs


def experts(layer, h2, cfg, forced=None):
    """h2: (N, D). The held experts' part of the routed sum. Returns (y,
    balancing loss, router stats)."""
    first, count = cfg["held"]
    weights, chosen, probs = route(h2, layer["router"], cfg, forced)

    def step(y, per_expert):
        weight, w_gate, w_up, w_down = per_expert
        return y + expert_term(h2, weight, w_gate, w_up, w_down), None

    y, _ = jax.lax.scan(step, jnp.zeros_like(h2),
                        (weights.T[first:first + count], layer["w_gate"],
                         layer["w_up"], layer["w_down"]))
    counts = jnp.sum(chosen, axis=0, dtype=jnp.int32)
    share = jax.lax.stop_gradient(counts.astype(F32) / jnp.sum(counts))
    balance = probs.shape[-1] * jnp.sum(share * jnp.mean(probs, axis=0))
    return y, balance, {"chosen": chosen, "counts": counts}


def forward(params, tokens, cfg, positions=None, forced=None):
    """tokens: (B, T) ints; ``positions`` (3, B, T) (None: a text row's, all
    three ``arange(T)``); ``forced``: None, or ``{"selection": [(B, T, T) a
    layer], "experts": [(B T, E) a layer]}``, either of which may be None.
    Returns (logits (B, T, V), {"align", "balance": the summed loss terms,
    "selection", "chosen", "counts": a list a layer})."""
    with jax.default_matmul_precision("highest"):
        b, t = tokens.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(t), (3, b, t))
        forced = forced or {}
        n = len(params["layers"])
        x = params["embed"][tokens]
        out = {"align": 0.0, "balance": 0.0, "selection": [], "chosen": [],
               "counts": []}
        for layer, sel, chose in zip(params["layers"],
                                     forced.get("selection") or [None] * n,
                                     forced.get("experts") or [None] * n):
            y, align, picked = attention(
                layer, rms(x, layer["attn_norm"], cfg["eps"]), positions, cfg,
                sel)
            x = x + y
            h2 = rms(x, layer["mlp_norm"], cfg["eps"])
            y, balance, stats = experts(layer, h2.reshape(-1, h2.shape[-1]),
                                        cfg, chose)
            x = x + y.reshape(x.shape)
            out["align"] = out["align"] + align
            out["balance"] = out["balance"] + balance
            out["selection"].append(picked)
            out["chosen"].append(stats["chosen"])
            out["counts"].append(stats["counts"])
        return rms(x, params["final_norm"], cfg["eps"]) @ params["head"], out


def loss_parts(params, tokens, cfg, positions=None, forced=None):
    """(loss, {"lm", "align", "balance", "logits", "selection", "chosen",
    "counts"}): the training loss and its three terms."""
    logits, out = forward(params, tokens, cfg, positions, forced)
    targets = jnp.roll(tokens, -1, axis=1)
    logp = jax.nn.log_softmax(logits, axis=-1)
    lm = jnp.mean(-jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0])
    loss = (lm + cfg["align_weight"] * out["align"]
            + cfg["balance_weight"] * out["balance"])
    return loss, {"lm": lm, "logits": logits, **out}


def loss_and_grads(params, tokens, cfg, positions=None, forced=None):
    """((loss, parts), gradients of the loss with respect to ``params``)."""
    return jax.jit(lambda p, t, pos, f: jax.value_and_grad(
        loss_parts, has_aux=True)(p, t, cfg, pos, f))(params, tokens, positions,
                                                      forced)


def init_params(key, cfg, scale=0.02):
    """Seeded float32 parameters in this file's own layout (normal, ``scale``;
    norm weights around 1 and the indexer key's bias around 0, so that their
    gradients are exercised): ``cfg["layers"]`` layers, each holding
    ``cfg["held"][1]`` experts' weights."""
    d, hd, heads, kv = (cfg[k] for k in ("hidden", "head_dim", "heads",
                                         "kv_heads"))
    hi, di, e, w = (cfg[k] for k in ("index_heads", "index_dim", "experts",
                                     "expert_width"))
    count, vocab = cfg["held"][1], cfg["vocab"]
    keys = iter(jax.random.split(key, 3 + 20 * cfg["layers"]))

    def normal(shape, mean=0.0):
        return mean + scale * jax.random.normal(next(keys), shape, F32)

    def layer():
        return {"attn_norm": normal((d,), 1.0), "mlp_norm": normal((d,), 1.0),
                "wq": normal((d, heads * hd)), "wk": normal((d, kv * hd)),
                "wv": normal((d, kv * hd)), "q_norm": normal((hd,), 1.0),
                "k_norm": normal((hd,), 1.0), "wo": normal((heads * hd, d)),
                "index_q": normal((d, hi * di)), "index_k": normal((d, di)),
                "index_k_norm_w": normal((di,), 1.0),
                "index_k_norm_b": normal((di,)), "index_w": normal((d, hi)),
                "router": normal((d, e)), "w_gate": normal((count, d, w)),
                "w_up": normal((count, d, w)), "w_down": normal((count, w, d))}

    return {"embed": normal((vocab, d)),
            "layers": [layer() for _ in range(cfg["layers"])],
            "final_norm": normal((d,), 1.0), "head": normal((d, vocab))}
