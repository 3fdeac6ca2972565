"""Mixture-of-experts with expert parallelism (EP) over a mesh axis.

Beyond the reference's scope (Horovod v0.16 is data-parallel only, SURVEY.md
§2.8) but first-class on TPU: experts shard across the ``ep`` axis and
tokens reach their expert through a single ``lax.all_to_all`` each way — the
canonical Switch-Transformer dispatch expressed as XLA collectives instead
of a runtime router.

Design (top-1 / switch routing, capacity-bounded, drop-on-overflow):

1. Each rank routes its LOCAL tokens: softmax gate → argmax expert, position
   within that expert's per-rank capacity C via a cumulative count; tokens
   beyond capacity are dropped (contribute zero, standard switch behavior).
2. Dispatch buffer (E, C, D) scatter-filled from kept tokens, viewed as
   (ep, E_local, C, D) and exchanged with ``all_to_all``: afterwards each
   rank holds, for each of ITS E_local experts, up to C tokens from every
   rank.
3. Local experts run as one batched einsum over the stacked expert weights
   (the MXU sees one big matmul, not a Python loop over experts).
4. The inverse ``all_to_all`` returns expert outputs to the owning ranks;
   tokens gather their row back and scale by the gate probability.

Everything is shape-static (capacity fixes the buffers), so the whole layer
jits into one program — no host round-trips, no dynamic shapes.

The second routing, beside the switch one (OLMoE, arXiv:2409.02060): softmax,
then the ``top_k`` largest probabilities as weights, NOT renormalised, and no
capacity - every chosen (token, expert) pair is computed, under any
imbalance, at static shapes (:func:`topk_route`, :func:`dropless_experts`):

1. the N x top_k pairs are sorted by expert (stable, so a token's rows keep
   their order inside an expert's group) and the tokens gathered into that
   order: N x top_k rows whatever the imbalance, only the group sizes vary;
2. the three SwiGLU products run as grouped products over the ragged groups
   (``jax.lax.ragged_dot``: on the TPU libtpu lowers it to a Mosaic grouped
   matmul that walks row tiles group by group, not to a masked dense product
   - PERF.md, PR 26);
3. the rows go back to token order through the inverse permutation and are
   summed with their weights.

Both permutations are gathers in the forward AND the backward pass
(:func:`_take_rows`): the transpose of a gather is a scatter-add, and the
inverse permutation is at hand, so the backward gathers through it instead.
This form holds all experts on the rank that holds the tokens (data-parallel
replicas); the ``all_to_all`` exchange of ``moe_apply`` is not part of it yet.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from ..common import device_names
from ..compat import axis_size

EP_AXIS = "ep"


class MoEParams(NamedTuple):
    gate: jax.Array  # (D, E)        — replicated
    w_in: jax.Array  # (E_local, D, H) — this rank's experts
    w_out: jax.Array  # (E_local, H, D)


def init_moe_params(key, dim, hidden, n_experts, ep_size, dtype=jnp.float32):
    """Full (unsharded) parameter set; shard w_in/w_out with P('ep') on dim 0
    (n_experts must be divisible by ep_size)."""
    if n_experts % ep_size:
        raise ValueError(f"{n_experts} experts not divisible by ep={ep_size}")
    kg, k1, k2 = jax.random.split(key, 3)
    scale = 1.0 / jnp.sqrt(dim)
    return MoEParams(
        gate=(jax.random.normal(kg, (dim, n_experts)) * scale).astype(dtype),
        w_in=(jax.random.normal(k1, (n_experts, dim, hidden)) * scale).astype(dtype),
        w_out=(jax.random.normal(k2, (n_experts, hidden, dim)) * scale).astype(dtype),
    )


def top1_route(logits, capacity: int):
    """Per-token expert choice + position within the expert's capacity.

    Returns (expert, prob, pos, keep): argmax expert id, its gate
    probability, the token's slot in the (expert, capacity) buffer, and the
    keep mask (False = overflowed capacity → dropped)."""
    n_experts = logits.shape[-1]
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    expert = jnp.argmax(probs, axis=-1)
    prob = jnp.take_along_axis(probs, expert[:, None], axis=-1)[:, 0]
    onehot = jax.nn.one_hot(expert, n_experts, dtype=jnp.int32)
    # slot = how many earlier tokens picked the same expert
    pos = jnp.sum((jnp.cumsum(onehot, axis=0) - 1) * onehot, axis=-1)
    keep = pos < capacity
    return expert, prob, pos, keep


def moe_apply(params: MoEParams, x, capacity: int, axis_name: str = EP_AXIS):
    """Switch-MoE forward for this rank's local tokens ``x (T, D)``; call
    inside shard_map with tokens sharded and experts sharded over
    ``axis_name``. Differentiable end to end (all_to_all transposes to the
    reverse exchange)."""
    ep = axis_size(axis_name)
    e_local, d, _h = params.w_in.shape
    n_experts = ep * e_local

    logits = x @ params.gate  # (T, E)
    expert, prob, pos, keep = top1_route(logits, capacity)

    # 2. dispatch buffer (E, C, D) → exchange → (ep, E_local, C, D)
    kept = jnp.where(keep[:, None], x, jnp.zeros_like(x))
    disp = jnp.zeros((n_experts, capacity, d), x.dtype).at[expert, pos].add(kept)
    disp = disp.reshape(ep, e_local, capacity, d)
    recv = lax.all_to_all(disp, axis_name, split_axis=0, concat_axis=0,
                          tiled=False)  # (ep, E_local, C, D): rank r's tokens

    # 3. batched expert MLP over (rank, expert, slot)
    h = jax.nn.relu(jnp.einsum("recd,edh->rech", recv, params.w_in))
    y = jnp.einsum("rech,ehd->recd", h, params.w_out)

    # 4. send results home; tokens gather their slot back
    back = lax.all_to_all(y, axis_name, split_axis=0, concat_axis=0,
                          tiled=False).reshape(n_experts, capacity, d)
    out = back[expert, pos] * (prob * keep)[:, None].astype(x.dtype)
    return out


def load_balancing_loss(logits, expert, n_experts: int):
    """Switch-Transformer auxiliary loss: n_e * Σ_e (fraction routed to e) ×
    (mean gate prob of e) — pushes the router toward uniform expert use."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    frac = jnp.mean(jax.nn.one_hot(expert, n_experts, dtype=jnp.float32), axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    return n_experts * jnp.sum(frac * mean_prob)


# ----------------------------------------------------- top-k, dropless (OLMoE)

def topk_route(logits, top_k: int):
    """Softmax in float32, then the ``top_k`` largest probabilities.

    Returns (probs (N, E), weights (N, top_k), experts (N, top_k)). The
    weights are the chosen probabilities as they are: not renormalised, so a
    token's weights sum to less than 1."""
    with jax.named_scope(device_names.MOE_ROUTE):
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        _, experts = lax.top_k(probs, top_k)
        # The weights through a one-hot product, not top_k's values: their
        # backward is then a product too, where top_k's is a scatter-add of
        # N x top_k scalars.
        onehot = experts[:, :, None] == jnp.arange(probs.shape[-1])
        weights = jnp.sum(jnp.where(onehot, probs[:, None, :], 0.0), axis=-1)
    return probs, weights, experts


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _take_rows(x, take, back, fan: int):
    """``x[take]``, whose backward is a gather too: row i of ``x`` is taken
    by exactly ``fan`` rows of the output, the rows ``back[i*fan:(i+1)*fan]``
    (``back`` is the inverse of the permutation ``take`` is made from)."""
    return x[take]


def _take_rows_fwd(x, take, back, fan):
    return x[take], back


def _take_rows_bwd(fan, back, g):
    dx = g[back]
    if fan > 1:
        dx = dx.reshape(-1, fan, g.shape[-1]).astype(jnp.float32).sum(
            axis=1).astype(g.dtype)
    return dx, None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


def _expert_counts(flat_experts, n_experts: int):
    """Rows per expert, as a compare-and-sum (no scatter: a scatter of N x
    top_k scalar updates runs one by one on the TPU)."""
    return jnp.sum(flat_experts[:, None] == jnp.arange(n_experts)[None, :],
                   axis=0, dtype=jnp.int32)


def dropless_experts(x, weights, experts, w_gate, w_up, w_down):
    """Every chosen (token, expert) pair through its SwiGLU expert, summed
    with its weight: ``sum_j weights[n, j] * down_e(silu(gate_e x_n) * up_e x_n)``
    with ``e = experts[n, j]``.

    x: (N, D); weights, experts: (N, top_k); w_gate, w_up: (E, D, H);
    w_down: (E, H, D), all in x's dtype. Returns (N, D) in x's dtype. The
    work is N x top_k rows whatever the routing."""
    n, d = x.shape
    top_k, n_experts = experts.shape[1], w_gate.shape[0]
    with jax.named_scope(device_names.MOE_DISPATCH):
        flat = experts.reshape(-1)
        order = jnp.argsort(flat, stable=True)          # sorted row -> pair
        inverse = jnp.argsort(order)                    # pair -> sorted row
        group_sizes = _expert_counts(flat, n_experts)
        rows = _take_rows(x, order // top_k, inverse, top_k)
    with jax.named_scope(device_names.MOE_EXPERTS):
        gate = lax.ragged_dot(rows, w_gate, group_sizes)
        up = lax.ragged_dot(rows, w_up, group_sizes)
        out = lax.ragged_dot(jax.nn.silu(gate) * up, w_down, group_sizes)
    with jax.named_scope(device_names.MOE_COMBINE):
        pairs = _take_rows(out, inverse, order, 1).reshape(n, top_k, d)
        return jnp.sum(pairs.astype(jnp.float32) * weights[:, :, None],
                       axis=1).astype(x.dtype)


def topk_load_balancing_loss(probs, experts):
    """OLMoE's load-balancing loss: ``E * sum_e f_e P_e`` with ``f_e`` the
    share of the N x top_k assignments that went to expert e (it sums to 1)
    and ``P_e`` the mean probability of e. The gradient flows through P."""
    n_experts = probs.shape[-1]
    share = _expert_counts(experts.reshape(-1), n_experts) / experts.size
    return n_experts * jnp.sum(share * jnp.mean(probs, axis=0))


def router_z_loss(logits):
    """``mean(logsumexp(logits)^2)``: keeps the router's logits small."""
    return jnp.mean(jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1) ** 2)


def record_expert_load(router_logits, top_k: int) -> float:
    """Given CONCRETE router logits (N, E) - from an evaluation loop or a
    check, never from inside a jitted step - set
    ``horovod_moe_expert_load_max_over_mean``: the fullest expert's rows over
    the mean (1 = balanced, E / top_k = every token on the same experts)."""
    import numpy as np

    from ..metrics.registry import registry

    logits = np.asarray(router_logits, np.float32)
    chosen = np.argpartition(-logits, top_k - 1, axis=-1)[:, :top_k]
    counts = np.bincount(chosen.reshape(-1), minlength=logits.shape[-1])
    load = float(counts.max() / counts.mean())
    registry().gauge(
        "horovod_moe_expert_load_max_over_mean",
        help="rows of the fullest expert over the mean, from the router "
             "logits last handed to ops.moe.record_expert_load").set(load)
    return load
