"""Mixture of experts, dropless: OLMoE's routing and expert products.

Beyond the reference's scope (Horovod v0.16 is data-parallel only, SURVEY.md
§2.8). Softmax, then the ``top_k`` largest probabilities as weights, NOT
renormalised, and no capacity (OLMoE, arXiv:2409.02060) - every chosen
(token, expert) pair is computed, under any imbalance, at static shapes
(:func:`topk_route`, :func:`dropless_experts`):

1. the N x top_k pairs are sorted by expert (stable, so a token's rows keep
   their order inside an expert's group) and the tokens gathered into that
   order: N x top_k rows whatever the imbalance, only the group sizes vary;
2. the three SwiGLU products run as grouped products over the ragged groups,
   and so do the six of their backward pass. Shapes the repo's own kernels
   tile (``grouped_matmul.takes_kernel``: bf16 or f32, both widths multiples
   of 128, the rows a multiple of the row tile) go through them: an expert's
   weight block resident, row tiles of 512 cut into blocks of 128 where a
   group border crosses them (PERF.md, PR 29). Any other shape
   goes through ``jax.lax.ragged_dot``, the general path (on the TPU libtpu's
   grouped matmul at 512-cube tiles, PERF.md, PR 26);
3. the rows go back to token order through the inverse permutation and are
   summed with their weights.

Both permutations are gathers in the forward AND the backward pass
(:func:`_take_rows`): the transpose of a gather is a scatter-add, and the
inverse permutation is at hand, so the backward gathers through it instead.
All experts live on the rank that holds the tokens (data-parallel replicas).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..common import device_names
from . import grouped_matmul as gm


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


def dropless_experts(x, weights, experts, w_gate, w_up, w_down,
                     interpret: bool = False):
    """Every chosen (token, expert) pair through its SwiGLU expert, summed
    with its weight: ``sum_j weights[n, j] * down_e(silu(gate_e x_n) * up_e x_n)``
    with ``e = experts[n, j]``.

    x: (N, D); weights, experts: (N, top_k); w_gate, w_up: (E, D, H);
    w_down: (E, H, D), all in x's dtype. Returns (N, D) in x's dtype. The
    work is N x top_k rows whatever the routing. ``interpret`` runs the
    grouped-product kernels, where the shapes take them, in the Pallas
    interpreter (asked for by the CPU tests, never inferred)."""
    from ..metrics import record_moe_grouped_plan

    n, d = x.shape
    top_k, n_experts = experts.shape[1], w_gate.shape[0]
    with jax.named_scope(device_names.MOE_DISPATCH):
        flat = experts.reshape(-1)
        order = jnp.argsort(flat, stable=True)          # sorted row -> pair
        inverse = jnp.argsort(order)                    # pair -> sorted row
        group_sizes = _expert_counts(flat, n_experts)
        rows = _take_rows(x, order // top_k, inverse, top_k)
    with jax.named_scope(device_names.MOE_EXPERTS):
        if gm.takes_kernel(rows, w_gate):
            plan = gm.grouped_plan(group_sizes, n * top_k,
                                   gm.row_tile(rows.dtype.itemsize))
            record_moe_grouped_plan(gm.border_overhead(n * top_k, n_experts))

            def product(a, w):
                return gm.grouped_matmul(a, w, plan, interpret)
        else:
            record_moe_grouped_plan(0.0)

            def product(a, w):
                return lax.ragged_dot(a, w, group_sizes)

        gate, up = product(rows, w_gate), product(rows, w_up)
        out = product(jax.nn.silu(gate) * up, w_down)
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
