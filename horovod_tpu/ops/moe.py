"""Mixture of experts, dropless: two routing rules and the expert products.

Beyond the reference's scope (Horovod v0.16 is data-parallel only, SURVEY.md
§2.8). Two routers: softmax, then the ``top_k`` largest probabilities as
weights, NOT renormalised (OLMoE, arXiv:2409.02060; :func:`topk_route`), and
sigmoid scores chosen by score + bias, the chosen scores renormalised and
scaled, the bias moved after each step by the sign of the experts' load and
never by a gradient (DeepSeek-V3, arXiv:2412.19437 §2.1.2;
:func:`sigmoid_route`, :func:`router_bias_update`). No capacity under either:
every chosen (token, expert) pair whose expert this rank holds is computed,
under any imbalance, at static shapes (:func:`dropless_experts`):

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

A rank holds either every expert (data-parallel replicas: ``held`` None) or
the experts ``[first, first + count)`` of the ``E`` the router chooses among
(one expert-parallel rank's share: ``held = (first, count)``, the weights
``(count, ...)``). It then routes over all ``E``, sorts the pairs of absent
experts behind the held ones, multiplies the held pairs' tiles only (the
group sizes sum to less than the row buffer; the plan's steps end with the
last held pair) and adds nothing for the absent ones. What the absent ranks
would add is not computed, and nothing here stands in for their exchange. The
two gathers still move the worst-case ``N x top_k`` buffer
(``horovod_moe_dispatch_rows``).
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


def sigmoid_route(logits, bias, top_k: int, scale: float):
    """DeepSeek-V3's router with one group: scores ``sigmoid(logits)`` in
    float32, the ``top_k`` experts by ``score + bias``, their weights the
    scores themselves (the bias chooses and never weighs), renormalised over
    the ``top_k`` and multiplied by ``scale``.

    Returns (scores (N, E), weights (N, top_k), experts (N, top_k)). ``bias``
    (E,) receives no gradient: it is state, moved by
    :func:`router_bias_update`."""
    with jax.named_scope(device_names.MOE_ROUTE):
        scores = jax.nn.sigmoid(logits.astype(jnp.float32))
        _, experts = lax.top_k(scores + lax.stop_gradient(bias), top_k)
        # the weights through a one-hot product, as in topk_route
        onehot = experts[:, :, None] == jnp.arange(scores.shape[-1])
        weights = jnp.sum(jnp.where(onehot, scores[:, None, :], 0.0), axis=-1)
        weights = scale * weights / (jnp.sum(weights, axis=-1, keepdims=True)
                                     + 1e-20)
    return scores, weights, experts


def router_bias_update(bias, counts, rate: float):
    """The auxiliary-loss-free balancing rule: ``bias + rate * sign(mean(c) -
    c)`` with ``c`` (E,) the pairs routed to each expert in the step, summed
    over ranks by the caller so that replicas keep one bias."""
    counts = counts.astype(jnp.float32)
    return bias + rate * jnp.sign(jnp.mean(counts) - counts)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _take_rows(x, take, back, live, fan: int):
    """``x[take]``, whose backward is a gather too: row i of ``x`` is taken
    by exactly ``fan`` rows of the output, the rows ``back[i*fan:(i+1)*fan]``
    (``back`` is the inverse of the permutation ``take`` is made from).
    ``live`` None: every row of the output is computed on. ``live`` a count:
    only the first ``live`` rows are; the gradient of the rows behind them
    was never written (the grouped products skip their tiles) and counts as
    zero."""
    return x[take]


def _take_rows_fwd(x, take, back, live, fan):
    return x[take], (back, live)


def _take_rows_bwd(fan, res, g):
    back, live = res
    dx = g[back]
    if live is not None:
        dx = jnp.where((back < live)[:, None], dx, jnp.zeros((), g.dtype))
    if fan > 1:
        dx = dx.reshape(-1, fan, g.shape[-1]).astype(jnp.float32).sum(
            axis=1).astype(g.dtype)
    return dx, None, None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


def _expert_counts(flat_experts, n_experts: int):
    """Rows per expert, as a compare-and-sum (no scatter: a scatter of N x
    top_k scalar updates runs one by one on the TPU)."""
    return jnp.sum(flat_experts[:, None] == jnp.arange(n_experts)[None, :],
                   axis=0, dtype=jnp.int32)


def dropless_experts(x, weights, experts, w_gate, w_up, w_down,
                     interpret: bool = False, held=None):
    """Every chosen (token, expert) pair through its SwiGLU expert, summed
    with its weight: ``sum_j weights[n, j] * down_e(silu(gate_e x_n) * up_e x_n)``
    with ``e = experts[n, j]``, over the ``j`` whose expert this rank holds.

    x: (N, D); weights, experts: (N, top_k); w_gate, w_up: (E, D, H);
    w_down: (E, H, D), all in x's dtype. Returns (N, D) in x's dtype.
    ``held`` None: the weights are every expert's, and the work is N x top_k
    rows whatever the routing. ``held = (first, count)``: ``experts`` index
    all the router's experts, the weights are those of ``[first, first +
    count)`` alone, the products visit the tiles of their pairs and the other
    pairs add nothing; no held pair is dropped under any routing (the row
    buffer is N x top_k). ``interpret`` runs the grouped-product kernels,
    where the shapes take them, in the Pallas interpreter (asked for by the
    CPU tests, never inferred)."""
    from ..metrics import record_moe_dispatch_rows, record_moe_grouped_plan

    n, d = x.shape
    top_k, n_experts = experts.shape[1], w_gate.shape[0]
    record_moe_dispatch_rows(n * top_k)
    with jax.named_scope(device_names.MOE_DISPATCH):
        flat = experts.reshape(-1)
        if held is not None:
            first, count = held
            if count != n_experts:
                raise ValueError(f"held {held} but {n_experts} experts' weights")
            # an absent expert's pairs sort behind every held one's
            flat = jnp.where((flat >= first) & (flat < first + count),
                             flat - first, count)
        order = jnp.argsort(flat, stable=True)          # sorted row -> pair
        inverse = jnp.argsort(order)                    # pair -> sorted row
        group_sizes = _expert_counts(flat, n_experts)
        # sorted rows that hold held pairs: all of them where all are held
        live = None if held is None else jnp.sum(group_sizes)
        rows = _take_rows(x, order // top_k, inverse, live, top_k)
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
        pairs = _take_rows(out, inverse, order, None, 1).reshape(n, top_k, d)
        if held is not None:    # rows behind the live ones were never written
            pairs = jnp.where((inverse < live).reshape(n, top_k, 1), pairs,
                              jnp.zeros((), pairs.dtype))
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
