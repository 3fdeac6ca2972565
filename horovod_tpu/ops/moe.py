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
2. the experts' products run as grouped products over the ragged groups:
   three forward and six backward for gated SwiGLU experts (``w_gate``
   given: ``down(silu(gate x) * up x)``), two and four for experts that are
   not gated (``w_gate`` None: ``down(relu(up x)^2)``, Nemotron-H's
   ``relu2``). Shapes the repo's own kernels
   tile (``grouped_matmul.takes_kernel``: bf16 or f32, both widths multiples
   of 128, the rows a multiple of the row tile) go through them: an expert's
   weight block resident, row tiles of 512 cut into blocks of 128 where a
   group border crosses them (PERF.md, PR 29). The weights may be a layer's
   float32 master parameters under bf16 rows: the kernels read them as they
   are and round a block in VMEM (PR 63). Any other shape
   goes through ``jax.lax.ragged_dot``, the general path (on the TPU libtpu's
   grouped matmul at 512-cube tiles, PERF.md, PR 26), on weights cast to the
   rows' dtype beforehand (``hvd_moe_weight_cast``);
3. the rows go back to token order through the inverse permutation and are
   summed with their weights.

Both permutations are gathers in the forward AND the backward pass
(:func:`_take_rows`): the transpose of a gather is a scatter-add, and the
inverse permutation is at hand, so the backward gathers through it instead.

A rank holds either every expert (data-parallel replicas: ``held`` None) or
the experts ``[first, first + count)`` of the ``of`` the router chooses among
(one expert-parallel rank's share: ``held = (first, count, of)``, the weights
``(count, ...)``). It then routes over all of them and sorts the pairs of
absent experts behind the held ones, so the rows that hold a held pair are
the sorted order's first ``live``, a count known on the device alone. NO
pass of that path moves the worst-case ``N x top_k`` rows
(:func:`_held_experts`): the gathers, the activation, the weighted sum and
their backward are loops over the windows of ``_WINDOW_ROWS`` rows that hold
live rows, the grouped products visit the held pairs' tiles (the plan's steps
end with the last held pair), and the row buffers between them are N x top_k
rows of ADDRESSES whose live prefix alone is written and read. Back in token
order nothing is scattered: the live pairs, sorted again by token, each sum
their token's pairs up to themselves, and a token takes the sum at its last
(:func:`_sum_by_token`). Every window runs where every pair is held, none
where none is: no capacity, no dropped pair. What the absent ranks would add
is not computed, and nothing here stands in for their exchange.
``horovod_moe_dispatch_rows`` reads the rows a layer's passes visit under a
balanced router (:func:`held_window_rows`).
"""

from __future__ import annotations

import functools
import operator
import sys

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

from ..common import device_names
from . import grouped_matmul as gm

# The name both routers give the experts they chose, before anything reads
# them: a caller that recomputes a layer in the backward pass saves them by it
# (``TransformerLM(remat=True)``: ``save_only_these_names``). A recomputed
# router whose scores differ from the forward's in the last bit breaks a
# float32 tie between a token's last chosen expert and the next the other
# way, and the backward then differentiates another expert set than the
# forward ran (seen on the v5e: one token of 2,048, up to 5% of the largest
# entry of the layer's gradients; PERF.md, PR 36).
CHOSEN_EXPERTS = "moe_chosen_experts"
# What :func:`sigmoid_route_tokens` names beside them, (N, top_k) float32
# each: the sigmoid scores AT the chosen experts, from which alone its
# backward is formed, and the weights the combine multiplies by. With the
# three saved, a recomputed layer reads what its router gave from memory, and
# the router's product, the sigmoid over (N, E) and the one-hot sums are dead
# in it: three float32 products at ``highest`` a layer (the forward and the
# backward's two), not four (PERF.md, PR 68).
CHOSEN_SCORES = "moe_chosen_scores"
CHOSEN_WEIGHTS = "moe_chosen_weights"
ROUTER_SAVED = (CHOSEN_EXPERTS, CHOSEN_SCORES, CHOSEN_WEIGHTS)


def topk_route(logits, top_k: int, renormalise: bool = False):
    """Softmax in float32, then the ``top_k`` largest probabilities.

    Returns (probs (N, E), weights (N, top_k), experts (N, top_k)). The
    weights are the chosen probabilities as they are: not renormalised, so a
    token's weights sum to less than 1 (OLMoE) - or, with ``renormalise``
    (Qwen3-MoE's ``norm_topk_prob``), divided by their sum."""
    with jax.named_scope(device_names.MOE_ROUTE):
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        experts = checkpoint_name(lax.top_k(probs, top_k)[1], CHOSEN_EXPERTS)
        # The weights through a one-hot product, not top_k's values: their
        # backward is then a product too, where top_k's is a scatter-add of
        # N x top_k scalars.
        onehot = experts[:, :, None] == jnp.arange(probs.shape[-1])
        weights = jnp.sum(jnp.where(onehot, probs[:, None, :], 0.0), axis=-1)
        if renormalise:
            weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return probs, weights, experts


def _at_chosen(scores, experts):
    """``scores`` (N, E) at ``experts`` (N, top_k), through a one-hot
    product and not ``top_k``'s values: the backward is then a product too,
    where ``top_k``'s is a scatter-add of N x top_k scalars."""
    onehot = experts[:, :, None] == jnp.arange(scores.shape[-1])
    return jnp.sum(jnp.where(onehot, scores[:, None, :], 0.0), axis=-1)


def sigmoid_route(logits, bias, top_k: int, scale: float,
                  eps: float = 1e-20):
    """DeepSeek-V3's router with one group: scores ``sigmoid(logits)`` in
    float32, the ``top_k`` experts by ``score + bias``, their weights the
    scores themselves (the bias chooses and never weighs), renormalised over
    the ``top_k`` and multiplied by ``scale``. ``eps`` is what the
    renormalisation adds to the chosen scores' sum before it divides: no
    configuration file has a key for it, it is the constant of a family's
    released modelling code (DeepSeek-V3's ``1e-20``, the default; LFM2's
    ``1e-6``, which a configuration module states: ``MoEMLP.route_eps``).

    Returns (scores (N, E), weights (N, top_k), experts (N, top_k)). ``bias``
    (E,) receives no gradient: it is state, moved by
    :func:`router_bias_update`."""
    with jax.named_scope(device_names.MOE_ROUTE):
        scores = jax.nn.sigmoid(logits.astype(jnp.float32))
        experts = checkpoint_name(
            lax.top_k(scores + lax.stop_gradient(bias), top_k)[1],
            CHOSEN_EXPERTS)
        weights = _at_chosen(scores, experts)
        weights = scale * weights / (jnp.sum(weights, axis=-1, keepdims=True)
                                     + eps)
    return scores, weights, experts


def router_logits(tokens, router):
    """``tokens @ router`` in float32 at full precision whatever the
    activations' dtype: 2*N*D*E operations, and a coarser product flips a
    token's 8th expert against its 9th far more often."""
    with jax.named_scope(device_names.MOE_LOGITS):
        return jnp.dot(tokens.astype(jnp.float32), router,
                       precision=lax.Precision.HIGHEST)


def _under_checkpoint() -> bool:
    """Whether ``jax.checkpoint`` (``nn.remat``) is tracing or
    differentiating the caller: one of its frames is on the stack. JAX has
    no public question for it; only a gauge hangs on the answer."""
    frame = sys._getframe(1)
    while frame is not None:
        if frame.f_code.co_filename.endswith("ad_checkpoint.py"):
            return True
        frame = frame.f_back
    return False


def save_names(*names):
    """``jax.checkpoint_policies.save_only_these_names(*names)``; where the
    names hold :data:`ROUTER_SAVED`, a policy that besides tells
    ``horovod_moe_router_recomputed`` when it is asked about a sigmoid
    router's chosen scores and saves them: that layer's router is not run
    again."""
    policy = jax.checkpoint_policies.save_only_these_names(*names)
    if not set(ROUTER_SAVED) <= set(names):
        return policy
    from ..metrics import record_moe_router_recomputed

    def observed(prim, *avals, **params):
        if params.get("name") == CHOSEN_SCORES:
            record_moe_router_recomputed(False)
        return policy(prim, *avals, **params)

    return observed


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def sigmoid_route_tokens(tokens, router, bias, top_k: int, scale: float,
                         eps: float, route):
    """The router's product and the sigmoid routing rule as ONE
    differentiable unit: ``route(router_logits(tokens, router), bias, top_k,
    scale[, eps])`` with ``route`` :func:`sigmoid_route` (the epsilon handed
    on only where it is not the default's ``1e-20``) or a function of its
    signature that forms the stated weights on an expert set of its own.
    Returns (logits (N, E), weights (N, top_k), experts (N, top_k)).

    Its backward is formed from ``tokens``, ``router``, the experts and the
    sigmoid scores AT them as the call returned them, (N, top_k), never from
    the (N, E) scores: ``w = scale c / (sum c + eps)`` and ``dc/dl = c (1 -
    c)`` at the chosen columns, a cotangent on ``logits`` added, then the two
    float32 products at ``highest`` that autodiff issues. ``bias`` receives
    none. The forward rule names the chosen scores and the weights
    (:data:`CHOSEN_SCORES`, :data:`CHOSEN_WEIGHTS`) and the experts again
    (:data:`CHOSEN_EXPERTS`: a ``route`` of the caller's may not have), so a
    caller that recomputes the layer under a policy that saves
    :data:`ROUTER_SAVED` (:func:`save_names`) runs no router in the
    recomputation. Forward mode (``jax.jvp``, ``jax.jacfwd``) raises, as for
    any ``jax.custom_vjp``."""
    return _route_tokens(tokens, router, bias, top_k, scale, eps, route)[0]


def _route_tokens(tokens, router, bias, top_k, scale, eps, route):
    """(what the unit returns, what its backward keeps)."""
    logits = router_logits(tokens, router)
    # the epsilon only where one is stated: the benchmark's tests hand in
    # variants of sigmoid_route's first four arguments
    stated = () if eps == 1e-20 else (eps,)
    scores, weights, experts = route(logits, bias, top_k, scale, *stated)
    with jax.named_scope(device_names.MOE_ROUTE):
        experts = checkpoint_name(experts, CHOSEN_EXPERTS)
        chosen = checkpoint_name(_at_chosen(scores, experts), CHOSEN_SCORES)
        weights = checkpoint_name(weights, CHOSEN_WEIGHTS)
    return (logits, weights, experts), (tokens, router, experts, chosen)


def _route_tokens_fwd(tokens, router, bias, top_k, scale, eps, route):
    from ..metrics import (record_moe_router_recomputed,
                           record_moe_router_saved_bytes)

    out, kept = _route_tokens(tokens, router, bias, top_k, scale, eps, route)
    (_, weights, _), (*_, chosen) = out, kept
    record_moe_router_saved_bytes(
        sum(named.size * named.dtype.itemsize for named in (chosen, weights)))
    # a policy that saves the names says so after this (save_names)
    record_moe_router_recomputed(_under_checkpoint())
    return out, kept


def _route_tokens_bwd(top_k, scale, eps, route, residuals, cotangents):
    tokens, router, experts, chosen = residuals
    d_logits, d_weights, _ = cotangents
    with jax.named_scope(device_names.MOE_ROUTE):
        total = jnp.sum(chosen, axis=-1, keepdims=True) + eps
        d_chosen = scale / total * (d_weights - jnp.sum(
            d_weights * chosen, axis=-1, keepdims=True) / total)
        at_chosen = d_chosen * chosen * (1.0 - chosen)
        onehot = experts[:, :, None] == jnp.arange(router.shape[-1])
        d_logits = d_logits + jnp.sum(
            jnp.where(onehot, at_chosen[:, :, None], 0.0), axis=1)
    with jax.named_scope(device_names.MOE_LOGITS):
        d_tokens = lax.dot_general(
            d_logits, router, (((1,), (1,)), ((), ())),
            precision=lax.Precision.HIGHEST).astype(tokens.dtype)
        d_router = lax.dot_general(
            tokens.astype(jnp.float32), d_logits, (((0,), (0,)), ((), ())),
            precision=lax.Precision.HIGHEST)
    return d_tokens, d_router, None


sigmoid_route_tokens.defvjp(_route_tokens_fwd, _route_tokens_bwd)


def router_bias_update(bias, counts, rate: float):
    """The auxiliary-loss-free balancing rule: ``bias + rate * sign(mean(c) -
    c)`` with ``c`` (E,) the pairs routed to each expert in the step, summed
    over ranks by the caller so that replicas keep one bias."""
    counts = counts.astype(jnp.float32)
    return bias + rate * jnp.sign(jnp.mean(counts) - counts)


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


def _takes_kernel(rows: int, dtype, w) -> bool:
    """Whether ``rows`` rows of ``dtype`` and the weights ``w`` as they are
    go through the repo's kernels."""
    return gm.takes_kernel(jax.ShapeDtypeStruct((rows, w.shape[1]), dtype), w)


def _grouped_product(group_sizes, rows: int, dtype, w, interpret: bool):
    """``a, w -> a[rows of g] @ w[g]`` for ``a`` of ``rows`` rows of ``dtype``
    in groups of ``group_sizes``: the repo's kernels where the shapes take
    them, on the weights AS THEY ARE (a layer's float32 parameters under bf16
    rows are rounded in VMEM); else ``lax.ragged_dot`` on the weights cast to
    the rows' dtype. Which, goes to ``horovod_moe_grouped_border_overhead``,
    ``horovod_moe_grouped_weight_itemsize`` and
    ``horovod_moe_grouped_weight_lookahead_share``."""
    from ..metrics import record_moe_grouped_plan

    if not _takes_kernel(rows, dtype, w):
        record_moe_grouped_plan(0.0, 0, 0.0)

        def ragged(a, w):
            with jax.named_scope(device_names.MOE_WEIGHT_CAST):
                w = w.astype(a.dtype)
            return lax.ragged_dot(a, w, group_sizes)

        return ragged
    plan = gm.grouped_plan(group_sizes, rows, gm.row_tile(dtype.itemsize))
    record_moe_grouped_plan(gm.border_overhead(rows, w.shape[0]),
                            w.dtype.itemsize, gm.lookahead_share(w.shape[0]))
    return lambda a, w: gm.grouped_matmul(a, w, plan, interpret)


def _swiglu(gate, up):
    return jax.nn.silu(gate) * up


def _relu2(up):
    """Nemotron-H's ``relu2``: ``relu(x)^2``, of an expert with no gate."""
    positive = jax.nn.relu(up)
    return positive * positive


def _activation(w_gate):
    """(name, function of the products before it) of the experts' activation:
    SwiGLU of ``(gate, up)`` where the experts have a gate, relu² of ``(up,)``
    where ``w_gate`` is None."""
    return ("relu2", _relu2) if w_gate is None else ("swiglu", _swiglu)


# -------------------------------------------------- one rank's share of them

# Sorted rows a pass of the held path visits at a time: whole row tiles of
# the grouped products at either itemsize, and 8 MiB of bf16 rows at 2,048
# columns, large enough that a gather runs at the rate of a long one.
_WINDOW_ROWS = 2048


def _window(pairs: int) -> int:
    """Rows of one window of a sorted order of ``pairs`` rows; an order that
    is no whole number of windows (a shorter one, above all) is ONE window."""
    return _WINDOW_ROWS if pairs % _WINDOW_ROWS == 0 else pairs


def held_window_rows(pairs: int, count: int, n_experts: int) -> int:
    """Sorted rows the passes of a layer that holds ``count`` of
    ``n_experts`` experts visit when the router is balanced: its share of
    the ``pairs`` pairs, in whole windows."""
    window = _window(pairs)
    return -(-pairs * count // (n_experts * window)) * window


def _buffer_rows(pairs: int, top_k: int, count: int) -> int:
    """Rows of the held path's row buffers. A token's ``top_k`` experts are
    distinct, so at most ``min(top_k, count)`` of its pairs fall on the
    ``count`` held ones: that many rows a token, in whole windows, hold every
    live row under any routing. ``pairs`` where ``top_k <= count`` (6 of 16
    held, 8 of 32); 8 / 22 of them where 8 experts are held and a token
    chooses 22."""
    window = _window(pairs)
    bound = pairs // top_k * min(top_k, count)
    return min(pairs, -(-bound // window) * window)


def _over_live_windows(live, pairs: int, body, carry):
    """``carry = body(r, carry)`` for the first row ``r`` of every window of
    the sorted order that holds a live row (a row ``< live``), in order: the
    trip count is known on the device alone, the shapes are static."""
    window = _window(pairs)
    return lax.fori_loop(
        0, lax.div(live + (window - 1), jnp.int32(window)),
        lambda i, c: body(i * window, c), carry)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _as_it_comes(after, shape, dtype, interpret):
    return pl.pallas_call(
        lambda after, out: None, out_shape=jax.ShapeDtypeStruct(shape, dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY), interpret=interpret)(after)


def _row_buffer(rows: int, width: int, dtype, w, after, interpret: bool):
    """``(rows, width)`` of ``dtype``, the ROWS' (never the weights': those
    may be float32 parameters under bf16 rows), whose rows a caller
    writes before it reads them. Beside the kernels (``rows`` rows take them
    through ``w``) it is memory as it comes (a ``pallas_call`` that writes
    nothing, jitted so that a model's layers share one copy): zeroing 98,304
    rows of which an eighth is ever used costs what the pass over the live
    ones does. ``after`` is an array the buffer is not needed before: the
    call takes it as an operand it never reads, or XLA, seeing a call that
    depends on nothing, makes every layer's buffers at the program's start
    and keeps them. Elsewhere zeros."""
    if not _takes_kernel(rows, dtype, w):
        return jnp.zeros((rows, width), dtype)
    return _as_it_comes(after, (rows, width), dtype, interpret)


def _sorted_share(flat, top_k: int, n_experts: int):
    """What the passes of the held path index by, from the pairs' experts
    ``flat`` (P,), ``n_experts`` for an absent one. Absent experts' pairs
    sort behind every held one's, so the ``live`` rows that hold a held pair
    are the sorted order's first. ``order`` (P,): a sorted row's pair;
    ``group_sizes``: rows per held expert. The live pairs AGAIN in pair
    order, which is token order, a token's pairs one after the other:
    ``rows`` (top_k - 1 + P,), the sorted row of the q-th such pair at
    ``top_k - 1 + q`` (behind the live ones anything); ``last`` (N,) the q
    of a token's last live pair and ``some`` (N,) whether it has one. Two
    sorts of keys alone, as where every expert is held: a sort is what a
    TPU program compiles longest, one that carries a payload longer still,
    and a gather of P scalars takes a millisecond, so what a pass needs of a
    pair (its token, its weight) it gathers for its window's pairs."""
    pairs = flat.shape[0]
    at = jnp.arange(pairs, dtype=jnp.int32)
    order = jnp.argsort(flat, stable=True)
    group_sizes = _expert_counts(flat, n_experts)
    live = jnp.sum(group_sizes)
    rows = jnp.argsort(jnp.where(at < live, order, pairs + at))
    mine = jnp.sum((flat < n_experts).reshape(-1, top_k), axis=1,
                   dtype=jnp.int32)
    return {"order": order, "live": live, "group_sizes": group_sizes,
            "rows": jnp.concatenate([jnp.zeros(top_k - 1, jnp.int32), rows]),
            "last": jnp.cumsum(mine) - 1, "some": mine > 0}


def _sum_by_token(share, value_at, top_k: int, sums):
    """``y[n] = sum of value_at(sorted rows, their pairs)`` over token n's
    live pairs, in float32 and rounded once to the dtype of ``sums``, the (P,
    width) buffer the sums are written to; (N, width). No row is scattered:
    a window of the live pairs in token order gathers its rows, every pair
    sums its token's pairs up to itself (a token's are at most ``top_k``
    neighbours), and a token then takes the sum at its last pair."""
    front, pairs = top_k - 1, share["order"].shape[0]
    window = _window(pairs)

    def runs(q, sums):
        rows = lax.dynamic_slice(share["rows"], (q,), (front + window,))
        pair = share["order"][rows]
        # the pairs in front of the first belong to no token
        owner = jnp.where(q + jnp.arange(front + window) >= front,
                          pair // top_k, -1)
        values = value_at(rows, pair)
        total = values[front:]
        for back in range(1, front + 1):
            # rows behind the live ones hold anything: selected, never added
            total = total + jnp.where(
                (owner[front - back:-back] == owner[front:])[:, None],
                values[front - back:-back], 0.0)
        return lax.dynamic_update_slice(sums, total.astype(sums.dtype), (q, 0))

    sums = _over_live_windows(share["live"], pairs, runs, sums)
    return jnp.where(share["some"][:, None],
                     sums[jnp.maximum(share["last"], 0)],
                     jnp.zeros((), sums.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _held_experts(x, weights, flat, w_gate, w_up, w_down, interpret: bool):
    """:func:`dropless_experts` for a rank that holds the experts of
    ``w_up`` alone (``w_gate`` None: experts without a gate); ``flat``
    (N x top_k,) is each pair's expert counted from the rank's first,
    ``w_up.shape[0]`` for an absent one. Every pass outside the grouped
    products (which visit the held pairs' tiles as they are) is a loop over
    the windows that hold live rows (:func:`_over_live_windows`): the row
    buffers keep the rows any routing can fill (:func:`_buffer_rows`) as
    ADDRESSES, and only the live prefix of each is ever written or read."""
    return _held_forward(x, weights, flat, w_gate, w_up, w_down, interpret)[0]


def _held_forward(x, weights, flat, w_gate, w_up, w_down, interpret):
    d, pairs, hidden = x.shape[1], flat.shape[0], w_up.shape[2]
    window, top_k = _window(pairs), weights.shape[1]
    held_rows = _buffer_rows(pairs, top_k, w_up.shape[0])
    _, activation = _activation(w_gate)
    with jax.named_scope(device_names.MOE_DISPATCH):
        share = _sorted_share(flat, top_k, w_up.shape[0])
        live = share["live"]

        def gather(r, rows):
            pair = lax.dynamic_slice(share["order"], (r,), (window,))
            return lax.dynamic_update_slice(rows, x[pair // top_k], (r, 0))

        rows = _over_live_windows(live, pairs, gather,
                                  _row_buffer(held_rows, d, x.dtype, w_up,
                                              share["order"], interpret))
    with jax.named_scope(device_names.MOE_EXPERTS):
        product = _grouped_product(share["group_sizes"], held_rows, x.dtype,
                                   w_up, interpret)
        # (gate, up), or (up,) for experts without a gate
        before = tuple(product(rows, w) for w in (w_gate, w_up)
                       if w is not None)

        def activate(r, h):
            return lax.dynamic_update_slice(
                h, activation(*(lax.dynamic_slice(a, (r, 0), (window, hidden))
                                for a in before)), (r, 0))

        h = _over_live_windows(live, pairs, activate,
                               _row_buffer(held_rows, hidden, x.dtype, w_up,
                                           before[0], interpret))
        out = product(h, w_down)
    with jax.named_scope(device_names.MOE_COMBINE):
        y = _sum_by_token(
            share, lambda rows, pair: out[rows].astype(jnp.float32)
            * weights.reshape(-1)[pair][:, None], top_k,
            _row_buffer(held_rows, d, x.dtype, w_up, out, interpret))
    return y, (share, weights, rows, before, h, out, w_gate, w_up, w_down)


def _held_backward(interpret, res, g):
    share, weights, rows, before, h, out, w_gate, w_up, w_down = res
    d, pairs, hidden = g.shape[1], share["order"].shape[0], w_up.shape[2]
    window, top_k, live = _window(pairs), weights.shape[1], share["live"]
    held_rows = rows.shape[0]
    _, activation = _activation(w_gate)
    product = _grouped_product(share["group_sizes"], held_rows, g.dtype, w_up,
                               interpret)

    def grads(a, w, dy):
        return jax.vjp(product, a, w)[1](dy)

    with jax.named_scope(device_names.MOE_COMBINE):

        def pull(r, carry):
            dout, dweights = carry
            pair = lax.dynamic_slice(share["order"], (r,), (window,))
            dy = g[pair // top_k].astype(jnp.float32)
            # rows behind the live ones were never written
            dweight = jnp.where(
                r + jnp.arange(window) < live,
                jnp.sum(dy * lax.dynamic_slice(out, (r, 0), (window, d)),
                        axis=1), 0.0)
            weight = weights.reshape(-1)[pair]
            # scalars to distinct places: the one scatter the chip runs at
            # a gather's rate (12 us a window, where gathering all P back
            # through the pairs' ranks takes 1.3 ms)
            return (lax.dynamic_update_slice(
                dout, (dy * weight[:, None]).astype(g.dtype), (r, 0)),
                    dweights.at[pair].set(dweight, unique_indices=True,
                                          mode="promise_in_bounds"))

        dout, dweights = _over_live_windows(
            live, pairs, pull,
            (_row_buffer(held_rows, d, g.dtype, w_up, g, interpret),
             jnp.zeros((pairs,), jnp.float32)))
    with jax.named_scope(device_names.MOE_EXPERTS):
        dh, dw_down = grads(h, w_down, dout)

        def activate(r, carry):
            # the gradients' rows are still those of the products before
            at = (lax.dynamic_slice(a, (r, 0), (window, hidden)) for a in carry)
            dbefore = jax.vjp(activation, *at)[1](
                lax.dynamic_slice(dh, (r, 0), (window, hidden)))
            return tuple(lax.dynamic_update_slice(a, da, (r, 0))
                         for a, da in zip(carry, dbefore))

        dbefore = _over_live_windows(live, pairs, activate, before)
        by, dws = zip(*(grads(rows, w, da) for w, da in zip(
            (w for w in (w_gate, w_up) if w is not None), dbefore)))
    with jax.named_scope(device_names.MOE_DISPATCH):
        dx = _sum_by_token(
            share, lambda rows, pair: functools.reduce(
                operator.add, (b[rows].astype(jnp.float32) for b in by)),
            top_k,
            _row_buffer(held_rows, d, g.dtype, w_up, by[-1], interpret))
    dw_gate, dw_up = (None, *dws) if w_gate is None else dws
    return (dx, dweights.reshape(weights.shape), None, dw_gate, dw_up, dw_down)


_held_experts.defvjp(_held_forward, _held_backward)


def dropless_experts(x, weights, experts, w_gate, w_up, w_down,
                     interpret: bool = False, held=None):
    """Every chosen (token, expert) pair through its expert, summed with its
    weight: ``sum_j weights[n, j] * f_e(x_n)`` with ``e = experts[n, j]``, over
    the ``j`` whose expert this rank holds. The experts are what their
    weights say: gated SwiGLU, ``f_e(x) = down_e(silu(gate_e x) * up_e x)``,
    or with ``w_gate`` None experts WITHOUT a gate, ``f_e(x) =
    down_e(relu(up_e x)^2)`` (Nemotron-H's ``relu2``): two grouped products
    forward and four backward where the gated ones take three and six.

    x: (N, D); weights, experts: (N, top_k); w_gate (or None), w_up:
    (E, D, H); w_down: (E, H, D), in x's dtype or the float32 parameters as
    they are (the products multiply them rounded to x's dtype, in the kernels'
    VMEM or by a cast before ``lax.ragged_dot``; their gradients come back
    in their own dtype). Returns (N, D) in x's dtype.
    ``held`` None: the weights are every expert's, and the work is N x top_k
    rows whatever the routing. ``held = (first, count, of)``: ``experts``
    index the ``of`` experts the router chooses among, the weights are those
    of ``[first, first + count)`` alone, and every pass (the gathers, the
    products, the weighted sum, and their backward) visits the held pairs'
    rows in whole windows (:func:`held_window_rows` under a balanced router)
    and adds nothing for the other pairs. No held pair is dropped under any
    routing: where every pair is held, every window runs. ``interpret`` runs
    the grouped-product kernels, where the shapes take them, in the Pallas
    interpreter (asked for by the CPU tests, never inferred)."""
    from ..metrics import record_moe_dispatch_rows

    n, d = x.shape
    top_k, n_experts = experts.shape[1], w_up.shape[0]
    name, activation = _activation(w_gate)
    record_moe_dispatch_rows(
        n * top_k if held is None else held_window_rows(n * top_k, *held[1:]),
        d * x.dtype.itemsize)
    if held is not None:
        first, count, of = held
        if count != n_experts:
            raise ValueError(f"held {held} but {n_experts} {name} experts' "
                             f"weights")
        with jax.named_scope(device_names.MOE_DISPATCH):
            flat = experts.reshape(-1)
            # an absent expert's pairs sort behind every held one's
            flat = jnp.where((flat >= first) & (flat < first + count),
                             flat - first, count)
        return _held_experts(x, weights, flat, w_gate, w_up, w_down, interpret)
    with jax.named_scope(device_names.MOE_DISPATCH):
        flat = experts.reshape(-1)
        order = jnp.argsort(flat, stable=True)          # sorted row -> pair
        inverse = jnp.argsort(order)                    # pair -> sorted row
        group_sizes = _expert_counts(flat, n_experts)
        rows = _take_rows(x, order // top_k, inverse, top_k)
    with jax.named_scope(device_names.MOE_EXPERTS):
        product = _grouped_product(group_sizes, n * top_k, rows.dtype, w_up,
                                   interpret)
        out = product(activation(*(product(rows, w) for w in (w_gate, w_up)
                                   if w is not None)), w_down)
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
