"""Tensor parallelism on the 3-D mesh's ``'model'`` axis (ISSUE 19).

Megatron-LM's column/row-parallel matmul decomposition (Shoeybi et al.,
arXiv:1909.08053) expressed as shard_map-level primitives over
``mesh.sharded_mesh(model=...)``'s third axis:

- a **column-parallel** layer holds a 1/model_size slice of its weight's
  OUTPUT dimension: ``y_r = act(x @ w1[:, r]) `` — no collective, the
  activation applies to the local slice;
- the paired **row-parallel** layer holds the matching slice of its
  weight's INPUT dimension and finishes with exactly one
  ``psum('model')``: ``y = psum_r(h_r @ w2[r, :]) + b2``.

One psum per pair is the whole wire cost of the forward.  The backward
needs care: JAX transposes ``lax.psum`` as another psum, which is wrong
for the in-body ``jax.value_and_grad`` pattern this repo trains with
(each rank holds the REPLICATED loss, so psum-of-cotangents would scale
every slice gradient by model_size).  The fix is Megatron's conjugate
``f``/``g`` pair, here :func:`copy_to_model` (identity forward, psum
backward — wraps the column half's input) and :func:`reduce_from_model`
(psum forward, identity backward — finishes the row half).  With those
two, the in-body gradients of slice parameters match the dense oracle's
slices bitwise, replicated parameters (``b_row``) receive identical
gradients on every model rank, and the model axis costs exactly one
collective per pair per direction — which is why the
``('batch','shard')`` gradient exchange
(:func:`~.sharded.reduce_scatter_gradients`) runs unchanged per model
group and the 3-D step rides the same ``fusion.build_plan`` bucketing,
per-tier wire-dtype opt-outs, and ``record_shard_plan`` gauges as the DP
and FSDP paths.

Exactness contract (the ISSUE 19 discipline): the TP forward reassociates
the hidden-dimension contraction (local partial products, then the psum),
so it matches the dense single-chip oracle BITWISE on exact-arithmetic
payloads (integer-valued floats within the exactly-representable range —
tests/test_tensor_parallel.py pins this) and within pinned dtype
tolerance on generic floats. ``model_size=1`` emits no collective at all
(the psum is skipped at trace time), keeping the degenerate 3-D mesh
bitwise-identical to the 2-D plan.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from . import fusion
from .mesh import MODEL_AXIS

__all__ = [
    "copy_to_model", "reduce_from_model",
    "column_parallel", "row_parallel", "tp_pair_apply", "tp_apply",
    "dense_pair_apply", "dense_apply", "tp_pair_slices", "tp_local_pairs",
    "tp_rank_pairs",
]


def _model_size(axis_name: str) -> int:
    """Size of the model axis in scope; 1 outside shard_map (or on a mesh
    that never named the axis) so every helper degrades to the dense
    arithmetic with no collective."""
    return fusion._axis_size(axis_name) or 1


# --------------------------------------------------- conjugate collectives
#
# Megatron's f/g: two ops that are transposes OF EACH OTHER, replacing the
# default psum-transposes-to-psum rule that would scale slice gradients by
# model_size under the in-body value_and_grad pattern.


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def copy_to_model(x, axis_name: str = MODEL_AXIS):
    """Identity forward / ``psum(axis_name)`` backward (Megatron's *f*).

    Wraps the column half's input: the forward activation is already
    replicated across model ranks, but each rank's backward produces only
    its slice's PARTIAL input-cotangent (``ct_h_r @ w_col_r.T``); the psum
    in the transpose completes the hidden-dimension sum so the cotangent
    leaving the pair is exact — which is what keeps the previous pair's
    (or embedding's) gradients bitwise in a chain."""
    return x


def _copy_fwd(x, axis_name):
    return x, None


def _copy_bwd(axis_name, _, ct):
    return (lax.psum(ct, axis_name),)


copy_to_model.defvjp(_copy_fwd, _copy_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def reduce_from_model(x, axis_name: str = MODEL_AXIS):
    """``psum(axis_name)`` forward / identity backward (Megatron's *g*).

    Finishes the row half: the forward psum completes the hidden
    contraction; the backward hands each rank the replicated cotangent
    UNCHANGED (each rank's partial product entered the sum exactly once).
    JAX's default transpose would psum the replicated cotangents —
    scaling every upstream gradient by model_size."""
    return lax.psum(x, axis_name)


def _reduce_fwd(x, axis_name):
    return lax.psum(x, axis_name), None


def _reduce_bwd(axis_name, _, ct):
    return (ct,)


reduce_from_model.defvjp(_reduce_fwd, _reduce_bwd)


# ------------------------------------------------------------- layer halves


def column_parallel(x, w, b=None, axis_name: str = MODEL_AXIS):
    """The pair's first half: ``x @ w (+ b)`` where ``w``/``b`` are this
    model rank's OUTPUT-dimension slices. No forward collective — the
    activations come out column-sliced, feeding :func:`row_parallel`
    directly; the input rides :func:`copy_to_model` so its backward
    cotangent is completed across ranks."""
    if _model_size(axis_name) > 1:
        x = copy_to_model(x, axis_name)
    y = x @ w
    return y if b is None else y + b


def row_parallel(x, w, b=None, axis_name: str = MODEL_AXIS):
    """The pair's second half: each rank contracts its INPUT-dimension
    slice, then ONE :func:`reduce_from_model` completes the
    hidden-dimension sum — the pair's only forward collective. The bias
    (replicated) is added AFTER the psum so it enters the sum exactly
    once, exactly as the dense oracle adds it. With the model axis out of
    scope (model_size=1) no collective is emitted."""
    y = x @ w
    if _model_size(axis_name) > 1:
        y = reduce_from_model(y, axis_name)
    return y if b is None else y + b


def tp_pair_apply(pair: dict, x, axis_name: str = MODEL_AXIS,
                  activation=jnp.tanh):
    """One column/row-parallel pair (a Megatron MLP block):
    ``row(act(col(x)))`` with one psum. ``pair`` holds this rank's local
    slices under the keys ``w_col (d_in, h/s)``, ``b_col (h/s,)``,
    ``w_row (h/s, d_out)``, ``b_row (d_out,)`` (biases optional)."""
    h = column_parallel(x, pair["w_col"], pair.get("b_col"), axis_name)
    if activation is not None:
        h = activation(h)
    return row_parallel(h, pair["w_row"], pair.get("b_row"), axis_name)


def tp_apply(pairs: Sequence[dict], x, axis_name: str = MODEL_AXIS,
             activation=jnp.tanh, final_activation=None):
    """A stack of column/row pairs — one ``psum(axis_name)`` per pair and
    nothing else on the model axis. Every pair's output is replicated
    across model ranks (the psum makes it so), which is what lets pairs
    chain without re-sharding activations."""
    for i, pair in enumerate(pairs):
        x = tp_pair_apply(pair, x, axis_name, activation)
        if final_activation is not None and i == len(pairs) - 1:
            x = final_activation(x)
    return x


# ------------------------------------------------------- single-chip oracle


def dense_pair_apply(pair: dict, x, activation=jnp.tanh):
    """The single-chip dense oracle of :func:`tp_pair_apply`: identical
    arithmetic on the FULL weights (``w_col (d_in, h)``, ``w_row
    (h, d_out)``)."""
    h = x @ pair["w_col"]
    if pair.get("b_col") is not None:
        h = h + pair["b_col"]
    if activation is not None:
        h = activation(h)
    y = h @ pair["w_row"]
    if pair.get("b_row") is not None:
        y = y + pair["b_row"]
    return y


def dense_apply(pairs: Sequence[dict], x, activation=jnp.tanh,
                final_activation=None):
    """Dense oracle of :func:`tp_apply` (full weights, one chip)."""
    for i, pair in enumerate(pairs):
        x = dense_pair_apply(pair, x, activation)
        if final_activation is not None and i == len(pairs) - 1:
            x = final_activation(x)
    return x


# ------------------------------------------------------------ param slicing


def tp_pair_slices(pair: dict, model_size: int) -> list:
    """Slice one full pair into ``model_size`` local pairs (host side):
    ``w_col``/``b_col`` split on the hidden (output) dimension, ``w_row``
    on its input dimension, ``b_row`` replicated. The hidden dimension
    must divide evenly — ragged tensor-parallel slices would break the
    uniform-plan property every model rank's ShardPlan relies on."""
    if model_size < 1:
        raise ValueError(f"model_size must be >= 1, got {model_size}")
    hidden = int(pair["w_col"].shape[-1])
    if hidden % model_size:
        raise ValueError(
            f"hidden dim {hidden} not divisible by model_size "
            f"{model_size}: tensor-parallel slices must be uniform")
    if int(pair["w_row"].shape[0]) != hidden:
        raise ValueError(
            f"w_col out dim {hidden} != w_row in dim "
            f"{int(pair['w_row'].shape[0])}: not a column/row pair")
    per = hidden // model_size
    out = []
    for r in range(model_size):
        sl = slice(r * per, (r + 1) * per)
        local = {"w_col": pair["w_col"][:, sl], "w_row": pair["w_row"][sl]}
        if pair.get("b_col") is not None:
            local["b_col"] = pair["b_col"][sl]
        if pair.get("b_row") is not None:
            local["b_row"] = pair["b_row"]
        out.append(local)
    return out


def tp_local_pairs(pairs: Sequence[dict], model_size: int) -> list:
    """Per-model-rank local trees for a whole pair stack: element ``r`` is
    the list of rank r's local pairs — the tree shape
    :func:`~.sharded.build_shard_plan` plans (pass any one of them: they
    are shape-uniform by construction) and
    :func:`~.sharded.shard_params_model` stacks."""
    sliced = [tp_pair_slices(p, model_size) for p in pairs]
    return [[s[r] for s in sliced] for r in range(model_size)]


def tp_rank_pairs(pairs: Sequence[dict], model_size: int, rank: int) -> list:
    """One model rank's local pair stack (host side)."""
    return tp_local_pairs(pairs, model_size)[rank]
