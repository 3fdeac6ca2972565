"""Sharded data parallelism (ZeRO / FSDP) through the bucketed planner.

ISSUE 14 tentpole: the same synchronous-SGD contract every Horovod data
plane honors — identical gradients applied to identical parameters on every
replica — can run with parameters, gradients, and optimizer state sharded
1/N per device (Rajbhandari et al., ZeRO; Zhao et al., FSDP). The swap is
purely on the wire: the per-bucket ``allreduce`` of the DP planner becomes

    reduce-scatter(bucket grads -> owning shard)   # equal ring bytes
    ... optimizer update on the 1/N shard ...
    allgather(bucket params)                       # the parameter refresh

over a named 2-D ``('batch', 'shard')`` mesh (mesh.sharded_mesh):
gradients still average across 'batch' (plain DP replicas), while 'shard'
carries the ZeRO partition. The degenerate ``shard=1`` mesh traces to the
DP plan's exchange — same buckets, same wire casts, same psum, equation
for equation — so the sharded path is a strict superset, not a fork.

ISSUE 19 adds the third ``'model'`` axis (parallel/tensor.py): each model
rank plans and exchanges its LOCAL tensor-parallel slice tree through the
very same machinery — the model axis needs no gradient collective here
because the ``psum('model')`` inside each column/row matmul pair already
broadcasts its cotangent under AD. ``model_size=1`` plans and exchanges
are bitwise the 2-D ones (no new HLO enters the step).

The bucket layout IS the shard layout: fusion.build_plan packs
leaves into same-dtype buckets padded to a multiple of the shard axis size,
and each rank owns one ``(1, chunk)`` row per bucket. Because buckets are
the unit of exchange, everything the planner already knows — per-tier
bucket sizing (HOROVOD_DCN_FUSION_THRESHOLD), the per-bucket wire-dtype
opt-outs (compression.md), trace-time plan gauges — applies unchanged.

Data model
----------

:class:`ShardedBuckets` is a registered pytree holding one buffer per
bucket. Host-side the buffers are ``(shard_size, chunk)``; inside
shard_map (``in_specs=P('shard')``) each rank sees its ``(1, chunk)`` row.
Optimizer state built by ``optimizer.init(sharded_params)`` mirrors the
container, so moments shard for free and
:func:`unshard_tree` / :func:`reshard_tree` can consolidate / re-partition
a whole training state for checkpoints (checkpoint.save_sharded).

Zero-pad discipline: fuse() pads each bucket's tail with zeros. Gradients
at the tail are exactly zero (fuse pads the gradient buffer the same way),
and :func:`mask_pad_updates` forces optimizer updates there to zero, so
the tail stays bitwise 0.0 forever — never trained, never leaked into
checkpoints (consolidation drops it; re-sharding re-pads fresh zeros).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from . import collectives, fusion
from .collectives import ReduceOp
from .mesh import BATCH_AXIS, MODEL_AXIS, SHARD_AXIS
from ..common.config import Config
from ..compression import compression_name


@jax.tree_util.register_pytree_node_class
class ShardedBuckets:
    """Pytree container of per-bucket shard buffers.

    Being a registered pytree is the load-bearing property: optax
    transformations tree_map straight through it (so ``optimizer.init``
    produces sharded moments), shard_map specs treat it as a prefix
    position, and :func:`unshard_tree` can find every sharded sub-state in
    an arbitrary training-state pytree by ``isinstance``."""

    def __init__(self, buffers: Sequence):
        self.buffers = tuple(buffers)

    def tree_flatten(self):
        return self.buffers, None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(children)

    def __len__(self) -> int:
        return len(self.buffers)

    def __iter__(self):
        return iter(self.buffers)

    def __getitem__(self, i):
        return self.buffers[i]

    def __repr__(self) -> str:
        shapes = ",".join(str(tuple(getattr(b, "shape", ()))) for b in self.buffers)
        return f"ShardedBuckets([{shapes}])"


@dataclass(frozen=True)
class ShardPlan:
    """A FusionPlan bound to a shard axis size: the bucket layout doubles as
    the parameter-partition layout. Built once (deterministically — every
    rank derives the identical plan from the tree structure and the knobs)
    and shared by shard_params / gather_params / reduce_scatter_gradients /
    the checkpoint consolidators."""

    base: fusion.FusionPlan
    shard_size: int
    threshold: int
    raw_sizes: tuple          # per-bucket elements before padding
    padded_sizes: tuple       # per-bucket elements after padding
    chunk_sizes: tuple        # per-rank elements: padded // shard_size
    bucket_dtypes: tuple
    # Size of the third ('model') mesh axis this plan coexists with
    # (ISSUE 19). The planned TREE is one model rank's LOCAL tree (its
    # tensor-parallel slices), so bucketing/padding/chunks are untouched by
    # this field — it rides along for the trace-time gauges and so
    # consumers (checkpoints, benches) know the full-model multiplier.
    # model_size=1 plans are field-for-field the PR 14 plans.
    model_size: int = 1

    @property
    def num_buckets(self) -> int:
        return self.base.num_buckets

    def state_bytes_per_rank(self) -> int:
        """Bytes of ONE sharded copy of the planned tree per rank (params;
        multiply by the optimizer's state factor for moments). The planned
        tree is already a single model rank's local slice tree, so no
        further division by model_size applies."""
        return sum(c * jnp.dtype(d).itemsize
                   for c, d in zip(self.chunk_sizes, self.bucket_dtypes))


def build_shard_plan(tree, shard_size: int, threshold: Optional[int] = None,
                     num_buckets: Optional[int] = None,
                     dcn_threshold: Optional[int] = None,
                     model_size: int = 1) -> ShardPlan:
    """Plan the sharded bucketing of ``tree``'s leaves.

    Same knobs as the DP planner — ``threshold`` None reads
    HOROVOD_FUSION_THRESHOLD, ``num_buckets`` None reads
    HOROVOD_NUM_BUCKETS — plus the per-tier cap: a bucket's reduce-scatter
    ships 1/shard of its bytes per rank, so HOROVOD_DCN_FUSION_THRESHOLD
    bounds bucket bytes at D*shard_size exactly as it does for the
    hierarchical ladder (fusion.dcn_capped_threshold). On ``shard_size=1``
    the plan is identical to the DP plan (pad_to=1, no padding).

    ``model_size`` records the 3-D mesh's third axis (ISSUE 19): pass the
    LOCAL tree — one model rank's tensor-parallel slices — and the bucket
    layout is computed over it exactly as over a full tree (every model
    rank derives the identical plan because the slice trees are
    structure- and shape-uniform). ``model_size=1`` yields a plan
    field-for-field identical to the 2-D planner's."""
    if shard_size < 1:
        raise ValueError(f"shard_size must be >= 1, got {shard_size}")
    if model_size < 1:
        raise ValueError(f"model_size must be >= 1, got {model_size}")
    cfg = None
    if threshold is None:
        cfg = Config.from_env()
        threshold = cfg.fusion_threshold
    if num_buckets is None:
        cfg = cfg or Config.from_env()
        num_buckets = cfg.num_buckets
    if shard_size > 1:
        threshold = fusion.dcn_capped_threshold(threshold, dcn_threshold,
                                                shard_size)
    plan = fusion.build_plan(tree, threshold, pad_to=shard_size,
                             num_buckets=num_buckets)
    raw, padded, chunks, dtypes = [], [], [], []
    for bucket in plan.buckets:
        n = sum(d.size for d in bucket)
        p = -(-n // shard_size) * shard_size
        raw.append(n)
        padded.append(p)
        chunks.append(p // shard_size)
        dtypes.append(bucket[0].dtype)
    return ShardPlan(plan, int(shard_size), int(threshold), tuple(raw),
                     tuple(padded), tuple(chunks), tuple(dtypes),
                     int(model_size))


def shard_params(params, plan: ShardPlan) -> ShardedBuckets:
    """Partition a full pytree into the plan's bucket layout: each bucket is
    fused (flatten + concatenate + zero-pad) and viewed as
    ``(shard_size, chunk)`` rows — pass into shard_map with
    ``in_specs=P('shard')`` so each rank receives its row."""
    buffers = fusion.fuse(params, plan.base)
    return ShardedBuckets(
        b.reshape(plan.shard_size, -1) for b in buffers)


def unshard_params(sharded: ShardedBuckets, plan: ShardPlan):
    """Host-side inverse of :func:`shard_params`: rebuild the full pytree
    from the ``(shard_size, chunk)`` buffers, dropping the pad tail."""
    flat = [jnp.reshape(b, (-1,)) for b in sharded]
    return fusion.unfuse(flat, plan.base)


def gather_params(sharded: ShardedBuckets, plan: ShardPlan,
                  shard_axis: str = SHARD_AXIS):
    """The ZeRO parameter refresh, inside shard_map: one tiled
    ``all_gather`` per bucket rebuilds the full parameters from each rank's
    ``(1, chunk)`` rows. Differentiable — the all_gather transpose delivers
    each full-parameter gradient as the reduce-scatter-sum into the owning
    shard, which is exactly what :func:`reduce_scatter_gradients` computes
    explicitly for the bucketed path. On ``shard_size=1`` no collective is
    emitted (the row IS the bucket), keeping the degenerate mesh's HLO
    identical to DP."""
    flat = []
    for b in sharded:
        if plan.shard_size == 1:
            flat.append(jnp.reshape(b, (-1,)))
        else:
            flat.append(lax.all_gather(b[0], shard_axis, axis=0, tiled=True))
    return fusion.unfuse(flat, plan.base)


def reduce_scatter_gradients(
    grads,
    plan: Optional[ShardPlan] = None,
    *,
    batch_axis: str = BATCH_AXIS,
    shard_axis: str = SHARD_AXIS,
    model_axis: str = MODEL_AXIS,
    op: ReduceOp = ReduceOp.AVERAGE,
    compression=None,
    compression_min_bytes: Optional[int] = None,
    threshold: Optional[int] = None,
    num_buckets: Optional[int] = None,
) -> ShardedBuckets:
    """The sharded gradient exchange: fuse -> (wire cast) -> per-bucket
    ``psum_scatter`` into the owning shard over ``shard_axis`` -> ``psum``
    across ``batch_axis`` -> (cast back, average) — ZeRO's equal-wire-cost
    replacement for the bucketed allreduce.

    ``grads`` is the FULL gradient pytree (what ``jax.grad`` of a loss over
    :func:`gather_params`-rebuilt parameters produces); the result is a
    :class:`ShardedBuckets` matching the parameter shard layout, ready for
    the inner optimizer update. Wire compression reuses the DP planner's
    per-bucket verdicts unchanged (wire_dtype_for_bucket opt-outs; the cast
    wraps BOTH collectives, so scatter and batch-psum ship wire-width).

    On a degenerate ``shard=1`` mesh the exchange is
    ``collectives.bucketed_allreduce`` over ``batch_axis`` on the plan's
    buffers — the same call, cast sequence, and plan the DP path traces,
    which since PR 59 hands that call a bucket's leaves where this one hands
    it the bucket's buffer — so the exchange is sharded==DP element for
    element there (the two whole steps remain separately compiled programs
    and agree to float32 rounding).

    On a 3-D ``('batch','shard','model')`` mesh (ISSUE 19) NOTHING extra
    goes on the wire here: ``grads`` is one model rank's LOCAL gradient
    tree. Tensor-parallel slice gradients are already per-rank values, and
    replicated-parameter gradients are already identical across model
    ranks — the conjugate ``copy_to_model``/``reduce_from_model`` pair
    inside each column/row matmul block (parallel/tensor.py) completes the
    model-axis cotangents during the backward itself, so the batch average
    over ``(batch, shard)`` finishes the data-parallel sum with zero
    model-axis collectives here. ``model_axis`` only names the axis for
    the trace-time gauges, so an operator can see the 3-D shape a step
    compiled."""
    if op not in (ReduceOp.SUM, ReduceOp.AVERAGE):
        raise ValueError(
            f"sharded gradient exchange supports SUM/AVERAGE only (got "
            f"{op}); reduce-scatter is a sum machine")
    if plan is None:
        shard_size = fusion._axis_size(shard_axis)
        if shard_size is None:
            raise ValueError(
                f"reduce_scatter_gradients needs the size of axis "
                f"{shard_axis!r}: call inside shard_map over a "
                f"('{batch_axis}', '{shard_axis}') mesh or pass plan=")
        model_in_scope = fusion._axis_size(model_axis)
        plan = build_shard_plan(grads, shard_size, threshold, num_buckets,
                                model_size=model_in_scope or 1)
    shard_size = plan.shard_size
    model_size = plan.model_size
    if model_size == 1:
        model_size = fusion._axis_size(model_axis) or 1
    batch_size = fusion._axis_size(batch_axis)
    if batch_size is None:
        if shard_size > 1:
            raise ValueError(
                f"reduce_scatter_gradients needs the size of axis "
                f"{batch_axis!r} in scope (the batch psum); got none")
        batch_size = 1

    from ..metrics import (record_plan, record_shard_plan, record_wire_plan)

    record_plan(plan.base, plan.threshold)
    buffers = fusion.fuse(grads, plan.base)
    orig_dtypes = [buf.dtype for buf in buffers]
    wire = [fusion.wire_dtype_for_bucket(compression, buf.dtype,
                                         int(buf.nbytes), op,
                                         compression_min_bytes)
            for buf in buffers]
    record_wire_plan(
        compression_name(compression),
        [(int(b.nbytes), w is not None,
          int(b.size) * (jnp.dtype(w).itemsize if w is not None else 0))
         for b, w in zip(buffers, wire)])
    # Trace-time shard-plan gauges (ISSUE 14 satellite): axis sizes plus
    # per-bucket scatter/gather bytes — the scatter operand ships at the
    # wire dtype, the parameter-refresh gather at the storage dtype.
    record_shard_plan(
        batch_size, shard_size,
        scatter_bytes=[int(b.size) * int(jnp.dtype(w).itemsize
                                         if w is not None else b.dtype.itemsize)
                       for b, w in zip(buffers, wire)],
        gather_bytes=[int(b.nbytes) for b in buffers],
        model_size=model_size)
    buffers = [b.astype(w) if w is not None else b
               for b, w in zip(buffers, wire)]
    with jax.named_scope(
            f"hvd_sharded_reduce_scatter_k{len(buffers)}s{shard_size}"):
        if shard_size == 1:
            # The DP path's exchange: identical collective call over the batch
            # axis (pmean divides at the wire dtype exactly as
            # fused_allreduce does), then the identical back-cast.
            reduced = collectives.bucketed_allreduce(buffers, batch_axis, op)
            reduced = [r.astype(dt) if w is not None else r
                       for r, w, dt in zip(reduced, wire, orig_dtypes)]
        else:
            world = shard_size * batch_size
            reduced = []
            for buf, w, dt in zip(buffers, wire, orig_dtypes):
                chunk = lax.psum_scatter(buf, shard_axis,
                                         scatter_dimension=0, tiled=True)
                if batch_size > 1:
                    chunk = lax.psum(chunk, batch_axis)
                if w is not None:
                    chunk = chunk.astype(dt)
                if op == ReduceOp.AVERAGE:
                    chunk = chunk / world
                reduced.append(chunk)
    return ShardedBuckets(r.reshape(1, -1) for r in reduced)


def mask_pad_updates(updates, plan: ShardPlan, shard_axis: str = SHARD_AXIS):
    """Zero the optimizer update on each bucket's zero-pad tail (inside
    shard_map). Gradients there are exactly zero by construction, but an
    optimizer chain is free to move zero-gradient entries (weight decay on
    restored garbage, gradient noise, schedule interpolation) — this mask
    is what makes 'the tail stays bitwise 0.0' an invariant instead of a
    hope.

    Buckets without padding (always the case on shard=1) are untouched —
    no mask op enters the HLO, preserving the degenerate mesh's bitwise
    identity with DP."""
    if not isinstance(updates, ShardedBuckets):
        raise TypeError(f"expected ShardedBuckets updates, got {type(updates)}")
    out = []
    for b, buf in enumerate(updates):
        raw, chunk = plan.raw_sizes[b], plan.chunk_sizes[b]
        if raw == plan.padded_sizes[b]:
            out.append(buf)
            continue
        if buf.shape[0] == plan.shard_size:
            # Host-side (shard_size, chunk) view: global positions.
            pos = jnp.arange(plan.padded_sizes[b]).reshape(plan.shard_size,
                                                           chunk)
        elif buf.shape[0] == plan.shard_size * plan.model_size:
            # Host-side model-stacked (model*shard, chunk) view
            # (shard_params_model): the pad layout repeats per model rank.
            pos = jnp.tile(
                jnp.arange(plan.padded_sizes[b]).reshape(plan.shard_size,
                                                         chunk),
                (plan.model_size, 1))
        else:
            row = lax.axis_index(shard_axis)
            pos = (row * chunk + jnp.arange(chunk))[None, :]
        out.append(jnp.where(pos < raw, buf, jnp.zeros_like(buf)))
    return ShardedBuckets(out)


def _is_sharded(x) -> bool:
    return isinstance(x, ShardedBuckets)


def unshard_tree(tree, plan: ShardPlan):
    """Consolidate every :class:`ShardedBuckets` in an arbitrary pytree
    (training state, optimizer moments, ...) into full leaves — the
    mesh-shape-independent form checkpoints store (the pad tail is dropped,
    so it can never be carried in a checkpoint). Non-sharded leaves pass
    through untouched."""
    return jax.tree_util.tree_map(
        lambda x: unshard_params(x, plan) if _is_sharded(x) else x,
        tree, is_leaf=_is_sharded)


def reshard_tree(full, template, plan: ShardPlan):
    """Inverse of :func:`unshard_tree`: re-partition the full-leaf pytree
    ``full`` into ``template``'s shard layout (fresh zero pad). ``template``
    is the live sharded state — it tells us WHERE the sharded sub-states
    sit; ``plan`` may target a different shard_size than the state that was
    saved, which is what makes sharded checkpoints restorable onto a
    reshaped mesh."""
    return jax.tree_util.tree_map(
        lambda t, f: shard_params(f, plan) if _is_sharded(t) else f,
        template, full, is_leaf=_is_sharded)


def shard_specs(tree, shard_axis: str = SHARD_AXIS,
                model_axis: Optional[str] = None):
    """shard_map in/out specs for a (possibly nested) sharded state:
    ``P(shard_axis)`` at every :class:`ShardedBuckets` position (a prefix
    spec — it applies to each buffer row-wise), ``P()`` (replicated) for
    everything else (step counters, scalars).

    With ``model_axis`` the buckets are the model-stacked
    ``(model*shard, chunk)`` buffers of :func:`shard_params_model`, and
    the spec becomes ``P((model_axis, shard_axis))`` — row 0 jointly
    partitioned over both axes, model-major, so each device again sees its
    own ``(1, chunk)`` row and the in-shard_map code path is byte-for-byte
    the 2-D one."""
    from jax.sharding import PartitionSpec as P

    spec = P(shard_axis) if model_axis is None else \
        P((model_axis, shard_axis))
    return jax.tree_util.tree_map(
        lambda x: spec if _is_sharded(x) else P(),
        tree, is_leaf=_is_sharded)


def shard_params_model(local_trees: Sequence, plan: ShardPlan) -> ShardedBuckets:
    """Partition PER-MODEL-RANK local trees (tensor-parallel slice trees,
    one per model rank, structure- and shape-uniform) into one stacked
    buffer per bucket: ``(model_size * shard_size, chunk)``, model-major.
    Pass into shard_map over the 3-D mesh with
    ``in_specs=P(('model', 'shard'))`` (see :func:`shard_specs`) so each
    device receives exactly its model rank's shard row — from there
    :func:`gather_params` / :func:`reduce_scatter_gradients` /
    :func:`mask_pad_updates` run unchanged within the device's model
    group."""
    if len(local_trees) != plan.model_size:
        raise ValueError(
            f"need one local tree per model rank: got {len(local_trees)} "
            f"trees for model_size={plan.model_size}")
    per_rank = [fusion.fuse(t, plan.base) for t in local_trees]
    return ShardedBuckets(
        jnp.concatenate(
            [bufs[b].reshape(plan.shard_size, -1) for bufs in per_rank],
            axis=0)
        for b in range(plan.num_buckets))


def unshard_params_model(sharded: ShardedBuckets, plan: ShardPlan) -> list:
    """Host-side inverse of :func:`shard_params_model`: the per-model-rank
    local trees, in model-rank order."""
    out = []
    for r in range(plan.model_size):
        rows = ShardedBuckets(
            b[r * plan.shard_size:(r + 1) * plan.shard_size]
            for b in sharded)
        out.append(unshard_params(rows, plan))
    return out


def state_bytes(tree) -> int:
    """Total array bytes in a pytree (host view: sharded buffers count their
    FULL (shard_size, chunk) global footprint — divide by shard_size for
    the per-rank share)."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        total += int(getattr(leaf, "nbytes",
                             jnp.asarray(leaf).nbytes))
    return total
