"""JAX framework binding — the first-class framework of the TPU build.

Parity map to the reference bindings:

- :func:`DistributedOptimizer`      ↔ hvd.DistributedOptimizer
  (torch/__init__.py:52-151, tensorflow/__init__.py:151-249). Wraps any optax
  GradientTransformation; grads are planned into buckets and allreduced
  in the plan's order before the inner update (a bucket's leaves as they
  are on the flat path, one padded buffer a bucket on the hierarchical
  ladder: parallel/fusion.py). Hook machinery is unnecessary: JAX grads
  arrive as a complete pytree, so "plan → psum" replaces the
  per-parameter grad-accumulator hooks.
- :func:`distributed_gradients` / :func:`grad` ↔ DistributedGradientTape
  (tensorflow/__init__.py:252-326).
- :func:`broadcast_parameters`      ↔ hvd.broadcast_parameters
  (torch/__init__.py:200-230) — rank-0-writes + broadcast-on-restore contract.
- :func:`broadcast_optimizer_state` ↔ hvd.broadcast_optimizer_state
  (torch/__init__.py:232-348). Optax state is a pytree, so the reference's
  scalar-wrapping dance collapses into one broadcast.
- :func:`metric_average`            ↔ MetricAverageCallback
  (_keras/callbacks.py:33-67).

Beyond the reference (round-5 additions for the multi-process compiled
plane and device-resident input):

- :func:`global_array` / :func:`replicate` — assemble process-spanning
  inputs under ``hvdrun --jax-distributed`` (docs/running.md).
- :func:`make_scan_train_loop` — K optimizer steps per dispatch drawing
  batches from a :class:`horovod_tpu.data.DeviceCache`; amortizes
  per-dispatch and per-transfer latency.

Everything here runs inside shard_map/pmap over a named mesh axis (default
``'hvd'``); use horovod_tpu.run_on_mesh / shard_map directly to enter SPMD.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import optax

from ..common.device_names import OPTIMIZER_UPDATE
from ..compression import Compression, Compressor
from ..parallel import collectives, fusion
from ..parallel import sharded as _sharded
from ..parallel.collectives import ReduceOp
from ..parallel.mesh import BATCH_AXIS, HVD_AXIS, SHARD_AXIS
from ..parallel.sharded import (  # noqa: F401  (re-exported API surface)
    ShardedBuckets,
    ShardPlan,
    build_shard_plan,
    gather_params,
    mask_pad_updates,
    reduce_scatter_gradients,
    shard_params,
    shard_specs,
    unshard_params,
)
from ..common.config import Config


def _resolved_threshold(fusion_threshold):
    """None -> the HOROVOD_FUSION_THRESHOLD env knob (reference: the same
    env var tunes the hot path, operations.cc:1838); explicit values win."""
    if fusion_threshold is not None:
        return fusion_threshold
    return Config.from_env().fusion_threshold


def _resolved_num_buckets(num_buckets):
    """None -> the HOROVOD_NUM_BUCKETS env knob (default 1 = single fused
    buffer; K > 1 = reverse-backward-order overlap buckets)."""
    if num_buckets is not None:
        return max(1, int(num_buckets))
    return Config.from_env().num_buckets


def _resolved_compression(compression):
    """None -> the HOROVOD_COMPRESSION env knob (the same knob both eager
    engines honor, common/config.py), so one env var flips the wire dtype
    on every data plane; an explicit argument — including an explicit
    ``Compression.none`` — wins."""
    if compression is not None:
        return compression
    return Compression.by_name(Config.from_env().compression)


def _resolved_hierarchical(hierarchical, op, ici_axis: str,
                           dcn_axis: str) -> bool:
    """Resolve the previously-dormant HOROVOD_HIERARCHICAL_ALLREDUCE knob
    for the compiled plane (ISSUE 7): ``None`` reads the env — the same
    knob both eager engines honor — so one env var flips every data plane
    onto the two-level ladder.

    The env-resolved verdict degrades LOUDLY to the flat allreduce when the
    ladder cannot serve the call (non-SUM/AVERAGE reductions — the ladder
    is a sum machine, mirroring fusion.py's guard — or a mesh without the
    ('dcn','ici') axes, e.g. the plain 1-D 'hvd' mesh). An EXPLICIT
    ``hierarchical=True`` argument keeps raising in fusion.py instead:
    the caller asked for the ladder by hand and deserves the error."""
    explicit = hierarchical is not None
    if hierarchical is None:
        hierarchical = Config.from_env().hierarchical_allreduce
    if not hierarchical:
        return False
    if op not in (ReduceOp.SUM, ReduceOp.AVERAGE):
        if explicit:
            return True   # fusion.py raises its clear SUM/AVERAGE-only error
        from ..utils.logging import log

        log("warning",
            f"hierarchical allreduce supports SUM/AVERAGE only; running "
            f"{op.name} on the flat allreduce")
        return False
    if not explicit and (fusion._axis_size(ici_axis) is None
                         or fusion._axis_size(dcn_axis) is None):
        from ..utils.logging import log

        log("warning",
            "HOROVOD_HIERARCHICAL_ALLREDUCE=1 but the active mesh has no "
            f"({dcn_axis!r}, {ici_axis!r}) axes (use "
            "horovod_tpu.parallel.mesh.hierarchical_mesh); running the "
            "flat allreduce")
        return False
    return True


def _resolved_sharded(sharded) -> bool:
    """None -> the HOROVOD_SHARD_PARAMS env knob (ISSUE 14): one env var
    flips DistributedOptimizer onto the ZeRO wire pattern the same way
    HOROVOD_HIERARCHICAL_ALLREDUCE flips the ladder; an explicit argument
    — including an explicit False — wins."""
    if sharded is not None:
        return bool(sharded)
    return Config.from_env().shard_params


def allreduce_gradients(
    grads,
    axis_name: str = HVD_AXIS,
    op: ReduceOp = ReduceOp.AVERAGE,
    compression: type[Compressor] | None = None,
    fusion_threshold: int | None = None,
    hierarchical: bool | None = None,
    num_buckets: int | None = None,
    compression_min_bytes: int | None = None,
    ici_axis: str = "ici",
    dcn_axis: str = "dcn",
    dcn_compression=None,
    dcn_threshold: int | None = None,
):
    """Fused allreduce of a gradient pytree (the DistributedOptimizer hot
    path). ``fusion_threshold=None`` reads HOROVOD_FUSION_THRESHOLD (default
    64 MiB) so the env knob tunes the compiled path like the reference's;
    ``num_buckets=None`` reads HOROVOD_NUM_BUCKETS the same way (K > 1
    issues one collective per reverse-backward-order bucket so XLA can
    overlap communication with the rest of the backward pass);
    ``compression=None`` reads HOROVOD_COMPRESSION (eligible buckets are
    cast to the 16-bit wire dtype around their psum — half the wire bytes;
    see docs/compression.md for the per-bucket opt-outs);
    ``hierarchical=None`` reads HOROVOD_HIERARCHICAL_ALLREDUCE (ISSUE 7:
    each bucket rides the psum_scatter(ici) → psum(dcn) → all_gather(ici)
    ladder on a ('dcn','ici') mesh, with ``dcn_compression`` /
    ``dcn_threshold`` tiering the wire dtype and bucket size for the slow
    fabric — docs/hierarchical.md)."""
    fusion_threshold = _resolved_threshold(fusion_threshold)
    num_buckets = _resolved_num_buckets(num_buckets)
    compression = _resolved_compression(compression)
    hierarchical = _resolved_hierarchical(hierarchical, op, ici_axis,
                                          dcn_axis)

    return fusion.fused_allreduce(
        grads,
        axis_name=axis_name,
        threshold=fusion_threshold,
        op=op,
        hierarchical=hierarchical,
        ici_axis=ici_axis,
        dcn_axis=dcn_axis,
        num_buckets=num_buckets,
        compression=compression,
        compression_min_bytes=compression_min_bytes,
        dcn_compression=dcn_compression,
        dcn_threshold=dcn_threshold,
    )


def DistributedOptimizer(
    optimizer: optax.GradientTransformation,
    axis_name: str = HVD_AXIS,
    op: ReduceOp = ReduceOp.AVERAGE,
    compression: type[Compressor] | None = None,
    fusion_threshold: int | None = None,
    hierarchical: bool | None = None,
    backward_passes_per_step: int = 1,
    num_buckets: int | None = None,
    compression_min_bytes: int | None = None,
    ici_axis: str = "ici",
    dcn_axis: str = "dcn",
    dcn_compression=None,
    dcn_threshold: int | None = None,
    sharded: bool | None = None,
    shard_plan: "ShardPlan | None" = None,
    batch_axis: str = BATCH_AXIS,
    shard_axis: str = SHARD_AXIS,
) -> optax.GradientTransformation:
    """Wrap an optax optimizer so that ``update()`` first averages gradients
    across the mesh axis, exactly where the reference wraps
    compute_gradients/step.

    ``backward_passes_per_step > 1`` accumulates that many local microbatch
    gradients before one fused allreduce + inner update (reference
    torch/__init__.py:71-93), cutting collective frequency by the same factor.

    ``num_buckets`` (or HOROVOD_NUM_BUCKETS) > 1 splits that allreduce into
    K reverse-backward-order buckets so XLA can overlap early buckets'
    communication with the remaining backward compute — composes with
    ``backward_passes_per_step`` (buckets split the one post-accumulation
    allreduce) and with ``hierarchical`` (each bucket rides the
    RS→psum→AG ladder independently). Autotuned jointly with
    ``fusion_threshold`` by jax.autotune.tune.

    ``compression`` (or HOROVOD_COMPRESSION) = ``hvd.Compression.bf16`` /
    ``fp16`` halves the bytes each bucket's collective moves: eligible
    buckets are cast to the wire dtype before the psum and back after
    (non-float and tiny buckets opt out per bucket). bf16 is the TPU pick —
    fp32 exponent range, so no loss scaling. The wire dtype joins the
    ``(fusion_threshold, num_buckets)`` joint autotune as a third dimension
    (``autotune.tune(compressions=...)``), where ``"topk@<ratio>"`` specs put
    the sparse ratio on the same categorical axis (ISSUE 9).
    ``hvd.Compression.topk`` / ``adaptive`` resolve here too: the eager
    engines sparsify / apply the per-tier policy, while this compiled
    path substitutes the policy's dense tier table (full width on ICI,
    bf16 on the DCN psum) — XLA collectives cannot ship runtime-sparse
    frames. Full story: docs/compression.md.

    ``hierarchical`` (or HOROVOD_HIERARCHICAL_ALLREDUCE) routes every
    bucket over the two-level fabric ladder on a ``('dcn','ici')`` mesh,
    with ``dcn_compression`` / ``dcn_threshold`` selecting the slow
    fabric's wire dtype and bucket cap independently of the ICI tier — the
    multi-pod configuration (docs/hierarchical.md). Joins the autotune as
    the FOURTH dimension (``jax.autotune.tune(hierarchicals=...)``).

    ``sharded`` (or HOROVOD_SHARD_PARAMS, ISSUE 14) switches the wrapper
    onto the ZeRO wire pattern over a ``('batch', 'shard')`` mesh
    (docs/sharded.md): ``init()`` takes the :class:`ShardedBuckets` layout
    from :func:`shard_params` (so optimizer state shards 1/shard_size for
    free), ``update()`` takes the FULL gradient pytree and reduce-scatters
    each fused bucket into the owning shard (wire casts and bucket sizing
    unchanged from DP), the inner update runs on the 1/shard_size rows,
    and the zero-pad tail is masked so it never trains. The parameter
    refresh is the caller's :func:`gather_params` in the forward pass —
    one bucketed allgather per step. On a degenerate ``shard=1`` mesh the
    exchange traces to the same equations as the DP path.

    On a 3-D ``('batch','shard','model')`` mesh (ISSUE 19) the same wrapper
    drives tensor-parallel training: ``grads`` is one model rank's LOCAL
    gradient tree (parallel/tensor.py's column/row pairs compute it with
    the conjugate copy/reduce collectives), the ``('batch','shard')``
    exchange runs unchanged per model group, and the model-stacked
    ``shard_params_model`` layout keeps every device on the identical
    ``(1, chunk)`` code path — ``model=1`` compiles bitwise-identically to
    the 2-D plan. The mesh shape — now including the third axis — joins
    the autotune as the SIXTH dimension
    (``jax.autotune.tune(mesh_shapes=...)``; ``HOROVOD_MESH`` accepts
    ``"<batch>x<shard>x<model>"``).
    """
    sharded = _resolved_sharded(sharded)
    if sharded and backward_passes_per_step > 1:
        # optax.MultiSteps accumulates incoming grads in the PARAMS
        # structure; the sharded path feeds FULL grads against sharded
        # params, so the accumulator shapes cannot line up. Accumulate
        # microbatch grads in the training loop instead (full-tree sum
        # before one opt.update call).
        raise ValueError(
            "DistributedOptimizer(sharded=True) does not compose with "
            "backward_passes_per_step > 1; accumulate microbatch gradients "
            "in the training loop and call update() once per exchange")

    def sharded_update_fn(grads, state, params=None, **extra):
        plan = shard_plan
        if plan is None:
            shard_size = fusion._axis_size(shard_axis)
            if shard_size is None:
                raise ValueError(
                    f"DistributedOptimizer(sharded=True) needs the size of "
                    f"axis {shard_axis!r}: call inside shard_map over a "
                    f"('{batch_axis}', '{shard_axis}') mesh (e.g. "
                    f"horovod_tpu.sharded_mesh()) or pass shard_plan=")
            plan = _sharded.build_shard_plan(
                grads, shard_size, _resolved_threshold(fusion_threshold),
                _resolved_num_buckets(num_buckets))
        reduced = _sharded.reduce_scatter_gradients(
            grads, plan,
            batch_axis=batch_axis, shard_axis=shard_axis, op=op,
            compression=_resolved_compression(compression),
            compression_min_bytes=compression_min_bytes)
        with jax.named_scope(OPTIMIZER_UPDATE):
            updates, new_state = optimizer.update(reduced, state, params,
                                                  **extra)
        return _sharded.mask_pad_updates(updates, plan, shard_axis), new_state

    def update_fn(grads, state, params=None, **extra):
        reduced = allreduce_gradients(
            grads,
            axis_name=axis_name,
            op=op,
            compression=compression,
            fusion_threshold=fusion_threshold,
            hierarchical=hierarchical,
            num_buckets=num_buckets,
            compression_min_bytes=compression_min_bytes,
            ici_axis=ici_axis,
            dcn_axis=dcn_axis,
            dcn_compression=dcn_compression,
            dcn_threshold=dcn_threshold,
        )
        with jax.named_scope(OPTIMIZER_UPDATE):
            return optimizer.update(reduced, state, params, **extra)

    wrapped = optax.GradientTransformationExtraArgs(
        optimizer.init, sharded_update_fn if sharded else update_fn)
    if backward_passes_per_step > 1:
        wrapped = optax.MultiSteps(wrapped, every_k_schedule=backward_passes_per_step).gradient_transformation()
    return wrapped


def distributed_gradients(
    grads_or_fn,
    axis_name: str = HVD_AXIS,
    compression: type[Compressor] | None = None,
    **kw,
):
    """DistributedGradientTape analog: either allreduce an existing grad
    pytree, or wrap a ``jax.grad``-style function so its output gradients are
    averaged across ranks."""
    if callable(grads_or_fn):
        fn = grads_or_fn

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            out = fn(*args, **kwargs)
            if isinstance(out, tuple) and len(out) == 2:  # value_and_grad
                val, grads = out
                return val, allreduce_gradients(grads, axis_name, compression=compression, **kw)
            return allreduce_gradients(out, axis_name, compression=compression, **kw)

        return wrapper
    return allreduce_gradients(grads_or_fn, axis_name, compression=compression, **kw)


def grad(fun: Callable, axis_name: str = HVD_AXIS, **grad_kw) -> Callable:
    """``jax.grad`` that returns rank-averaged gradients."""
    return distributed_gradients(jax.grad(fun, **grad_kw), axis_name=axis_name)


def value_and_grad(fun: Callable, axis_name: str = HVD_AXIS, **grad_kw) -> Callable:
    """``jax.value_and_grad`` with rank-averaged gradients."""
    return distributed_gradients(jax.value_and_grad(fun, **grad_kw), axis_name=axis_name)


def broadcast_parameters(params, root_rank: int = 0, axis_name: str = HVD_AXIS):
    """Replace every leaf with root's value — initial-state consistency
    (reference broadcast_parameters, torch/__init__.py:200-230, and
    BroadcastGlobalVariablesHook, tensorflow/__init__.py:117-148)."""
    return jax.tree_util.tree_map(
        lambda t: collectives.broadcast(t, root_rank, axis_name), params
    )


def broadcast_optimizer_state(opt_state, root_rank: int = 0, axis_name: str = HVD_AXIS):
    """Broadcast optimizer state (reference torch/__init__.py:232-348; optax
    state is already a pytree of arrays/scalars, so no scalar wrapping is
    needed). Integer leaves (step counters) ride the same masked-psum."""

    def bcast_leaf(t):
        arr = jnp.asarray(t)
        return collectives.broadcast(arr, root_rank, axis_name)

    return jax.tree_util.tree_map(bcast_leaf, opt_state)


def broadcast_sharded_state(state, root_rank: int = 0,
                            batch_axis: str = BATCH_AXIS):
    """Initial-state consistency for the SHARDED layout (ISSUE 14): each
    shard row is owned by a different rank, so broadcasting from one global
    root would clobber every other rank's partition. The correct contract
    broadcasts along the BATCH (replica) axis only — rank (root, s) seeds
    shard s on every batch row — which is exactly what this does for an
    arbitrary pytree of :class:`ShardedBuckets` / replicated leaves.

    Works on params, optimizer state, or a whole training-state dict;
    :class:`ShardedBuckets` containers pass through transparently (they are
    pytrees). The plain :func:`broadcast_parameters` /
    :func:`broadcast_optimizer_state` stay the replicated-layout entry
    points."""
    return jax.tree_util.tree_map(
        lambda t: collectives.broadcast(jnp.asarray(t), root_rank,
                                        batch_axis), state)


def broadcast_object(obj, root_rank: int = 0, axis_name: str = HVD_AXIS):
    """Pytree-of-arrays broadcast; alias used by checkpoint-resume flows
    (reference resume_from_epoch broadcast in examples/pytorch_imagenet_resnet50.py)."""
    return jax.tree_util.tree_map(
        lambda t: collectives.broadcast(jnp.asarray(t), root_rank, axis_name), obj
    )


def global_array(local_data, spec=None, mesh=None, global_shape=None):
    """Assemble a process-spanning ``jax.Array`` from this process's local
    shard — the input half of the multi-process compiled plane.

    Under ``hvdrun --jax-distributed`` every process holds only its slice of
    the batch (the reference's per-rank DataLoader shard,
    examples/pytorch_imagenet_resnet50.py DistributedSampler), but a jitted
    step over the global mesh needs globally-shaped arrays. ``spec`` defaults
    to row-sharding along the ``'hvd'`` axis; pass ``P()`` for replicated
    leaves (parameters, optimizer state). Single-process worlds return the
    committed array unchanged in shape, so training loops are written once.
    """
    from jax.sharding import NamedSharding, PartitionSpec

    if mesh is None:
        from ..common import basics

        mesh = basics.default_mesh()
    if spec is None:
        spec = PartitionSpec(HVD_AXIS)
    return jax.make_array_from_process_local_data(
        NamedSharding(mesh, spec), local_data, global_shape)


def replicate(pytree, mesh=None):
    """Replicate every leaf of ``pytree`` across the global mesh (params /
    optimizer state on the multi-process compiled plane)."""
    from jax.sharding import PartitionSpec

    return jax.tree_util.tree_map(
        lambda t: global_array(t, spec=PartitionSpec(), mesh=mesh), pytree)


def make_scan_train_loop(train_step, cache, steps_per_dispatch: int = 8,
                         donate: bool = True):
    """Compile ``train_step`` into a K-steps-per-dispatch loop fed by a
    :class:`horovod_tpu.data.DeviceCache` — the TPU-native training-loop
    shape with ZERO host involvement between optimizer steps.

    Two costs motivate it: per-dispatch host latency, amortized over K
    steps, and per-step host→device transfers, which are zero here because
    batches come from the device-resident cache. (Neither cost has been
    measured on this installation: examples/realdata_benchmark.py is the
    harness.)

    ``train_step(params, opt_state, x, y) -> (params, opt_state, loss)``.
    Returns a jitted function
    ``fn(params, opt_state, ctr, data, labels) -> (params, opt_state,
    ctr, mean_loss)`` — thread ``ctr`` (from ``cache.counter()``) and pass
    ``cache.data`` / ``cache.labels`` every call (arguments, not
    closures: a closed-over shard would bake into the executable as a
    constant). With ``donate`` (default) params/opt_state/ctr update in
    place.
    """
    if steps_per_dispatch < 1:
        raise ValueError(f"steps_per_dispatch must be >= 1, got "
                         f"{steps_per_dispatch}")

    def scanned(params, opt_state, ctr, data, labels):
        def body(carry, _):
            p, o, c = carry
            x, y, c = cache.sample(c, data, labels)
            p, o, loss = train_step(p, o, x, y)
            return (p, o, c), loss

        (params, opt_state, ctr), losses = jax.lax.scan(
            body, (params, opt_state, ctr), None, length=steps_per_dispatch)
        return params, opt_state, ctr, losses.mean()

    return jax.jit(scanned, donate_argnums=(0, 1, 2) if donate else ())


def metric_average(value, axis_name: str = HVD_AXIS):
    """Average a scalar metric across ranks (reference MetricAverageCallback,
    _keras/callbacks.py:33-67)."""
    return collectives.allreduce(jnp.asarray(value), axis_name, ReduceOp.AVERAGE)
