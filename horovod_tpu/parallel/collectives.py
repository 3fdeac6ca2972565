"""In-jit collective operations over named mesh axes.

TPU-native data plane replacing the reference's MPI/NCCL execution paths in
PerformOperation (operations.cc:768-1621):

- allreduce      → lax.psum / pmean            (MPI_Allreduce / ncclAllReduce,
                                                operations.cc:1491-1586 / 1221-1446)
- allgather      → lax.all_gather(tiled)       (MPI_Allgatherv, operations.cc:843-1113)
- broadcast      → masked psum from root       (MPI_Bcast, operations.cc:1592-1612)
- reducescatter  → lax.psum_scatter            (internal step of hierarchical
                                                allreduce, operations.cc:1350)
- alltoall       → lax.all_to_all              (not exposed by the reference;
                                                required for sequence parallelism)
- hierarchical_allreduce → psum_scatter(ici) → psum(dcn) → all_gather(ici),
  the reference's NCCL ReduceScatter → cross-node MPI_Allreduce → NCCL
  AllGather ladder (operations.cc:1284-1436) as a mesh-axis composition.

These run *inside* shard_map/pmap bodies; XLA compiles them onto ICI/DCN.
There are no runtime communicator objects — the mesh axes are the
communicators. Op ordering is fixed at trace time, which supersedes the
reference's runtime coordinator negotiation for the compiled path (see
horovod_tpu/common/engine.py for the eager/host path that keeps the
negotiation semantics).
"""

from __future__ import annotations

from enum import Enum
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax

from .mesh import HVD_AXIS, DCN_AXIS, ICI_AXIS
from ..compat import axis_size


class ReduceOp(Enum):
    """Reduction ops. The reference supports only sum/average (allreduce
    divides by size when average=True, tensorflow/__init__.py:46-92); min/max/
    product come free with XLA and are exposed for completeness."""

    SUM = "sum"
    AVERAGE = "average"
    MIN = "min"
    MAX = "max"
    PRODUCT = "product"


def allreduce(x, axis_name: str = HVD_AXIS, op: ReduceOp = ReduceOp.AVERAGE):
    """Allreduce over a mesh axis. Default averages, matching hvd.allreduce
    (tensorflow/__init__.py:46: average=True)."""
    if op == ReduceOp.AVERAGE:
        return lax.pmean(x, axis_name)
    if op == ReduceOp.SUM:
        return lax.psum(x, axis_name)
    if op == ReduceOp.MIN:
        return lax.pmin(x, axis_name)
    if op == ReduceOp.MAX:
        return lax.pmax(x, axis_name)
    if op == ReduceOp.PRODUCT:
        # Exact for negatives, zeros, and infs: gather the axis's values and
        # multiply (a log-space psum would NaN on negatives and mishandle
        # zeros). O(axis) memory for one op nobody fuses — correctness wins.
        return jnp.prod(lax.all_gather(x, axis_name), axis=0)
    raise ValueError(f"unknown op {op}")


def bucketed_allreduce(buffers: Sequence, axis_name: str = HVD_AXIS,
                       op: ReduceOp = ReduceOp.AVERAGE) -> list:
    """One independent collective per array, in ISSUE order.

    On the flat data-parallel path ``fusion.fused_allreduce`` calls this once
    a bucket with the bucket's LEAVES as they are (PR 59: no flat buffer is
    filled; the copies in and out cost 0.45 + 0.25 + 0.95 ms round
    ``resnet50_4chip``'s 97.49 MiB bucket and 6.94 ms of
    ``solar_open2_seq8192_1chip``'s step: PERF_LEDGER.jsonl, PR 58); the
    sharded planner's degenerate ``shard=1`` case still passes its rank-1
    buffers. The order comes from fusion.build_plan's reverse-backward-order
    split: bucket 0 holds the last layers' gradients, which the backward
    pass produces first, so its psums' operands are ready while the rest of
    the backward compute is still running. Each psum is emitted as its own op
    (no jnp-level dependency between them) and forming the transfers is left
    to XLA's all-reduce combiner and latency-hiding scheduler — the compiled
    plane analog of Horovod's background thread starting allreduces
    mid-backward (operations.cc PerformOperation)."""
    return [allreduce(b, axis_name, op) for b in buffers]


def grouped_allreduce(xs, axis_name: str = HVD_AXIS, op: ReduceOp = ReduceOp.AVERAGE):
    """Allreduce a pytree in one logical group — the collective-launch analog
    of the reference's tensor fusion (operations.cc:2154-2266). XLA merges the
    psums; for explicit flat-buffer fusion with a byte threshold see
    horovod_tpu.parallel.fusion."""
    return jax.tree_util.tree_map(lambda t: allreduce(t, axis_name, op), xs)


def allgather(x, axis_name: str = HVD_AXIS):
    """Concatenate along dim 0 across the axis — hvd.allgather semantics
    (mpi_ops.cc allgather with rank-0-dim concat, operations.cc:843-928).
    Shapes must match on non-0 dims (validated at trace time, which replaces
    ConstructResponse's runtime shape check, operations.cc:412-444)."""
    return lax.all_gather(x, axis_name, axis=0, tiled=True)


def broadcast(x, root_rank: int = 0, axis_name: str = HVD_AXIS):
    """Every device gets root's value — hvd.broadcast (operations.cc:1592-1612).

    Implemented as a masked psum: one all-reduce, no O(size) gather buffer.
    """
    idx = lax.axis_index(axis_name)
    masked = jnp.where(idx == root_rank, x, jnp.zeros_like(x))
    return lax.psum(masked, axis_name)


def reducescatter(x, axis_name: str = HVD_AXIS, scatter_dim: int = 0, average: bool = False):
    """Reduce across the axis and scatter dim-0 shards. Exposed as a public op
    (the reference uses ReduceScatter only internally, operations.cc:1350)."""
    out = lax.psum_scatter(x, axis_name, scatter_dimension=scatter_dim, tiled=True)
    if average:
        out = out / axis_size(axis_name)
    return out


def alltoall(x, axis_name: str = HVD_AXIS, split_dim: int = 0, concat_dim: int = 0):
    """All-to-all exchange — the primitive sequence/context parallelism needs
    (absent from the reference, see SURVEY.md §5.7; first-class here)."""
    return lax.all_to_all(x, axis_name, split_axis=split_dim, concat_axis=concat_dim, tiled=True)


def ppermute(x, perm: Sequence[tuple[int, int]], axis_name: str = HVD_AXIS):
    """Point-to-point permutation (ring step for ring attention / pipeline)."""
    return lax.ppermute(x, axis_name, perm=perm)


def ring_shift(x, axis_name: str = HVD_AXIS, shift: int = 1):
    """Shift values around the axis ring by ``shift`` positions."""
    n = axis_size(axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis_name, perm=perm)


def hierarchical_allgather(x, ici_axis: str = ICI_AXIS, dcn_axis: str = DCN_AXIS):
    """Two-stage allgather: gather over ICI first, then over DCN
    (reference hierarchical allgather via MPI shared-memory window +
    cross-node Allgatherv, operations.cc:929-1034). Note the concat order is
    (dcn-major, ici-minor) — matches rank order for the ('dcn','ici') mesh."""
    local = lax.all_gather(x, ici_axis, axis=0, tiled=True)
    return lax.all_gather(local, dcn_axis, axis=0, tiled=True)


def sparse_allreduce(values, indices, axis_name: str = HVD_AXIS,
                     average: bool = True):
    """Sparse-gradient allreduce as a pair of allgathers (reference
    hvd.allreduce on tf.IndexedSlices, tensorflow/__init__.py:72-83): embed
    gradients stay in (values, indices) form — the caller scatter-adds them
    into the dense parameter. When ``average``, values are pre-divided by
    world size like the reference."""
    if average:
        values = values / axis_size(axis_name)
    all_values = lax.all_gather(values, axis_name, axis=0, tiled=True)
    all_indices = lax.all_gather(indices, axis_name, axis=0, tiled=True)
    return all_values, all_indices


def hierarchical_allreduce(
    x,
    ici_axis: str = ICI_AXIS,
    dcn_axis: str = DCN_AXIS,
    average: bool = True,
    dcn_wire_dtype=None,
):
    """Two-level allreduce: ReduceScatter over ICI → Allreduce over DCN →
    AllGather over ICI (reference operations.cc:1284-1436). DCN traffic is
    1/ici_size of the flat allreduce — the same bandwidth win the reference's
    NCCL+MPI ladder buys on RoCE clusters.

    ``dcn_wire_dtype`` (ISSUE 7 per-fabric-tier wire dtype): cast the
    already-scattered shard to this dtype around the cross-host ``psum``
    only — the slow fabric carries 16-bit payloads while both ICI stages
    stay at full width. Combined with the 1/ici_size scatter this is where
    the multi-pod bytes go from B to B/(2·ici_size) per device.

    Requires dim 0 divisible by the ici axis size; callers fuse into flat
    buffers padded to the axis size (fusion.py handles this).
    """
    scattered = lax.psum_scatter(x, ici_axis, scatter_dimension=0, tiled=True)
    orig = scattered.dtype
    if dcn_wire_dtype is not None and jnp.dtype(dcn_wire_dtype) != orig:
        scattered = scattered.astype(dcn_wire_dtype)
    reduced = lax.psum(scattered, dcn_axis)
    if reduced.dtype != orig:
        reduced = reduced.astype(orig)
    out = lax.all_gather(reduced, ici_axis, axis=0, tiled=True)
    if average:
        out = out / (axis_size(ici_axis) * axis_size(dcn_axis))
    return out
