"""Tensor fusion: plan many small gradients into few buckets, one exchange a
bucket.

TPU-native equivalent of the reference's fusion pipeline — the coordinator's
greedy same-dtype/device merge up to HOROVOD_FUSION_THRESHOLD
(operations.cc:2154-2266), the per-(device,framework) fusion buffer
(fusion_buffer_manager.h:41-47), and the MEMCPY_IN/OUT_FUSION_BUFFER steps of
PerformOperation (operations.cc:798-814, 1491-1586).

Differences by design:
- Bucket construction happens at *trace time* from the gradient pytree, so
  every rank builds identical buckets deterministically (tree_flatten order) —
  no runtime negotiation needed for the compiled path. This resolves the
  async-enqueue-vs-XLA ordering problem called out in SURVEY.md §7.
- On the flat data-parallel path a bucket is NOT a copy (PR 59): its leaves
  go to the collective as they are, in the plan's order, and XLA's all-reduce
  combiner forms the transfers. The plan still decides the order in which the
  reductions are emitted, the wire verdict (on the bucket's dtype and total
  bytes) and every trace-time gauge. The "memcpy into fusion buffer" was a
  concatenate into a rank-1 buffer and a slice + reshape back; on the chip
  neither is a bitcast of a tiled 2-D or 3-D array, and the pair cost 6.94 ms
  of ``solar_open2_seq8192_1chip``'s 181.56 ms step, in a world of one where
  XLA drops the collective itself (PERF_LEDGER.jsonl, PR 58:
  ``reshape__bench_optimizer__bench_optimizer/hvd_fusion_unpack/`` 0.1273 s).
- ``fuse`` / ``unfuse`` stay for the two planners whose reduce-scatter needs
  one divisible length a bucket: ``hierarchical=True`` here and
  ``parallel/sharded.py``. ``horovod_fusion_staged_bytes`` says how many bytes
  a step copies into such buffers (0 on the flat path).
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import collectives
from .mesh import HVD_AXIS
from ..common.device_names import FUSED_ALLREDUCE, FUSION_PACK, FUSION_UNPACK
from ..common.config import (DEFAULT_COMPRESSION_MIN_BYTES,
                             DEFAULT_FUSION_THRESHOLD, _env_int)
from ..compat import axis_size
from ..compression import compiled_formats, compression_name, numpy_wire_dtype


@dataclass(frozen=True)
class _Leaf:
    index: int          # position in tree_flatten order
    shape: tuple
    dtype: Any
    size: int           # elements


@dataclass(frozen=True)
class FusionPlan:
    """Static bucketing of a pytree's leaves: list of buckets, each a tuple of
    leaf descriptors with the same dtype, total bytes ≤ threshold (single
    oversize leaves get their own bucket, as in the reference where a tensor
    larger than the threshold is sent unfused).

    Bucket order is ISSUE order: the collective for ``buckets[0]`` is
    emitted first. With ``reverse_order`` (the K-bucket overlap plan) that
    is reverse backward order — last-layer gradients, which the backward
    pass produces first, ride the first collective, mirroring the order
    Horovod's background thread naturally enqueues them in."""

    treedef: Any
    buckets: tuple[tuple[_Leaf, ...], ...]
    pad_to: int = 1     # pad each buffer length to a multiple (hierarchical RS)
    reverse_order: bool = False

    @property
    def num_buckets(self) -> int:
        return len(self.buckets)


def _leaf_descs(tree) -> tuple[list[_Leaf], Any]:
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    descs = []
    for i, leaf in enumerate(leaves):
        shape = tuple(leaf.shape)
        dtype = jnp.asarray(leaf).dtype if not hasattr(leaf, "dtype") else leaf.dtype
        descs.append(_Leaf(i, shape, jnp.dtype(dtype), int(np.prod(shape)) if shape else 1))
    return descs, treedef


def build_plan(tree, threshold: int = DEFAULT_FUSION_THRESHOLD, pad_to: int = 1,
               num_buckets: int = 1) -> FusionPlan:
    """Plan the bucketing of ``tree``'s leaves.

    ``num_buckets <= 1`` (default): the historical single-pass greedy
    same-dtype merge up to ``threshold``, in forward tree_flatten order —
    fewest, largest collectives (reference operations.cc:2154-2266).

    ``num_buckets = K > 1``: the overlap plan. Leaves are walked in REVERSE
    tree_flatten order (last-layer gradients first — the order the backward
    pass produces them in) and packed into ~K byte-balanced same-dtype
    buckets. Issuing one independent collective per bucket in this order
    lets XLA's latency-hiding scheduler start allreducing early buckets
    while the rest of the backward compute is still in flight — the
    compiled plane expression of Horovod's background-thread overlap
    (PAPER.md L1; same design point as PyTorch DDP's reverse-order
    gradient buckets). ``threshold`` remains a hard cap on bucket bytes,
    so the two knobs compose: K sets the minimum split, the threshold
    bounds each piece."""
    descs, treedef = _leaf_descs(tree)
    if num_buckets > 1:
        buckets = _reverse_order_buckets(descs, num_buckets, threshold)
        return FusionPlan(treedef, tuple(tuple(b) for b in buckets), pad_to,
                          reverse_order=True)

    # Greedy same-dtype packing in deterministic order (reference merges only
    # matching dtype/device responses, operations.cc:2165-2207).
    buckets: list[list[_Leaf]] = []
    cur: dict[Any, list[_Leaf]] = {}
    cur_bytes: dict[Any, int] = {}
    for d in descs:
        nbytes = d.size * jnp.dtype(d.dtype).itemsize
        key = d.dtype
        if key in cur and cur_bytes[key] + nbytes <= threshold:
            cur[key].append(d)
            cur_bytes[key] += nbytes
        else:
            if key in cur:
                buckets.append(cur[key])
            cur[key] = [d]
            cur_bytes[key] = nbytes
    for key in sorted(cur.keys(), key=str):
        buckets.append(cur[key])
    buckets.sort(key=lambda b: b[0].index)
    return FusionPlan(treedef, tuple(tuple(b) for b in buckets), pad_to)


def dcn_capped_threshold(threshold: int, dcn_threshold: Optional[int],
                         scatter_width: int) -> int:
    """Compose the per-fabric-tier bucket cap with the plain threshold.

    A bucket whose exchange scatters 1/``scatter_width`` of its bytes over
    the slow fabric (the hierarchical ladder's cross-host psum, or the
    sharded planner's per-shard chunk) is bounded by
    ``HOROVOD_DCN_FUSION_THRESHOLD`` on that tier, so the effective bucket
    cap is ``dcn_threshold * scatter_width`` — min-composed with the plain
    threshold (both stay hard caps). ``dcn_threshold`` None reads the env;
    0 means no separate cap."""
    if dcn_threshold is None:
        dcn_threshold = _env_int("HOROVOD_DCN_FUSION_THRESHOLD", 0)
    if dcn_threshold and dcn_threshold > 0:
        cap = int(dcn_threshold) * int(scatter_width)
        return min(threshold, cap) if threshold > 0 else cap
    return threshold


def _reverse_order_buckets(descs: Sequence[_Leaf], num_buckets: int,
                           threshold: int) -> list[list[_Leaf]]:
    """K-way byte-balanced split in reverse leaf order (overlap plan).

    Greedy over leaves from last to first: a bucket closes when it reaches
    the balanced target (total/K) while earlier buckets remain in budget, or
    when the dtype changes (buffers are concatenated, so a bucket is
    single-dtype), or when adding the leaf would blow the ``threshold`` cap.
    The final bucket absorbs any remainder, so the plan yields exactly K
    buckets for a single-dtype tree with >= K leaves and at most a few more
    across dtype transitions — never a silent merge back to one."""
    remaining = sum(d.size * d.dtype.itemsize for d in descs)
    buckets: list[list[_Leaf]] = []
    cur: list[_Leaf] = []
    cur_bytes = 0

    def target() -> int:
        # Re-balance over what's left (current bucket included): a static
        # total/K target lets a bucket that lands just under it swallow the
        # next one's share and the plan quietly underfills K.
        left = num_buckets - len(buckets)
        return max(1, -(-(cur_bytes + remaining) // max(1, left)))   # ceil

    for d in reversed(descs):
        nbytes = d.size * d.dtype.itemsize
        # Pre-add close: dtype change, threshold cap, or a leaf that would
        # overshoot the balanced target by more than the bucket's current
        # shortfall (2*cur + n > 2*target) — without the last rule a K much
        # larger than the leaf count silently merges leaves that should
        # each get their own bucket.
        if cur and (cur[0].dtype != d.dtype
                    or (threshold > 0 and cur_bytes + nbytes > threshold)
                    or (2 * cur_bytes + nbytes > 2 * target()
                        and len(buckets) < num_buckets - 1)):
            buckets.append(cur)
            cur, cur_bytes = [], 0
        cur.append(d)
        cur_bytes += nbytes
        remaining -= nbytes
        if cur_bytes >= target() and len(buckets) < num_buckets - 1:
            buckets.append(cur)
            cur, cur_bytes = [], 0
    if cur:
        buckets.append(cur)
    return buckets


def fuse(tree, plan: FusionPlan) -> list:
    """Flatten + concatenate each bucket into one 1-D buffer (the fusion
    buffer fill, MEMCPY_IN_FUSION_BUFFER)."""
    leaves = jax.tree_util.tree_leaves(tree)
    buffers = []
    for bucket in plan.buckets:
        flat = [jnp.ravel(leaves[d.index]) for d in bucket]
        buf = jnp.concatenate(flat) if len(flat) > 1 else flat[0]
        if plan.pad_to > 1:
            rem = buf.shape[0] % plan.pad_to
            if rem:
                buf = jnp.pad(buf, (0, plan.pad_to - rem))
        buffers.append(buf)
    return buffers


def unfuse(buffers: Sequence, plan: FusionPlan):
    """Split buffers back into leaves (MEMCPY_OUT_FUSION_BUFFER) and rebuild
    the pytree."""
    leaves: list = [None] * plan.treedef.num_leaves
    for bucket, buf in zip(plan.buckets, buffers):
        offset = 0
        for d in bucket:
            leaves[d.index] = jnp.reshape(buf[offset : offset + d.size], d.shape)
            offset += d.size
    return jax.tree_util.tree_unflatten(plan.treedef, leaves)


def wire_dtype_for_bucket(compression, dtype, nbytes: int, op,
                          min_bytes: Optional[int] = None):
    """Per-bucket wire-compression verdict for the compiled plane: the wire
    dtype the bucket's collective should run at, or None to opt out.

    Opt-outs (ISSUE 5): non-float buckets (casting ints corrupts), buckets
    already at/below 2 bytes/element, buckets smaller than
    HOROVOD_COMPRESSION_MIN_BYTES (the cast pair costs more than it saves,
    and loss scalars keep full precision), and non-linear reductions
    (PRODUCT rides an all-gather; MIN/MAX results are exact per element, so
    they pass through uncompressed rather than silently losing bits)."""
    if op not in (collectives.ReduceOp.SUM, collectives.ReduceOp.AVERAGE):
        return None
    if min_bytes is None:
        min_bytes = _env_int("HOROVOD_COMPRESSION_MIN_BYTES",
                             DEFAULT_COMPRESSION_MIN_BYTES)
    if nbytes < min_bytes:
        return None
    wire = numpy_wire_dtype(compression_name(compression), dtype)
    return jnp.dtype(wire) if wire is not None else None


# One-shot warning latch: topk on the compiled plane runs dense (see the
# resolution block in fused_allreduce); say so once, not per trace.
# (The 'adaptive' analog stopped warning in ISSUE 16: the bf16
# substitution moved into common/policy.py compiled_tier_format as the
# DESIGNED tier answer — see COMPILED_TOPK_SUBSTITUTE — and a designed
# behaviour is not warning material. The fallback counter remains.)
_TOPK_COMPILED_WARNED = False


def fused_allreduce(
    tree,
    axis_name: str = HVD_AXIS,
    threshold: int = DEFAULT_FUSION_THRESHOLD,
    op: collectives.ReduceOp = collectives.ReduceOp.AVERAGE,
    hierarchical: bool = False,
    ici_axis: str = "ici",
    dcn_axis: str = "dcn",
    num_buckets: int = 1,
    compression=None,
    compression_min_bytes: Optional[int] = None,
    dcn_compression=None,
    dcn_threshold: Optional[int] = None,
):
    """The Horovod fast path: plan → the buckets' collectives in issue
    order.

    Flat (``hierarchical=False``): a bucket's leaves go to the collective as
    they are, one ``collectives.allreduce`` a leaf, buckets in issue order and
    a bucket's leaves in its own; nothing is ravelled, concatenated, padded,
    sliced or reshaped, and XLA's all-reduce combiner forms the transfers
    (PR 59; the copies cost 6.94 ms of a 181.56 ms step in
    ``solar_open2_seq8192_1chip`` and 0.70 + 0.95 ms round the 97.49 MiB
    bucket of ``resnet50_4chip``: PERF_LEDGER.jsonl, PR 58). Hierarchical:
    fuse → ladder → unfuse, one padded rank-1 buffer a bucket, because the
    reduce-scatter needs a length the ``ici`` axis divides.

    ``compression`` (a :class:`horovod_tpu.compression.Compressor`, a
    HOROVOD_COMPRESSION name, or None) is the wire optimization: eligible
    buckets are cast to the 16-bit wire dtype right before their collective
    and cast back right after, halving the bytes every ``psum`` moves over
    ICI/DCN (reference FP16Compressor semantics, decided per bucket
    instead of per tensor). Eligibility is per bucket, on the bucket's dtype
    and total bytes — see :func:`wire_dtype_for_bucket`; on the flat path the
    cast pair is applied to each of an eligible bucket's leaves.

    ``num_buckets > 1`` switches to the reverse-backward-order overlap plan
    (build_plan): the collectives are issued last-layer-first, each
    becoming schedulable as soon as its gradients exist — the knob
    the A/B bench and the autotuner drive (HOROVOD_NUM_BUCKETS).

    Fabric-aware tiering (ISSUE 7, ``hierarchical=True`` only):
    ``dcn_compression`` picks a wire dtype for the cross-host psum alone —
    full width on ICI, 16-bit on DCN (None inherits HOROVOD_DCN_COMPRESSION
    from the env, which itself defaults to the global ``compression``);
    ``dcn_threshold`` caps the bytes any one bucket ships over DCN (the
    ladder scatters 1/ici_size of the bucket cross-host, so the effective
    bucket cap becomes ``dcn_threshold * ici_size``; None reads
    HOROVOD_DCN_FUSION_THRESHOLD, 0 = no separate cap). The per-tier plan
    lands in trace-time gauges (metrics.record_tier_plan)."""
    # Policy names resolve to concrete dense formats here (ISSUE 9 + 13):
    # the compiled plane can't ship runtime-sparse frames (XLA collectives
    # have static shapes), so 'topk' runs dense — LOUDLY. 'adaptive' now
    # reads the FIRST-CLASS per-tier table from common/policy.py: the ICI
    # tier resolves here, the DCN tier resolves per fused bucket below
    # (same (size, dtype, tier) inputs the eager engines evaluate per
    # tensor); only a tier whose table answer is the genuinely unservable
    # 'topk' counts a fallback and substitutes bf16 (ROADMAP satellite).
    _comp_name = compression_name(compression)
    _adaptive = _comp_name == "adaptive"
    if _comp_name == "topk":
        global _TOPK_COMPILED_WARNED
        if not _TOPK_COMPILED_WARNED:
            _TOPK_COMPILED_WARNED = True
            from ..utils.logging import log

            log("warning",
                "HOROVOD_COMPRESSION=topk applies to the eager engines "
                "only; the compiled plane ships dense buckets (use "
                "bf16/adaptive for a compiled plane wire cut)")
        _ici_fmt, _dcn_fmt = compiled_formats(_comp_name)
        if dcn_compression is None:
            dcn_compression = (os.environ.get("HOROVOD_DCN_COMPRESSION", "")
                               or _dcn_fmt)
        compression = _ici_fmt
    elif _adaptive:
        from ..common.policy import compiled_tier_format

        # ICI: the table is size-independent on the fast fabric (full
        # width); resolved through the policy module all the same so a
        # future table change lands here without code edits.
        compression = compiled_tier_format(1 << 30, jnp.float32, "ici")
    pad_to = 1
    if hierarchical and op not in (collectives.ReduceOp.SUM,
                                   collectives.ReduceOp.AVERAGE):
        # The reduce-scatter → psum → all-gather ladder is a sum machine;
        # silently summing a requested MAX/MIN/PRODUCT would be wrong.
        raise ValueError(
            f"hierarchical fusion supports SUM/AVERAGE only (got {op}); "
            f"use hierarchical=False for {op.name}")
    if hierarchical:
        # psum_scatter needs dim 0 divisible by the ici axis size; plan pads.
        # The size must resolve whether or not the leaves are tracers (a
        # shard_map body may pass closed-over concrete arrays), so fall back
        # from the trace's axis env to the ambient `with Mesh(...)` context.
        pad_to = _axis_size(ici_axis)
        if pad_to is None:
            raise ValueError(
                f"hierarchical fusion needs the size of axis {ici_axis!r}: "
                f"call inside shard_map/pmap or under `with mesh:`")
        # Per-fabric-tier bucket sizing: cap what any single bucket ships
        # over the slow fabric. A bucket's DCN shard is nbytes/ici_size, so
        # a DCN cap of D bounds bucket bytes at D*ici_size — composed with
        # the plain threshold as a min (both remain hard caps). Shared with
        # the sharded planner (sharded.build_shard_plan), where the scatter
        # width is the shard axis size.
        threshold = dcn_capped_threshold(threshold, dcn_threshold, pad_to)
    plan = build_plan(tree, threshold, pad_to=pad_to, num_buckets=num_buckets)
    # Telemetry (ISSUE 2): record the bucket geometry — count, per-bucket
    # bytes in issue order, buffer occupancy, planned overlap bound, the
    # bytes staged into flat buffers — in the metrics registry. Runs at TRACE
    # time (once per compile), so the compiled hot path carries zero
    # instrumentation cost.
    from ..metrics import record_plan, record_wire_plan

    record_plan(plan, threshold, staged=hierarchical)
    # A bucket is a list of arrays: its leaves as they are on the flat path,
    # its one padded rank-1 buffer where the ladder's reduce-scatter needs
    # one. Every verdict and gauge below reads the bucket's dtype and the
    # sum over its arrays, so both forms record the same numbers.
    with jax.named_scope(FUSION_PACK):
        if hierarchical:
            buckets = [[buf] for buf in fuse(tree, plan)]
        else:
            leaves = jax.tree_util.tree_leaves(tree)
            buckets = [[jnp.asarray(leaves[d.index]) for d in bucket]
                       for bucket in plan.buckets]
        orig_dtypes = [b[0].dtype for b in buckets]
        # Wire compression (ISSUE 5): per-bucket cast to the 16-bit wire
        # dtype around the collective. Decided at trace time, so the hot
        # path carries exactly one convert pair per array of an eligible
        # bucket and nothing else.
        wire = [wire_dtype_for_bucket(compression, b[0].dtype, _nbytes(b),
                                      op, compression_min_bytes)
                for b in buckets]
        record_wire_plan(
            compression_name(compression),
            [(_nbytes(b), w is not None,
              _size(b) * (jnp.dtype(w).itemsize if w is not None else 0))
             for b, w in zip(buckets, wire)])
        buckets = [[x.astype(w) for x in b] if w is not None else b
                   for b, w in zip(buckets, wire)]
    # Per-fabric-tier wire dtype (ISSUE 7): the DCN psum of the hierarchical
    # ladder may run at its own (usually narrower) wire dtype. Computed
    # against the AS-SHIPPED buffer dtype — a bucket already cast to a
    # 16-bit ICI wire opts out (nothing narrower to gain), and all the
    # per-bucket opt-outs of wire_dtype_for_bucket apply unchanged.
    dcn_wire = [None] * len(buckets)
    _dcn_plan_name = ""
    if hierarchical:
        if (_adaptive and dcn_compression is None
                and not os.environ.get("HOROVOD_DCN_COMPRESSION", "")):
            # Adaptive DCN tier, per fused bucket (ISSUE 13 satellite): the
            # policy table answers with the same (size, dtype, tier) inputs
            # the eager engines use, with the topk answer already
            # substituted by the designed servable format
            # (policy.COMPILED_TOPK_SUBSTITUTE — XLA collectives cannot
            # ship runtime-sparse frames). The counter tracks substituting
            # traces for observability; no warning, this is the table.
            from ..common.policy import compiled_tier_format

            _fmts = []
            _fallbacks = 0
            for b in buckets:
                fmt, substituted = compiled_tier_format(
                    _nbytes(b), b[0].dtype, "dcn", with_fallback=True)
                _fallbacks += 1 if substituted else 0
                _fmts.append(fmt)
            dcn_wire = [wire_dtype_for_bucket(f, b[0].dtype, _nbytes(b),
                                              op, compression_min_bytes)
                        for f, b in zip(_fmts, buckets)]
            _dcn_plan_name = "adaptive"
            if _fallbacks:
                from ..metrics import registry as _metrics_registry

                _metrics_registry().counter(
                    "horovod_compiled_adaptive_fallback_total",
                    help="compiled plane traces where an 'adaptive' DCN "
                         "tier answered topk and shipped the designed "
                         "substitute (common/policy.py "
                         "COMPILED_TOPK_SUBSTITUTE) instead — by design, "
                         "not an error: XLA collectives cannot ship "
                         "runtime-sparse frames").inc()
        else:
            if dcn_compression is None:
                dcn_compression = (
                    os.environ.get("HOROVOD_DCN_COMPRESSION", "")
                    or compression)
            dcn_wire = [wire_dtype_for_bucket(dcn_compression, b[0].dtype,
                                              _nbytes(b), op,
                                              compression_min_bytes)
                        for b in buckets]
            _dcn_plan_name = compression_name(dcn_compression)
    from ..metrics import record_tier_plan

    record_tier_plan(
        hierarchical,
        ici_wire=compression_name(compression),
        dcn_wire=_dcn_plan_name,
        ici_size=pad_to,
        bucket_bytes=[_nbytes(b) for b in buckets],
        dcn_bucket_bytes=[
            (_size(b) // pad_to) * int(jnp.dtype(dw).itemsize
                                       if dw is not None
                                       else b[0].dtype.itemsize)
            for b, dw in zip(buckets, dcn_wire)] if hierarchical else [])
    # Named scopes (common/device_names.py): the device profile's HLO ops
    # carry the bucket count on the collectives and pack / unpack on the
    # casts (and, hierarchical, the copies) around them. Metadata only: no
    # operation is added.
    with jax.named_scope(f"{FUSED_ALLREDUCE}{len(buckets)}"):
        if hierarchical:
            reduced = [
                [collectives.hierarchical_allreduce(
                    b[0], ici_axis=ici_axis, dcn_axis=dcn_axis,
                    average=(op == collectives.ReduceOp.AVERAGE),
                    dcn_wire_dtype=dw)]
                for b, dw in zip(buckets, dcn_wire)
            ]
        else:
            reduced = [collectives.bucketed_allreduce(b, axis_name, op)
                       for b in buckets]
    with jax.named_scope(FUSION_UNPACK):
        reduced = [[x.astype(dt) for x in r] if w is not None else r
                   for r, w, dt in zip(reduced, wire, orig_dtypes)]
        if hierarchical:
            return unfuse([r[0] for r in reduced], plan)
        out: list = [None] * plan.treedef.num_leaves
        for bucket, r in zip(plan.buckets, reduced):
            for d, x in zip(bucket, r):
                out[d.index] = x
        return jax.tree_util.tree_unflatten(plan.treedef, out)


def _nbytes(arrays) -> int:
    """Bytes of a bucket's arrays together."""
    return sum(int(x.nbytes) for x in arrays)


def _size(arrays) -> int:
    """Elements of a bucket's arrays together."""
    return sum(int(x.size) for x in arrays)


def _axis_size(axis_name: str):
    """Resolve a mesh axis size from the active trace or, failing that, the
    ambient ``with Mesh(...)`` context; None if neither binds the name.

    The ambient-mesh fallback reads ``jax._src.mesh.thread_resources`` — a
    private API a jax upgrade may move (ADVICE r3). It is best-effort
    behind try/except: if it disappears, we return None and the caller
    raises its actionable "pass ici_axis_size=" ValueError instead of an
    ImportError at trace time."""
    try:
        return int(axis_size(axis_name))
    except NameError:
        pass
    try:
        from jax._src import mesh as mesh_lib

        env_mesh = mesh_lib.thread_resources.env.physical_mesh
        if not env_mesh.empty and axis_name in env_mesh.shape:
            return int(env_mesh.shape[axis_name])
    except (ImportError, AttributeError):
        pass
    return None
