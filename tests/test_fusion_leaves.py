"""The flat gradient exchange without the fusion buffer's copies (PR 59): on
``fused_allreduce(..., hierarchical=False)`` a bucket's leaves go to the
collective as they are. The plan still decides the order, the wire verdict
and every trace-time gauge; ``fuse`` / ``unfuse`` stay for the hierarchical
and sharded planners."""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from horovod_tpu.common import device_names as names
from horovod_tpu.compat import shard_map
from horovod_tpu.metrics import registry
from horovod_tpu.metrics.overlap import last_plan, last_tier_plan, last_wire_plan
from horovod_tpu.parallel import collectives, fusion
from horovod_tpu.parallel.collectives import ReduceOp
from horovod_tpu.parallel.mesh import data_parallel_mesh

OPS = [ReduceOp.SUM, ReduceOp.AVERAGE, ReduceOp.MIN, ReduceOp.MAX,
       ReduceOp.PRODUCT]
SPLITS = 600            # bytes: the f32 and bf16 leaves split into buckets
WHOLE = 1 << 20         # every dtype in one bucket


def _tree(ranks, seed=0):
    """Per-rank values on a leading axis: f32, bf16 and int32 leaves, a
    scalar, and a leaf (4,096 bytes a rank) larger than ``SPLITS``."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)

    def normal(i, shape, dtype=jnp.float32):
        return jax.random.normal(keys[i], (ranks,) + shape).astype(dtype)

    return {
        "conv": normal(0, (3, 3, 4, 5)),
        "bias": normal(1, (5,)),
        "scalar": normal(2, ()),
        "oversize": normal(3, (32, 32)),
        "half_w": normal(4, (16, 9), jnp.bfloat16),
        "half_b": normal(5, (9,), jnp.bfloat16),
        "count": jax.random.randint(keys[6], (ranks, 7), -3, 4, jnp.int32),
        "tail": normal(7, (2, 65)),
    }


def _local(tree):
    return jax.tree_util.tree_map(lambda t: t[0], tree)


def _through_buffers(tree, axis_name, threshold, op, num_buckets,
                     compression, min_bytes):
    """What the parent ran on the flat path: fuse -> wire cast -> one
    collective a buffer -> cast back -> unfuse."""
    plan = fusion.build_plan(tree, threshold, num_buckets=num_buckets)
    buffers = fusion.fuse(tree, plan)
    dtypes = [b.dtype for b in buffers]
    wire = [fusion.wire_dtype_for_bucket(compression, b.dtype, int(b.nbytes),
                                         op, min_bytes) for b in buffers]
    buffers = [b.astype(w) if w is not None else b
               for b, w in zip(buffers, wire)]
    reduced = [collectives.allreduce(b, axis_name, op) for b in buffers]
    reduced = [r.astype(dt) if w is not None else r
               for r, w, dt in zip(reduced, wire, dtypes)]
    return fusion.unfuse(reduced, plan)


def _both(mesh, tree, threshold, op, num_buckets, compression, min_bytes=64):
    def run(fn):
        return jax.jit(shard_map(
            lambda t: fn(_local(t)), mesh=mesh, in_specs=P("hvd"),
            out_specs=P(), check_vma=False))(tree)

    got = run(lambda t: fusion.fused_allreduce(
        t, "hvd", threshold=threshold, op=op, num_buckets=num_buckets,
        compression=compression, compression_min_bytes=min_bytes))
    want = run(lambda t: _through_buffers(
        t, "hvd", threshold, op, num_buckets, compression, min_bytes))
    return got, want


def _assert_bitwise(got, want):
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want))
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        assert a.dtype == b.dtype and a.shape == b.shape, path
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), path


# ------------------------------------------ (a) the values, bitwise

@pytest.mark.parametrize("wire", [None, "bf16"], ids=["full", "bf16wire"])
@pytest.mark.parametrize("threshold", [SPLITS, WHOLE], ids=["split", "whole"])
@pytest.mark.parametrize("num_buckets", [1, 4], ids=["k1", "k4"])
@pytest.mark.parametrize("op", OPS, ids=[o.name for o in OPS])
def test_leaves_equal_buffers_bitwise_on_eight(mesh8, op, num_buckets,
                                               threshold, wire):
    got, want = _both(mesh8, _tree(8), threshold, op, num_buckets, wire)
    _assert_bitwise(got, want)


@pytest.mark.parametrize("wire", [None, "bf16"], ids=["full", "bf16wire"])
@pytest.mark.parametrize("op", OPS, ids=[o.name for o in OPS])
def test_leaves_equal_buffers_bitwise_on_a_mesh_of_one(op, wire):
    mesh = data_parallel_mesh(jax.devices()[:1])
    tree = _tree(1, seed=3)
    got, want = _both(mesh, tree, SPLITS, op, 4, wire)
    _assert_bitwise(got, want)
    if wire is None and op != ReduceOp.AVERAGE:
        _assert_bitwise(got, _local(tree))      # the identity, to the bit
    elif wire is None:      # pmean of an integer leaf divides: float32 of it
        _assert_bitwise(
            {k: v for k, v in got.items() if k != "count"},
            {k: v for k, v in _local(tree).items() if k != "count"})


def test_plan_order_is_the_order_of_the_reductions(mesh8):
    """Buckets in issue order, a bucket's leaves in its own: with K > 1 the
    last leaf's reduction is emitted first."""
    tree = {f"l{i}": jnp.full((8, 3 + i), float(i)) for i in range(6)}
    jaxpr = jax.make_jaxpr(shard_map(
        lambda t: fusion.fused_allreduce(_local(t), "hvd", op=ReduceOp.SUM,
                                         num_buckets=3),
        mesh=mesh8, in_specs=P("hvd"), out_specs=P(), check_vma=False))(tree)
    widths = [int(m) for m in re.findall(r"f32\[(\d+)\] = psum", str(jaxpr))]
    plan = fusion.build_plan(_local(tree), num_buckets=3)
    assert widths == [d.size for b in plan.buckets for d in b]
    assert widths[0] == 8 and sorted(widths) == [3, 4, 5, 6, 7, 8]


# ------------------------------------------ (b) the lowered module

def _op_lines(text, scope):
    """StableHLO operations whose location names ``scope`` (the lowered
    text with debug info keeps each operation's scope in a ``loc``)."""
    locs = {m.group(1) for m in re.finditer(
        r'^(#loc\d+) = loc\("[^"]*' + scope + r'[^"]*"', text, re.M)}
    return [line for line in text.splitlines()
            if "stablehlo." in line
            and any(ref in locs for ref in re.findall(r"#loc\d+", line))]


def _lowered(mesh, axes, **kw):
    tree = _tree(mesh.size)
    return jax.jit(shard_map(
        lambda t: fusion.fused_allreduce(_local(t), threshold=WHOLE, **kw),
        mesh=mesh, in_specs=P(axes), out_specs=P(), check_vma=False)
    ).lower(tree).as_text(debug_info=True)


def test_flat_path_lowers_no_copy_into_or_out_of_a_buffer(mesh8):
    text = _lowered(mesh8, "hvd", compression="bf16", compression_min_bytes=64)
    pack = _op_lines(text, names.FUSION_PACK)
    unpack = _op_lines(text, names.FUSION_UNPACK)
    assert pack and unpack                      # the cast pair, per leaf
    assert all("stablehlo.convert" in line for line in pack + unpack)
    for word in ("concatenate", "pad", "reshape", "slice"):
        assert not any(word in line for line in pack + unpack), word
    # The f32 bucket's five leaves: five casts each way (the bf16 bucket is
    # at two bytes an element already, the int32 one is no float).
    assert len(pack) == len(unpack) == 5
    assert len(_op_lines(text, names.FUSED_ALLREDUCE + "3")) >= 8

    bare = _lowered(mesh8, "hvd")
    assert names.FUSION_PACK not in bare and names.FUSION_UNPACK not in bare
    assert names.FUSED_ALLREDUCE + "3" in bare


def test_hierarchical_path_still_lowers_the_buffer(mesh_2x4):
    text = _lowered(mesh_2x4, ("dcn", "ici"), hierarchical=True)
    pack = " ".join(_op_lines(text, names.FUSION_PACK))
    unpack = " ".join(_op_lines(text, names.FUSION_UNPACK))
    assert "stablehlo.concatenate" in pack and "stablehlo.reshape" in pack
    assert "stablehlo.slice" in unpack and "stablehlo.reshape" in unpack


# ------------------------------------------ (c) the gauges

# What the PARENT's fused_allreduce (flat buffers, commit 7d83852) recorded
# for ``_tree``'s local leaves, written here from a run of that tree.
PARENT_GAUGES = {
    (SPLITS, 1): dict(
        plan=[(0, 20), (1, 720), (2, 28), (3, 306), (4, 4096), (5, 524)],
        wire=("bf16", [(20, False, 0), (720, True, 360), (28, False, 0),
                       (306, False, 0), (4096, True, 2048),
                       (524, True, 262)]),
        tier={"hierarchical": False, "ici_wire": "bf16", "dcn_wire": "",
              "ici_size": 1, "buckets": 6,
              "bytes_per_step": {"ici": 3024, "dcn": 0}},
        gauges={"horovod_fusion_buckets": 6.0,
                "horovod_fusion_planned_bytes": 5694.0,
                "horovod_fusion_buffer_occupancy": 6.826666666666667,
                "horovod_overlap_efficiency_planned": 0.0,
                "horovod_compiled_wire_bytes_per_step": 3024.0,
                "horovod_compiled_wire_buckets": 3.0}),
    (WHOLE, 4): dict(
        plan=[(0, 524), (1, 4096), (2, 306), (3, 28), (4, 740)],
        wire=("bf16", [(524, True, 262), (4096, True, 2048), (306, False, 0),
                       (28, False, 0), (740, True, 370)]),
        tier={"hierarchical": False, "ici_wire": "bf16", "dcn_wire": "",
              "ici_size": 1, "buckets": 5,
              "bytes_per_step": {"ici": 3014, "dcn": 0}},
        gauges={"horovod_fusion_buckets": 5.0,
                "horovod_fusion_planned_bytes": 5694.0,
                "horovod_fusion_buffer_occupancy": 0.00390625,
                "horovod_overlap_efficiency_planned": 0.8700386371619249,
                "horovod_compiled_wire_bytes_per_step": 3014.0,
                "horovod_compiled_wire_buckets": 3.0}),
}
# The same of the hierarchical ladder on ('dcn', 'ici') = (2, 4), no wire
# format: f32 1,340 elements (4 divides them), int32 7 -> 8, bf16 153 -> 156.
PARENT_HIERARCHICAL = dict(
    plan=[(0, 5360), (1, 32), (2, 312)],
    tier={"hierarchical": True, "ici_wire": "none", "dcn_wire": "none",
          "ici_size": 4, "buckets": 3,
          "bytes_per_step": {"ici": 5704, "dcn": 1426}})


def _trace(mesh, axes, **kw):
    jax.make_jaxpr(shard_map(
        lambda t: fusion.fused_allreduce(_local(t), **kw),
        mesh=mesh, in_specs=P(axes), out_specs=P(), check_vma=False)
    )(_tree(mesh.size))
    return registry()


@pytest.mark.parametrize("threshold,num_buckets", sorted(PARENT_GAUGES),
                         ids=["split_k1", "whole_k4"])
def test_gauges_read_what_the_parents_read(mesh8, threshold, num_buckets):
    want = PARENT_GAUGES[(threshold, num_buckets)]
    reg = _trace(mesh8, "hvd", threshold=threshold, num_buckets=num_buckets,
                 compression="bf16", compression_min_bytes=64)
    assert last_plan() == want["plan"]
    assert last_wire_plan() == want["wire"]
    assert last_tier_plan() == want["tier"]
    assert {n: reg.gauge(n).value for n in want["gauges"]} == want["gauges"]
    assert reg.gauge("horovod_fusion_staged_bytes").value == 0


def test_staged_bytes_are_the_padded_bytes_where_a_buffer_is_filled(mesh_2x4):
    reg = _trace(mesh_2x4, ("dcn", "ici"), threshold=WHOLE, hierarchical=True)
    assert last_plan() == PARENT_HIERARCHICAL["plan"]
    assert last_tier_plan() == PARENT_HIERARCHICAL["tier"]
    assert reg.gauge("horovod_fusion_planned_bytes").value == 5704
    assert reg.gauge("horovod_fusion_staged_bytes").value == 5704

    _trace(data_parallel_mesh(), "hvd", threshold=WHOLE)
    assert last_plan() == [(0, 5360), (1, 28), (2, 306)]
    assert reg.gauge("horovod_fusion_planned_bytes").value == 5694
    assert reg.gauge("horovod_fusion_staged_bytes").value == 0


def test_sharded_planner_still_stages_its_buffers():
    from horovod_tpu.parallel.mesh import sharded_mesh
    from horovod_tpu.parallel.sharded import reduce_scatter_gradients

    mesh = sharded_mesh(shard=4)
    jax.make_jaxpr(shard_map(
        lambda t: tuple(reduce_scatter_gradients(
            {k: v for k, v in _local(t).items() if v.dtype == jnp.float32})),
        mesh=mesh, in_specs=P(("batch", "shard")), out_specs=P(),
        check_vma=False))(_tree(8))
    assert registry().gauge("horovod_fusion_staged_bytes").value == 1340 * 4


# ------------------------------------------ (d) the optimizer on a mesh of one

@pytest.mark.parametrize("inner", ["sgd_momentum", "adamw"])
def test_distributed_optimizer_on_a_mesh_of_one_is_the_bare_optimizer(inner):
    import horovod_tpu as hvd

    bare = {"sgd_momentum": optax.sgd(0.1, momentum=0.9),
            "adamw": optax.adamw(1e-2, weight_decay=0.1)}[inner]
    wrapped = hvd.jax.DistributedOptimizer(bare, num_buckets=3)
    mesh = data_parallel_mesh(jax.devices()[:1])
    floats = {k: v for k, v in _local(_tree(1, seed=5)).items()
              if jnp.issubdtype(v.dtype, jnp.floating)}
    grads = {k: v for k, v in _local(_tree(1, seed=6)).items() if k in floats}

    def two_updates(opt):
        def body(params, grads):
            state = opt.init(params)
            first, state = opt.update(grads, state, params)
            second, state = opt.update(grads, state,
                                       optax.apply_updates(params, first))
            return first, second

        return jax.jit(shard_map(body, mesh=mesh, in_specs=(P(), P()),
                                 out_specs=P(), check_vma=False))(floats, grads)

    _assert_bitwise(two_updates(wrapped), two_updates(bare))
