"""Tensor parallelism on the 3-D ('batch','shard','model') mesh (ISSUE 19).

Coverage map:
- TP forward == dense single-chip oracle: BITWISE on exact-arithmetic
  (integer-valued float) payloads, pinned dtype tolerance on generic
  floats;
- TP backward (the in-body ``jax.value_and_grad`` pattern the repo trains
  with) == dense oracle's slice gradients BITWISE, replicated parameters
  receiving identical gradients on every model rank — through single
  pairs AND chained pairs (the inter-pair cotangent rides
  ``copy_to_model``'s psum transpose);
- the conjugate f/g pair is load-bearing: a control shows JAX's default
  psum-transposes-to-psum rule scales slice gradients by model_size;
- model=1 on the 3-D mesh walks the IDENTICAL bit pattern as the 2-D
  plan (full DistributedOptimizer trajectory, uint8 compare);
- composed TP x FSDP x DP training (model=2, shard=2, batch=2) tracks the
  dense DP oracle within pinned tolerance, with the model-stacked
  ``(model*shard, chunk)`` host layout and ``P(('model','shard'))``
  specs;
- trace-time gauges record the model axis;
- per-chip parameter + optimizer state falls >= 1.8x at model=2.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu import metrics as hvd_metrics
from horovod_tpu.compat import shard_map
from horovod_tpu.parallel import sharded as sh
from horovod_tpu.parallel import tensor as tp
from horovod_tpu.parallel.mesh import sharded_mesh


# ------------------------------------------------------------ tiny helpers


def int_pair(rng, d_in, h, d_out, lo=-3, hi=4):
    """A column/row pair with integer-valued float32 weights: every
    product and sum stays exactly representable, so TP-vs-dense equality
    is bitwise and any mismatch is a routing/transpose bug, not
    rounding."""
    return {
        "w_col": jnp.asarray(rng.randint(lo, hi, (d_in, h)).astype(np.float32)),
        "b_col": jnp.asarray(rng.randint(lo, hi, (h,)).astype(np.float32)),
        "w_row": jnp.asarray(rng.randint(lo, hi, (h, d_out)).astype(np.float32)),
        "b_row": jnp.asarray(rng.randint(lo, hi, (d_out,)).astype(np.float32)),
    }


def stack_local(local_pairs):
    """[rank][...] local trees -> one tree with a leading model dim, ready
    for in_specs=P('model')."""
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *local_pairs)


def bitwise_equal(a, b) -> bool:
    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and bool(
        np.array_equal(a.view(np.uint8), b.view(np.uint8)))


# ------------------------------------------------------------- forward


def test_tp_forward_bitwise_vs_dense(mesh8):
    """One psum per pair: the TP forward reassociates only the hidden
    contraction, so integer payloads reproduce the dense oracle
    bitwise at every model size."""
    del mesh8
    rng = np.random.RandomState(0)
    pair = int_pair(rng, 4, 8, 3)
    x = jnp.asarray(rng.randint(-2, 3, (5, 4)).astype(np.float32))
    want = tp.dense_pair_apply(pair, x, activation=None)
    for S in (2, 4, 8):
        mesh = sharded_mesh(batch=8 // S, shard=1, model=S)
        stacked = stack_local(tp.tp_pair_slices(pair, S))

        def body(sp, x):
            local = jax.tree_util.tree_map(lambda t: t[0], sp)
            return tp.tp_pair_apply(local, x, activation=None)[None]

        got = jax.jit(shard_map(
            body, mesh=mesh, in_specs=(P("model"), P()),
            out_specs=P(("batch", "shard", "model")),
            check_vma=False))(stacked, x)
        for r in range(8):
            assert bitwise_equal(got[r], want), \
                f"model={S}: device {r} diverged from the dense oracle"


def test_tp_forward_pinned_tolerance_generic_floats(mesh8):
    """Generic float payloads + tanh: the reassociated hidden sum is the
    only rounding difference, pinned at float32 dtype tolerance."""
    del mesh8
    k = jax.random.PRNGKey(3)
    k1, k2 = jax.random.split(k)
    pairs = [
        {"w_col": jax.random.normal(k1, (6, 8)) * 0.3,
         "b_col": jnp.zeros((8,)),
         "w_row": jax.random.normal(k2, (8, 6)) * 0.3,
         "b_row": jnp.full((6,), 0.1)},
    ]
    x = jax.random.normal(jax.random.PRNGKey(5), (4, 6))
    mesh = sharded_mesh(batch=4, shard=1, model=2)
    stacked = [stack_local(tp.tp_pair_slices(p, 2)) for p in pairs]

    with jax.default_matmul_precision("highest"):
        want = tp.dense_apply(pairs, x)

        def body(sp, x):
            local = jax.tree_util.tree_map(lambda t: t[0], sp)
            return tp.tp_apply(local, x)[None]

        got = jax.jit(shard_map(
            body, mesh=mesh, in_specs=(P("model"), P()),
            out_specs=P(("batch", "shard", "model")),
            check_vma=False))(stacked, x)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=1e-6, rtol=1e-6)


# ------------------------------------------------------------- backward


def _tp_grads(mesh, S, stacked, x, activation=None):
    """In-body value_and_grad — the composition DistributedOptimizer uses:
    grads of the REPLICATED loss wrt this rank's local slices."""

    def body(sp, x):
        local = jax.tree_util.tree_map(lambda t: t[0], sp)

        def loss_fn(lp):
            return jnp.sum(tp.tp_apply(lp, x, activation=activation))

        _, g = jax.value_and_grad(loss_fn)(local)
        return jax.tree_util.tree_map(lambda t: t[None], g)

    return jax.jit(shard_map(
        body, mesh=mesh, in_specs=(P("model"), P()),
        out_specs=P("model"), check_vma=False))(stacked, x)


def _assert_grads_match_dense(g, dgrad, S):
    for i, dg in enumerate(dgrad):
        want_slices = tp.tp_pair_slices(dg, S)
        for k in ("w_col", "b_col", "w_row"):
            want = np.stack([np.asarray(w[k]) for w in want_slices])
            assert bitwise_equal(np.asarray(g[i][k]), want), \
                f"pair{i}.{k}: slice gradient diverged from dense oracle"
        for r in range(S):
            assert bitwise_equal(np.asarray(g[i]["b_row"])[r],
                                 np.asarray(dg["b_row"])), \
                f"pair{i}.b_row rank{r}: replicated gradient diverged"


def test_tp_backward_bitwise_vs_dense(mesh8):
    """The in-body gradient contract: slice params get the dense
    gradient's slices bitwise; the replicated post-psum bias gets the
    IDENTICAL dense gradient on every model rank."""
    del mesh8
    rng = np.random.RandomState(0)
    pairs = [int_pair(rng, 4, 8, 3)]
    x = jnp.asarray(rng.randint(-2, 3, (2, 4)).astype(np.float32))
    dgrad = jax.grad(
        lambda ps, x: jnp.sum(tp.dense_apply(ps, x, activation=None)))(
            pairs, x)
    for S in (2, 4):
        mesh = sharded_mesh(batch=8 // S, shard=1, model=S)
        stacked = [stack_local(tp.tp_pair_slices(p, S)) for p in pairs]
        g = _tp_grads(mesh, S, stacked, x)
        _assert_grads_match_dense(g, dgrad, S)


def test_tp_chain_backward_bitwise(mesh8):
    """Chained pairs: the cotangent leaving pair i+1 must arrive at pair i
    COMPLETED across model ranks (copy_to_model's psum transpose) — a
    partial cotangent would silently corrupt every upstream slice
    gradient."""
    del mesh8
    rng = np.random.RandomState(1)
    pairs = [int_pair(rng, 4, 6, 4, lo=-2, hi=3),
             int_pair(rng, 4, 8, 3, lo=-2, hi=3)]
    x = jnp.asarray(rng.randint(-2, 3, (3, 4)).astype(np.float32))
    dgrad = jax.grad(
        lambda ps, x: jnp.sum(tp.dense_apply(ps, x, activation=None)))(
            pairs, x)
    S = 2
    mesh = sharded_mesh(batch=4, shard=1, model=S)
    stacked = [stack_local(tp.tp_pair_slices(p, S)) for p in pairs]
    g = _tp_grads(mesh, S, stacked, x)
    _assert_grads_match_dense(g, dgrad, S)


def test_naive_psum_transpose_would_scale_grads(mesh8):
    """Control for the conjugate f/g pair: JAX transposes a plain
    ``lax.psum`` as another psum, which under the in-body pattern scales
    every slice gradient by exactly model_size. The pair is load-bearing,
    not decorative."""
    del mesh8
    rng = np.random.RandomState(0)
    pair = int_pair(rng, 4, 8, 3)
    x = jnp.asarray(rng.randint(-2, 3, (2, 4)).astype(np.float32))
    dgrad = jax.grad(
        lambda p, x: jnp.sum(tp.dense_pair_apply(p, x, activation=None)))(
            pair, x)
    S = 4
    mesh = sharded_mesh(batch=2, shard=1, model=S)
    stacked = stack_local(tp.tp_pair_slices(pair, S))

    def naive_pair(lp, x):
        h = x @ lp["w_col"] + lp["b_col"]
        return jax.lax.psum(h @ lp["w_row"], "model") + lp["b_row"]

    def body(sp, x):
        local = jax.tree_util.tree_map(lambda t: t[0], sp)
        _, g = jax.value_and_grad(
            lambda lp: jnp.sum(naive_pair(lp, x)))(local)
        return jax.tree_util.tree_map(lambda t: t[None], g)

    g = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(P("model"), P()),
        out_specs=P("model"), check_vma=False))(stacked, x)
    want = np.stack([np.asarray(w["w_col"])
                     for w in tp.tp_pair_slices(dgrad, S)])
    got = np.asarray(g["w_col"])
    assert np.array_equal(got, want * S), \
        "expected the naive psum to scale slice grads by model_size"
    assert not np.array_equal(got, want)


# --------------------------------------------------- trajectory identities


def _loss_pairs(pairs, x, y, apply):
    return jnp.mean((apply(pairs, x) - y) ** 2)


def _make_pairs(seed=0):
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(seed), 4)
    return [
        {"w_col": jax.random.normal(k1, (12, 16)) * 0.3,
         "b_col": jnp.zeros((16,)),
         "w_row": jax.random.normal(k2, (16, 12)) * 0.3,
         "b_row": jnp.zeros((12,))},
        {"w_col": jax.random.normal(k3, (12, 8)) * 0.3,
         "b_col": jnp.zeros((8,)),
         "w_row": jax.random.normal(k4, (8, 5)) * 0.3,
         "b_row": jnp.zeros((5,))},
    ]


def _pairs_data(n=4, seed=11):
    x = jax.random.normal(jax.random.PRNGKey(seed), (8 * n, 12))
    y = jax.random.normal(jax.random.PRNGKey(seed + 1), (8 * n, 5))
    return x, y


def _train_tp(mesh, model_size, pairs, x, y, steps=5, num_buckets=2):
    """DistributedOptimizer(sharded=True) over the 3-D mesh: TP slices in
    the model-stacked host layout, the ('batch','shard') exchange
    unchanged per model group. Returns each model rank's final local
    pairs."""
    inner = optax.adam(1e-2)
    local = tp.tp_local_pairs(pairs, model_size)
    plan = sh.build_shard_plan(local[0], mesh.shape["shard"],
                               threshold=1 << 20, num_buckets=num_buckets,
                               model_size=model_size)
    sp = sh.shard_params_model(local, plan)
    opt = hvd.jax.DistributedOptimizer(inner, sharded=True, shard_plan=plan)
    st = opt.init(sp)
    specs = sh.shard_specs(st, model_axis="model")
    sp_spec = sh.shard_specs(sp, model_axis="model")

    def step(sp, st, x, y):
        local = sh.gather_params(sp, plan)
        loss, g = jax.value_and_grad(
            lambda p: _loss_pairs(p, x, y, tp.tp_apply))(local)
        upd, st = opt.update(g, st, sp)
        return optax.apply_updates(sp, upd), st, \
            jax.lax.pmean(loss, ("batch", "shard"))

    run = jax.jit(shard_map(
        step, mesh=mesh,
        in_specs=(sp_spec, specs, P(("batch", "shard")),
                  P(("batch", "shard"))),
        out_specs=(sp_spec, specs, P()), check_vma=False))
    for _ in range(steps):
        sp, st, _ = run(sp, st, x, y)
    return sh.unshard_params_model(sp, plan), plan


def _train_dp_pairs(pairs, x, y, world=4, steps=5, num_buckets=2):
    mesh = Mesh(np.asarray(jax.devices()[:world]), ("hvd",))
    opt = hvd.jax.DistributedOptimizer(optax.adam(1e-2),
                                       fusion_threshold=1 << 20,
                                       num_buckets=num_buckets)
    st = opt.init(pairs)

    def step(p, st, x, y):
        loss, g = jax.value_and_grad(
            lambda p: _loss_pairs(p, x, y, tp.dense_apply))(p)
        upd, st = opt.update(g, st, p)
        return optax.apply_updates(p, upd), st, jax.lax.pmean(loss, "hvd")

    run = jax.jit(shard_map(step, mesh=mesh,
                            in_specs=(P(), P(), P("hvd"), P("hvd")),
                            out_specs=(P(), P(), P()), check_vma=False))
    for _ in range(steps):
        pairs, st, _ = run(pairs, st, x, y)
    return pairs


def test_model1_3d_bitwise_identical_to_2d(mesh8):
    """The ISSUE 19 headline discipline: model=1 on the 3-D mesh compiles
    to the IDENTICAL bit pattern as the 2-D plan — same plan, same
    collectives (no model-axis op is emitted), same update arithmetic —
    through a full DistributedOptimizer trajectory."""
    del mesh8
    pairs = _make_pairs()
    x, y = _pairs_data(4)
    # 2-D reference: the PR 14 path on a (4,2) mesh.
    mesh2d = Mesh(np.asarray(jax.devices()[:8]).reshape(4, 2),
                  ("batch", "shard"))
    plan2 = sh.build_shard_plan(pairs, 2, threshold=1 << 20, num_buckets=2)
    sp2 = sh.shard_params(pairs, plan2)
    opt2 = hvd.jax.DistributedOptimizer(optax.adam(1e-2), sharded=True,
                                        shard_plan=plan2)
    st2 = opt2.init(sp2)
    specs2 = sh.shard_specs(st2)

    def step2(sp, st, x, y):
        full = sh.gather_params(sp, plan2)
        _, g = jax.value_and_grad(
            lambda p: _loss_pairs(p, x, y, tp.dense_apply))(full)
        upd, st = opt2.update(g, st, sp)
        return optax.apply_updates(sp, upd), st

    run2 = jax.jit(shard_map(
        step2, mesh=mesh2d,
        in_specs=(P("shard"), specs2, P(("batch", "shard")),
                  P(("batch", "shard"))),
        out_specs=(P("shard"), specs2), check_vma=False))
    for _ in range(5):
        sp2, st2 = run2(sp2, st2, x, y)
    want = sh.unshard_params(sp2, plan2)

    # 3-D degenerate: model=1 named on the mesh, model-stacked layout.
    mesh3d = sharded_mesh(batch=4, shard=2, model=1)
    got_ranks, _ = _train_tp(mesh3d, 1, pairs, x, y, steps=5)
    assert len(got_ranks) == 1
    got = got_ranks[0]
    for i in range(len(pairs)):
        for k in pairs[i]:
            assert bitwise_equal(got[i][k], want[i][k]), \
                f"pair{i}.{k}: model=1 3-D diverged from the 2-D plan bitwise"


def test_tp_sharded_training_matches_dense_dp(mesh8):
    """Composed TP x FSDP x DP on the full (2,2,2) cube: five optimizer
    steps track the dense DP oracle within pinned float32 tolerance, and
    the replicated b_row stays bitwise-identical across model ranks (the
    per-model-group exchanges see identical operands)."""
    del mesh8
    pairs = _make_pairs()
    x, y = _pairs_data(4)
    with jax.default_matmul_precision("highest"):
        want = _train_dp_pairs(pairs, x, y, world=4, steps=5)
        got_ranks, _ = _train_tp(sharded_mesh(batch=2, shard=2, model=2),
                                 2, pairs, x, y, steps=5)
    # Model ranks agree bitwise on replicated leaves.
    for i in range(len(pairs)):
        assert bitwise_equal(got_ranks[0][i]["b_row"],
                             got_ranks[1][i]["b_row"]), \
            f"pair{i}.b_row diverged across model ranks"
    # Reassemble the full pairs from rank slices and compare to dense DP.
    for i in range(len(pairs)):
        full_w_col = np.concatenate(
            [np.asarray(r[i]["w_col"]) for r in got_ranks], axis=-1)
        full_b_col = np.concatenate(
            [np.asarray(r[i]["b_col"]) for r in got_ranks])
        full_w_row = np.concatenate(
            [np.asarray(r[i]["w_row"]) for r in got_ranks], axis=0)
        np.testing.assert_allclose(full_w_col, np.asarray(want[i]["w_col"]),
                                   atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(full_b_col, np.asarray(want[i]["b_col"]),
                                   atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(full_w_row, np.asarray(want[i]["w_row"]),
                                   atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(np.asarray(got_ranks[0][i]["b_row"]),
                                   np.asarray(want[i]["b_row"]),
                                   atol=2e-5, rtol=2e-5)


def test_tp_gauges_record_model_axis(mesh8):
    """Trace-time shard-plan gauges carry the third axis: after a TP step
    the recorded plan shows (batch, shard, model) = the compiled cube."""
    del mesh8
    pairs = _make_pairs()
    x, y = _pairs_data(4)
    _train_tp(sharded_mesh(batch=2, shard=2, model=2), 2, pairs, x, y,
              steps=1)
    plan = hvd_metrics.last_shard_plan()
    assert plan is not None
    assert plan["batch"] == 2 and plan["shard"] == 2 and plan["model"] == 2


def test_tp_halves_per_chip_state(mesh8):
    """At model=2 beside shard=2 a chip holds >= 1.8x less parameter +
    optimizer state than at model=1 (adam, the same two pairs; the slack
    is one bucket's pad)."""
    del mesh8
    # Bytes alone are counted: two 64 -> 512 -> 64 pairs of zeros.
    pairs = [{"w_col": jnp.zeros((64, 512)), "b_col": jnp.zeros((512,)),
              "w_row": jnp.zeros((512, 64)), "b_row": jnp.zeros((64,))}] * 2
    shard = 2

    def per_chip(model):
        local = tp.tp_local_pairs(pairs, model)
        plan = sh.build_shard_plan(local[0], shard, model_size=model)
        sp = sh.shard_params_model(local, plan)
        opt = hvd.jax.DistributedOptimizer(optax.adam(1e-3), sharded=True,
                                           shard_plan=plan)
        return sh.state_bytes({"params": sp, "opt": opt.init(sp)}) // (
            model * shard)

    assert per_chip(1) / per_chip(2) >= 1.8


# ------------------------------------------------------- sixth dimension


def test_autotune_sixth_dimension():
    """The 3-D mesh shape joins the joint autotune: 3-axis spec strings
    flow through tune(mesh_shapes=...) exactly like the 2-axis ones, and
    the winner's config records the full cube."""
    from horovod_tpu.jax.autotune import tune

    seen = []

    def step_factory(fusion_threshold, num_buckets, mesh_shape):
        seen.append(mesh_shape)
        import time as _t

        delay = 0.0002 if mesh_shape == "2x2x2" else 0.003

        def run():
            _t.sleep(delay)

        return run

    report = tune(step_factory, thresholds=(1 << 20,), num_buckets=(1,),
                  mesh_shapes=("8x1x1", "4x2x1", "2x2x2"),
                  warmup=0, iters=1, reps=1, gp_rounds=0)
    assert set(seen) == {"8x1x1", "4x2x1", "2x2x2"}
    assert report.best.mesh_shape == "2x2x2"
    assert report.best.config.get("mesh") == "2x2x2"
