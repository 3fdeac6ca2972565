"""Sharded data parallelism through the Horovod API (ISSUE 14) — the
bucketed reduce-scatter/allgather planner on the ('batch','shard') mesh.

Coverage map (the ISSUE's test satellite):
- mesh spec parsing + HOROVOD_MESH resolution;
- shard-plan invariants: padding, chunk ownership, shard=1 plan identical
  to the DP plan;
- reduce-scatter-sum correctness vs the dense allreduce oracle on a 2x4
  mesh (exact integer payloads — any mismatch is a routing bug);
- sharded == DP on a degenerate shard=1 mesh: the exchange traces to the
  same equations, and the full training loop through DistributedOptimizer
  agrees to float32 rounding; within dtype tolerance on 2x2, 1x4, 2x4;
- zero-pad discipline: the tail receives zero gradients, the masked update
  keeps it bitwise 0.0 even under an optimizer chain that moves
  zero-gradient entries (gradient noise);
- sharded checkpoint save -> restore -> resume exactness, including
  restore onto a RESHAPED mesh;
- trace-time shard-plan gauges + the per-bucket wire-compression opt-outs
  riding along unchanged;
- the mesh shape as the FIFTH joint-autotune dimension.
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
from horovod_tpu.parallel.mesh import parse_mesh_spec, sharded_mesh


def make_params(seed: int = 0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    # 33 and 9 are deliberately not divisible by the shard sizes under test.
    return {
        "w1": jax.random.normal(k1, (16, 33)) * 0.3,
        "b1": jnp.zeros((33,)),
        "w2": jax.random.normal(k2, (33, 9)) * 0.3,
    }


def loss_fn(p, x, y):
    h = jnp.tanh(x @ p["w1"] + p["b1"])
    return jnp.mean((h @ p["w2"] - y) ** 2)


def make_data(n: int):
    x = jax.random.normal(jax.random.PRNGKey(7), (8 * n, 16))
    y = jax.random.normal(jax.random.PRNGKey(8), (8 * n, 9))
    return x, y


def grid_mesh(batch: int, shard: int) -> Mesh:
    devs = jax.devices()[:batch * shard]
    return Mesh(np.asarray(devs).reshape(batch, shard), ("batch", "shard"))


# ---------------------------------------------------------------- mesh spec


def test_parse_mesh_spec():
    # 1-/2-axis back-compat: existing spellings resolve to model=1.
    assert parse_mesh_spec("", 8) == (8, 1, 1)
    assert parse_mesh_spec("8", 8) == (8, 1, 1)
    assert parse_mesh_spec("4x2", 8) == (4, 2, 1)
    assert parse_mesh_spec("2X4", 8) == (2, 4, 1)
    assert parse_mesh_spec("4×2", 8) == (4, 2, 1)  # unicode ×, the docs spelling
    assert parse_mesh_spec("-1x2", 8) == (4, 2, 1)
    assert parse_mesh_spec("2x-1", 8) == (2, 4, 1)
    # 3-axis specs (ISSUE 19), -1 legal in any one position.
    assert parse_mesh_spec("4x2x1", 8) == (4, 2, 1)
    assert parse_mesh_spec("2x2x2", 8) == (2, 2, 2)
    assert parse_mesh_spec("2X2×2", 8) == (2, 2, 2)
    assert parse_mesh_spec("-1x2x2", 8) == (2, 2, 2)
    assert parse_mesh_spec("2x-1x2", 8) == (2, 2, 2)
    assert parse_mesh_spec("4x1x-1", 8) == (4, 1, 2)
    for bad in ("3x2", "axb", "-1x-1", "0x8", "4x3",
                # malformed / oversubscribed 3-axis shapes
                "2x2x3", "4x2x2", "0x2x4", "2x2x0", "axbxc",
                "-1x-1x2", "2x-1x-1", "1x2x3x4", "16x1x1"):
        with pytest.raises(ValueError):
            parse_mesh_spec(bad, 8)


def test_sharded_mesh_from_env(monkeypatch):
    monkeypatch.setenv("HOROVOD_MESH", "2x4")
    mesh = sharded_mesh()
    assert mesh.shape == {"batch": 2, "shard": 4}
    monkeypatch.delenv("HOROVOD_MESH")
    mesh = sharded_mesh()
    assert mesh.shape == {"batch": 8, "shard": 1}
    assert sharded_mesh(shard=2).shape == {"batch": 4, "shard": 2}


def test_sharded_mesh_third_axis(monkeypatch):
    """The mesh goes 3-D exactly when the model axis is NAMED: a 3-axis
    env spec (even `...x1`) or an explicit model= argument — 2-axis
    spellings keep the bit-identical 2-D mesh."""
    monkeypatch.setenv("HOROVOD_MESH", "2x2x2")
    assert sharded_mesh().shape == {"batch": 2, "shard": 2, "model": 2}
    monkeypatch.setenv("HOROVOD_MESH", "4x2x1")
    assert sharded_mesh().shape == {"batch": 4, "shard": 2, "model": 1}
    monkeypatch.delenv("HOROVOD_MESH")
    assert sharded_mesh(model=2).shape == \
        {"batch": 4, "shard": 1, "model": 2}
    assert sharded_mesh(batch=2, shard=2, model=2).shape == \
        {"batch": 2, "shard": 2, "model": 2}
    m = sharded_mesh(batch=2, shard=2, model=2)
    assert m.axis_names == ("batch", "shard", "model")
    with pytest.raises(ValueError):
        sharded_mesh(batch=8, shard=1, model=2)   # oversubscribed
    with pytest.raises(ValueError):
        sharded_mesh(batch=4, shard=1)            # 4x1x1 != 8 devices


# ---------------------------------------------------------------- shard plan


def test_shard_plan_padding_and_chunks():
    params = make_params()
    plan = sh.build_shard_plan(params, 4, threshold=1 << 20, num_buckets=2)
    assert plan.shard_size == 4
    for raw, padded, chunk in zip(plan.raw_sizes, plan.padded_sizes,
                                  plan.chunk_sizes):
        assert padded % 4 == 0 and padded - raw < 4 and chunk * 4 == padded
    total = sum(int(l.size) for l in jax.tree_util.tree_leaves(params))
    assert sum(plan.raw_sizes) == total


def test_shard1_plan_identical_to_dp_plan():
    """The degenerate mesh's bucket layout IS the DP layout — same bucket
    boundaries, no padding."""
    from horovod_tpu.parallel import fusion

    params = make_params()
    plan = sh.build_shard_plan(params, 1, threshold=1 << 20, num_buckets=3)
    dp = fusion.build_plan(params, 1 << 20, pad_to=1, num_buckets=3)
    assert plan.base.buckets == dp.buckets
    assert plan.raw_sizes == plan.padded_sizes


def test_dcn_threshold_caps_shard_buckets():
    """HOROVOD_DCN_FUSION_THRESHOLD applies unchanged: a bucket's scatter
    ships 1/shard of its bytes, so the cap bounds bucket bytes at D*shard
    (single oversize leaves keep their own bucket, as everywhere else)."""
    params = {f"w{i}": jnp.zeros((1 << 10,), jnp.float32)   # 64 x 4 KiB
              for i in range(64)}
    plan = sh.build_shard_plan(params, 4, threshold=1 << 30,
                               dcn_threshold=16 << 10)
    assert plan.num_buckets > 1
    for padded, dt in zip(plan.padded_sizes, plan.bucket_dtypes):
        assert padded * jnp.dtype(dt).itemsize <= (16 << 10) * 4


@pytest.mark.parametrize("ragged", [True, False], ids=["ragged", "divisible"])
def test_shard_unshard_roundtrip(ragged):
    """A tree of 858 elements (leaves of 33 and 9 columns: padded at shard
    sizes 4 and 8) and one every shard size divides (never padded)."""
    params = make_params() if ragged else {
        "w": jax.random.normal(jax.random.PRNGKey(0), (16, 32)),
        "b": jnp.arange(32.0)}
    for s in (1, 2, 4, 8):
        plan = sh.build_shard_plan(params, s, threshold=1 << 20)
        assert (plan.raw_sizes != plan.padded_sizes) == (ragged and s > 2)
        back = sh.unshard_params(sh.shard_params(params, plan), plan)
        for a, b in zip(jax.tree_util.tree_leaves(params),
                        jax.tree_util.tree_leaves(back)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_state_bytes_per_rank_shrinks_shard_fold():
    params = make_params()
    dp_bytes = sh.state_bytes(params)
    plan = sh.build_shard_plan(params, 4, threshold=1 << 20)
    per_rank = plan.state_bytes_per_rank()
    # 1/4 plus at most one pad row per bucket
    assert per_rank < dp_bytes / 4 + 4 * plan.num_buckets * 4
    sp = sh.shard_params(params, plan)
    assert sh.state_bytes(sp) // 4 == per_rank


# ------------------------------------------------- reduce-scatter vs oracle


def test_reduce_scatter_matches_dense_oracle_2x4(mesh8):
    """Gathering the sharded gradient exchange's result must reproduce the
    dense pmean oracle BITWISE on exactly-summable payloads — the
    reduce-scatter-sum correctness proof on a 2x4 mesh."""
    del mesh8  # only asserts the 8-device platform
    mesh = grid_mesh(2, 4)
    # Integer-valued floats: every reduction order is exact, so equality is
    # bitwise and any mismatch is a misrouted chunk, not rounding.
    grads = {
        "a": jnp.arange(131, dtype=jnp.float32).reshape(131) % 13,
        "b": (jnp.arange(64, dtype=jnp.float32).reshape(8, 8) % 7) - 3.0,
    }
    plan = sh.build_shard_plan(grads, 4, threshold=1 << 20, num_buckets=2)

    def body(g):
        g = jax.tree_util.tree_map(lambda t: jnp.squeeze(t, 0), g)
        # Per-rank distinct integer payloads (rank = batch*4 + shard).
        r = jax.lax.axis_index("batch") * 4 + jax.lax.axis_index("shard")
        g = jax.tree_util.tree_map(lambda t: t + r.astype(t.dtype), g)
        reduced = sh.reduce_scatter_gradients(g, plan)
        full = sh.gather_params(reduced, plan)
        oracle = jax.tree_util.tree_map(
            lambda t: jax.lax.pmean(t, ("batch", "shard")), g)
        return jax.tree_util.tree_map(lambda t: t[None], (full, oracle))

    stacked = jax.tree_util.tree_map(
        lambda t: jnp.broadcast_to(t[None], (8,) + t.shape), grads)
    got, want = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(P(("batch", "shard")),),
        out_specs=P(("batch", "shard")), check_vma=False))(stacked)
    for k in grads:
        np.testing.assert_array_equal(np.asarray(got[k]),
                                      np.asarray(want[k]))


# --------------------------------------------------- training-loop parity


def _train(mesh, batch, shard, params, x, y, steps=5, num_buckets=2,
           inner=optax.adam(1e-2)):
    """Run the full DistributedOptimizer loop and return the final FULL
    params. shard=1 exercises the degenerate (bitwise-DP) plan."""
    plan = sh.build_shard_plan(params, shard, threshold=1 << 20,
                               num_buckets=num_buckets)
    sp = sh.shard_params(params, plan)
    opt = hvd.jax.DistributedOptimizer(inner, sharded=True, shard_plan=plan)
    opt_state = opt.init(sp)
    specs = sh.shard_specs(opt_state)

    def step(sp, st, x, y):
        full = sh.gather_params(sp, plan)
        loss, g = jax.value_and_grad(lambda p: loss_fn(p, x, y))(full)
        upd, st = opt.update(g, st, sp)
        return optax.apply_updates(sp, upd), st, \
            jax.lax.pmean(loss, ("batch", "shard"))

    run = jax.jit(shard_map(
        step, mesh=mesh,
        in_specs=(P("shard"), specs, P(("batch", "shard")),
                  P(("batch", "shard"))),
        out_specs=(P("shard"), specs, P()), check_vma=False))
    for _ in range(steps):
        sp, opt_state, _ = run(sp, opt_state, x, y)
    return sh.unshard_params(sp, plan), sp, plan


def _train_dp(params, x, y, world=4, steps=5, num_buckets=2):
    mesh = Mesh(np.asarray(jax.devices()[:world]), ("hvd",))
    opt = hvd.jax.DistributedOptimizer(optax.adam(1e-2),
                                       fusion_threshold=1 << 20,
                                       num_buckets=num_buckets)
    st = opt.init(params)

    def step(p, st, x, y):
        loss, g = jax.value_and_grad(lambda p: loss_fn(p, x, y))(p)
        upd, st = opt.update(g, st, p)
        return optax.apply_updates(p, upd), st, jax.lax.pmean(loss, "hvd")

    run = jax.jit(shard_map(step, mesh=mesh,
                            in_specs=(P(), P(), P("hvd"), P("hvd")),
                            out_specs=(P(), P(), P()), check_vma=False))
    for _ in range(steps):
        params, st, _ = run(params, st, x, y)
    return params


def _exchange_eqns(fn, mesh, out_specs, grads):
    """The equations of a gradient exchange, from the leaves up to the
    divide that follows its last psum, with the axis NAME erased (the DP
    path reduces over 'hvd', the shard=1 path over 'batch')."""
    closed = jax.make_jaxpr(shard_map(
        fn, mesh=mesh, in_specs=P(), out_specs=out_specs,
        check_vma=False))(grads)
    (sm,) = [e for e in closed.jaxpr.eqns if e.primitive.name == "shard_map"]
    eqns = sm.params["jaxpr"].eqns
    last = max(i for i, e in enumerate(eqns) if e.primitive.name == "psum")
    return [(e.primitive.name, tuple(str(v.aval) for v in e.invars),
             sorted((k, str(v)) for k, v in e.params.items() if k != "axes"))
            for e in eqns[:last + 2]]


def _psum_elements(eqns):
    """Elements each psum of an exchange reduces, in the order emitted."""
    sizes = []
    for name, invars, _ in eqns:
        if name == "psum":
            (aval,) = invars
            dims = aval[aval.index("[") + 1:-1]
            sizes.append(int(np.prod([int(n) for n in dims.split(",") if n])))
    return sizes


def test_sharded_equals_dp_on_shard1(mesh8):
    """The acceptance headline, stated as what the code controls: on a
    degenerate shard=1 mesh the exchange follows the SAME plan as today's
    DP path — same buckets in the same order, the same elements reduced and
    divided the same way — and the training loops agree to float32
    rounding. Since PR 59 the two differ in what a bucket IS: the sharded
    planner fills one rank-1 buffer a bucket (one psum and one divide a
    bucket), the DP path hands the collective the bucket's leaves as they
    are (one psum and one divide a leaf, buckets in issue order, a bucket's
    leaves in its own). (Bit equality of the two loops was the earlier
    form; they are two separately compiled XLA programs — the sharded one
    updates (1, chunk) bucket rows, the DP one the parameter leaves — and
    XLA owes them no common fusion or FMA choice: 1 ULP apart on jax 0.9.0.)"""
    del mesh8
    params = make_params()
    plan = sh.build_shard_plan(params, 1, threshold=1 << 20, num_buckets=2)
    dp_eqns = _exchange_eqns(
        lambda g: hvd.jax.allreduce_gradients(
            g, fusion_threshold=1 << 20, num_buckets=2),
        Mesh(np.asarray(jax.devices()[:4]), ("hvd",)), P(), params)
    sharded_eqns = _exchange_eqns(
        lambda g: sh.reduce_scatter_gradients(g, plan),
        grid_mesh(4, 1), P("shard"), params)
    buckets = [[d.size for d in b] for b in plan.base.buckets]
    assert len(buckets) == 2
    assert _psum_elements(sharded_eqns) == [sum(b) for b in buckets]
    assert _psum_elements(dp_eqns) == [n for b in buckets for n in b]
    for eqns in (sharded_eqns, dp_eqns):    # the DP path fills no buffer
        names = [name for name, _, _ in eqns]
        assert ("concatenate" in names) is (eqns is sharded_eqns)
        assert names.count("div") == names.count("psum")

    x, y = make_data(4)
    dp = _train_dp(params, x, y, world=4)
    got, _, _ = _train(grid_mesh(4, 1), 4, 1, params, x, y)
    for k in params:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(dp[k]),
                                   rtol=2e-6, atol=2e-7,
                                   err_msg=f"{k}: shard=1 diverged from DP")


@pytest.mark.parametrize("batch,shard", [(2, 2), (1, 4), (2, 4)],
                         ids=["2x2", "1x4", "2x4"])
def test_sharded_trajectory_matches_dp(mesh8, batch, shard):
    """Replicated DP against ZeRO composed with a batch axis (2x2, 2x4) and
    alone (1x4: every rank owns a quarter of every bucket)."""
    del mesh8
    params = make_params()
    x, y = make_data(batch * shard)
    with jax.default_matmul_precision("highest"):
        dp = _train_dp(params, x, y, world=batch * shard)
        got, _, _ = _train(grid_mesh(batch, shard), batch, shard, params, x, y)
    for k in params:
        np.testing.assert_allclose(np.asarray(dp[k]), np.asarray(got[k]),
                                   atol=2e-6, rtol=2e-6)


# ------------------------------------------------------- zero-pad discipline


@pytest.mark.parametrize("inner", [
    optax.chain(optax.adam(1e-2), optax.add_noise(0.01, 0.0, 0)),
    optax.adamw(1e-2, weight_decay=0.1),
], ids=["noise", "adamw_weight_decay"])
def test_pad_tail_stays_zero_under_noise(mesh8, inner):
    """An optimizer chain that moves zero-gradient entries (gradient noise)
    would drift the pad tail; the masked update pins it to bitwise 0.0 —
    the leak named by the ISSUE satellite. AdamW's decay reads the
    parameters themselves: the tail must give it nothing to decay."""
    del mesh8
    params = make_params()
    x, y = make_data(8)
    _, sp, plan = _train(grid_mesh(2, 4), 2, 4, params, x, y, steps=4,
                         inner=inner)
    padded_any = False
    for b, buf in enumerate(sp):
        flat = np.asarray(buf).reshape(-1)
        tail = flat[plan.raw_sizes[b]:]
        padded_any = padded_any or tail.size > 0
        assert (tail == 0.0).all(), f"bucket {b} pad tail drifted: {tail}"
    assert padded_any, "test vacuous: no bucket had padding"


def test_mask_pad_updates_zeroes_only_the_tail():
    params = make_params()
    plan = sh.build_shard_plan(params, 4, threshold=1 << 20)
    ones = sh.ShardedBuckets(
        jnp.ones((plan.shard_size, c)) for c in plan.chunk_sizes)
    masked = sh.mask_pad_updates(ones, plan)
    for b, buf in enumerate(masked):
        flat = np.asarray(buf).reshape(-1)
        raw = plan.raw_sizes[b]
        assert (flat[:raw] == 1.0).all()
        assert (flat[raw:] == 0.0).all()


def test_unmasked_noise_would_drift_tail():
    """Control for the invariant above: WITHOUT the mask, the same noise
    chain provably moves the tail — the mask is load-bearing, not
    decorative."""
    params = make_params()
    plan = sh.build_shard_plan(params, 4, threshold=1 << 20)
    assert any(r != p for r, p in zip(plan.raw_sizes, plan.padded_sizes))
    sp = sh.shard_params(params, plan)
    noisy = optax.add_noise(0.01, 0.0, 0)
    st = noisy.init(sp)
    zero_grads = jax.tree_util.tree_map(jnp.zeros_like, sp)
    upd, _ = noisy.update(zero_grads, st)
    drifted = False
    for b, buf in enumerate(upd):
        tail = np.asarray(buf).reshape(-1)[plan.raw_sizes[b]:]
        drifted = drifted or (tail.size and (tail != 0.0).any())
    assert drifted


# ----------------------------------------------------------- checkpointing


def test_sharded_checkpoint_save_restore_resume(mesh8, tmp_path):
    """save -> restore -> resume walks the identical trajectory as never
    having checkpointed (bitwise), through the consolidated mesh-shape-
    independent checkpoint format."""
    del mesh8
    from horovod_tpu import checkpoint as hvd_ckpt

    params = make_params()
    x, y = make_data(8)
    mesh = grid_mesh(2, 4)
    inner = optax.adam(1e-2)
    plan = sh.build_shard_plan(params, 4, threshold=1 << 20, num_buckets=2)
    sp = sh.shard_params(params, plan)
    opt = hvd.jax.DistributedOptimizer(inner, sharded=True, shard_plan=plan)
    st = opt.init(sp)
    specs = sh.shard_specs(st)

    def step(sp, st, x, y):
        full = sh.gather_params(sp, plan)
        _, g = jax.value_and_grad(lambda p: loss_fn(p, x, y))(full)
        upd, st = opt.update(g, st, sp)
        return optax.apply_updates(sp, upd), st

    run = jax.jit(shard_map(
        step, mesh=mesh,
        in_specs=(P("shard"), specs, P(("batch", "shard")),
                  P(("batch", "shard"))),
        out_specs=(P("shard"), specs), check_vma=False))
    for _ in range(3):
        sp, st = run(sp, st, x, y)
    state = {"params": sp, "opt_state": st, "step": 3}
    hvd_ckpt.save_sharded(str(tmp_path / "ckpt"), state, plan)
    # Continue the original for 2 more steps -> the reference trajectory.
    sp_ref, st_ref = sp, st
    for _ in range(2):
        sp_ref, st_ref = run(sp_ref, st_ref, x, y)
    # Restore into the sharded layout and resume.
    restored = hvd_ckpt.restore_sharded(str(tmp_path / "ckpt"), state, plan)
    assert int(np.asarray(restored["step"])) == 3
    sp_r, st_r = restored["params"], restored["opt_state"]
    for _ in range(2):
        sp_r, st_r = run(sp_r, st_r, x, y)
    a = sh.unshard_params(sp_ref, plan)
    b = sh.unshard_params(sp_r, plan)
    for k in a:
        assert np.array_equal(np.asarray(a[k]), np.asarray(b[k])), \
            f"{k}: resume diverged from the uncheckpointed trajectory"


def test_sharded_checkpoint_restores_onto_reshaped_mesh(tmp_path):
    """The consolidated format is mesh-shape independent: a shard=2
    checkpoint restores onto a shard=4 plan (and back to full)."""
    from horovod_tpu import checkpoint as hvd_ckpt

    params = make_params()
    plan2 = sh.build_shard_plan(params, 2, threshold=1 << 20)
    sp2 = sh.shard_params(params, plan2)
    hvd_ckpt.save_sharded(str(tmp_path / "ck"), {"params": sp2}, plan2)

    plan4 = sh.build_shard_plan(params, 4, threshold=1 << 20)
    template = {"params": sh.shard_params(params, plan4)}
    restored = hvd_ckpt.restore_sharded(str(tmp_path / "ck"), template,
                                        plan4)
    got = sh.unshard_params(restored["params"], plan4)
    for k in params:
        np.testing.assert_array_equal(np.asarray(params[k]),
                                      np.asarray(got[k]))


# ------------------------------------------------------------- observability


def test_shard_plan_gauges_recorded(mesh8):
    del mesh8
    params = make_params()
    x, y = make_data(8)
    _train(grid_mesh(2, 4), 2, 4, params, x, y, steps=1)
    plan = hvd_metrics.last_shard_plan()
    assert plan is not None
    assert plan["batch"] == 2 and plan["shard"] == 4
    assert plan["buckets"] >= 1
    assert plan["bytes_per_step"]["scatter"] == sum(plan["scatter_bytes"])
    assert plan["bytes_per_step"]["gather"] == sum(plan["gather_bytes"])
    snap = hvd_metrics.snapshot()
    names = set(snap.get("gauges", {}))
    assert any(n.startswith("horovod_compiled_shard_plan") for n in names)
    assert any(n.startswith("horovod_compiled_shard_bytes_per_step")
               for n in names)


def test_wire_compression_rides_the_scatter(mesh8):
    """The per-bucket wire-dtype verdicts apply unchanged: with bf16 the
    recorded scatter bytes halve while the gather (storage dtype) stays —
    and a tiny bucket under HOROVOD_COMPRESSION_MIN_BYTES opts out."""
    del mesh8
    mesh = grid_mesh(2, 4)
    big = {"w": jnp.ones((1 << 14,), jnp.float32)}          # 64 KiB
    plan = sh.build_shard_plan(big, 4, threshold=1 << 20)

    def body(g):
        g = jax.tree_util.tree_map(lambda t: jnp.squeeze(t, 0), g)
        out = sh.reduce_scatter_gradients(
            g, plan, compression="bf16", compression_min_bytes=0)
        return jax.tree_util.tree_map(lambda t: t[None], out)

    stacked = jax.tree_util.tree_map(
        lambda t: jnp.broadcast_to(t[None], (8,) + t.shape), big)
    jax.jit(shard_map(body, mesh=mesh, in_specs=(P(("batch", "shard")),),
                      out_specs=P(("batch", "shard")),
                      check_vma=False))(stacked)
    plan_rec = hvd_metrics.last_shard_plan()
    assert plan_rec["bytes_per_step"]["scatter"] * 2 == \
        plan_rec["bytes_per_step"]["gather"]
    wire = hvd_metrics.last_wire_plan()
    assert wire[0] == "bf16" and all(c for _, c, _ in wire[1])

    # Opt-out: same payload under the min-bytes floor ships full width.
    def body2(g):
        g = jax.tree_util.tree_map(lambda t: jnp.squeeze(t, 0), g)
        out = sh.reduce_scatter_gradients(
            g, plan, compression="bf16", compression_min_bytes=1 << 20)
        return jax.tree_util.tree_map(lambda t: t[None], out)

    jax.jit(shard_map(body2, mesh=mesh, in_specs=(P(("batch", "shard")),),
                      out_specs=P(("batch", "shard")),
                      check_vma=False))(stacked)
    plan_rec = hvd_metrics.last_shard_plan()
    assert plan_rec["bytes_per_step"]["scatter"] == \
        plan_rec["bytes_per_step"]["gather"]


# ----------------------------------------------------- broadcast + autotune


def test_broadcast_sharded_state(mesh8):
    """Initial-state consistency on the 2-D mesh: the broadcast rides the
    BATCH axis only, so every replica row adopts root's shard without any
    rank's partition being clobbered."""
    del mesh8
    mesh = grid_mesh(2, 4)
    params = make_params()
    plan = sh.build_shard_plan(params, 4, threshold=1 << 20)
    sp = sh.shard_params(params, plan)

    def body(sp):
        # Perturb non-root batch rows, then broadcast back from batch row 0.
        b = jax.lax.axis_index("batch")
        skew = jax.tree_util.tree_map(
            lambda t: t + b.astype(t.dtype) * 100.0, sp)
        fixed = hvd.jax.broadcast_sharded_state(skew)
        return fixed

    out = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(P("shard"),),
        out_specs=P("shard"), check_vma=False))(sp)
    got = sh.unshard_params(out, plan)
    for k in params:
        np.testing.assert_array_equal(np.asarray(params[k]),
                                      np.asarray(got[k]))


def test_autotune_fifth_dimension():
    """jax.autotune.tune(mesh_shapes=...): the mesh shape is explored
    exhaustively beside (threshold, buckets, compression, ladder) and the
    winner's config records it."""
    from horovod_tpu.jax.autotune import tune

    seen = []

    def step_factory(fusion_threshold, num_buckets, mesh_shape):
        seen.append((fusion_threshold, num_buckets, mesh_shape))
        import time as _t

        delay = 0.0002 if mesh_shape == "4x2" else 0.003

        def run():
            _t.sleep(delay)

        return run

    report = tune(step_factory, thresholds=(1 << 20,), num_buckets=(1, 2),
                  mesh_shapes=("8x1", "4x2"),
                  warmup=0, iters=1, reps=1, gp_rounds=0)
    assert {m for (_, _, m) in seen} == {"8x1", "4x2"}
    assert report.best.mesh_shape == "4x2"
    assert report.best.config.get("mesh") == "4x2"
    assert "mesh" in report.knob_curve()
