"""Overlap scheduler tests: reverse-order bucket planning, numerical
equivalence of K-bucket vs single-bucket allreduce on the 8-device CPU mesh,
and joint (fusion_threshold, num_buckets) autotuner convergence — the
bucketed compute/comm-overlap path of fusion.py / collectives.py /
DistributedOptimizer (Horovod's background-thread overlap expressed at the
XLA graph level; ISSUE 1 tentpole)."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.compat import shard_map
from horovod_tpu.parallel import fusion


def _tree(sizes=(100, 200, 300, 7), dtype=jnp.float32):
    return {f"w{i}": jnp.arange(s, dtype=dtype) for i, s in enumerate(sizes)}


# ------------------------------------------------------------------ planning


def test_reverse_plan_bucket_count_and_order():
    tree = _tree()
    n_leaves = len(jax.tree_util.tree_leaves(tree))
    for k in (2, 3, 4):
        plan = fusion.build_plan(tree, num_buckets=k)
        assert plan.reverse_order
        assert plan.num_buckets == k
        # Bucket 0 starts at the LAST flatten index (last-layer grads — what
        # the backward pass produces first) and indices never increase.
        flat_idx = [d.index for b in plan.buckets for d in b]
        assert flat_idx[0] == n_leaves - 1
        assert flat_idx == sorted(flat_idx, reverse=True)
        # Every leaf appears exactly once.
        assert sorted(flat_idx) == list(range(n_leaves))


def test_reverse_plan_respects_k_up_to_leaf_granularity():
    tree = [jnp.zeros((10,)) for _ in range(20)]
    for k, expect in ((1, 1), (4, 4), (7, 7), (20, 20), (30, 20)):
        assert fusion.build_plan(tree, num_buckets=k).num_buckets == expect


def test_reverse_plan_buckets_are_byte_balanced():
    tree = [jnp.zeros((64,)) for _ in range(16)]
    plan = fusion.build_plan(tree, num_buckets=4)
    sizes = [sum(d.size for d in b) for b in plan.buckets]
    assert max(sizes) <= 2 * min(sizes)


def test_reverse_plan_single_dtype_buckets_and_threshold_cap():
    tree = {"a": jnp.zeros((64,), jnp.float32),
            "b": jnp.zeros((64,), jnp.bfloat16),
            "c": jnp.zeros((64,), jnp.float32)}
    plan = fusion.build_plan(tree, num_buckets=2)
    for b in plan.buckets:
        assert len({d.dtype for d in b}) == 1
    # Threshold stays a hard cap in the K-bucket plan: 16 float32 leaves of
    # 64 B each with a 128 B cap can never fuse more than 2 leaves.
    big = [jnp.zeros((16,), jnp.float32) for _ in range(16)]
    plan = fusion.build_plan(big, threshold=128, num_buckets=2)
    for b in plan.buckets:
        assert sum(d.size * d.dtype.itemsize for d in b) <= 128


def test_reverse_plan_padding_invariant_roundtrip():
    tree = _tree((33, 65, 127))
    plan = fusion.build_plan(tree, num_buckets=3, pad_to=8)
    bufs = fusion.fuse(tree, plan)
    assert all(b.shape[0] % 8 == 0 for b in bufs)
    back = fusion.unfuse(bufs, plan)
    for a, b in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_single_bucket_plan_unchanged():
    """num_buckets=1 must stay the historical forward-order greedy merge."""
    tree = _tree()
    plan = fusion.build_plan(tree, num_buckets=1)
    assert not plan.reverse_order
    assert plan.num_buckets == 1
    assert [d.index for d in plan.buckets[0]] == [0, 1, 2, 3]


# ------------------------------------------------- numerical equivalence


def test_k_bucket_equals_single_bucket_allreduce(mesh8):
    """K-bucket and single-bucket fused allreduce must agree bitwise on the
    8-device CPU mesh: bucketing reorders the leaves' reductions (a
    bucket's leaves go to the collective as they are, PR 59), not the
    per-element cross-rank sums."""
    key = jax.random.PRNGKey(0)
    grads = {
        "w1": jax.random.normal(key, (8, 33, 7)),
        "w2": jax.random.normal(jax.random.PRNGKey(1), (8, 129)),
        "w3": jax.random.normal(jax.random.PRNGKey(2), (8, 5, 5)),
        "w4": jax.random.normal(jax.random.PRNGKey(3), (8, 257)),
    }

    def reducer(nb):
        return jax.jit(shard_map(
            lambda g: fusion.fused_allreduce(g, num_buckets=nb),
            mesh=mesh8, in_specs=P("hvd"), out_specs=P(), check_vma=False))

    ref = reducer(1)(grads)
    for k in (2, 3, 8):
        out = reducer(k)(grads)
        for a, b in zip(jax.tree_util.tree_leaves(ref),
                        jax.tree_util.tree_leaves(out)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_distributed_optimizer_num_buckets_trajectory_matches(mesh8):
    """One SGD step through DistributedOptimizer(num_buckets=K) lands on the
    same parameters as the single-bucket optimizer."""
    x = jnp.ones((16, 12))
    y = jnp.zeros((16,), jnp.int32)
    w = {"a": jnp.full((12, 8), 0.1), "b": jnp.zeros((8,))}

    def one_step(nb):
        opt = hvd.jax.DistributedOptimizer(optax.sgd(0.1), num_buckets=nb)
        state = opt.init(w)

        def train(w, state, x, y):
            def loss_fn(w):
                logits = x @ w["a"] + w["b"]
                return optax.softmax_cross_entropy_with_integer_labels(
                    logits, y).mean()

            g = jax.grad(loss_fn)(w)
            up, state = opt.update(g, state, w)
            return optax.apply_updates(w, up)

        step = jax.jit(shard_map(
            train, mesh=mesh8,
            in_specs=(P(), P(), P("hvd"), P("hvd")),
            out_specs=P(), check_vma=False))
        return step(w, state, x, y)

    ref = one_step(1)
    for k in (2, 4):
        out = one_step(k)
        for a, b in zip(jax.tree_util.tree_leaves(ref),
                        jax.tree_util.tree_leaves(out)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_num_buckets_env_knob(monkeypatch):
    from horovod_tpu.common.config import Config
    from horovod_tpu.jax import _resolved_num_buckets

    monkeypatch.setenv("HOROVOD_NUM_BUCKETS", "6")
    cfg = Config.from_env()
    assert cfg.num_buckets == 6
    assert "HOROVOD_NUM_BUCKETS" in cfg.pinned
    assert _resolved_num_buckets(None) == 6
    assert _resolved_num_buckets(3) == 3       # explicit argument wins
    monkeypatch.delenv("HOROVOD_NUM_BUCKETS")
    assert Config.from_env().num_buckets == 1


# --------------------------------------------------- joint autotuning


def _sim(threshold: int, nb: int) -> float:
    """Synthetic objective over the 2-D space: best at a large threshold and
    ~8 buckets (overlap pays until launch overhead bites)."""
    t_mb = threshold / (1 << 20)
    return (math.log2(t_mb + 1) / 8.0) * math.exp(
        -((math.log2(nb) - 3.0) ** 2) / 4.0)


def test_native_manager_converges_over_threshold_and_buckets():
    """The 5-dim native BO (autotuner.h) must move BOTH knobs toward the
    simulated optimum when the bucket dimension is opened."""
    from horovod_tpu.autotune import ParameterManager

    pm = ParameterManager(fusion_threshold=2 << 20, cycle_time_ms=5.0,
                          cycle_pinned=True, num_buckets=1)
    start = _sim(2 << 20, 1)
    for _ in range(5000):
        if not pm.active:
            break
        score = _sim(pm.fusion_threshold, pm.num_buckets)
        pm.update(int(score * 1e6), 1.0)
    assert not pm.active
    final = _sim(pm.fusion_threshold, pm.num_buckets)
    assert final > start * 1.5
    assert pm.num_buckets > 1                  # found the overlap win
    pm.close()


def test_native_manager_bucket_pin_respected():
    from horovod_tpu.autotune import ParameterManager

    pm = ParameterManager(fusion_threshold=8 << 20, cycle_time_ms=5.0,
                          num_buckets=4, num_buckets_pinned=True)
    for _ in range(3000):
        if not pm.active:
            break
        pm.update(1000000, 0.01)
    assert pm.num_buckets == 4                 # pinned knob never moved
    pm.close()


def test_ei_suggest_joint_prefers_unexplored_interior():
    from horovod_tpu.jax.autotune import _ei_suggest_joint

    measured = {(1 << 20, 1): 1.0, (1 << 20, 8): 1.4,
                (1 << 28, 1): 1.1, (1 << 28, 8): 3.0,
                (1 << 24, 4): 2.0}
    nxt = _ei_suggest_joint(measured, (1 << 20, 1 << 28), (1, 8))
    assert nxt is not None
    th, nb = nxt
    assert (1 << 20) <= th <= (1 << 28)
    assert 1 <= nb <= 8
    assert nxt not in measured


def test_compiled_tuner_joint_grid_and_report(mesh8, tmp_path):
    """tune(num_buckets=...) must cover the (threshold × buckets) seed grid,
    call the factory with the num_buckets kwarg, and report a best config
    carrying both knobs."""
    from horovod_tpu.jax.autotune import tune

    built = []
    x = jnp.ones((16, 8))
    y = jnp.zeros((16,), jnp.int32)
    w = jnp.zeros((8, 4))

    def step_factory(fusion_threshold, num_buckets):
        built.append((fusion_threshold, num_buckets))
        opt = hvd.jax.DistributedOptimizer(
            optax.sgd(0.1), fusion_threshold=fusion_threshold,
            num_buckets=num_buckets)
        state = [w, opt.init(w)]

        def train(w, ostate, x, y):
            g = jax.grad(lambda w: ((x @ w) ** 2).mean())(w)
            up, ostate = opt.update(g, ostate, w)
            return optax.apply_updates(w, up), ostate

        step = jax.jit(shard_map(train, mesh=mesh8,
                                 in_specs=(P(), P(), P("hvd"), P("hvd")),
                                 out_specs=(P(), P()), check_vma=False))

        def run():
            state[0], state[1] = step(state[0], state[1], x, y)
            jax.block_until_ready(state[0])

        return run

    log = tmp_path / "joint.csv"
    report = tune(step_factory, thresholds=(1 << 18, 1 << 22),
                  num_buckets=(1, 2), warmup=1, iters=2, reps=2,
                  gp_rounds=0, log_path=str(log))
    assert {(t, b) for t, b in built} == {
        (1 << 18, 1), (1 << 18, 2), (1 << 22, 1), (1 << 22, 2)}
    assert report.best.config["num_buckets"] in (1, 2)
    assert report.best.config["fusion_threshold"] in (1 << 18, 1 << 22)
    text = log.read_text()
    assert text.startswith("branch,fusion_threshold,num_buckets,steps_per_s")
    assert len(text.strip().splitlines()) == len(report.table) + 1
    assert "num_buckets" in report.knob_curve()
