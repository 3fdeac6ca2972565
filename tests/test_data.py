"""Rank-sharded input pipeline — the DistributedSampler contract
(disjoint per-rank coverage, per-epoch reshuffle, equal step counts) and
real file IO through np.memmap (reference real-data recipe,
docs/benchmarks.md:40-63)."""

from __future__ import annotations

import os
import subprocess
import sys

import numpy as np
import pytest

from horovod_tpu.data import (
    DistributedSampler,
    MemmapArrayDataset,
    write_synthetic_shards,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_sampler_partitions_disjoint_and_complete():
    n, size = 103, 4  # non-divisible: tail is padded by wrapping
    per_rank = [list(DistributedSampler(n, rank=r, size=size, shuffle=False))
                for r in range(size)]
    lengths = {len(ix) for ix in per_rank}
    assert lengths == {26}, "all ranks must take the same number of steps"
    flat = [i for ix in per_rank for i in ix]
    assert set(flat) == set(range(n)), "every sample must be covered"
    # only the wrap-pad duplicates: total - n
    assert len(flat) - len(set(flat)) == 26 * size - n


def test_sampler_reshuffles_per_epoch_identically_across_ranks():
    samplers = [DistributedSampler(64, rank=r, size=2, seed=7) for r in (0, 1)]
    first = [s.indices().tolist() for s in samplers]
    assert not set(first[0]) & set(first[1]), "ranks must be disjoint"
    for s in samplers:
        s.set_epoch(1)
    second = [s.indices().tolist() for s in samplers]
    assert first[0] != second[0], "epoch must reshuffle"
    assert not set(second[0]) & set(second[1]), \
        "ranks must stay disjoint after reshuffle (same permutation)"


def test_sampler_batches_drop_ragged_tail():
    s = DistributedSampler(100, rank=0, size=2, shuffle=False)  # 50 idx
    batches = list(s.batches(16))
    assert [len(b) for b in batches] == [16, 16, 16]
    assert [len(b) for b in s.batches(16, drop_last=False)][-1] == 2


def test_memmap_dataset_roundtrip(tmp_path):
    d = write_synthetic_shards(str(tmp_path), 20, (3, 4, 4), 10, seed=1)
    ds = MemmapArrayDataset(d)
    assert len(ds) == 20
    x, y = ds[[3, 7, 7]]
    assert x.shape == (3, 3, 4, 4) and y.shape == (3,)
    assert x.dtype == np.float32 and y.dtype == np.int64
    np.testing.assert_array_equal(ds[[7]][0][0], x[1])
    # memmap: the file is the storage, not RAM
    assert isinstance(ds.images, np.memmap)


def test_sampler_rejects_bad_world():
    with pytest.raises(ValueError, match="outside world"):
        DistributedSampler(10, rank=3, size=2)
    with pytest.raises(ValueError, match="empty dataset"):
        DistributedSampler(0, rank=0, size=1)


@pytest.mark.slow
def test_imagenet_example_trains_from_files(tmp_path):
    """E2e: 2 ranks write + read npy shards from disk through the launcher;
    each rank reads a disjoint half per epoch and training completes."""
    data_dir = str(tmp_path / "shards")
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner", "-np", "2", "--",
         sys.executable, "examples/pytorch_imagenet_resnet50.py",
         "--epochs", "2", "--data-dir", data_dir, "--make-data", "128",
         "--batch-size", "16", "--image-size", "8",
         "--checkpoint-dir", str(tmp_path / "ck")],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert os.path.exists(os.path.join(data_dir, "images.npy"))
    assert '"epoch": 2' in proc.stdout


def test_device_cache_epoch_contract():
    """DeviceCache.sample visits every shard row exactly once per epoch in a
    seeded order that changes across epochs — the in-jit realization of
    DistributedSampler.set_epoch's reshuffle contract (the device-resident
    pipeline of examples/realdata_benchmark.py)."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.data import DeviceCache

    n, batch = 32, 8
    images = np.arange(n, dtype=np.uint8).reshape(n, 1, 1, 1)
    labels = np.arange(n, dtype=np.int64)
    cache = DeviceCache(images, labels, batch_size=batch, seed=3)

    @jax.jit
    def draw(ctr):
        x, y, ctr = cache.sample(ctr)
        return x, y, ctr

    ctr = cache.counter()
    epochs = []
    for _ in range(2):
        seen = []
        for _ in range(n // batch):
            x, y, ctr = draw(ctr)
            rows = np.asarray(y)
            # x (uint8, normalize) is the same row id scaled: check pairing
            np.testing.assert_allclose(
                np.asarray(x).reshape(batch),
                rows.astype(np.float32) / 127.5 - 1.0, rtol=1e-6)
            seen.extend(rows.tolist())
        assert sorted(seen) == list(range(n))  # exactly once per epoch
        epochs.append(seen)
    assert epochs[0] != epochs[1]  # reshuffled across epochs
    assert int(ctr) == 2 * (n // batch)


def test_device_cache_validation():
    from horovod_tpu.data import DeviceCache

    with pytest.raises(ValueError, match="mismatch"):
        DeviceCache(np.zeros((4, 1)), np.zeros(3), batch_size=2)
    with pytest.raises(ValueError, match="cannot fill"):
        DeviceCache(np.zeros((2, 1)), np.zeros(2), batch_size=4)


def test_scan_train_loop_matches_stepwise():
    """hvd.jax.make_scan_train_loop: K scanned steps per dispatch over a
    DeviceCache must produce the EXACT trajectory of calling the same
    train_step K times with the same cache draws — the scan is a dispatch
    optimization, not a semantic change."""
    import jax
    import jax.numpy as jnp
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.data import DeviceCache

    n, batch, K = 32, 4, 4
    rng = np.random.default_rng(0)
    images = rng.integers(0, 256, (n, 3), dtype=np.uint8)
    labels = (images.sum(axis=1) % 5).astype(np.int64)
    cache = DeviceCache(images, labels, batch_size=batch, seed=7)

    opt = optax.sgd(0.1)
    params = {"w": jnp.zeros((3, 5)), "b": jnp.zeros((5,))}
    state0 = opt.init(params)

    def train_step(p, o, x, y):
        def loss_fn(p):
            logits = x @ p["w"] + p["b"]
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, y).mean()

        loss, g = jax.value_and_grad(loss_fn)(p)
        up, o = opt.update(g, o, p)
        return optax.apply_updates(p, up), o, loss

    # stepwise oracle (no scan, no donation)
    p_ref, o_ref, ctr = dict(params), state0, cache.counter()
    losses_ref = []
    for _ in range(K):
        x, y, ctr = cache.sample(ctr, cache.data, cache.labels)
        p_ref, o_ref, loss = train_step(p_ref, o_ref, x, y)
        losses_ref.append(float(loss))

    loop = hvd.jax.make_scan_train_loop(train_step, cache,
                                        steps_per_dispatch=K, donate=False)
    p_s, o_s, ctr_s, mean_loss = loop(dict(params), state0, cache.counter(),
                                      cache.data, cache.labels)
    assert int(ctr_s) == K
    np.testing.assert_allclose(float(mean_loss), np.mean(losses_ref),
                               rtol=1e-6)
    for k in params:
        np.testing.assert_allclose(np.asarray(p_s[k]), np.asarray(p_ref[k]),
                                   rtol=1e-6, atol=1e-7)

    # Default donated path: params/opt_state/ctr update in place, and the
    # cache shard must NOT be donated (a second call reuses it).
    loop_d = hvd.jax.make_scan_train_loop(train_step, cache,
                                          steps_per_dispatch=K)
    p_d, o_d, ctr_d, _ = loop_d(
        jax.tree_util.tree_map(lambda t: jnp.array(t, copy=True), dict(params)),
        jax.tree_util.tree_map(lambda t: jnp.array(t, copy=True), state0),
        cache.counter(), cache.data, cache.labels)
    for k in params:
        np.testing.assert_allclose(np.asarray(p_d[k]), np.asarray(p_ref[k]),
                                   rtol=1e-6, atol=1e-7)
    # shard survives donation and a second dispatch continues the epoch
    p_d, o_d, ctr_d, _ = loop_d(p_d, o_d, ctr_d, cache.data, cache.labels)
    assert int(ctr_d) == 2 * K

    with pytest.raises(ValueError, match="steps_per_dispatch"):
        hvd.jax.make_scan_train_loop(train_step, cache, steps_per_dispatch=0)
