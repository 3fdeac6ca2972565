"""Sequence-parallel attention correctness: ring and Ulysses schedules must
match the dense causal oracle on a sequence-sharded virtual mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from horovod_tpu.compat import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu.ops.ring_attention import (
    causal_reference,
    ring_attention,
    ulysses_attention,
)


def qkv(b=2, t=64, h=8, d=16, seed=0):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (
        jax.random.normal(k1, (b, t, h, d), jnp.float32),
        jax.random.normal(k2, (b, t, h, d), jnp.float32),
        jax.random.normal(k3, (b, t, h, d), jnp.float32),
    )


@pytest.fixture()
def sp_mesh():
    # 4 of the 8 virtual devices: the ring schedule unrolls one scan step per
    # device, so compile time scales with mesh size — 4 exercises the same
    # index math (>2 avoids trivial neighbour symmetry) at half the compile.
    return Mesh(np.asarray(jax.devices()[:4]), ("sp",))


def _run_sharded(fn, mesh, *args):
    return shard_map(
        fn, mesh=mesh, in_specs=P(None, "sp"), out_specs=P(None, "sp"),
        check_vma=False,
    )(*args)


@pytest.mark.slow
def test_ring_attention_matches_oracle(sp_mesh):
    q, k, v = qkv()
    with jax.default_matmul_precision("highest"):
        ref = causal_reference(q, k, v)
        out = _run_sharded(lambda a, b, c: ring_attention(a, b, c, "sp"), sp_mesh, q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.slow
def test_ring_attention_is_causal(sp_mesh):
    """Changing future tokens must not change past outputs."""
    q, k, v = qkv(t=32)
    k2, v2 = k.at[:, 16:].set(0.0), v.at[:, 16:].set(0.0)
    with jax.default_matmul_precision("highest"):
        a = _run_sharded(lambda x, y, z: ring_attention(x, y, z, "sp"), sp_mesh, q, k, v)
        b = _run_sharded(lambda x, y, z: ring_attention(x, y, z, "sp"), sp_mesh, q, k2, v2)
    np.testing.assert_allclose(np.asarray(a[:, :16]), np.asarray(b[:, :16]), atol=1e-6)
    assert not np.allclose(np.asarray(a[:, 16:]), np.asarray(b[:, 16:]))


def test_ulysses_matches_oracle(sp_mesh):
    q, k, v = qkv()
    with jax.default_matmul_precision("highest"):
        ref = causal_reference(q, k, v)
        out = _run_sharded(
            lambda a, b, c: ulysses_attention(a, b, c, "sp"), sp_mesh, q, k, v
        )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.slow
def test_ulysses_flash_matches_oracle(sp_mesh):
    """impl='flash': the post-all-to-all local attention runs through the
    pallas kernel; grads flow through its custom VJP and the all_to_all
    transposes."""
    q, k, v = qkv()
    w = jax.random.normal(jax.random.PRNGKey(9), q.shape, jnp.float32)
    uly = shard_map(
        lambda a, b, c: ulysses_attention(a, b, c, "sp", impl="flash",
                                          interpret=True),
        mesh=sp_mesh, in_specs=P(None, "sp"), out_specs=P(None, "sp"),
        check_vma=False)
    with jax.default_matmul_precision("highest"):
        ref = causal_reference(q, k, v)
        out = uly(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)
        g_u = jax.grad(lambda a, b, c: jnp.sum(uly(a, b, c) * w),
                       argnums=(0, 1, 2))(q, k, v)
        g_r = jax.grad(lambda a, b, c: jnp.sum(causal_reference(a, b, c) * w),
                       argnums=(0, 1, 2))(q, k, v)
    for got, want, name in zip(g_u, g_r, "qkv"):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=3e-5, rtol=3e-5,
                                   err_msg=f"d{name} mismatch")


def test_ulysses_rejects_bad_heads(sp_mesh):
    q, k, v = qkv(h=6)  # 6 % 8 != 0
    with pytest.raises(ValueError, match="not divisible"):
        _run_sharded(lambda a, b, c: ulysses_attention(a, b, c, "sp"), sp_mesh, q, k, v)


@pytest.mark.slow
def test_transformer_sp_equals_dense(sp_mesh):
    """Full model: sp-sharded forward with ring attention == single-device
    forward with dense attention, same params."""
    from horovod_tpu.models import TransformerLM

    dense = TransformerLM(vocab=64, dim=32, heads=4, layers=2, dtype=jnp.float32)
    sp = TransformerLM(vocab=64, dim=32, heads=4, layers=2, dtype=jnp.float32,
                       sp_axis="sp")
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 64)
    params = dense.init(jax.random.PRNGKey(0), tokens)["params"]

    with jax.default_matmul_precision("highest"):
        ref = dense.apply({"params": params}, tokens)

        def fwd(tokens):
            t_local = tokens.shape[1]
            pos = (jax.lax.axis_index("sp") * t_local + jnp.arange(t_local))[None, :]
            return sp.apply({"params": params}, tokens, pos)

        out = shard_map(fwd, mesh=sp_mesh, in_specs=P(None, "sp"),
                        out_specs=P(None, "sp"), check_vma=False)(tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4, rtol=1e-4)


@pytest.mark.slow
def test_zigzag_ring_matches_oracle(sp_mesh):
    """Zigzag layout (load-balanced causal sharding): shard the zigzag-
    reordered sequence, run ring attention with zigzag masking, undo the
    permutation — must equal the dense oracle on the ORIGINAL order."""
    from horovod_tpu.ops.ring_attention import zigzag_shard, zigzag_unshard

    n = sp_mesh.size
    q, k, v = qkv(t=64)
    qz, kz, vz = (zigzag_shard(x, n) for x in (q, k, v))
    with jax.default_matmul_precision("highest"):
        ref = causal_reference(q, k, v)
        out_z = _run_sharded(
            lambda a, b, c: ring_attention(a, b, c, "sp", zigzag=True),
            sp_mesh, qz, kz, vz)
        out = zigzag_unshard(out_z, n)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_zigzag_shard_roundtrip():
    from horovod_tpu.ops.ring_attention import zigzag_shard, zigzag_unshard

    x = jnp.arange(2 * 32 * 3).reshape(2, 32, 3).astype(jnp.float32)
    y = zigzag_unshard(zigzag_shard(x, 4), 4)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))


@pytest.mark.slow
def test_ring_attention_gqa_matches_oracle(sp_mesh):
    """GQA kv (fewer heads) through the dense ring: the ring rotates the
    small kv blocks and replicates heads inside the local block product —
    must equal the oracle on pre-replicated kv (ADVICE r2 #3)."""
    q, _, _ = qkv(h=8)
    _, k, v = qkv(h=2, seed=1)
    rep = q.shape[2] // k.shape[2]
    with jax.default_matmul_precision("highest"):
        ref = causal_reference(q, jnp.repeat(k, rep, axis=2),
                               jnp.repeat(v, rep, axis=2))
        out = _run_sharded(lambda a, b, c: ring_attention(a, b, c, "sp"),
                           sp_mesh, q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_ring_attention_rejects_nondivisible_gqa(sp_mesh):
    q, _, _ = qkv(h=8)
    _, k, v = qkv(h=3, seed=1)
    with pytest.raises(ValueError, match="multiple of kv heads"):
        _run_sharded(lambda a, b, c: ring_attention(a, b, c, "sp"),
                     sp_mesh, q, k, v)


def test_ulysses_rejects_unsplittable_gqa_kv(sp_mesh):
    """GQA kv that can't split over the axis must fail loudly and point at
    the ring path, not mis-shard through the all-to-all (ADVICE r2 #1)."""
    q, _, _ = qkv(h=8)
    _, k, v = qkv(h=2, seed=1)  # 2 kv heads % 4 devices != 0
    with pytest.raises(ValueError, match="GQA kv heads"):
        _run_sharded(lambda a, b, c: ulysses_attention(a, b, c, "sp"),
                     sp_mesh, q, k, v)


@pytest.mark.parametrize("impl", ["dense", "flash"])
def test_ulysses_gqa_matches_oracle(sp_mesh, impl):
    """GQA kv that DOES divide the axis (4 kv heads / 4 devices) must shard
    through the all-to-all and match the oracle — the split keeps the
    q→kv grouping contiguous per device."""
    q, _, _ = qkv(h=8, t=128 if impl == "flash" else 64)
    _, k, v = qkv(h=4, t=128 if impl == "flash" else 64, seed=1)
    rep = q.shape[2] // k.shape[2]
    with jax.default_matmul_precision("highest"):
        ref = causal_reference(q, jnp.repeat(k, rep, axis=2),
                               jnp.repeat(v, rep, axis=2))
        out = _run_sharded(
            lambda a, b, c: ulysses_attention(a, b, c, "sp", impl=impl,
                                          interpret=True),
            sp_mesh, q, k, v)
    tol = 2e-2 if impl == "flash" else 2e-5
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=tol, rtol=tol)


def test_ring_attention_matches_oracle_fast():
    """Fast-tier dense-oracle pin (ISSUE 19 promotion satellite): the ring
    schedule vs the causal reference at the smallest ring (2 devices,
    short sequence) — the online-softmax rescale is pinned at float32
    tolerance outside -m slow too."""
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("sp",))
    q, k, v = qkv(b=1, t=16, h=2, d=8, seed=4)
    with jax.default_matmul_precision("highest"):
        ref = causal_reference(q, k, v)
        out = shard_map(
            lambda a, b, c: ring_attention(a, b, c, "sp"), mesh=mesh,
            in_specs=P(None, "sp"), out_specs=P(None, "sp"),
            check_vma=False)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
