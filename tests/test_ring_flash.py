"""ring_flash correctness: the pallas-fused ring schedule must match the
dense causal oracle — forward AND gradients, contiguous AND zigzag layouts.

The kernels run in interpret mode on the CPU test mesh. Interpret mode
skips Mosaic's block-tiling constraints, so the multi-block tests force
explicit small block sizes to exercise the grid accumulation and per-block
``pl.when`` skips; the TPU BlockSpec layouts themselves (the part
interpret mode cannot check) are guarded by the layout notes in
ring_flash.py and were validated on a real v5e chip at t_local=2560 —
the block_k=320 case that rejects a lane-major kpos layout.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from horovod_tpu.compat import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu.ops.ring_attention import (
    causal_reference,
    zigzag_shard,
    zigzag_unshard,
)
from horovod_tpu.ops.ring_flash import ring_flash_attention


def qkv(b=1, t=64, h=2, d=8, seed=0):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (
        jax.random.normal(k1, (b, t, h, d), jnp.float32),
        jax.random.normal(k2, (b, t, h, d), jnp.float32),
        jax.random.normal(k3, (b, t, h, d), jnp.float32),
    )


@pytest.fixture()
def sp_mesh():
    return Mesh(np.asarray(jax.devices()[:4]), ("sp",))


def _sharded(mesh, fn):
    return shard_map(fn, mesh=mesh, in_specs=P(None, "sp"),
                     out_specs=P(None, "sp"), check_vma=False)


def test_ring_flash_matches_oracle(sp_mesh):
    q, k, v = qkv()
    with jax.default_matmul_precision("highest"):
        ref = causal_reference(q, k, v)
        out = _sharded(sp_mesh, lambda a, b, c: ring_flash_attention(
            a, b, c, "sp", interpret=True))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.slow
def test_ring_flash_zigzag_matches_oracle(sp_mesh):
    n = sp_mesh.size
    q, k, v = qkv(t=64)
    qz, kz, vz = (zigzag_shard(x, n) for x in (q, k, v))
    with jax.default_matmul_precision("highest"):
        ref = causal_reference(q, k, v)
        out_z = _sharded(sp_mesh, lambda a, b, c: ring_flash_attention(
            a, b, c, "sp", zigzag=True, interpret=True))(qz, kz, vz)
        out = zigzag_unshard(out_z, n)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.slow  # re-tiered r5: multi-process spawn cost; core coverage stays fast
def test_ring_flash_multiblock_matches_oracle(sp_mesh):
    """Explicit small blocks: t_local=16 with block_q=8/block_k=4 gives a
    2x4 grid per ring step — exercises the scratch carry across k-blocks
    and the per-block pl.when skip (single-block runs never enter them)."""
    q, k, v = qkv(t=64)
    with jax.default_matmul_precision("highest"):
        ref = causal_reference(q, k, v)
        out = _sharded(sp_mesh, lambda a, b, c: ring_flash_attention(
            a, b, c, "sp", block_q=8, block_k=4, interpret=True))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.slow
def test_ring_flash_multiblock_grads_match_oracle(sp_mesh):
    q, k, v = qkv(t=32)  # t_local=8 with bq=4/bk=2: 2x4 grid per step
    w = jax.random.normal(jax.random.PRNGKey(9), q.shape, jnp.float32)
    ring = _sharded(sp_mesh, lambda a, b, c: ring_flash_attention(
        a, b, c, "sp", block_q=4, block_k=2, interpret=True))
    with jax.default_matmul_precision("highest"):
        g_ring = jax.grad(lambda a, b, c: jnp.sum(ring(a, b, c) * w),
                          argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(lambda a, b, c: jnp.sum(causal_reference(a, b, c) * w),
                         argnums=(0, 1, 2))(q, k, v)
    for got, want, name in zip(g_ring, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=3e-5, rtol=3e-5,
                                   err_msg=f"d{name} mismatch")


@pytest.mark.slow
def test_ring_flash_zigzag_grads_match_oracle(sp_mesh):
    """dQ accumulates locally, dK/dV ride the ring home — all three must
    equal autodiff through the dense oracle. Zigzag layout: the masking
    must use the true (non-contiguous) global positions in both passes.
    (Contiguous-layout gradients are covered by the multiblock test above
    and the full-model parity test below.)"""
    n = sp_mesh.size
    q, k, v = qkv(t=32)
    w = jax.random.normal(jax.random.PRNGKey(9), q.shape, jnp.float32)
    qz, kz, vz = (zigzag_shard(x, n) for x in (q, k, v))
    wz = zigzag_shard(w, n)

    ring = _sharded(sp_mesh, lambda a, b, c: ring_flash_attention(
        a, b, c, "sp", zigzag=True, interpret=True))
    with jax.default_matmul_precision("highest"):
        g_ring = jax.grad(lambda a, b, c: jnp.sum(ring(a, b, c) * wz),
                          argnums=(0, 1, 2))(qz, kz, vz)
        g_ref = jax.grad(lambda a, b, c: jnp.sum(causal_reference(a, b, c) * w),
                         argnums=(0, 1, 2))(q, k, v)
    for got_z, want, name in zip(g_ring, g_ref, "qkv"):
        got = zigzag_unshard(got_z, n)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=3e-5, rtol=3e-5,
                                   err_msg=f"d{name} mismatch")


@pytest.mark.slow
def test_ring_flash_gqa_matches_replicated_oracle(sp_mesh):
    """GQA through the ring: 4 q heads over 2 kv heads; the ring rotates
    only the small kv blocks and the dK/dV that ride home with them must
    equal the replicated-oracle group sums."""
    hkv, group = 2, 2
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (1, 32, hkv * group, 8), jnp.float32)
    k = jax.random.normal(ks[1], (1, 32, hkv, 8), jnp.float32)
    v = jax.random.normal(ks[2], (1, 32, hkv, 8), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(9), q.shape, jnp.float32)

    def rep(x):
        return jnp.repeat(x, group, axis=2)

    ring = _sharded(sp_mesh, lambda a, b, c: ring_flash_attention(
        a, b, c, "sp", interpret=True))
    with jax.default_matmul_precision("highest"):
        out = ring(q, k, v)
        ref = causal_reference(q, rep(k), rep(v))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)
        g_ring = jax.grad(lambda a, b, c: jnp.sum(ring(a, b, c) * w),
                          argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(
            lambda a, b, c: jnp.sum(causal_reference(a, rep(b), rep(c)) * w),
            argnums=(0, 1, 2))(q, k, v)
    for got, want, name in zip(g_ring, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=3e-5, rtol=3e-5,
                                   err_msg=f"d{name} mismatch")


@pytest.mark.slow
def test_transformer_sp_flash_equals_dense(sp_mesh):
    """Full model: sp-sharded forward with ring-FLASH attention == the
    single-device dense forward, same params."""
    from horovod_tpu.models import TransformerLM

    dense = TransformerLM(vocab=64, dim=32, heads=4, layers=2,
                          dtype=jnp.float32)
    sp = TransformerLM(vocab=64, dim=32, heads=4, layers=2,
                       dtype=jnp.float32, sp_axis="sp", attention="flash",
                       flash_interpret=True)
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
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)
