"""Pallas flash-attention correctness (interpret mode on the CPU mesh):
forward and all three gradients against the dense causal oracle, non-causal
mode, block validation, and the TransformerLM attention="flash" path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops.flash_attention import flash_attention
from horovod_tpu.ops.ring_attention import causal_reference

B, T, H, D = 2, 128, 2, 32


def qkv(seed=0, t=T):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (B, t, H, D), jnp.float32) for k in ks)


def _dense(q, k, v, causal, sm_scale=None):
    """f32 oracle for either mode, kv heads replicated for GQA; v's head
    size may differ from q's and k's, and the scale may be given."""
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (
        q.shape[-1] ** -0.5 if sm_scale is None else sm_scale)
    if causal:
        t = q.shape[1]
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)


def test_forward_matches_oracle():
    q, k, v = qkv()
    with jax.default_matmul_precision("highest"):
        out = flash_attention(q, k, v, True, 32, 32, True)
        ref = causal_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-6, rtol=2e-6)


def test_gradients_match_oracle():
    q, k, v = qkv(1)
    g = jax.random.normal(jax.random.PRNGKey(9), q.shape, jnp.float32)
    with jax.default_matmul_precision("highest"):
        gf = jax.grad(lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, True, 32, 32, True) * g), argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lambda q, k, v: jnp.sum(
            causal_reference(q, k, v) * g), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-6, rtol=5e-6)


# name: (t, h, hkv, d, block_q, block_k, causal, dtype[, v's head size,
# sm_scale]). Which bodies of the three kernels each reaches (blocks below
# the diagonal: no mask; blocks the diagonal crosses: sub-tiles masked where
# it crosses them, unmasked below it, skipped above it):
BODY_CASES = {
    # 4 q blocks: 6 unmasked + 4 masked block steps, and the seam between
    "square_blocks": (128, 2, 2, 32, 32, 32, True, jnp.float32),
    # block_q = 2 x block_k: the diagonal crosses two k blocks per q block
    "diagonal_crosses_two": (128, 2, 2, 32, 64, 32, True, jnp.float32),
    "one_block": (64, 2, 2, 32, 64, 64, True, jnp.float32),
    "non_causal": (128, 2, 2, 32, 32, 32, False, jnp.float32),
    "gqa": (128, 4, 2, 32, 32, 32, True, jnp.float32),
    "gqa_crosses_two": (128, 4, 2, 32, 64, 32, True, jnp.float32),
    "bf16": (128, 2, 2, 32, 32, 32, True, jnp.bfloat16),
    "bf16_non_causal": (128, 2, 2, 32, 32, 32, False, jnp.bfloat16),
    # the default blocks: sub-tiles of 256 x 512 inside a 1024 x 1024 block,
    # two of the eight wholly above the diagonal in a crossed block
    "sub_tiles_one_block": (1024, 1, 1, 16, 1024, 1024, True, jnp.float32),
    "sub_tiles_both_bodies": (2048, 1, 1, 16, 1024, 1024, True, jnp.float32),
    "sub_tiles_non_causal": (1024, 1, 1, 16, 512, 1024, False, jnp.float32),
    "sub_tiles_bf16": (2048, 1, 1, 16, 1024, 1024, True, jnp.bfloat16),
    # block_q = 2 x block_k at T = one q block: both crossed blocks, the
    # second with its first q columns dead against every key of the block
    "sub_tiles_crosses_two": (1024, 1, 1, 16, 1024, 512, True, jnp.float32),
    # dK/dV's walk over a group's q heads and q blocks (j = g * nq + qi)
    "sub_tiles_gqa_group_walk": (2048, 4, 2, 16, 1024, 1024, True,
                                 jnp.float32),
    # latent attention: q and k 192 wide, v / dO / dV 128
    "sub_tiles_192_128": (1024, 1, 1, 192, 1024, 1024, True, jnp.bfloat16,
                          128),
    "192_128_f32": (128, 2, 2, 48, 64, 32, True, jnp.float32, 32),
    # Granite: grouped-query heads of 64, scores x 1/64 (not 64 ** -0.5)
    "sub_tiles_d64_scale": (1024, 2, 1, 64, 1024, 1024, True, jnp.bfloat16,
                            64, 0.015625),
    "non_causal_scale": (128, 2, 2, 32, 32, 32, False, jnp.float32, 32, 0.3),
    # blocks no sub-tile divides: walked whole (_sub_tile's fall-back)
    "block_no_sub_tile_divides": (768, 1, 1, 16, 384, 384, True,
                                  jnp.float32),
    "f32_at_512_blocks": (1024, 1, 1, 16, 512, 512, True, jnp.float32),
    # the folded causal-dense grids (a row walks q block p, then nq - 1 - p;
    # dK/dV the same over k blocks): an even count of q blocks ...
    "folded_eight_q_blocks": (256, 2, 2, 32, 32, 32, True, jnp.float32),
    # ... an ODD one, whose middle block has a row of its own, half of it dead
    "folded_odd": (96, 2, 2, 32, 32, 32, True, jnp.float32),
    "folded_five_q_blocks_bf16": (160, 2, 2, 32, 32, 32, True, jnp.bfloat16),
    # block_q = 2 x block_k: q blocks fold in threes, k blocks in sixes
    "folded_odd_crosses_two": (192, 2, 2, 32, 64, 32, True, jnp.float32),
    "folded_crosses_two": (256, 2, 2, 32, 64, 32, True, jnp.float32),
    # block_q = 3 x block_k at 3 q blocks: 9 k blocks, the middle one alone
    "folded_odd_crosses_three": (288, 1, 1, 16, 96, 32, True, jnp.float32),
    # grouped-query heads: each half of a dK/dV row sweeps the group's heads
    "folded_gqa_odd": (96, 4, 2, 32, 32, 32, True, jnp.float32),
    "folded_gqa_six_over_one": (128, 6, 1, 32, 64, 32, True, jnp.float32),
    "folded_gqa_d64_scale": (160, 8, 2, 64, 32, 32, True, jnp.bfloat16, 64,
                             0.015625),
    # latent attention's 192 | 128 over an even and an odd count
    "folded_192_128": (128, 2, 2, 48, 32, 32, True, jnp.float32, 32),
    "folded_192_128_odd": (96, 2, 2, 48, 32, 32, True, jnp.float32, 32),
    "folded_sub_tiles_192_128_odd": (3072, 1, 1, 192, 1024, 1024, True,
                                     jnp.bfloat16, 128),
}


@pytest.mark.parametrize("case", sorted(BODY_CASES))
def test_both_bodies_match_oracle(case):
    """Forward and all three gradients against the dense f32 oracle for
    every way a block step can run."""
    t, h, hkv, d, block_q, block_k, causal, dtype, *rest = BODY_CASES[case]
    dv, sm_scale = rest + [d, None][len(rest):]
    ks = jax.random.split(jax.random.PRNGKey(t + h + block_q), 4)
    q, k, v = (jax.random.normal(kk, (1, t, n, w), jnp.float32).astype(dtype)
               for kk, n, w in zip(ks, (h, hkv, hkv), (d, d, dv)))
    g = jax.random.normal(ks[3], (1, t, h, dv), jnp.float32)
    # bf16: the kernels feed bf16 operands (and a bf16 p) to the products,
    # the oracle sees the same inputs in f32
    out_tol, grad_tol = ((2e-6, 5e-6) if dtype == jnp.float32
                         else (2e-2, 4e-2))

    def flash(q, k, v):
        return flash_attention(q, k, v, causal, block_q, block_k, True,
                               sm_scale)

    def both(fn, *x):
        out, vjp = jax.vjp(fn, *x)
        return (out,) + vjp(g.astype(out.dtype))

    with jax.default_matmul_precision("highest"):
        got = both(flash, q, k, v)
        want = both(lambda *x: _dense(*x, causal, sm_scale),
                    *(x.astype(jnp.float32) for x in (q, k, v)))
    for name, a, b, tol in zip(("out", "dq", "dk", "dv"), got, want,
                               (out_tol,) + (grad_tol,) * 3):
        assert a.dtype == dtype
        np.testing.assert_allclose(np.asarray(a.astype(jnp.float32)),
                                   np.asarray(b), atol=tol, rtol=tol,
                                   err_msg=f"{case}: {name}")


@pytest.mark.parametrize("t,block_q,block_k,causal,live,masked,skipped", [
    (16384, 1024, 1024, True, 136, 16, 96),     # lm217m_long_1chip, granite
    (8192, 1024, 1024, True, 36, 8, 48),        # kanana2_seq8192_1chip
    (4096, 1024, 1024, True, 10, 4, 24),        # olmoe_seq4096_1chip
    (1024, 1024, 1024, True, 1, 1, 6),          # lm217m_short_1chip
    (16384, 1024, 1024, False, 256, 0, 0),
    (1024, 1024, 512, True, 2, 2, 6),           # block_q = 2 x block_k
    (1024, 512, 512, True, 3, 2, 2),
    (128, 64, 32, True, 6, 4, 0),               # blocks under a sub-tile
    (96, 48, 48, True, 3, 2, 0),
    (3072, 1024, 1024, True, 6, 3, 18),         # an odd count of q blocks
    (160, 32, 32, True, 15, 5, 0),
])
def test_block_census(t, block_q, block_k, causal, live, masked, skipped):
    """The census's closed form counts what the kernels' own predicates
    select over the grid; the backward's sub-tile counts, what a walk over
    every position of the crossed blocks finds."""
    from horovod_tpu.ops.flash_attention import (_BWD_SUB_CROSSED, _crossed,
                                                 _q_major_grid, _q_major_step,
                                                 _sub_tile, block_census)

    got = block_census(t, block_q, block_k, causal)
    assert got[:2] == (live, masked) and got[3] == skipped
    # ``live`` is what the grid the call runs holds of live steps: every
    # step of it but an odd count's half row (none of a rectangle's, whose
    # steps above the diagonal the causal-dense grids no longer hold)
    rows, steps = _q_major_grid(t, block_q, block_k, causal, None)[:2]
    row, step = (x.ravel() for x in np.meshgrid(
        np.arange(rows, dtype=np.int32), np.arange(steps, dtype=np.int32),
        indexing="ij"))
    qi, ki, _, _ = _q_major_step(row, step, block_q, block_k, t // block_k,
                                 causal, None)
    ratio = block_q // block_k
    runs = (np.ones(rows * steps, bool) if not causal
            else np.asarray(ki) < (np.asarray(qi) + 1) * ratio)
    assert runs.sum() == live
    nq = t // block_q
    assert rows * steps - live == (
        steps // 2 if causal and nq > 1 and nq % 2 else 0)
    sub_q = _sub_tile(block_q, _BWD_SUB_CROSSED[0])
    sub_k = _sub_tile(block_k, _BWD_SUB_CROSSED[1])
    assert got[2] == live * (block_q // sub_q) * (block_k // sub_k)
    if causal:
        # by positions: a block is live where some query of it sees a key
        # of it, and a sub-tile is skipped where none does
        q_pos, k_pos = np.arange(t)[:, None], np.arange(t)[None, :]
        block_sees = (q_pos >= k_pos).reshape(
            t // block_q, block_q, t // block_k, block_k).any(axis=(1, 3))
        grid = [(qi, ki) for qi in range(t // block_q)
                for ki in range(t // block_k) if block_sees[qi, ki]]
        assert len(grid) == live
        crossed = [(qi, ki) for qi, ki in grid
                   if _crossed(qi, ki, block_q, block_k)]
        assert len(crossed) == masked
        sees = (q_pos >= k_pos).reshape(
            t // sub_q, sub_q, t // sub_k, sub_k).any(axis=(1, 3))
        dead = sum(
            not sees[(qi * block_q + q0) // sub_q,
                     (ki * block_k + k0) // sub_k]
            for qi, ki in crossed for q0 in range(0, block_q, sub_q)
            for k0 in range(0, block_k, sub_k))
        assert dead == skipped


@pytest.mark.parametrize("first,sub_q,sub_k,want", [
    (-512, 256, 512, None),     # wholly below: no mask
    (-511, 256, 512, None),     # its last key is its first query's own
    (-510, 256, 512, -510),     # its last key passes its first query
    (0, 256, 256, 0),           # on the diagonal
    (255, 256, 256, 255),       # one live entry: last query, first key
    (256, 256, 256, False),     # wholly above: skipped
    (1, 1, 1, False),
    (0, 1, 1, None),
])
def test_mask_offset(first, sub_q, sub_k, want):
    from horovod_tpu.ops.flash_attention import _mask_offset

    got = _mask_offset(first, sub_q, sub_k)
    assert got is want or got == want
    a, b = np.arange(sub_q)[:, None], np.arange(sub_k)[None, :]
    live = a - b >= first
    assert (got is False) == (not live.any())
    assert (got is None) == bool(live.all())


@pytest.mark.parametrize("t,block,causal,share,skipped", [
    (128, 32, True, 0.6, 0.0),      # 10 live block steps, 4 on the diagonal
    (64, 64, True, 0.0, 0.0),       # one block per row: always the masked body
    (128, 32, False, 1.0, 0.0),
    (1024, 1024, True, 0.0, 0.375),     # 6 of the one block's 16 sub-tiles
    (1024, 512, True, 1 / 3, 1 / 6),    # 2 of 3 live blocks x 4, one each
    (1024, 1024, False, 1.0, 0.0),
])
def test_unmasked_share_gauge_after_a_traced_call(t, block, causal, share,
                                                  skipped):
    from horovod_tpu.metrics import registry

    q, k, v = qkv(7, t=t)
    flash_attention(q, k, v, causal, block, block, True)
    gauges = registry().snapshot()["gauges"]
    assert gauges["horovod_flash_unmasked_block_share"] == pytest.approx(share)
    assert gauges["horovod_flash_bwd_skipped_subtile_share"] == pytest.approx(
        skipped)


def test_gqa_matches_replicated_oracle():
    """Grouped-query attention: 4 q heads over 2 kv heads must equal the
    oracle with kv heads explicitly replicated — forward and all grads
    (the oracle's autodiff sums each group's dk/dv for free)."""
    hkv, group = 2, 2
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, 64, hkv * group, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, 64, hkv, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, 64, hkv, D), jnp.float32)
    g = jax.random.normal(jax.random.PRNGKey(9), q.shape, jnp.float32)

    def rep(x):
        return jnp.repeat(x, group, axis=2)

    with jax.default_matmul_precision("highest"):
        out = flash_attention(q, k, v, True, 16, 8, True)
        ref = causal_reference(q, rep(k), rep(v))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-6, rtol=2e-6)
        gf = jax.grad(lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, True, 16, 8, True) * g), argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lambda q, k, v: jnp.sum(
            causal_reference(q, rep(k), rep(v)) * g), argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-6, rtol=5e-6, err_msg=name)


def test_gqa_rejects_bad_heads():
    q, k, v = qkv()
    k3 = jnp.repeat(k[:, :, :1], 3, axis=2)  # 3 kv heads, H=2 q heads
    v3 = jnp.repeat(v[:, :, :1], 3, axis=2)
    with pytest.raises(ValueError, match="not divisible"):
        flash_attention(q, k3, v3, True, 32, 32, True)


def test_non_causal_full_softmax():
    q, k, v = qkv(2)
    with jax.default_matmul_precision("highest"):
        out = flash_attention(q, k, v, False, 32, 32, True)
        ref = _dense(q, k, v, False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-6, rtol=2e-6)


def test_block_sizes_are_ceilings():
    """Requested block sizes auto-shrink to the largest conforming divisor
    of the sequence (96 with a 64 ceiling fits at 48) — and still match the
    oracle."""
    from horovod_tpu.ops.flash_attention import _check_blocks

    assert _check_blocks(96, 64, 64, True) == (48, 48)
    # TPU quantum: blocks shrink to the largest conforming divisor
    assert _check_blocks(1536, 1024, 512, False) == (768, 384)
    # ...or fall back to the always-legal whole axis when none exists
    assert _check_blocks(130, 1024, 512, False) == (130, 130)
    assert _check_blocks(1160, 1024, 512, False) == (1160, 232)
    # sub-quantum ceilings round up to the quantum
    assert _check_blocks(4096, 64, 64, False) == (128, 64)
    q, k, v = qkv(3, t=96)
    with jax.default_matmul_precision("highest"):
        out = flash_attention(q, k, v, True, 64, 64, True)
        ref = causal_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-6, rtol=2e-6)


@pytest.mark.slow
def test_transformer_flash_equals_dense():
    from horovod_tpu.models import TransformerLM

    tok = jax.random.randint(jax.random.PRNGKey(4), (2, 128), 0, 64)
    dense = TransformerLM(vocab=64, dim=32, heads=4, layers=2, dtype=jnp.float32)
    flash = TransformerLM(vocab=64, dim=32, heads=4, layers=2, dtype=jnp.float32,
                          attention="flash", flash_interpret=True)
    params = dense.init(jax.random.PRNGKey(0), tok)["params"]
    with jax.default_matmul_precision("highest"):
        od = dense.apply({"params": params}, tok)
        of = flash.apply({"params": params}, tok)
    np.testing.assert_allclose(np.asarray(of), np.asarray(od),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.slow
def test_transformer_gqa_flash_equals_dense():
    """kv_heads < heads: the dense path replicates kv heads, the flash
    path aliases them in the kernel — same params, same output."""
    from horovod_tpu.models import TransformerLM

    tok = jax.random.randint(jax.random.PRNGKey(4), (2, 128), 0, 64)
    kw = dict(vocab=64, dim=32, heads=4, kv_heads=2, layers=2,
              dtype=jnp.float32)
    dense = TransformerLM(**kw)
    flash = TransformerLM(**kw, attention="flash", flash_interpret=True)
    params = dense.init(jax.random.PRNGKey(0), tok)["params"]
    # GQA swaps the fused qkv kernel for split q/kv projections
    assert "q_proj" in params["block_0"] and "kv_proj" in params["block_0"]
    with jax.default_matmul_precision("highest"):
        od = dense.apply({"params": params}, tok)
        of = flash.apply({"params": params}, tok)
    np.testing.assert_allclose(np.asarray(of), np.asarray(od),
                               atol=2e-5, rtol=2e-5)


def test_non_causal_gradients_match_oracle():
    """Covers the causal=False loop bounds in BOTH backward kernels."""
    q, k, v = qkv(5)
    g = jax.random.normal(jax.random.PRNGKey(6), q.shape, jnp.float32)

    with jax.default_matmul_precision("highest"):
        gf = jax.grad(lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, False, 32, 32, True) * g), argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lambda q, k, v: jnp.sum(
            _dense(q, k, v, False) * g), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-6, rtol=5e-6)


def test_compiled_kernel_without_tpu_raises():
    """Interpret mode is the caller's to ask for, never inferred from the
    platform: on this CPU mesh the default (compiled) kernel raises, and so
    does a flash model built without flash_interpret."""
    from horovod_tpu.models import TransformerLM

    q, k, v = qkv()
    with pytest.raises(ValueError, match="[Ii]nterpret"):
        flash_attention(q, k, v, True, 32, 32)
    tok = jnp.ones((1, 32), jnp.int32)
    model = TransformerLM(vocab=8, dim=16, heads=2, layers=1,
                          attention="flash")
    with pytest.raises(ValueError, match="[Ii]nterpret"):
        model.init(jax.random.PRNGKey(0), tok)


def test_unknown_attention_value_rejected():
    from horovod_tpu.models import TransformerLM

    tok = jnp.ones((1, 32), jnp.int32)
    bad = TransformerLM(vocab=8, dim=16, heads=2, layers=1, attention="Flash")
    with pytest.raises(ValueError, match="unknown attention"):
        bad.init(jax.random.PRNGKey(0), tok)
    # attention="flash" + sp_axis is a VALID pair (ring_flash_attention);
    # its parity is covered in tests/test_ring_flash.py.


# ------------------------------------------------------------------ window
# name: (t, h, hkv, d, block_q, block_k, window, dtype). A query at p sees the
# keys p - window < j <= p. Which bodies each reaches: blocks the diagonal, the
# window's lower edge or both cross (static offsets, sub-tiles outside the
# band skipped), blocks wholly inside the band (no mask), and the grid rows
# whose k block (q block) lies outside the sequence (nothing runs).
WINDOW_CASES = {
    # groups of 6 and of 8 (Laguna's full and sliding layers over 8 kv heads)
    "smaller_than_a_block_group_6": (128, 6, 1, 32, 32, 32, 20, jnp.float32),
    "equal_to_a_block_group_8": (128, 8, 1, 32, 32, 32, 32, jnp.float32),
    "one_past_a_block": (128, 2, 2, 32, 32, 32, 33, jnp.float32),
    # wider than two blocks: a block wholly inside the band between the edges
    "larger_than_a_block": (128, 2, 2, 32, 32, 32, 72, jnp.float32),
    "block_q_twice_block_k": (128, 6, 1, 32, 64, 32, 40, jnp.float32),
    "block_q_four_times_block_k": (256, 2, 1, 32, 64, 16, 50, jnp.float32),
    "window_of_one": (128, 2, 2, 32, 32, 32, 1, jnp.float32),
    "all_but_one_key": (128, 2, 2, 32, 32, 32, 127, jnp.float32),
    # the cell's plan in small: blocks of the window's size, sub-tiles of 256
    "sub_tiles_window_512": (1024, 1, 1, 16, 512, 512, 512, jnp.float32),
    "sub_tiles_bf16_group_6": (1024, 6, 1, 16, 512, 512, 512, jnp.bfloat16),
    # not a multiple of the sub-tile: both edges inside sub-tiles
    "sub_tiles_window_300": (1024, 2, 1, 16, 512, 512, 300, jnp.float32),
    "sub_tiles_block_q_twice_block_k": (2048, 2, 1, 16, 1024, 512, 300,
                                        jnp.float32),
    # blocks the caller does not give: the defaults, here one 1024 block
    # whose sub-tiles outside the band are skipped
    "default_blocks": (1024, 1, 1, 16, None, None, 256, jnp.float32),
}


def _both(fn, g, *x):
    out, vjp = jax.vjp(fn, *x)
    return (out,) + vjp(g.astype(out.dtype))


@pytest.mark.parametrize("case", sorted(WINDOW_CASES))
def test_windowed_kernels_match_oracle(case):
    """Forward, dQ, dK and dV under a window against
    ``causal_reference(window=)`` (kv heads replicated for it)."""
    t, h, hkv, d, block_q, block_k, window, dtype = WINDOW_CASES[case]
    ks = jax.random.split(jax.random.PRNGKey(t + h + window), 4)
    q, k, v = (jax.random.normal(kk, (1, t, n, d), jnp.float32).astype(dtype)
               for kk, n in zip(ks, (h, hkv, hkv)))
    g = jax.random.normal(ks[3], (1, t, h, d), jnp.float32)
    out_tol, grad_tol = ((2e-6, 5e-6) if dtype == jnp.float32
                         else (2e-2, 4e-2))

    def flash(q, k, v):
        return flash_attention(q, k, v, True, block_q, block_k, True, None,
                               window)

    def oracle(q, k, v):
        k, v = (jnp.repeat(x, h // hkv, axis=2) for x in (k, v))
        return causal_reference(q, k, v, window=window)

    with jax.default_matmul_precision("highest"):
        got = _both(flash, g, q, k, v)
        want = _both(oracle, g, *(x.astype(jnp.float32) for x in (q, k, v)))
    for name, a, b, tol in zip(("out", "dq", "dk", "dv"), got, want,
                               (out_tol,) + (grad_tol,) * 3):
        assert a.dtype == dtype
        np.testing.assert_allclose(np.asarray(a.astype(jnp.float32)),
                                   np.asarray(b), atol=tol, rtol=tol,
                                   err_msg=f"{case}: {name}")


@pytest.mark.parametrize("window", [128, 1000])
def test_a_window_that_reaches_every_key_is_the_causal_dense_call(window):
    """T <= window: the same kernels, bit for bit, as ``window=None``."""
    q, k, v = qkv(11)
    g = jax.random.normal(jax.random.PRNGKey(12), q.shape, jnp.float32)
    got = _both(lambda *x: flash_attention(*x, True, 32, 32, True, None,
                                           window), g, q, k, v)
    want = _both(lambda *x: flash_attention(*x, True, 32, 32, True), g,
                 q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    traced = str(jax.make_jaxpr(lambda *x: flash_attention(
        *x, True, 32, 32, True, None, window))(q, k, v))
    assert "hvd_flash_fwd" in traced and "hvd_flash_win" not in traced


def test_windowed_calls_carry_names_of_their_own():
    q, k, v = qkv(13)
    text = str(jax.make_jaxpr(jax.grad(lambda *x: jnp.sum(flash_attention(
        *x, True, 32, 32, True, None, 40)), argnums=(0, 1, 2)))(q, k, v))
    for name in ("hvd_flash_win_fwd", "hvd_flash_win_bwd_dq",
                 "hvd_flash_win_bwd_dkv"):
        assert name in text
    assert "hvd_flash_fwd" not in text and "hvd_flash_bwd" not in text


def test_window_needs_a_causal_call():
    q, k, v = qkv(14)
    with pytest.raises(ValueError, match="window=16 needs causal=True"):
        flash_attention(q, k, v, False, 32, 32, True, None, 16)
    with pytest.raises(ValueError, match="window >= 1"):
        flash_attention(q, k, v, True, 32, 32, True, None, 0)


@pytest.mark.parametrize("t,block_q,block_k,window", [
    (16384, 512, 512, 512),     # laguna_xs2_seq16384_1chip's sliding layers
    (16384, 1024, 1024, 512),   # what the default blocks would visit
    (16384, 1024, 512, 512),
    (16384, 256, 256, 512),
    (2048, 512, 512, 300),
    (2048, 1024, 256, 700),
    (1024, 512, 512, 1),
    (128, 32, 32, 72),
    (128, 64, 16, 50),
    (96, 48, 48, 95),
])
def test_block_census_under_a_window(t, block_q, block_k, window):
    """The census's closed form, the three kernels' grids with their own
    predicates, and a count by positions agree: a block step runs where some
    query of it sees a key of it, is masked where some does not, and a
    sub-tile of a masked block is skipped where none does."""
    from horovod_tpu.ops.flash_attention import (_BWD_SUB_CROSSED,
                                                 _band_offsets, _band_steps,
                                                 _mask_offset, _sub_tile,
                                                 block_census)

    live, masked, sub_tiles, skipped = block_census(t, block_q, block_k, True,
                                                    window)
    nq, nk, ratio = t // block_q, t // block_k, block_q // block_k
    q_pos, k_pos = np.arange(t)[:, None], np.arange(t)[None, :]
    band = (q_pos >= k_pos) & (k_pos > q_pos - window)
    by_block = band.reshape(nq, block_q, nk, block_k)
    sees, all_seen = by_block.any(axis=(1, 3)), by_block.all(axis=(1, 3))
    assert live == sees.sum() and masked == (sees & ~all_seen).sum()
    # the kernels' predicates over their grids: forward and dq by (qi, step),
    # dk/dv by (ki, step), each block step met exactly once
    crossed, inside = _band_offsets(t, block_q, block_k, window)
    k_steps, q_steps = _band_steps(block_q, block_k, window)
    by_q = [(qi, step + (qi + 1) * ratio - k_steps)
            for qi in range(nq) for step in range(k_steps)]
    by_k = [(step + ki // ratio, ki) for ki in range(nk)
            for step in range(q_steps)]
    for grid in (by_q, by_k):
        runs = [(qi, ki) for qi, ki in grid if ki >= 0 and qi < nq
                and ki * block_k - qi * block_q in crossed + inside]
        assert len(set(runs)) == len(runs) == live
        assert all(sees[qi, ki] for qi, ki in runs)
        assert sum(ki * block_k - qi * block_q in crossed
                   for qi, ki in runs) == masked
    assert all(all_seen[qi, ki] for qi, ki in by_q if ki >= 0
               and ki * block_k - qi * block_q in inside)
    # the backward's sub-tiles of the masked blocks, by positions
    sub_q = _sub_tile(block_q, _BWD_SUB_CROSSED[0])
    sub_k = _sub_tile(block_k, _BWD_SUB_CROSSED[1])
    assert sub_tiles == live * (block_q // sub_q) * (block_k // sub_k)
    sub_sees = band.reshape(t // sub_q, sub_q, t // sub_k, sub_k).any(axis=(1, 3))
    dead = sum(not sub_sees[(qi * block_q + q0) // sub_q,
                            (ki * block_k + k0) // sub_k]
               for qi in range(nq) for ki in range(nk)
               if sees[qi, ki] and not all_seen[qi, ki]
               for q0 in range(0, block_q, sub_q)
               for k0 in range(0, block_k, sub_k))
    assert dead == skipped
    # and a sub-tile's own answer, entry by entry
    for first in crossed:
        off = _mask_offset(first, block_q, block_k, window)
        a, b = np.arange(block_q)[:, None], np.arange(block_k)[None, :]
        lo, hi = off
        entries = ((a - b >= first) & (a - b < first + window))
        assert ((lo is None) == bool((a - b >= first).all())
                and (hi is None) == bool((a - b < first + window).all()))
        assert entries.any() and not entries.all()


def test_census_of_the_cell_and_the_window_gauge():
    """At 16,384 positions a window of 512 at the default blocks runs 31 block
    steps a head where the causal-dense call at those blocks runs 136 (and at
    blocks of the window's size 63 of 528): what
    ``horovod_flash_window_block_share`` reads."""
    from horovod_tpu.metrics import registry
    from horovod_tpu.ops.flash_attention import _plan, block_census

    assert _plan(16384, None, None, False, 512) == (1024, 1024, 512)
    assert _plan(16384, None, None, False, None) == (1024, 1024, None)
    assert _plan(16384, None, None, False, 16384) == (1024, 1024, None)
    assert _plan(16384, 512, 256, False, 4096) == (512, 256, 4096)
    assert _plan(2048, 512, 512, False, 512) == (512, 512, 512)
    assert block_census(16384, 512, 512, True, 512)[:2] == (63, 63)
    assert block_census(16384, 512, 512, True)[0] == 528
    # 16 q blocks x (the diagonal's block + the one before it) less the first;
    # of a block's 16 sub-tiles the diagonal's runs 9 and the other one 3:
    # 189 sub-tiles of 256 x 256 for a band of 126 of them
    assert block_census(16384, 1024, 1024, True, 512) == (
        31, 31, 31 * 16, 16 * 7 + 15 * 13)
    assert block_census(16384, 1024, 1024, True)[0] == 136
    q, k, v = qkv(15)
    flash_attention(q, k, v, True, 32, 32, True, None, 32)
    gauges = registry().snapshot()["gauges"]
    # 4 q blocks x (the diagonal's block + the one before it) less the first
    assert gauges["horovod_flash_window_block_share"] == pytest.approx(7 / 10)
    assert gauges["horovod_flash_unmasked_block_share"] == 0.0


def test_the_ring_schedules_raise_on_a_window():
    from horovod_tpu.models import TransformerLM
    from horovod_tpu.ops.ring_attention import ring_attention
    from horovod_tpu.ops.ring_flash import ring_flash_attention

    q, k, v = qkv(16)
    with pytest.raises(NotImplementedError, match="no window"):
        ring_attention(q, k, v, "sp", window=32)
    with pytest.raises(NotImplementedError, match="no window"):
        ring_flash_attention(q, k, v, "sp", False, 32, 32, True, window=32)
    model = TransformerLM(vocab=8, dim=16, heads=2, layers=1, sp_axis="sp",
                          layer_types=("sliding_attention",), sliding_window=8)
    with pytest.raises(ValueError, match="window needs sp_axis=None"):
        model.init(jax.random.PRNGKey(0), jnp.ones((1, 32), jnp.int32))


def test_hybrid_fields_flash_equals_dense():
    """A head size of its own, head counts and kinds per layer, a window, a
    rotary scheme per kind and the gate: the flash path (windowed kernels on
    the sliding layer) and the dense path give one output from one set of
    parameters, and each field moves it."""
    import dataclasses

    from horovod_tpu.models import RotaryScheme, TransformerLM

    tok = jax.random.randint(jax.random.PRNGKey(4), (2, 128), 0, 64)
    yarn = RotaryScheme(theta=500000.0, dims=8, factor=64.0, original_max=4096,
                        beta_fast=64.0, beta_slow=1.0,
                        attention_factor=1.4158883083359672)
    kw = dict(vocab=64, dim=32, heads=4, kv_heads=2, layers=2, head_dim=16,
              heads_per_layer=(4, 6), dtype=jnp.float32,
              layer_types=("full_attention", "sliding_attention"),
              sliding_window=24, full_rotary=yarn,
              sliding_rotary=RotaryScheme(theta=10000.0), attn_gate=True)
    dense = TransformerLM(**kw)
    params = dense.init(jax.random.PRNGKey(0), tok)["params"]
    assert params["block_0"]["q_proj"]["kernel"].shape == (32, 4 * 16)
    assert params["block_1"]["q_proj"]["kernel"].shape == (32, 6 * 16)
    assert params["block_1"]["o_proj"]["kernel"].shape == (6 * 16, 32)
    assert params["block_1"]["kv_proj"]["kernel"].shape == (32, 2 * 2 * 16)
    assert params["block_1"]["gate_proj"]["kernel"].shape == (32, 6)

    def run(**changed):
        model = TransformerLM(**{**kw, **changed})
        with jax.default_matmul_precision("highest"):
            return np.asarray(model.apply({"params": params}, tok))

    want = run()
    np.testing.assert_allclose(
        run(attention="flash", flash_interpret=True, block_q=32, block_k=32),
        want, atol=2e-5, rtol=2e-5)
    for changed in (dict(sliding_window=128), dict(sliding_window=23),
                    dict(full_rotary=dataclasses.replace(yarn, dims=None)),
                    dict(full_rotary=dataclasses.replace(yarn, factor=None)),
                    dict(full_rotary=dataclasses.replace(
                        yarn, attention_factor=1.0)),
                    dict(sliding_rotary=RotaryScheme(theta=500000.0)),
                    dict(layer_types=("sliding_attention", "full_attention"))):
        assert np.max(np.abs(run(**changed) - want)) > 1e-4, changed
    # the default scheme IS the plain rotary embedding
    plain = dict(full_rotary=None, sliding_rotary=None, rope_theta=777.0)
    np.testing.assert_allclose(
        run(**plain), run(full_rotary=RotaryScheme(theta=777.0),
                          sliding_rotary=RotaryScheme(theta=777.0)),
        atol=1e-5, rtol=1e-5)


def test_yarn_frequencies_are_transformers():
    """``RotaryScheme.inv_freq`` at Laguna-XS.2's numbers against the formula
    of ``transformers``' ``_compute_yarn_parameters`` written out by hand."""
    import math

    from horovod_tpu.models import RotaryScheme

    scheme = RotaryScheme(theta=500000.0, dims=64, factor=64.0,
                          original_max=4096, beta_fast=64.0, beta_slow=1.0)
    got = scheme.inv_freq(64)

    def c(n):
        return 64 * math.log(4096 / (2 * math.pi * n)) / (2 * math.log(500000))

    low, high = math.floor(c(64)), math.ceil(c(1))
    assert (low, high) == (5, 16)
    for i in range(32):
        f = 500000 ** (-2 * i / 64)
        r = min(max((i - low) / (high - low), 0), 1)
        assert got[i] == pytest.approx(f / 64 * r + f * (1 - r), rel=1e-6)
    assert got[0] == 1.0 and got[31] == pytest.approx(
        500000 ** (-62 / 64) / 64, rel=1e-6)
    assert scheme.scale() == pytest.approx(1.4158883083359672, rel=1e-12)
    assert RotaryScheme().scale() == 1.0


def test_tp_param_specs_shard_the_gate_with_its_heads():
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.models import TransformerLM
    from horovod_tpu.models.transformer import tp_param_specs

    model = TransformerLM(vocab=8, dim=16, heads=4, kv_heads=2, layers=1,
                          head_dim=8, attn_gate=True)
    params = model.init(jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))["params"]
    specs = tp_param_specs(params)
    assert specs["block_0"]["gate_proj"]["kernel"] == P(None, "tp")
    assert specs["block_0"]["q_proj"]["kernel"] == P(None, "tp")
    assert specs["block_0"]["o_proj"]["kernel"] == P("tp", None)
    with pytest.raises(ValueError, match="head_dim's to state"):
        TransformerLM(vocab=8, dim=16, heads=3, layers=1).init(
            jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))
    with pytest.raises(ValueError, match="each of head_dim 8"):
        TransformerLM(vocab=8, dim=16, heads=4, kv_heads=3, layers=1,
                      head_dim=8).init(jax.random.PRNGKey(0),
                                       jnp.ones((1, 8), jnp.int32))
