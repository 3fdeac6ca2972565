"""Latent attention (MLA) on the CPU at tiny sizes: the flash kernels with a
v head size of their own (interpreter), interleaved rotary pairs, the
attention branch of ``Block``, ``first_k_dense``, and the whole tiny model,
its loss and its gradients against ``tests/references/kanana2.py``."""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from references import kanana2  # noqa: E402

from horovod_tpu.models import BIAS_COLLECTION, LatentDims, TransformerLM  # noqa: E402
from horovod_tpu.models.transformer import (Block, _rope,  # noqa: E402
                                            chunked_lm_loss)
from horovod_tpu.ops.flash_attention import flash_attention  # noqa: E402

CFG = {"hidden": 32, "heads": 4, "qk_nope": 8, "qk_rope": 4, "v_dim": 8,
       "kv_rank": 16, "eps": 1e-6, "rope_theta": 1e6, "top_k": 3,
       "route_scale": 2.448, "held": (0, 8), "experts": 8, "first_k_dense": 1,
       "dense_width": 48, "expert_width": 16, "shared_width": 24, "vocab": 64}


def attention_two_widths(q, k, v, scale):
    """``causal_attention`` generalised: v's head size is its own."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    t = q.shape[1]
    seen = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("d_qk,d_v,kv_heads", [(24, 16, 4), (16, 24, 4),
                                               (24, 16, 2), (16, 16, 4)])
@pytest.mark.parametrize("which", ["out", "dq", "dk", "dv"])
def test_flash_with_a_v_head_size_of_its_own(d_qk, d_v, kv_heads, which):
    ks = jax.random.split(jax.random.PRNGKey(d_qk + d_v), 4)
    b, t, h = 2, 64, 4
    q = jax.random.normal(ks[0], (b, t, h, d_qk))
    k = jax.random.normal(ks[1], (b, t, kv_heads, d_qk))
    v = jax.random.normal(ks[2], (b, t, kv_heads, d_v))
    g = jax.random.normal(ks[3], (b, t, h, d_v))

    def flash(q, k, v):
        return flash_attention(q, k, v, True, 16, 8, True, None)

    def plain(q, k, v):
        k, v = (jnp.repeat(x, h // kv_heads, axis=2) for x in (k, v))
        return attention_two_widths(q, k, v, d_qk ** -0.5)

    out, vjp = jax.vjp(flash, q, k, v)
    want, want_vjp = jax.vjp(plain, q, k, v)
    i = ("out", "dq", "dk", "dv").index(which)
    got, ref = ((out,) + vjp(g))[i], ((want,) + want_vjp(g))[i]
    assert got.shape == ref.shape
    np.testing.assert_allclose(got, ref, atol=3e-5, rtol=3e-5)


def test_flash_refuses_q_and_k_of_two_sizes():
    q, k = jnp.ones((1, 16, 2, 8)), jnp.ones((1, 16, 2, 16))
    with pytest.raises(ValueError, match="head size"):
        flash_attention(q, k, k, True, 16, 16, True, None)


def _shared_key_operands(dn, dr, dv, dtype=jnp.float32, b=2, t=64, h=4):
    """(q (Dn + Dr), k_nope, the ONE head's k_shared, v, dO)."""
    ks = jax.random.split(jax.random.PRNGKey(dn + dr), 5)
    shapes = ((b, t, h, dn + dr), (b, t, h, dn), (b, t, 1, dr), (b, t, h, dv),
              (b, t, h, dv))
    return [jax.random.normal(k, s).astype(dtype) for k, s in zip(ks, shapes)]


def _with_shared_key(blocks, causal=True):
    def call(q, k_nope, k_shared, v):
        return flash_attention(q, k_nope, v, causal, *blocks, True, None, None,
                               k_shared)
    return call


def _with_assembled_key(blocks, causal=True):
    """The call as ``_latent_attention`` made it before the kernels took the
    shared part as an operand: k put together, the one head copied to all;
    JAX slices dk and sums its shared lanes over the heads."""
    def call(q, k_nope, k_shared, v):
        k = jnp.concatenate([k_nope, jnp.broadcast_to(
            k_shared, k_nope.shape[:3] + k_shared.shape[3:])], axis=-1)
        return flash_attention(q, k, v, causal, *blocks, True, None)
    return call


@pytest.mark.parametrize("which", ["out", "dq", "dk_nope", "dk_shared", "dv"])
@pytest.mark.parametrize("dn,dr,dv,blocks", [
    (128, 64, 128, (64, 64)),       # the cells' widths; one k block a row
    (128, 64, 128, (32, 16)),       # several, two a q block
    (8, 4, 8, (64, 64)), (8, 4, 8, (16, 8)), (16, 8, 24, (16, 16)),
    (8, 4, 8, (32, 32)),            # two q blocks: ONE folded row
    (8, 4, 8, (16, 16)),            # four: two folded rows
], ids=lambda x: "x".join(map(str, x)) if isinstance(x, tuple) else str(x))
def test_shared_key_call_is_the_assembled_call(dn, dr, dv, blocks, which):
    """The shared rotary key as ONE head through an index map: out, dq, the
    heads' own dk, the shared part's gradient (the assembled dk's shared lanes
    summed over the heads) and dv are the assembled call's, to float32's
    rounding of a sum over four heads."""
    *operands, g = _shared_key_operands(dn, dr, dv)
    out, vjp = jax.vjp(_with_shared_key(blocks), *operands)
    want, want_vjp = jax.vjp(_with_assembled_key(blocks), *operands)
    i = ("out", "dq", "dk_nope", "dk_shared", "dv").index(which)
    got, ref = ((out,) + vjp(g))[i], ((want,) + want_vjp(g))[i]
    assert got.shape == ref.shape and got.dtype == ref.dtype
    np.testing.assert_allclose(got, ref, atol=2e-6 * float(jnp.max(jnp.abs(ref))),
                               rtol=0)


@pytest.mark.parametrize("causal,kv_heads,dtype", [
    (False, 4, jnp.float32), (True, 2, jnp.float32), (True, 4, jnp.bfloat16)],
    ids=["non_causal", "grouped_query", "bf16"])
def test_shared_key_call_in_its_other_forms(causal, kv_heads, dtype):
    """Not causal (the rectangle), under grouped-query heads (a kv head's
    share of the gradient holds its group's) and in bf16 (the gradient a
    head is rounded to bf16 before the sum over heads, as an assembled dk
    is)."""
    q, k_nope, k_shared, v, g = _shared_key_operands(8, 4, 8, dtype)
    k_nope, v = k_nope[:, :, :kv_heads], v[:, :, :kv_heads]
    out, vjp = jax.vjp(_with_shared_key((16, 16), causal), q, k_nope,
                       k_shared, v)
    want, want_vjp = jax.vjp(_with_assembled_key((16, 16), causal), q, k_nope,
                             k_shared, v)
    for got, ref in zip((out,) + vjp(g), (want,) + want_vjp(g)):
        assert got.shape == ref.shape and got.dtype == ref.dtype
        tol = 2e-6 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(
            got.astype(jnp.float32), ref.astype(jnp.float32), rtol=0,
            atol=tol * float(jnp.max(jnp.abs(ref.astype(jnp.float32)))))


@pytest.mark.parametrize("which", ["out", "dq", "dkv", "dk_shared"])
@pytest.mark.parametrize("dn,dr,dv,blocks,kv_heads", [
    (128, 64, 128, (32, 16), 4),    # the cells' widths
    (8, 4, 8, (64, 64), 4), (8, 4, 8, (16, 8), 4), (16, 4, 8, (16, 16), 2),
    (8, 0, 8, (16, 16), 2),         # no shared part: plain grouped-query
], ids=lambda x: "x".join(map(str, x)) if isinstance(x, tuple) else str(x))
def test_k_and_v_in_one_array_is_the_call_in_parts(dn, dr, dv, blocks,
                                                   kv_heads, which):
    """``v=None``: k's array holds ``[k | v]`` a head as ONE projection wrote
    them; the index maps read each by its lane block and dK/dV writes ``[dk |
    dv]`` the same way. Out and every gradient are the split call's, to the
    bit."""
    q, k_nope, k_shared, v, g = _shared_key_operands(dn, max(dr, 1), dv)
    if not dr:
        q, k_shared = q[..., :dn], None
    kv = jnp.concatenate([k_nope, v], axis=-1)[:, :, :kv_heads]

    def whole(q, kv, k_shared):
        return flash_attention(q, kv, None, True, *blocks, True, None, None,
                               k_shared)

    def parts(q, kv, k_shared):
        return flash_attention(q, kv[..., :dn], kv[..., dn:], True, *blocks,
                               True, None, None, k_shared)

    out, vjp = jax.vjp(whole, q, kv, k_shared)
    want, want_vjp = jax.vjp(parts, q, kv, k_shared)
    i = ("out", "dq", "dkv", "dk_shared").index(which)
    got, ref = ((out,) + vjp(g))[i], ((want,) + want_vjp(g))[i]
    if ref is None:
        assert got is None and which == "dk_shared" and not dr
        return
    assert got.shape == ref.shape and got.dtype == ref.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_shared_key_call_refuses_what_it_does_not_build():
    q, k_nope, k_shared, v, _ = _shared_key_operands(8, 4, 8)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k_nope, v, True, 16, 16, True, None, 24, k_shared)
    with pytest.raises(ValueError, match="head size"):     # q is Dn + Dr wide
        flash_attention(q[..., :8], k_nope, v, True, 16, 16, True, None, None,
                        k_shared)
    with pytest.raises(ValueError, match="one head"):
        flash_attention(q, k_nope, v, True, 16, 16, True, None, None,
                        jnp.repeat(k_shared, 2, axis=2))
    # [k | v] in one array: v's lanes are a lane BLOCK after k's
    kv = jnp.concatenate([k_nope, v, v[..., :4]], axis=-1)      # 8 | 12
    with pytest.raises(ValueError, match="divides"):
        flash_attention(q, kv, None, True, 16, 16, True, None, None, k_shared)


def _shared_key_lanes():
    from horovod_tpu.metrics import registry
    return registry().snapshot()["gauges"]["horovod_flash_shared_key_lanes"]


def test_shared_key_lanes_gauge_reads_the_latest_traced_call():
    q, k_nope, k_shared, v, _ = _shared_key_operands(128, 64, 128, t=16, h=1)
    k = jnp.concatenate([k_nope, k_shared], axis=-1)
    jax.make_jaxpr(_with_shared_key((16, 16)))(q, k_nope, k_shared, v)
    assert _shared_key_lanes() == 64
    jax.make_jaxpr(lambda *a: flash_attention(*a, True, 16, 16, True))(q, k, v)
    assert _shared_key_lanes() == 0
    # a latent layer's own call, then a multi-head layer's
    x, pos = jnp.ones((1, 16, 32)), jnp.arange(16)[None]
    for mla, lanes in ((LatentDims(kv_rank=16, qk_nope=8, qk_rope=4, v=8), 4),
                       (None, 0)):
        block = Block(dim=32, heads=4, dtype=jnp.float32, mla=mla,
                      attention="flash", flash_interpret=True, mlp_hidden=48)
        jax.eval_shape(block.init, jax.random.PRNGKey(0), x, pos)
        assert _shared_key_lanes() == lanes


def _rope_by_halves(x, pos, theta=10000.0):
    """The rotation as ``_rope`` computed it before it turned in place: each
    head cut into halves, the halves concatenated again."""
    half = x.shape[-1] // 2
    ang = pos[..., None].astype(jnp.float32) * (
        1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half)))
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def test_rope_defaults_are_the_old_rotation():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 2, 8))
    pos = jnp.arange(8)[None]
    np.testing.assert_allclose(_rope(x, pos), _rope_by_halves(x, pos),
                               atol=1e-6)


@pytest.mark.parametrize("dtype,heads,d", [
    (jnp.float32, 2, 8), (jnp.float32, 3, 128), (jnp.bfloat16, 8, 128),
    (jnp.bfloat16, 4, 64)])
def test_rope_in_place_is_the_rotation_by_halves(dtype, heads, d):
    """Values to the bit (a term through the matrix of 0 and +-1 is one
    operand, and ``a - b`` is ``a + (-b)``); gradients to the dtype's
    rounding (the term through the matrix is rounded to it on the way back)."""
    t = 32
    keys = jax.random.split(jax.random.PRNGKey(heads + d), 2)
    x, g = (jax.random.normal(k, (2, t, heads, d)).astype(dtype) for k in keys)
    pos = jnp.arange(t)[None] + 7
    got, got_vjp = jax.vjp(lambda x: _rope(x, pos), x)
    want, want_vjp = jax.vjp(lambda x: _rope_by_halves(x, pos), x)
    assert got.dtype == dtype
    np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)),
                                  np.asarray(want.astype(jnp.float32)))
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got_vjp(g)[0].astype(jnp.float32),
                               want_vjp(g)[0].astype(jnp.float32),
                               atol=tol, rtol=tol)


def _rope_of_the_trailing_lanes_by_parts(x, pos, theta, interleave, lead):
    """q as ``_latent_attention`` turned it before ``lead=``: the head cut at
    ``lead``, the trailing part turned, the two put together again."""
    x_pass, x_turn = jnp.split(x, [lead], axis=-1)
    return jnp.concatenate([x_pass, _rope(x_turn, pos, theta, interleave)],
                           axis=-1)


@pytest.mark.parametrize("dtype,lead,d", [
    (jnp.bfloat16, 128, 192), (jnp.float32, 128, 192), (jnp.bfloat16, 8, 12),
    (jnp.float32, 16, 24)])
@pytest.mark.parametrize("interleave", [False, True],
                         ids=["half_split", "interleaved"])
def test_rope_of_the_trailing_lanes_in_place_is_split_turn_concatenate(
        dtype, lead, d, interleave):
    """Bit for bit: the turned lanes are the same arithmetic on the same
    lanes, the leading ones pass times 1 plus 0 times 0. The gradient too,
    to the dtype's rounding (its term through the matrix of 0 and +-1)."""
    t = 32
    keys = jax.random.split(jax.random.PRNGKey(lead + d), 2)
    x, g = (jax.random.normal(k, (2, t, 3, d)).astype(dtype) for k in keys)
    pos = jnp.arange(t)[None] + 5
    got, got_vjp = jax.vjp(lambda x: _rope(x, pos, 1e6, interleave, lead), x)
    want, want_vjp = jax.vjp(lambda x: _rope_of_the_trailing_lanes_by_parts(
        x, pos, 1e6, interleave, lead), x)
    assert got.dtype == dtype and got.shape == x.shape
    np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)),
                                  np.asarray(want.astype(jnp.float32)))
    np.testing.assert_array_equal(np.asarray(got[..., :lead]),
                                  np.asarray(x[..., :lead]))
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got_vjp(g)[0].astype(jnp.float32),
                               want_vjp(g)[0].astype(jnp.float32),
                               atol=tol, rtol=tol)
    # the pass turned the other way round (``_turned``) IS the gradient: the
    # float32 one, rounded to the dtype once
    exact = jax.vjp(lambda x: _rope_of_the_trailing_lanes_by_parts(
        x, pos, 1e6, interleave, lead), x.astype(jnp.float32))[1](
            g.astype(jnp.float32))[0]
    np.testing.assert_array_equal(
        np.asarray(got_vjp(g)[0].astype(jnp.float32)),
        np.asarray(exact.astype(dtype).astype(jnp.float32)))


@pytest.mark.parametrize("rope", [True, False], ids=["rope", "nope"])
def test_latent_attention_builds_no_part_of_q_and_no_assembled_k(rope):
    """Forward and backward of a latent layer through the flash kernels hold
    no array of q's rotary lanes alone a head, and none of k's at ``qk_nope +
    qk_rope`` lanes: q goes to the kernels whole as projected, ``[k_nope |
    v]`` whole as ``kv_b_proj`` wrote it and the rotary key as its one head
    (PERF.md §6, PR 61)."""
    dims = LatentDims(kv_rank=16, qk_nope=16, qk_rope=4, v=8)
    block = Block(dim=32, heads=4, dtype=jnp.float32, mla=dims, rope=rope,
                  rope_interleave=True, attention="flash", block_q=16,
                  block_k=16, flash_interpret=True, mlp_hidden=48)
    x, pos = jnp.ones((2, 32, 32)), jnp.arange(32)[None]
    params = block.init(jax.random.PRNGKey(0), x, pos)["params"]
    jaxpr = jax.make_jaxpr(jax.grad(lambda p, x: jnp.sum(
        block.apply({"params": p}, x, pos)), argnums=(0, 1)))(params, x)

    def shapes(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                continue        # inside the kernels: blocks, not arrays
            yield from (v.aval.shape for v in eqn.outvars)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from shapes(sub)

    found = set(shapes(jaxpr.jaxpr))
    assert (2, 32, 4, 20) in found          # q whole, head by head: 16 | 4
    assert (2, 32, 4, 24) in found          # [k_nope | v] whole: 16 | 8
    assert (2, 32, 1, 4) in found           # the one rotary key head
    # no q_rope, no rotary key copied to the heads (4 lanes a head) and no
    # k_nope alone (16): nothing to assemble a k from
    assert not [s for s in found if len(s) == 4 and s[2] == 4
                and s[3] in (4, 16)]


@pytest.mark.parametrize("interleave", [False, True])
def test_rope_builds_no_half_heads(interleave):
    """Forward and backward hold no array of half a head at every position:
    whole heads go in, through and out (a half of 64 lanes fills half a
    vector register's lanes and is relaid twice; PERF.md §6, PR 39)."""
    x = jnp.ones((1, 64, 8, 128), jnp.bfloat16)
    pos = jnp.arange(64)[None]
    jaxpr = jax.make_jaxpr(lambda x, g: jax.vjp(
        lambda x: _rope(x, pos, 10000.0, interleave), x)[1](g))(x, x)

    def shapes(jaxpr):
        for eqn in jaxpr.eqns:
            yield from (v.aval.shape for v in eqn.outvars)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from shapes(sub)

    found = list(shapes(jaxpr.jaxpr))
    assert (1, 64, 8, 128) in found
    # cos and sin are one head's, (1, 64, 1, 64): no head of x is cut
    assert not [s for s in found if len(s) == 4 and s[2] > 1 and s[-1] < 128]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_interleaved_rope_is_the_reference_and_scores_as_deinterleaved(dtype):
    key = jax.random.split(jax.random.PRNGKey(1), 2)
    q, k = (jax.random.normal(kk, (1, 16, 3, 8)).astype(dtype) for kk in key)
    pos = jnp.arange(16)[None]
    got_q, got_k = (_rope(x, pos, 1e6, True) for x in (q, k))
    want_q = kanana2.rope_interleaved(q.astype(jnp.float32), 1e6)
    np.testing.assert_allclose(got_q.astype(jnp.float32), want_q,
                               atol=1e-6 if dtype == jnp.float32 else 2e-2)
    if dtype != jnp.float32:
        return
    # Hugging Face's form: de-interleave (evens, then odds), rotate halves
    def hf(x):
        return _rope(jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1), pos, 1e6)

    scores = jnp.einsum("bqhd,bkhd->bhqk", got_q, got_k)
    np.testing.assert_allclose(scores, jnp.einsum("bqhd,bkhd->bhqk", hf(q), hf(k)),
                               atol=1e-5)


def reference_layer_of(block_params):
    return {"wq": block_params["q_proj"]["kernel"],
            "wkva": block_params["kv_a_proj"]["kernel"],
            "kv_norm": block_params["kv_a_norm"]["scale"],
            "wkvb": block_params["kv_b_proj"]["kernel"],
            "wo": block_params["o_proj"]["kernel"]}


@pytest.mark.parametrize("rope", [True, False], ids=["rope", "nope"])
@pytest.mark.parametrize("attention", ["dense", "flash"])
def test_attention_branch_against_the_reference(monkeypatch, attention, rope):
    """``rope=False`` (``mla_use_nope``) against the same reference with its
    rotation taken out: the reference's equations are otherwise the layer's."""
    if not rope:
        monkeypatch.setattr(kanana2, "rope_interleaved", lambda x, theta: x)
    dims = LatentDims(kv_rank=16, qk_nope=8, qk_rope=4, v=8)
    block = Block(dim=32, heads=4, dtype=jnp.float32, mla=dims, rope_theta=1e6,
                  rope_interleave=True, attention=attention, block_q=16,
                  block_k=16, flash_interpret=True, mlp_hidden=48, rope=rope)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, 32))
    pos = jnp.arange(32)[None]
    params = block.init(jax.random.PRNGKey(3), x, pos)["params"]
    params = jax.tree_util.tree_map(
        lambda p: p + 0.1 * jax.random.normal(jax.random.PRNGKey(p.size), p.shape),
        params)
    # the MLP switched off: the block is x + attention(rms(x))
    params["mlp_down"]["kernel"] = jnp.zeros_like(params["mlp_down"]["kernel"])

    def got(params, x):
        return block.apply({"params": params}, x, pos)

    def want(params, x):
        with jax.default_matmul_precision("highest"):
            h = kanana2.rms(x, params["RMSNorm_0"]["scale"], 1e-6)
            return x + kanana2.attention(reference_layer_of(params), h, CFG)

    np.testing.assert_allclose(got(params, x), want(params, x), atol=2e-5)
    g_got = jax.grad(lambda p, x: jnp.sum(got(p, x) ** 2), argnums=(0, 1))(params, x)
    g_want = jax.grad(lambda p, x: jnp.sum(want(p, x) ** 2), argnums=(0, 1))(params, x)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(g_got)[0],
                            jax.tree_util.tree_leaves(g_want)):
        if "mlp_" in str(path) or "RMSNorm_1" in str(path):
            continue        # the reference has no MLP to differentiate
        np.testing.assert_allclose(a, b, atol=2e-4 * float(jnp.max(jnp.abs(b))))


def tiny_model(**kw):
    base = dict(vocab=64, dim=32, heads=4, layers=3, dtype=jnp.float32,
                mla=LatentDims(kv_rank=16, qk_nope=8, qk_rope=4, v=8),
                rope_theta=1e6, rope_interleave=True, first_k_dense=1,
                mlp_hidden=48, moe_experts=8, moe_every=1, moe_top_k=3,
                moe_hidden=16, moe_router="sigmoid", moe_route_scale=2.448,
                moe_shared_hidden=24, rms_norm_eps=1e-6)
    base.update(kw)
    return TransformerLM(**base)


def to_reference(tree):
    layers = []
    for i in range(sum(k.startswith("block_") for k in tree)):
        block = tree[f"block_{i}"]
        layer = {"attn_norm": block["RMSNorm_0"]["scale"],
                 "mlp_norm": block["RMSNorm_1"]["scale"],
                 **reference_layer_of(block)}
        if "moe" in block:
            moe = block["moe"]
            layer.update(router=moe["router"], w_gate=moe["w_gate"],
                         w_up=moe["w_up"], w_down=moe["w_down"],
                         s_gate=moe["shared_gate"]["kernel"],
                         s_up=moe["shared_up"]["kernel"],
                         s_down=moe["shared_down"]["kernel"])
        else:
            layer.update(w_gate=block["mlp_gate"]["kernel"],
                         w_up=block["mlp_up"]["kernel"],
                         w_down=block["mlp_down"]["kernel"])
        layers.append(layer)
    return {"embed": tree["embed"]["embedding"], "layers": layers,
            "final_norm": tree["RMSNorm_0"]["scale"],
            "head": tree["lm_head"]["kernel"]}


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_model_loss_and_gradients_against_the_reference(remat):
    model = tiny_model(remat=remat)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 32), 0, 64)
    variables = model.init(jax.random.PRNGKey(5), tokens)
    params = variables["params"]
    assert sorted(params["block_0"]) == [
        "RMSNorm_0", "RMSNorm_1", "kv_a_norm", "kv_a_proj", "kv_b_proj",
        "mlp_down", "mlp_gate", "mlp_up", "o_proj", "q_proj"]     # dense first
    assert "moe" in params["block_1"] and "moe" in params["block_2"]
    bias = jax.tree_util.tree_map(         # a bias that changes the choice
        lambda b: 0.3 * jax.random.normal(jax.random.PRNGKey(6), b.shape),
        variables[BIAS_COLLECTION])
    assert sorted(bias) == ["block_1", "block_2"]

    def loss(params):
        hidden = model.apply({"params": params, BIAS_COLLECTION: bias}, tokens,
                             return_hidden=True)
        return chunked_lm_loss(hidden, params["lm_head"]["kernel"],
                               jnp.roll(tokens, -1, axis=1))

    biases = [bias[b]["moe"]["router_bias"] for b in ("block_1", "block_2")]
    (want, parts), want_grads = kanana2.loss_and_grads(
        to_reference(params), biases, tokens, CFG)
    got, grads = jax.value_and_grad(loss)(params)
    assert abs(float(got) - float(want)) <= 1e-6 * float(want)
    logits = model.apply({"params": params, BIAS_COLLECTION: bias}, tokens)
    np.testing.assert_allclose(logits, parts["logits"], atol=1e-5 * float(jnp.max(jnp.abs(parts["logits"]))))
    # the bias chose: without it another set of experts is taken somewhere
    _, no_bias = kanana2.loss_parts(to_reference(params),
                                    [jnp.zeros_like(b) for b in biases],
                                    tokens, CFG)
    assert any(bool(jnp.any(a != b)) for a, b in zip(parts["chosen"],
                                                     no_bias["chosen"]))
    flat_got = jax.tree_util.tree_leaves(to_reference(grads))
    flat_want = jax.tree_util.tree_leaves(want_grads)
    assert len(flat_got) == len(flat_want)
    for a, b in zip(flat_got, flat_want):
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-5 * float(jnp.max(jnp.abs(b)))


def test_first_k_dense_and_moe_every_state_one_pattern():
    tokens = jnp.zeros((1, 8), jnp.int32)
    for kw in (dict(moe_every=2), dict(moe_experts=0)):
        with pytest.raises(ValueError, match="first_k_dense"):
            tiny_model(**kw).init(jax.random.PRNGKey(0), tokens)
    # without first_k_dense the old pattern is what it was
    params = tiny_model(first_k_dense=0, moe_every=2, layers=4).init(
        jax.random.PRNGKey(0), tokens)["params"]
    assert ["moe" in params[f"block_{i}"] for i in range(4)] == [
        False, True, False, True]


def test_latent_attention_refuses_what_it_does_not_build():
    tokens = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError, match="latent attention"):
        tiny_model(kv_heads=2).init(jax.random.PRNGKey(0), tokens)
