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


@pytest.mark.parametrize("attention", ["dense", "flash"])
def test_attention_branch_against_the_reference(attention):
    dims = LatentDims(kv_rank=16, qk_nope=8, qk_rope=4, v=8)
    block = Block(dim=32, heads=4, dtype=jnp.float32, mla=dims, rope_theta=1e6,
                  rope_interleave=True, attention=attention, block_q=16,
                  block_k=16, flash_interpret=True, mlp_hidden=48)
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
