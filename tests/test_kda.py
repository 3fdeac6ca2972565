"""``ops.kda.kda`` (the chunked gated delta rule with a decay a channel)
against the recurrence that defines it, a step a token, forward and the
gradients of q, k, v, g and beta: at several chunk sizes, a row of one chunk,
a row shorter than a chunk, rows that cross chunk and block borders, with
beta 0 (nothing is ever written), with every decay 1 (the plain delta rule),
and at decays so strong that any factorised ``exp(-G)`` overflows float32:
finite, and right. Then the mixer (``models.kda.KDAMixer``) against the plain
reference's layer, its initialisation, the gauges and the shapes refused.

Tolerances, as shares of max|reference| per array: float32 at ``highest``
5e-6 (the same float32 arithmetic in another order; observed <= 8e-7); bf16
operands 3e-2 (q, k, v, the solve's result, the scores and the carried state
are each rounded once for a product: 4e-3 a rounding).
"""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmarks.reference import kimi_linear as plain  # noqa: E402

from horovod_tpu.models.kda import KDADims, KDAMixer  # noqa: E402
from horovod_tpu.ops import kda as kda_ops  # noqa: E402

F32_TOL, BF16_TOL = 5e-6, 3e-2
PARTS = ("o", "dq", "dk", "dv", "dg", "dbeta")


def inputs(t, heads=3, d=32, rows=2, seed=0, g=None, beta=None):
    """q, k as the mixer hands them over (unit length a head, q times
    d^-0.5), v normal, g = -exp(uniform log) in [-1.6, -0.001] as the layer
    initialises it, beta a sigmoid, and a cotangent."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    shape = (rows, t, heads, d)
    q = plain.unit(jax.random.normal(ks[0], shape)) * d ** -0.5
    k = plain.unit(jax.random.normal(ks[1], shape))
    v = jax.random.normal(ks[2], shape)
    if g is None:
        g = -jnp.exp(jax.random.uniform(ks[3], shape, minval=np.log(1e-3),
                                        maxval=np.log(1.6)))
    else:
        g = jnp.full(shape, g, jnp.float32)
    if beta is None:
        beta = jax.nn.sigmoid(jax.random.normal(ks[4], shape[:3]))
    else:
        beta = jnp.full(shape[:3], beta, jnp.float32)
    return (q, k, v, g, beta), jax.random.normal(ks[5], shape)


def with_grads(fn, args, do):
    o, vjp = jax.vjp(fn, *args)
    return (o.astype(jnp.float32), *vjp(do.astype(o.dtype)))


def chunked(args, do, chunk, dtype=jnp.float32):
    q, k, v, g, beta = args
    cast = (q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta)
    precision = "highest" if dtype == jnp.float32 else None
    with jax.default_matmul_precision(precision):
        return with_grads(lambda *a: kda_ops.kda(*a, chunk=chunk), cast, do)


def recurrence(args, do):
    with jax.default_matmul_precision("highest"):
        return with_grads(kda_ops.kda_recurrence, args, do)


def shares(got, want):
    return {name: float(jnp.max(jnp.abs(g.astype(jnp.float32) - w))
                        / jnp.maximum(jnp.max(jnp.abs(w)), 1e-30))
            for name, g, w in zip(PARTS, got, want)}


# (positions, chunk): one chunk; shorter than a chunk; several chunks in one
# block; more chunks than a block holds (the carried state and its cotangent
# cross block borders); a chunk with one sub-block; a chunk that is no whole
# number of sub-blocks of 16 (its one sub-block is the chunk)
CASES = [(64, 64), (48, 64), (256, 64), (640, 64), (128, 16), (96, 32),
         (72, 24), (1152, 64)]


@pytest.mark.parametrize("t,chunk", CASES)
def test_chunked_form_is_the_recurrence(t, chunk):
    args, do = inputs(t)
    worst = shares(chunked(args, do, chunk), recurrence(args, do))
    assert max(worst.values()) <= F32_TOL, worst


def test_the_ops_recurrence_is_the_references():
    args, do = inputs(40)
    with jax.default_matmul_precision("highest"):
        want = with_grads(plain.delta_rule, args, do)
    assert max(shares(recurrence(args, do), want).values()) <= 1e-6


@pytest.mark.parametrize("name,g,beta", [
    ("beta_0_nothing_is_written", None, 0.0),
    ("every_decay_1_the_plain_delta_rule", 0.0, None),
    ("decay_1_and_beta_1", 0.0, 1.0)])
def test_special_gates(name, g, beta):
    args, do = inputs(192, g=g, beta=beta)
    got, want = chunked(args, do, 64), recurrence(args, do)
    if beta == 0.0:
        # the state stays 0 and so does o; beta's gradient does not
        assert float(jnp.max(jnp.abs(got[0]))) == 0.0
        assert float(jnp.max(jnp.abs(want[5]))) > 1e-3
        np.testing.assert_allclose(np.asarray(got[5]), np.asarray(want[5]),
                                   atol=1e-6 * float(jnp.max(jnp.abs(want[5]))))
        return
    assert max(shares(got, want).values()) <= F32_TOL


# -1.6 a token is the strongest decay the layer initialises (A = 16, dt =
# 0.1): 102 over a chunk, so a factorised exp(-G) is past float32 (e^88);
# -6 passes it inside one sub-block of 16; -320 (A = 16, softplus(20)) wipes
# the state every token: o_t = beta_t (k_t . q_t) v_t.
@pytest.mark.parametrize("g", [-1.6, -6.0, -320.0])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, F32_TOL),
                                       (jnp.bfloat16, BF16_TOL)])
def test_the_strongest_decay_is_finite_and_right(g, dtype, tol):
    args, do = inputs(256, g=g)
    got, want = chunked(args, do, 64, dtype), recurrence(args, do)
    for part in got:
        assert bool(jnp.all(jnp.isfinite(part.astype(jnp.float32))))
    # g's gradient underflows with the decays: compare where it is not 0
    worst = shares(got, want)
    if g == -320.0:
        q, k, v, _, beta = args
        alone = beta[..., None] * jnp.sum(k * q, -1, keepdims=True) * v
        np.testing.assert_allclose(np.asarray(want[0]), np.asarray(alone),
                                   atol=1e-6)
        assert float(jnp.max(jnp.abs(want[4]))) == 0.0
        worst.pop("dg")
    elif g == -6.0:
        # e^-6 a token: what reaches g is 1e-3 of the other gradients and a
        # difference of sums that nearly cancel (observed 2.8e-5 in float32)
        assert worst.pop("dg") <= max(tol, 1e-4)
    assert max(worst.values()) <= tol, worst


def test_a_factorised_exponent_would_overflow_here():
    """What the sub-blocks are for: at -1.6 a token the running sum over a
    chunk of 64 is -102.4, and exp of its negation is not a float32."""
    assert not np.isfinite(np.exp(np.float32(1.6 * 64)))
    assert np.isfinite(np.exp(np.float32(1.6 * kda_ops.SUB)))


def test_bf16_operands_keep_decays_and_state_in_float32():
    args, do = inputs(512, seed=3)
    got, want = chunked(args, do, 64, jnp.bfloat16), recurrence(args, do)
    worst = shares(got, want)
    assert 1e-4 < max(worst.values()) <= BF16_TOL, worst
    assert got[4].dtype == jnp.float32 and got[5].dtype == jnp.float32


def test_the_backward_is_chunked_and_saves_block_starts(hvd):
    """One custom_vjp: the backward's program holds a loop over BLOCKS of
    chunks and no loop of one step a token; the forward of a differentiated
    call keeps the state each block starts from, which the gauge reports."""
    args, do = inputs(1152, heads=2, d=16, rows=1)
    jaxpr = str(jax.make_jaxpr(lambda *a: jax.vjp(
        lambda *b: kda_ops.kda(*b, chunk=64), *a)[1](do))(*args))
    assert "custom_vjp_call" in str(jax.make_jaxpr(
        lambda *a: kda_ops.kda(*a, chunk=64))(*args))
    lengths = {int(n) for n in __import__("re").findall(r"length=(\d+)", jaxpr)}
    # 1152 = 18 chunks = 6 blocks of 3: loops over blocks (6) and over a
    # block's chunks (3) and nothing a token long
    assert lengths == {3, 6}, lengths
    assert kda_ops.plan(1152, 64) == (64, 16, 3 * 64)
    gauges = hvd.metrics.registry().snapshot()["gauges"]
    assert gauges["horovod_kda_chunk_len"] == 64
    assert gauges["horovod_kda_saved_state_bytes_per_layer"] == 6 * 2 * 16 * 16 * 4
    assert kda_ops.saved_state_bytes(1, 16384, 32, 128, 128) == 128 * 2 ** 20


def test_shapes_that_are_no_whole_number_of_chunks_are_refused():
    args, _ = inputs(100)
    with pytest.raises(ValueError, match="no whole number of chunks of 64"):
        kda_ops.kda(*args, chunk=64)
    with pytest.raises(ValueError, match="chunk must be positive"):
        kda_ops.kda(*args, chunk=0)
    assert kda_ops.plan(40, 64) == (40, 40, 40)      # the row is the chunk


def test_unit_lower_inverse_is_the_inverse():
    """``(I - n)(I + n^2)(I + n^4)...`` against the product it inverts, at
    entries of the size a chunk's ``beta k k^T`` has (|.| <= 1, most small)."""
    n = 0.1 * jnp.tril(jax.random.normal(jax.random.PRNGKey(0), (3, 64, 64)), -1)
    with jax.default_matmul_precision("highest"):
        product = (jnp.eye(64) + n) @ kda_ops._unit_lower_inverse(n)
    np.testing.assert_allclose(np.asarray(product),
                               np.broadcast_to(np.eye(64), (3, 64, 64)),
                               atol=2e-6)
    one = jnp.zeros((1, 1)).at[0, 0].set(0.0)
    np.testing.assert_array_equal(np.asarray(kda_ops._unit_lower_inverse(one)),
                                  [[1.0]])


# ---------------------------------------------------------------- the mixer

DIMS = KDADims(heads=2, head_dim=16, conv=4, chunk=16)
DIM, SEQ = 24, 80


def mixer_case(dtype=jnp.float32, seed=1):
    mixer = KDAMixer(dim=DIM, dims=DIMS, rms_norm_eps=1e-5, dtype=dtype)
    h = jax.random.normal(jax.random.PRNGKey(seed), (2, SEQ, DIM), jnp.float32)
    params = mixer.init(jax.random.PRNGKey(seed + 1), h)["params"]
    # weights of a size at which every part matters
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: x if x.ndim < 2 or path[-1].key.endswith("_conv")
        else 3.0 * x, params)
    return mixer, params, h


def reference_layer(params):
    return {"wq": params["q_proj"]["kernel"], "wk": params["k_proj"]["kernel"],
            "wv": params["v_proj"]["kernel"], "conv_q": params["q_conv"],
            "conv_k": params["k_conv"], "conv_v": params["v_conv"],
            "wf_a": params["f_a_proj"]["kernel"],
            "wf_b": params["f_b_proj"]["kernel"], "dt_bias": params["dt_bias"],
            "a_log": params["A_log"], "wb": params["b_proj"]["kernel"],
            "wg_a": params["g_a_proj"]["kernel"],
            "wg_b": params["g_b_proj"]["kernel"], "o_norm": params["o_norm"],
            "wo": params["o_proj"]["kernel"]}


CFG = {"heads": DIMS.heads, "kda_head_dim": DIMS.head_dim, "eps": 1e-5}


def test_mixer_is_the_references_layer_with_every_leafs_gradient():
    mixer, params, h = mixer_case()
    weight = jax.random.normal(jax.random.PRNGKey(9), (2, SEQ, DIM))
    with jax.default_matmul_precision("highest"):
        got, got_grads = jax.value_and_grad(lambda p, x: jnp.sum(
            mixer.apply({"params": p}, x) * weight), argnums=(0, 1))(params, h)
        want, want_grads = jax.value_and_grad(lambda p, x: jnp.sum(
            plain.kda(p, x, CFG) * weight), argnums=(0, 1))(
                reference_layer(params), h)
    with jax.default_matmul_precision("highest"):
        out, ref = (mixer.apply({"params": params}, h),
                    plain.kda(reference_layer(params), h, CFG))
    assert float(jnp.max(jnp.abs(out - ref)) / jnp.max(jnp.abs(ref))) <= 2e-5
    assert abs(float(got - want)) <= 1e-4 * abs(float(want))
    got_leaves = reference_layer(got_grads[0])
    assert set(got_leaves) == set(want_grads[0])
    for name, w in want_grads[0].items():
        share = float(jnp.max(jnp.abs(got_leaves[name] - w)) / jnp.max(jnp.abs(w)))
        # A_log's two numbers are sums over every token and channel of terms
        # of both signs that nearly cancel (observed 1.2e-4)
        # (observed 1.2e-4); the taps' are sums over every token too (3.8e-5)
        assert share <= (5e-4 if name == "a_log" else 1e-4), (name, share)
    assert float(jnp.max(jnp.abs(got_grads[1] - want_grads[1]))
                 / jnp.max(jnp.abs(want_grads[1]))) <= 1e-4


def test_mixer_in_bf16_stays_in_its_band():
    mixer, params, h = mixer_case(jnp.bfloat16)
    with jax.default_matmul_precision("highest"):
        want = plain.kda(reference_layer(params), h, CFG)
    got = mixer.apply({"params": params}, h.astype(jnp.bfloat16))
    assert got.dtype == jnp.bfloat16
    share = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))
                  / jnp.max(jnp.abs(want)))
    assert 1e-4 < share <= 5e-2, share


def test_mixer_initialises_as_the_family_does():
    mixer, _, h = mixer_case()
    params = mixer.init(jax.random.PRNGKey(0), h)["params"]
    a = np.exp(np.asarray(params["A_log"]))
    assert params["A_log"].shape == (2,) and a.min() >= 1.0 and a.max() <= 16.0
    dt = np.asarray(jax.nn.softplus(params["dt_bias"]))
    assert params["dt_bias"].shape == (32,)
    assert dt.min() >= 1e-3 * 0.999 and dt.max() <= 0.1 * 1.001
    assert params["o_norm"].shape == (16,)      # ONE weight, shared by heads
    np.testing.assert_array_equal(np.asarray(params["o_norm"]), 1.0)
    assert {"q_conv", "k_conv", "v_conv"} <= set(params)
    assert params["q_conv"].shape == (4, 32)
    assert not any(path[-1].key == "bias" for path, _ in
                   jax.tree_util.tree_flatten_with_path(params)[0])
    assert params["f_a_proj"]["kernel"].shape == (24, 16)
    assert params["g_b_proj"]["kernel"].shape == (16, 32)
    assert params["b_proj"]["kernel"].shape == (24, 2)


def test_the_convolution_takes_the_fused_kernels_under_kdas_names():
    """At a shape the convolution's kernels tile the mixer runs them three
    times under ``hvd_kda_conv_fwd`` (in the interpreter here), to the
    jax.numpy form's result; Mamba-2's default names stay what they were."""
    from horovod_tpu.models import kda as kda_model
    from horovod_tpu.ops import mamba_fused

    dims = KDADims(heads=1, head_dim=128, conv=4, chunk=64)
    mixer = KDAMixer(dim=16, dims=dims, dtype=jnp.float32, interpret=True)
    h = jax.random.normal(jax.random.PRNGKey(0), (1, 256, 16), jnp.float32)
    params = mixer.init(jax.random.PRNGKey(1), h)["params"]
    traced = str(jax.make_jaxpr(lambda p, x: mixer.apply({"params": p}, x))(
        params, h))
    # the three calls share ONE traced copy of the kernel (a jitted call)
    assert "hvd_kda_conv_fwd" in traced
    assert traced.count("name=_conv_fwd_call") == 3
    assert "hvd_mamba_conv_fwd" not in traced
    fused = mixer.apply({"params": params}, h)
    real = mamba_fused.conv_takes_kernel
    try:
        mamba_fused.conv_takes_kernel = lambda *a, **k: False
        plain_path = mixer.apply({"params": params}, h)
    finally:
        mamba_fused.conv_takes_kernel = real
    np.testing.assert_allclose(np.asarray(fused), np.asarray(plain_path),
                               atol=2e-5, rtol=2e-5)
    assert kda_model.CONV_NAMES == ("hvd_kda_conv_fwd", "hvd_kda_conv_bwd")
    import inspect
    assert inspect.signature(mamba_fused.conv_silu).parameters[
        "names"].default == ("hvd_mamba_conv_fwd", "hvd_mamba_conv_bwd")
