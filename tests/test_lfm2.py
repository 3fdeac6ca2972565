"""What LFM2-24B-A2B forced in the program (ISSUE 62): the gated
short-convolution mixer (``models/short_conv.py``) and its pass as a kernel
pair (``ops/mamba_fused.py`` ``gated_conv``, in the interpreter) against the
definition; Mamba's and KDA's ``silu(conv + bias)`` through the touched file,
bit for bit what they were; RMSNorm a head then the plain rotation at heads of
64 in ``Block._attention``, and the flash path at 32 | 8 heads of 64 on such
operands against dense attention; the sigmoid router's stated epsilon; the
layer kind ``"conv"`` with its errors and gauges; and the system's model
against ``benchmarks/reference/lfm2_moe.py`` a layer of each kind and at the
cut's seven-layer pattern, in float32 and as trained. The configuration's
files, the share's arithmetic and the parameter count are held in
tests/benchmark/test_benchmark_lfm2.py.

Tolerances (observed on the CPU; shares of max|reference| an array unless
said otherwise):
* the pass in float32, ``jax.numpy`` form and kernels: 2e-6 (observed <=
  2.3e-7: the same float32 products and sums in another order); in bf16: y
  and the wide gradient 8e-3 (one rounding to bf16 of values up to the
  array's largest: 2^-9 of it, observed <= 3.2e-3), the taps' gradient 2e-6
  (float32 sums on both sides).
* the model against the plain reference in float32 under ``highest``: logits
  and every leaf's gradient 2e-5 (observed <= 1.2e-6), the loss 1e-6, the
  bias after a step equal; as trained (bf16 activations, the kernels in the
  interpreter, recomputation): logits 2e-2 (observed <= 4e-3), the loss
  3e-4, a leaf's gradient 0.3 in the Euclidean norm (observed <= 0.18: a
  flipped expert moves a router's column whole).
"""

from __future__ import annotations

import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from horovod_tpu.common import device_names  # noqa: E402
from horovod_tpu.models import (BIAS_COLLECTION, ShortConvDims,  # noqa: E402
                                ShortConvMixer, TransformerLM, short_conv)
from horovod_tpu.models.transformer import Block  # noqa: E402
from horovod_tpu.ops import mamba_fused  # noqa: E402
from horovod_tpu.ops.moe import sigmoid_route  # noqa: E402


def share(got, want):
    got, want = (np.asarray(x, np.float64) for x in (got, want))
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


def gauge(hvd, name):
    return hvd.metrics.registry().snapshot()["gauges"].get(name)


# ------------------------------------------------------- the gated convolution

def definition(bcx, taps, dy):
    """``C * conv(B * X)`` and its two gradients in float64, a tap at a time,
    each row of the batch by itself and nothing before a row's start."""
    bcx, taps, dy = (np.asarray(x, np.float64) for x in (bcx, taps, dy))
    d, k = taps.shape[1], taps.shape[0]
    b, c, x = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
    u = b * x
    conv, du, dk = np.zeros_like(u), np.zeros_like(u), np.zeros_like(taps)
    g = dy * c
    t = u.shape[1]
    for j in range(k):
        back = k - 1 - j        # conv[t] += taps[j] * u[t - back]
        conv[:, back:] += taps[j] * u[:, :t - back]
        du[:, :t - back] += taps[j] * g[:, back:]
        dk[j] = np.sum(g[:, back:] * u[:, :t - back], axis=(0, 1))
    return c * conv, np.concatenate([du * x, dy * conv, du * b], -1), dk


def conv_case(dtype, taps=3):
    """Two rows of two row tiles each, 256 channels."""
    t = 2 * mamba_fused.row_tile(jnp.dtype(dtype).itemsize)
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    bcx = jax.random.normal(ks[0], (2, t, 3 * 256), jnp.float32).astype(dtype)
    kernel = jax.random.uniform(ks[1], (taps, 256), jnp.float32, -0.6, 0.6)
    dy = jax.random.normal(ks[2], (2, t, 256), jnp.float32).astype(dtype)
    return bcx, kernel, dy


def value_and_grads(fn, bcx, kernel, dy):
    y, vjp = jax.vjp(fn, bcx, kernel)
    return (y, *vjp(dy))


FORMS = {"jax_numpy": short_conv.gated_conv,
         "kernels": lambda bcx, k: mamba_fused.gated_conv(bcx, k, True)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("form", sorted(FORMS))
def test_gated_conv_against_its_definition(form, dtype):
    """Forward and both gradients over two rows of two row tiles: the zero
    history at each row's start, the tile's border and the batch's border
    are all in what is compared."""
    bcx, kernel, dy = conv_case(dtype)
    assert mamba_fused.gated_conv_takes_kernel(bcx, kernel)
    got = jax.jit(lambda *a: value_and_grads(FORMS[form], *a))(bcx, kernel, dy)
    want = definition(bcx, kernel, dy)
    assert got[0].dtype == got[1].dtype == dtype and got[2].dtype == jnp.float32
    limits = (2e-6,) * 3 if dtype == jnp.float32 else (8e-3, 8e-3, 2e-6)
    for name, g, w, limit in zip(("y", "d_bcx", "d_taps"), got, want, limits):
        assert share(g, w) <= limit, (form, name, share(g, w))
    tile = bcx.shape[1] // 2
    for rows in (slice(0, 4), slice(tile - 4, tile + 4)):   # start; border
        assert share(got[0][:, rows], want[0][:, rows]) <= limits[0]
        assert share(got[1][:, rows], want[1][:, rows]) <= limits[1]


@pytest.mark.parametrize("form", sorted(FORMS))
def test_nothing_leaks_from_one_row_into_the_next(form):
    """The second row's result and gradient do not move with the first
    row's values, and its first positions see a zero history."""
    bcx, kernel, dy = conv_case(jnp.float32)
    run = jax.jit(lambda *a: value_and_grads(FORMS[form], *a))
    base = run(bcx, kernel, dy)
    other = run(bcx.at[0].set(7.0 * bcx[0] + 1.0), kernel, dy)
    np.testing.assert_array_equal(base[0][1], other[0][1])
    np.testing.assert_array_equal(base[1][1], other[1][1])
    d = kernel.shape[1]
    b, c, x = (np.asarray(bcx[1, :1, i * d:(i + 1) * d]) for i in range(3))
    np.testing.assert_allclose(np.asarray(base[0][1, :1]),
                               c * np.asarray(kernel[-1]) * b * x, rtol=1e-6)


@pytest.mark.parametrize("shape,taps,takes", [
    ((2, 512, 384), 3, True),           # bf16 tile 512, D = 128
    ((1, 8192, 6144), 3, True),         # the cell's
    ((2, 500, 384), 3, False),          # no whole row tile
    ((2, 512, 3 * 96), 3, False),       # D no multiple of 128
    ((2, 512, 384), 10, False),         # more taps than ride along
    ((2, 512, 3 * 4096), 3, False),     # a block past the kernels' VMEM budget
])
def test_which_shapes_take_the_kernels(shape, taps, takes):
    bcx = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    kernel = jax.ShapeDtypeStruct((taps, shape[2] // 3), jnp.float32)
    assert mamba_fused.gated_conv_takes_kernel(bcx, kernel) is takes


def test_the_mixer_takes_the_kernels_where_the_shape_tiles(hvd):
    """Kernels and ``jax.numpy`` form give one mixer output, and the gauges
    say which ran: ``horovod_kda_chunk_len``'s and ``_kernel_scans``' rules."""
    mixer = ShortConvMixer(dim=128, dims=ShortConvDims(3), dtype=jnp.float32,
                           interpret=True)
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 256, 128), jnp.float32)
    params = mixer.init(jax.random.PRNGKey(2), h)
    assert set(params["params"]) == {"in_proj", "conv_kernel", "out_proj"}
    assert params["params"]["conv_kernel"].shape == (3, 128)
    fused = mixer.apply(params, h)
    assert gauge(hvd, "horovod_short_conv_taps") == 3
    passes = gauge(hvd, "horovod_short_conv_kernel_passes")
    assert passes >= 1
    mixer.apply(params, h)
    assert gauge(hvd, "horovod_short_conv_kernel_passes") == passes + 1
    plain = mixer.apply(params, h[:, :200])      # no whole row tile
    assert gauge(hvd, "horovod_short_conv_kernel_passes") == 0
    # causal: the first 200 positions do not see the rest
    assert share(fused[:, :200], plain) <= 2e-6


# Mamba's call (xBC out of the wide projection where it lies, two outputs, a
# bias) and KDA's (a run of its own, one output, no bias, its kernels' names)
# through ``conv_silu`` of the PARENT commit (ce7922e), interpreter on the
# CPU: sha256 of the outputs and the three gradients.
OLD_OUTPUTS = {
    "mamba_bf16": "0b2088d02bcd400cd2b2a5fcf2b1ff9c2de815ea622fa85658470427b863f81a",
    "mamba_f32": "c5af66e43167b103c4dda66e88d8e6c8b49b8f86b1a5cab65053f8e375c0bbee",
    "kda_bf16": "9c0e18072a0be61ea15532ae065b49832540553d6160a56c2b7a25e8880faee8",
    "kda_f32": "470d83e9c035e50b473eaca0d6492a0967fbc3041a32596d7d2f45a95342cb0e",
}


@pytest.mark.parametrize("case", sorted(OLD_OUTPUTS))
def test_silu_conv_through_the_touched_file_is_bit_for_bit_what_it_was(case):
    dtype, t = ((jnp.bfloat16, 1024) if case.endswith("bf16")
                else (jnp.float32, 512))
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    if case.startswith("mamba"):
        wide = jax.random.normal(ks[0], (2, t, 128 + 384 + 8),
                                 jnp.float32).astype(dtype)
        x = wide[..., 128:512]
        kw = dict(splits=(256, 128), wide=wide, start=128)
        bias = 0.1 * jax.random.normal(ks[2], (384,), jnp.float32)
    else:
        x = jax.random.normal(ks[0], (2, t, 256), jnp.float32).astype(dtype)
        kw = dict(names=(device_names.KDA_CONV_FWD, device_names.KDA_CONV_BWD))
        bias = jnp.zeros((256,), jnp.float32)
    kernel = jax.random.uniform(ks[1], (4, x.shape[-1]), jnp.float32, -0.5, 0.5)

    def run(x, kernel, bias):
        out, vjp = jax.vjp(lambda x, k, b: mamba_fused.conv_silu(
            x, k, b, True, **kw), x, kernel, bias)
        outs = out if isinstance(out, tuple) else (out,)
        g = tuple(jnp.cos(o.astype(jnp.float32)).astype(o.dtype) for o in outs)
        return outs + vjp(g if isinstance(out, tuple) else g[0])

    digest = hashlib.sha256()
    for array in jax.jit(run)(x, kernel, bias):
        digest.update(np.asarray(array.astype(jnp.float32)).tobytes())
    assert digest.hexdigest() == OLD_OUTPUTS[case]


# ------------------------------------- head norm, rotation, flash at heads of 64

def attention_block(attention, **kw):
    return Block(dim=256, heads=32, kv_heads=8, head_dim=64, dtype=jnp.float32,
                 attention=attention, flash_interpret=True, qk_head_norm=True,
                 rope_theta=1e6, rms_norm_eps=1e-5, sublayers="mixer",
                 block_q=128, block_k=128, **kw)


def attention_case(t=256):
    x = jax.random.normal(jax.random.PRNGKey(3), (1, t, 256), jnp.float32)
    positions = jnp.arange(t)[None]
    params = attention_block("dense").init(jax.random.PRNGKey(4), x, positions)
    scale = lambda path, leaf: (  # noqa: E731 - weights that are not all one
        1.0 + 0.3 * jnp.sin(jnp.arange(leaf.size, dtype=jnp.float32))
        if leaf.ndim == 1 and leaf.size == 64 else leaf)
    return x, positions, jax.tree_util.tree_map_with_path(scale, params)


def test_head_norm_then_rotation_at_64_against_the_definition():
    """q and k: RMSNorm over each head's 64 with ONE weight of 64, THEN the
    half-split rotation of all 64 dimensions at base 1e6; scores x 64^-0.5."""
    x, positions, variables = attention_case()
    p = variables["params"]
    assert p["q_head_norm"]["scale"].shape == p["k_head_norm"]["scale"].shape == (64,)
    got = attention_block("dense").apply(variables, x, positions)
    with jax.default_matmul_precision("highest"):
        def rms(v, w):
            return v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + 1e-5) * w

        def turn(v):
            half = 32
            angles = (jnp.arange(v.shape[1], dtype=jnp.float32)[:, None]
                      * 1e6 ** (-jnp.arange(half, dtype=jnp.float32) / half))
            cos, sin = jnp.cos(angles)[:, None], jnp.sin(angles)[:, None]
            lo, hi = v[..., :half], v[..., half:]
            return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], -1)

        h = rms(x, p["RMSNorm_0"]["scale"])
        q = (h @ p["q_proj"]["kernel"]).reshape(1, -1, 32, 64)
        k, v = jnp.split(h @ p["kv_proj"]["kernel"], 2, -1)
        k, v = (a.reshape(1, -1, 8, 64) for a in (k, v))
        q, k = turn(rms(q, p["q_head_norm"]["scale"])), turn(
            rms(k, p["k_head_norm"]["scale"]))
        k, v = (jnp.repeat(a, 4, axis=2) for a in (k, v))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * 64 ** -0.5
        s = jnp.where(jnp.tril(jnp.ones((s.shape[-1],) * 2, bool)), s, -jnp.inf)
        a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
        want = x + a.reshape(1, -1, 32 * 64) @ p["o_proj"]["kernel"]
    assert share(got, want) <= 2e-5


def test_flash_at_32_over_8_heads_of_64_on_turned_operands_against_dense():
    x, positions, variables = attention_case()

    def loss(attention):
        def fn(params, x):
            out = attention_block(attention).apply({"params": params}, x,
                                                   positions)
            return jnp.sum(jnp.sin(out))
        return jax.jit(jax.value_and_grad(fn, argnums=(0, 1)))

    with jax.default_matmul_precision("highest"):
        (dense, dense_grads), (flash, flash_grads) = (
            loss(a)(variables["params"], x) for a in ("dense", "flash"))
    assert abs(float(dense) - float(flash)) <= 1e-4 * abs(float(dense))
    flat = jax.tree_util.tree_leaves_with_path(dense_grads)
    for (path, want), got in zip(flat, jax.tree_util.tree_leaves(flash_grads)):
        assert share(got, want) <= 2e-5, jax.tree_util.keystr(path)


# ----------------------------------------------------------------- the router

def test_sigmoid_route_with_the_stated_epsilon_and_with_the_default():
    from benchmarks.reference import lfm2_moe as plain

    logits = 2.0 * jax.random.normal(jax.random.PRNGKey(6), (96, 64))
    bias = 0.01 * jax.random.normal(jax.random.PRNGKey(7), (64,))
    scores, weights, experts = sigmoid_route(logits, bias, 4, 1.0, 1e-6)
    chosen = jnp.take_along_axis(scores, experts, axis=-1)
    np.testing.assert_allclose(
        weights, chosen / (chosen.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    # the reference's route, from the logits' product with an identity router
    cfg = {"top_k": 4, "route_scale": 1.0, "route_eps": 1e-6}
    with jax.default_matmul_precision("highest"):
        ref, ref_chosen, _ = plain.route(logits, jnp.eye(64), bias, cfg)
    mask = jnp.any(experts[:, :, None] == jnp.arange(64), axis=1)
    np.testing.assert_array_equal(mask, ref_chosen)
    np.testing.assert_allclose(
        jnp.take_along_axis(ref, experts, axis=-1), weights, rtol=2e-6)
    assert float(jnp.max(jnp.abs(weights.sum(-1) - 1.0))) < 1e-5
    # the epsilon matters where the chosen scores are small ...
    low = sigmoid_route(logits - 22.0, bias, 4, 1.0, 1e-6)[1].sum(-1)
    assert float(jnp.max(low)) < 0.5
    # ... and the default is today's router, bit for bit
    today = sigmoid_route(logits, bias, 4, 2.5)[1]
    explicit = 2.5 * chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    np.testing.assert_array_equal(np.asarray(today), np.asarray(explicit))
    np.testing.assert_array_equal(
        np.asarray(today), np.asarray(sigmoid_route(logits, bias, 4, 2.5, 1e-20)[1]))


# ------------------------------------------------ the layer kind and its errors

def tiny_lm(**kw):
    base = dict(vocab=64, dim=128, heads=4, kv_heads=2, layers=2,
                layer_types=("conv", "full_attention"), conv=ShortConvDims(3),
                dtype=jnp.float32)
    base.update(kw)
    return TransformerLM(**base)


TOKENS = jnp.zeros((1, 16), jnp.int32)


@pytest.mark.parametrize("kw,message", [
    (dict(conv=None), "a 'conv' layer needs the mixer's sizes"),
    (dict(layer_types=("conv", "convolution")), "must name"),
    (dict(conv=ShortConvDims(0)), "0 taps"),
])
def test_conv_layers_state_their_sizes_or_raise(kw, message):
    with pytest.raises(ValueError, match=message):
        tiny_lm(**kw).init(jax.random.PRNGKey(0), TOKENS)


def test_a_block_has_one_mixer_and_an_experts_only_layer_none():
    from horovod_tpu.models import KDADims

    x, positions = jnp.zeros((1, 16, 128)), jnp.arange(16)[None]
    both = Block(dim=128, heads=4, conv=ShortConvDims(3),
                 kda=KDADims(heads=1, head_dim=128))
    with pytest.raises(ValueError, match="conv and kda stated for ONE layer"):
        both.init(jax.random.PRNGKey(0), x, positions)
    half = Block(dim=128, heads=4, conv=ShortConvDims(3), sublayers="mlp",
                 moe_experts=4, moe_top_k=2)
    with pytest.raises(ValueError, match="conv stated for a layer"):
        half.init(jax.random.PRNGKey(0), x, positions)
    gated = Block(dim=128, heads=4, conv=ShortConvDims(3), attn_gate="head")
    with pytest.raises(ValueError, match="gates softmax attention"):
        gated.init(jax.random.PRNGKey(0), x, positions)


def test_first_k_dense_over_a_conv_layer_is_allowed():
    """The dense leading layer's mixer is a convolution; the experts start in
    the layer after it, whatever its kind."""
    model = tiny_lm(mlp_hidden=96, first_k_dense=1, moe_experts=4, moe_every=1,
                    moe_top_k=2, moe_hidden=32, moe_router="sigmoid")
    variables = jax.eval_shape(model.init, jax.random.PRNGKey(0), TOKENS)
    first, second = (variables["params"][f"block_{i}"] for i in (0, 1))
    assert {"mixer", "mlp_gate", "mlp_up", "mlp_down"} <= set(first)
    assert "moe" not in first and "moe" in second and "mixer" not in second
    assert set(variables[BIAS_COLLECTION]) == {"block_1"}


# ------------------------------------ the model against the plain reference

PATTERNS = {"conv_dense": [0], "conv_experts": [3], "attention_experts": [2],
            "the_cut": [0, 2, 3, 4, 5, 6, 7]}
TINY = {"vocab_held": 128, "vocab_size": 512, "hidden_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "intermediate_size": 192, "moe_intermediate_size": 64,
        "num_experts": 16, "expert_parallel": 4, "experts_held": 4,
        "experts_first": 8, "num_experts_per_tok": 3}


def tiny_config(pattern):
    from benchmarks import run

    resolved = run.resolve_cell(run.load_manifest(), "lfm2_seq8192_1chip")
    layers = PATTERNS[pattern]
    return resolved["module"], {
        **resolved["config"], **TINY, "layers": len(layers),
        "layers_run": layers, "dense_layers_held": int(layers[0] == 0)}


@pytest.mark.parametrize("leg", ["f32", "bf16"])
@pytest.mark.parametrize("pattern", sorted(PATTERNS))
def test_the_model_against_the_plain_reference(hvd, pattern, leg):
    """Logits, loss, EVERY leaf's gradient (all of it) and the bias after one
    step; the kernels of the pass and of attention in the interpreter, one
    row of 256 tokens (a whole row tile in float32)."""
    module, config = tiny_config(pattern)
    plain, exact, trained = module.check_programs(config, flash_interpret=True)
    model = module._model(config, flash_interpret=True)
    key = jax.random.PRNGKey(11)
    params, bias = jax.jit(module._init_state(model, config))(key)
    # a bias that is not all zero, so that it chooses
    bias = jax.tree_util.tree_map(
        lambda b: 0.02 * jnp.cos(jnp.arange(b.size, dtype=b.dtype)), bias)
    tokens = module._tokens_fn(config, 1, 256)(jax.random.fold_in(key, 1))
    rows = jnp.arange(4096, dtype=jnp.int32)    # every row of every matrix
    n_expert_layers = config["layers"] - config["dense_layers_held"]
    no_sets = [jnp.zeros((tokens.size, 16), bool)] * n_expert_layers
    before = module.biases_to_reference(bias)
    with jax.default_matmul_precision("highest"):
        want = plain(params, bias, tokens, rows, no_sets, False)
    program, precision = (exact, "highest") if leg == "f32" else (trained, None)
    with jax.default_matmul_precision(precision):
        got = program(params, bias, tokens, rows)
    assert len(got["chosen"]) == n_expert_layers
    agree = np.ones(tokens.size, bool)
    for g, w in zip(got["chosen"], want["chosen"]):
        agree &= np.all(np.asarray(g) == np.asarray(w), axis=-1)
    kinds = module.reference_config(config)["kinds"]
    assert set(want["grads"]) == set(got["grads"])
    assert {name.split(".")[-1] for name in want["grads"]} >= (
        ({"w_in", "taps", "w_out"} if "conv" in kinds else set())
        | ({"wq", "wkv", "q_norm", "k_norm", "wo"} if "attention" in kinds
           else set()) | {"embed", "final_norm"})
    if leg == "f32":
        assert agree.all()
        assert share(got["logits"], want["logits"]) <= 2e-5
        assert abs(float(got["loss"]) - float(want["loss"])) <= 1e-6 * float(want["loss"])
        for name, leaf in want["grads"].items():
            assert share(got["grads"][name], leaf) <= 2e-5, name
        for g, w, b in zip(got["bias_after"], want["bias_after"], before):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
            assert float(jnp.max(jnp.abs(g - b))) == pytest.approx(0.001)
    else:
        assert agree.mean() >= 0.8
        assert share(np.asarray(got["logits"])[0][agree],
                     np.asarray(want["logits"])[0][agree]) <= 2e-2
        assert abs(float(got["loss"]) - float(want["loss"])) <= 3e-4 * float(want["loss"])
        for name, leaf in want["grads"].items():
            g, w = (np.asarray(x, np.float64) for x in (got["grads"][name], leaf))
            assert np.linalg.norm(g - w) <= 0.3 * np.linalg.norm(w), name
