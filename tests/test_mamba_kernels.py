"""The Mamba-2 mixer's two fused kernel pairs (``ops/mamba_fused.py``) in the
Pallas interpreter on the CPU, against the ``jax.numpy`` definitions they
stand in for: ``silu(ops.ssd.causal_depthwise_conv)`` and
``models.mamba.gated_rms_norm``. Values and every gradient, float32 and bf16,
rows spanning three row tiles (the rows that cross a tile's border, the zeros
before a row's start, the backward's reversed walk), two batch rows, one and
two groups; which shapes take the kernels; the mixer with kernels against the
mixer without."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.common import device_names as names
from horovod_tpu.models import mamba
from horovod_tpu.models.mamba import Mamba2Dims, Mamba2Mixer, gated_rms_norm
from horovod_tpu.ops import mamba_fused as mf
from horovod_tpu.ops.ssd import causal_depthwise_conv

EPS = 1e-5
# largest error over max|want|: sums in another order in float32; in bf16 one
# rounding of silu where jax.numpy rounds the sigmoid and then the product
LIMIT = {jnp.float32: (2e-6, 5e-6), jnp.bfloat16: (1e-2, 2e-2)}


def definition_conv(x, kernel, bias):
    return jax.nn.silu(causal_depthwise_conv(x, kernel, bias))


def definition_norm(groups):
    return lambda y, z, scale: gated_rms_norm(y, z, scale, groups,
                                              EPS).astype(y.dtype)


def share(got, want):
    got, want = (np.asarray(a.astype(jnp.float32)) for a in (got, want))
    return np.abs(got - want).max() / np.abs(want).max()


def draw(key, shape, dtype, scale=1.0):
    return (scale * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def rows_of_three_tiles(dtype):
    return 3 * mf.row_tile(jnp.dtype(dtype).itemsize)


def held(fused, definition, args, cotangent, dtype):
    value_limit, grad_limit = LIMIT[dtype]
    got, got_vjp = jax.vjp(fused, *args)
    want, want_vjp = jax.vjp(definition, *args)
    assert got.dtype == want.dtype == dtype and got.shape == want.shape
    assert share(got, want) <= value_limit
    for g, w in zip(got_vjp(cotangent), want_vjp(cotangent)):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert share(g, w) <= grad_limit


@pytest.mark.parametrize("taps", [2, 4])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_conv_silu_matches_its_definition(dtype, taps):
    t, c = rows_of_three_tiles(dtype), 384       # three chunks of 128 lanes
    ks = jax.random.split(jax.random.PRNGKey(taps), 4)
    args = (draw(ks[0], (2, t, c), dtype), draw(ks[1], (taps, c), jnp.float32, 0.5),
            draw(ks[2], (c,), jnp.float32, 0.1))
    assert mf.conv_takes_kernel(args[0], args[1])
    held(lambda x, k, b: mf.conv_silu(x, k, b, True), definition_conv, args,
         draw(ks[3], (2, t, c), dtype), dtype)


@pytest.mark.parametrize("groups", [1, 2])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_gate_norm_matches_its_definition(dtype, groups):
    t, inner = rows_of_three_tiles(dtype), 256
    ks = jax.random.split(jax.random.PRNGKey(groups), 4)
    args = (draw(ks[0], (2, t, inner), dtype), draw(ks[1], (2, t, inner), dtype),
            1.0 + draw(ks[2], (inner,), jnp.float32, 0.1))
    assert mf.norm_takes_kernel(args[0], args[1], groups)
    held(lambda y, z, s: mf.gate_norm(y, z, s, groups, EPS, True),
         definition_norm(groups), args, draw(ks[3], (2, t, inner), dtype), dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_the_chains_read_their_columns_out_of_the_wide_projection(dtype):
    """As the mixer calls them: xBC and z are column runs of ONE wide array
    (z | xBC | dt, the last run not whole lanes), the convolution's result
    comes back as three runs, and the wide array's gradient is what the
    slices and the definitions give."""
    t, inner, bc, heads = rows_of_three_tiles(dtype), 256, 128, 8
    c = inner + 2 * bc
    ks = jax.random.split(jax.random.PRNGKey(5), 8)
    wide = draw(ks[0], (2, t, inner + c + heads), dtype)
    y = draw(ks[1], (2, t, inner), dtype)
    kernel, bias = draw(ks[2], (4, c), jnp.float32, 0.5), draw(ks[3], (c,), jnp.float32, 0.1)
    scale = 1.0 + draw(ks[4], (inner,), jnp.float32, 0.1)
    cots = [draw(k, (2, t, w), dtype) for k, w in zip(ks[5:], (c, inner))]

    def chains(fused):
        def run(wide, y, kernel, bias, scale):
            z, x, _ = jnp.split(wide, [inner, inner + c], axis=-1)
            if fused:
                assert mf.conv_takes_kernel(x, kernel, (inner, bc, bc))
                conv = jnp.concatenate(mf.conv_silu(
                    x, kernel, bias, True, splits=(inner, bc, bc), wide=wide,
                    start=inner), axis=-1)
                normed = mf.gate_norm(y, z, scale, 1, EPS, True, wide=wide)
            else:
                conv = definition_conv(x, kernel, bias)
                normed = definition_norm(1)(y, z, scale)
            return conv, normed
        return run

    args = (wide, y, kernel, bias, scale)
    got, got_vjp = jax.vjp(chains(True), *args)
    want, want_vjp = jax.vjp(chains(False), *args)
    value_limit, grad_limit = LIMIT[dtype]
    for g, w in zip(got, want):
        assert g.dtype == dtype and share(g, w) <= value_limit
    for g, w in zip(got_vjp(tuple(cots)), want_vjp(tuple(cots))):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert share(g, w) <= grad_limit
    with pytest.raises(ValueError, match="not at a whole lane tile"):
        mf.conv_silu(wide[..., 64:64 + c], kernel, bias, True, wide=wide,
                     start=64)


@pytest.mark.parametrize("where", ["row_start", "tile_border", "piece_border"])
def test_one_input_row_reaches_the_rows_after_it_and_no_other(where):
    """A single non-zero input row: the convolution puts it into that row and
    the K - 1 after it, across a piece's and a tile's border, and the rows
    before a batch row's start are zeros (the second batch row's first rows do
    not see the first's last)."""
    tile, c, taps = mf.row_tile(4), 128, 4
    t = 2 * tile
    at = {"row_start": 0, "tile_border": tile - 1,
          "piece_border": mf._PIECE_ROWS - 2}[where]
    x = jnp.zeros((2, t, c), jnp.float32).at[0, at].set(1.0).at[0, t - 1].set(1.0)
    kernel = jnp.arange(1.0, taps + 1)[:, None] * jnp.ones((taps, c))
    conv = np.asarray(mf.conv_silu(x, kernel, jnp.zeros((c,)), True))
    want = np.asarray(definition_conv(x, kernel, jnp.zeros((c,))))
    np.testing.assert_allclose(conv, want, rtol=1e-6, atol=1e-7)
    lit = sorted(set(np.nonzero(conv[0, :, 0])[0]))
    assert lit == list(range(at, at + taps)) + [t - 1]
    assert not conv[1].any()        # nothing leaks into the next batch row


def test_conv_backward_carries_over_a_tile_border():
    """dx of a row reads g of the K - 1 rows AFTER it: a cotangent on a
    tile's first row alone reaches the last rows of the tile before it, and
    the parameter gradients sum over both tiles."""
    tile, c, taps = mf.row_tile(4), 128, 4
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    args = (draw(ks[0], (1, 2 * tile, c), jnp.float32),
            draw(ks[1], (taps, c), jnp.float32, 0.5), jnp.zeros((c,)))
    dy = jnp.zeros((1, 2 * tile, c)).at[0, tile].set(1.0)
    got = jax.vjp(lambda x, k, b: mf.conv_silu(x, k, b, True), *args)[1](dy)
    want = jax.vjp(definition_conv, *args)[1](dy)
    rows = np.nonzero(np.asarray(got[0])[0, :, 0])[0]
    assert list(rows) == list(range(tile - taps + 1, tile + 1))
    for g, w in zip(got, want):
        assert share(g, w) <= 5e-6


@pytest.mark.parametrize("shape,dtype,taps,takes", [
    ((1, 512, 4352), jnp.bfloat16, 4, True),     # the cell's row, a tile of it
    ((1, 1024, 4352), jnp.float32, 4, True),     # the check's float32 leg
    ((2, 512, 160), jnp.bfloat16, 4, False),     # channels not whole lanes
    ((2, 96, 256), jnp.float32, 4, False),       # rows under a tile
    ((2, 128, 128), jnp.float32, 4, False),      # the tiny benchmark tests
    ((2, 512, 256), jnp.float16, 4, False),      # neither bf16 nor f32
    ((2, 512, 256), jnp.bfloat16, 10, False),    # more taps than a border holds
    ((1, 512, 8192), jnp.bfloat16, 4, False),    # a block of rows over 5 MiB
], ids=["cell", "check_f32", "channels_160", "rows_96", "tiny", "f16", "taps_10",
        "too_wide"])
def test_which_shapes_take_the_kernels(shape, dtype, taps, takes):
    x = jax.ShapeDtypeStruct(shape, dtype)
    kernel = jax.ShapeDtypeStruct((taps, shape[2]), jnp.float32)
    assert mf.conv_takes_kernel(x, kernel) is takes
    # u | B | C: every run of the result has to be whole lanes too
    assert mf.conv_takes_kernel(x, kernel, (shape[2] - 128, 64, 64)) is False
    assert mf.conv_takes_kernel(x, kernel, (shape[2] - 128, 128)) is takes
    if taps == 4:
        assert mf.norm_takes_kernel(x, x, 1) is takes
        # groups of 80 or 2176 features: whole lanes only for the second
        assert mf.norm_takes_kernel(x, x, 2) is (takes and shape[2] == 4352)
        assert mf.norm_takes_kernel(x, jax.ShapeDtypeStruct(
            shape, jnp.float16), 1) is False


def mixer_case(dtype, seq, head_dim, state):
    dims = Mamba2Dims(heads=4, head_dim=head_dim, state=state, groups=2,
                      conv=4, chunk=64)
    mixer = Mamba2Mixer(dim=32, dims=dims, dtype=dtype, interpret=True)
    h = draw(jax.random.PRNGKey(1), (2, seq, 32), dtype)
    params = mixer.init(jax.random.PRNGKey(2), h)["params"]
    # parameters off their initial values, so that every gradient is live
    keys = iter(jax.random.split(jax.random.PRNGKey(3), 16))
    params = jax.tree_util.tree_map(
        lambda p: p + 0.1 * jax.random.normal(next(keys), p.shape), params)

    def run(params):
        return mixer.apply({"params": params}, h)

    return mixer, params, run


def fused_passes(hvd):
    return hvd.metrics.registry().snapshot()["gauges"][
        "horovod_mamba_fused_passes"]


def test_the_mixer_with_kernels_is_the_mixer_without(hvd, monkeypatch):
    """inner 128 in two groups of 64 would not tile; heads of 64 do: inner
    256, channels 256 + 2 x 2 x 64 = 512, 256 rows of float32 = one tile."""
    _, params, run = mixer_case(jnp.float32, 256, 64, 64)
    loss = lambda p: jnp.sum(jnp.sin(run(p)))      # noqa: E731
    text = jax.jit(run).lower(params).as_text(debug_info=True)
    assert names.MAMBA_CONV_FWD in text and names.MAMBA_GATE_NORM_FWD in text
    got, got_grads = run(params), jax.grad(loss)(params)
    assert fused_passes(hvd) == 2

    monkeypatch.setattr(mf, "conv_takes_kernel", lambda x, k, splits: False)
    monkeypatch.setattr(mf, "norm_takes_kernel", lambda y, z, g: False)
    _, params, run = mixer_case(jnp.float32, 256, 64, 64)  # traced afresh
    bare = jax.jit(run).lower(params).as_text(debug_info=True)
    assert names.MAMBA_CONV_FWD not in bare
    assert names.MAMBA_GATE_NORM_FWD not in bare and names.MAMBA_CONV in bare
    want, want_grads = run(params), jax.grad(loss)(params)
    assert fused_passes(hvd) == 0
    assert share(got, want) <= 5e-6
    for path, g in jax.tree_util.tree_flatten_with_path(got_grads)[0]:
        w = want_grads
        for p in path:
            w = w[p.key]
        assert share(g, w) <= 2e-5, path


def test_one_chain_alone_counts_one(hvd):
    """Heads of 32: inner 128 in two groups of 64 keeps the norm in
    ``jax.numpy``; the 128 + 2 x 2 x 64 = 384 channels take the convolution's
    kernel."""
    _, params, run = mixer_case(jnp.float32, 256, 32, 64)
    text = jax.jit(run).lower(params).as_text(debug_info=True)
    assert names.MAMBA_CONV_FWD in text
    assert names.MAMBA_GATE_NORM_FWD not in text
    assert fused_passes(hvd) == 1


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_shapes_that_do_not_tile_keep_todays_values(hvd, monkeypatch, dtype):
    """Channels 32 + 2 x 2 x 16 = 96 and 96 rows: no kernel is called (each
    would raise), the gauge reads 0, and the values are those of the
    definitions composed by hand, bit for bit."""
    def refuse(*a, **k):
        raise AssertionError("a kernel was called for a shape it cannot tile")

    monkeypatch.setattr(mf, "conv_silu", refuse)
    monkeypatch.setattr(mf, "gate_norm", refuse)
    mixer, params, run = mixer_case(dtype, 96, 8, 16)
    got = run(params)
    assert fused_passes(hvd) == 0

    calls = []
    real_conv, real_norm = mamba.causal_depthwise_conv, mamba.gated_rms_norm
    monkeypatch.setattr(mamba, "causal_depthwise_conv",
                        lambda *a: calls.append("conv") or real_conv(*a))
    monkeypatch.setattr(mamba, "gated_rms_norm",
                        lambda *a: calls.append("norm") or real_norm(*a))
    again = run(params)
    assert calls == ["conv", "norm"]     # the module-level definitions, by name
    assert got.dtype == dtype
    np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)),
                                  np.asarray(again.astype(jnp.float32)))
