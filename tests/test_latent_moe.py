"""Experts WITHOUT a gate (``relu(up x)^2``) in a latent, on the CPU at tiny
sizes: ``ops.moe.dropless_experts(w_gate=None)`` with and without ``held``
against a dense loop, forward and backward, through the grouped-product
kernels in the interpreter and through ``lax.ragged_dot``, where every pair is
held and where none is; ``MoEMLP(activation="relu2", latent=)`` against
``tests/references/nemotron3.py``; and the guide's share test: the 8 tensor
shares' Mamba-2, attention and shared-expert results and all expert shares'
routed results, the latent projections and the router counted once, add up
to the uncut reference layer's."""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from nemotron3_layout import layer_to_system  # noqa: E402
from references import nemotron3  # noqa: E402

from horovod_tpu.models import BIAS_COLLECTION, MoEMLP  # noqa: E402
from horovod_tpu.models.mamba import Mamba2Dims  # noqa: E402
from horovod_tpu.models.transformer import Block  # noqa: E402
from horovod_tpu.ops import moe as ops_moe  # noqa: E402


def seeded(shape, seed, scale=0.5):
    return scale * jax.random.normal(jax.random.PRNGKey(seed), shape)


def close(got, want, rel):
    """max|got - want| within ``rel`` of max|want|."""
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert got.shape == want.shape
    assert np.max(np.abs(got - want)) <= rel * max(np.max(np.abs(want)), 1e-30)


# ------------------------------------------------- ops.moe.dropless_experts

def dense_loop(x, weights, experts, w_up, w_down, first=0):
    """Every held expert on every token, masked: the definition."""
    y = jnp.zeros_like(x)
    with jax.default_matmul_precision("highest"):
        for e in range(w_up.shape[0]):
            weight = jnp.sum(jnp.where(experts == first + e, weights, 0.0), axis=1)
            y = y + weight[:, None] * (
                jnp.square(jax.nn.relu(x @ w_up[e])) @ w_down[e])
    return y


def distinct_experts(key, n, top_k, lo, hi):
    """(n, top_k) distinct experts a token, drawn from ``[lo, hi)``."""
    scores = jax.random.uniform(key, (n, hi - lo))
    return lo + jax.lax.top_k(scores, top_k)[1]


# (N, D, H, all experts, top_k): lax.ragged_dot's shapes; the kernels' in
# float32 (rows a multiple of 256, widths of 128); and an order of two whole
# windows whose row buffers hold ONE (top_k 8 over 2 held: _buffer_rows)
SHAPES = {"ragged": (24, 16, 8, 8, 3), "kernels": (64, 128, 128, 8, 4),
          "short_buffers": (512, 128, 128, 16, 8)}


@pytest.mark.parametrize("routing", ["mixed", "every_pair_held", "none_held"])
@pytest.mark.parametrize("held", [None, (2, 4), (6, 2)],
                         ids=["all", "held4", "held2"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_relu2_experts_against_a_dense_loop(shape, held, routing):
    n, d, h, e, top_k = SHAPES[shape]
    first, count = held or (0, e)
    if held is None and routing != "mixed":
        pytest.skip("every expert held: one routing")
    if routing == "every_pair_held" and top_k > count:
        pytest.skip("a token's experts are distinct: top_k <= count")
    if shape == "short_buffers":
        assert (ops_moe._buffer_rows(n * top_k, top_k, 2), n * top_k) == (
            2048, 4096)
    lo, hi = {"mixed": (0, e), "every_pair_held": (first, first + count),
              "none_held": (0, first)}[routing]
    experts = distinct_experts(jax.random.PRNGKey(5), n, top_k, lo, hi) \
        if hi - lo >= top_k else jnp.broadcast_to(
            jnp.arange(top_k) % max(hi - lo, 1) + lo, (n, top_k))
    if routing == "none_held" and hi - lo < top_k:
        pytest.skip("fewer absent experts in front than a token chooses")
    x = seeded((n, d), 0, 1.0)
    weights = jax.nn.softmax(seeded((n, top_k), 1, 1.0))
    w_up, w_down = seeded((count, d, h), 2, 0.3), seeded((count, h, d), 3, 0.3)
    g = seeded((n, d), 4, 1.0)

    def got(x, weights, w_up, w_down):
        with jax.default_matmul_precision("highest"):
            return ops_moe.dropless_experts(
                x, weights, experts, None, w_up, w_down, True,
                None if held is None else (first, count, e))

    def want(x, weights, w_up, w_down):
        return dense_loop(x, weights, experts, w_up, w_down, first)

    args = (x, weights, w_up, w_down)
    close(got(*args), want(*args), 2e-6)
    if routing == "none_held":
        assert not np.any(np.asarray(got(*args)))
    got_grads = jax.grad(lambda *a: jnp.sum(got(*a) * g), argnums=(0, 1, 2, 3))(*args)
    want_grads = jax.grad(lambda *a: jnp.sum(want(*a) * g), argnums=(0, 1, 2, 3))(*args)
    for name, a, b in zip(("x", "weights", "w_up", "w_down"), got_grads,
                          want_grads):
        assert float(jnp.max(jnp.abs(a - b))) <= 2e-5 * max(
            float(jnp.max(jnp.abs(b))), 1e-30), name


@pytest.mark.parametrize("pairs,top_k,count,rows", [
    (98304, 6, 16, 98304),      # kanana2's cell: as before
    (131072, 8, 32, 131072),    # laguna's
    (360448, 22, 8, 131072),    # 22 of 512 with 8 held: 8 rows a token
    (4096, 8, 2, 2048),
    (66, 3, 2, 66),             # no whole windows: one window, all of it
])
def test_row_buffers_hold_what_any_routing_can_fill(pairs, top_k, count, rows):
    assert ops_moe._buffer_rows(pairs, top_k, count) == rows


def test_the_gauges_say_what_a_dispatched_row_holds(hvd):
    x = seeded((16, 24), 0).astype(jnp.bfloat16)
    experts = distinct_experts(jax.random.PRNGKey(1), 16, 2, 0, 4)
    w_up, w_down = (seeded(s, 2).astype(jnp.bfloat16)
                    for s in ((4, 24, 8), (4, 8, 24)))
    ops_moe.dropless_experts(x, jnp.ones((16, 2), jnp.float32) / 2, experts,
                             None, w_up, w_down)
    gauges = hvd.metrics.registry().snapshot()["gauges"]
    assert gauges["horovod_moe_dispatch_row_bytes"] == 24 * 2
    assert gauges["horovod_moe_dispatch_rows"] == 16 * 2


@pytest.mark.parametrize("steps, window, rows, windows", [
    ([[5632] * 6], 2048, 33792.0, 18.0),                 # balanced: three a layer
    ([[6145, 2048, 1, 0, 9000, 4096]], 2048, 21290.0, 4 + 1 + 1 + 0 + 5 + 2),
    ([[100, 300], [300, 500]], 640, 600.0, 2.0),         # a mean over the steps
])
def test_live_rows_gauges_follow_the_steps_handed_in(hvd, steps, window, rows,
                                                     windows):
    """``record_moe_live_rows``: the rows the layers REALLY visited, from a
    training loop's own concrete numbers, and the windows that hold them (a
    layer past a window's edge runs one more); no step, no gauge."""
    registry = hvd.metrics.registry()
    names = ("horovod_moe_live_rows_per_step",
             "horovod_moe_live_windows_per_step")
    hvd.metrics.record_moe_live_rows(np.zeros((0, 6), np.int32), window)
    assert not set(names) & set(registry.snapshot()["gauges"])
    hvd.metrics.record_moe_live_rows(np.asarray(steps, np.int32), window)
    gauges = registry.snapshot()["gauges"]
    assert [gauges[name] for name in names] == [rows, windows]
    for name in names:
        assert registry.remove(name)


def test_messages_state_the_activation():
    x = seeded((4, 8), 0)
    experts = jnp.zeros((4, 1), jnp.int32)
    with pytest.raises(ValueError, match="relu2 experts' weights"):
        ops_moe.dropless_experts(x, jnp.ones((4, 1)), experts, None,
                                 seeded((2, 8, 4), 1), seeded((2, 4, 8), 2),
                                 held=(0, 3, 8))
    with pytest.raises(ValueError, match="swiglu experts' weights"):
        ops_moe.dropless_experts(x, jnp.ones((4, 1)), experts,
                                 seeded((2, 8, 4), 1), seeded((2, 8, 4), 1),
                                 seeded((2, 4, 8), 2), held=(0, 3, 8))
    layer = MoEMLP(dim=8, hidden=4, n_experts=4, top_k=5, activation="relu2")
    with pytest.raises(ValueError, match="top_k 5 of 4 relu2 experts"):
        layer.init(jax.random.PRNGKey(0), x[None])
    with pytest.raises(ValueError, match="'swiglu'.*or 'relu2'"):
        MoEMLP(dim=8, hidden=4, n_experts=4, top_k=2, activation="gelu").init(
            jax.random.PRNGKey(0), x[None])


# ------------------------------------- the layer against the plain reference

D, E, TOP_K, LATENT, W, WS, SCALE = 32, 16, 5, 16, 24, 16, 5.0
CFG = {"top_k": TOP_K, "route_scale": SCALE, "eps": 1e-5}


def expert_layer(seed=1, first=0, count=E, shared=WS):
    """A seeded ``E`` layer in the reference's layout, holding ``count``
    experts from ``first`` and ``shared`` of the shared expert's columns."""
    whole = {"norm": 1.0 + seeded((D,), seed, 0.1),
             "router": seeded((D, E), seed + 1),
             "w_fc1": seeded((D, LATENT), seed + 2, 0.3),
             "w_up": seeded((E, LATENT, W), seed + 3, 0.3),
             "w_down": seeded((E, W, LATENT), seed + 4, 0.3),
             "w_fc2": seeded((LATENT, D), seed + 5, 0.3),
             "s_up": seeded((D, WS), seed + 6, 0.3),
             "s_down": seeded((WS, D), seed + 7, 0.3)}
    return {**whole, "w_up": whole["w_up"][first:first + count],
            "w_down": whole["w_down"][first:first + count],
            "s_up": whole["s_up"][:, :shared],
            "s_down": whole["s_down"][:shared]}


def moe_of(held=None, shared=WS):
    return MoEMLP(dim=D, hidden=W, n_experts=E, top_k=TOP_K, dtype=jnp.float32,
                  router="sigmoid", route_scale=SCALE, shared_hidden=shared,
                  held=held, activation="relu2", latent=LATENT)


@pytest.mark.parametrize("held", [None, (4, 4), (14, 2)])
def test_latent_layer_and_its_gradients_against_the_reference(held):
    first, count = held or (0, E)
    layer = expert_layer(first=first, count=count)
    x, bias = seeded((2, 24, D), 0, 1.0), seeded((E,), 9, 0.2)
    g = seeded(x.shape, 8, 1.0)

    def got(layer, x):
        return moe_of(held).apply(
            {"params": layer_to_system(layer)["moe"],
             BIAS_COLLECTION: {"router_bias": bias}}, x)

    def want(layer, x):
        with jax.default_matmul_precision("highest"):
            y, _ = nemotron3.latent_experts(
                x.reshape(-1, D), layer, bias, {**CFG, "held": (first, count)})
        return y.reshape(x.shape)

    with jax.default_matmul_precision("highest"):
        close(got(layer, x), want(layer, x), 2e-6)
        got_grads = jax.grad(lambda p, x: jnp.sum(got(p, x) * g),
                             argnums=(0, 1))(layer, x)
    want_grads = jax.grad(lambda p, x: jnp.sum(want(p, x) * g),
                          argnums=(0, 1))(layer, x)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got_grads)[0],
                            jax.tree_util.tree_leaves(want_grads)):
        if "norm" in str(path):     # the layer's norm is the block's, not MoEMLP's
            continue
        assert float(jnp.max(jnp.abs(a - b))) <= 2e-5 * float(
            jnp.max(jnp.abs(b))), path


def test_the_dispatched_row_is_the_latents():
    """The experts' weights are latent-wide, and what is dispatched is the
    projected token: a layer whose two latent projections are the identity
    (latent == dim) is the layer without a latent."""
    layer = expert_layer()
    eye = {**layer, "w_fc1": jnp.eye(D), "w_fc2": jnp.eye(D),
           "w_up": seeded((E, D, W), 3, 0.3), "w_down": seeded((E, W, D), 4, 0.3)}
    x, bias = seeded((1, 16, D), 0, 1.0), jnp.zeros((E,))
    params = layer_to_system(eye)["moe"]
    kw = dict(dim=D, hidden=W, n_experts=E, top_k=TOP_K, dtype=jnp.float32,
              router="sigmoid", route_scale=SCALE, shared_hidden=WS,
              activation="relu2")
    with jax.default_matmul_precision("highest"):
        with_latent = MoEMLP(latent=D, **kw).apply(
            {"params": params, BIAS_COLLECTION: {"router_bias": bias}}, x)
        without = MoEMLP(**kw).apply(
            {"params": {k: v for k, v in params.items() if "latent" not in k},
             BIAS_COLLECTION: {"router_bias": bias}}, x)
    close(with_latent, without, 1e-6)
    assert MoEMLP(latent=LATENT, **kw).init(
        jax.random.PRNGKey(0), x)["params"]["w_up"].shape == (E, LATENT, W)


# -------------------------------------------------------------- the share test

RANKS = 8
HEADS, KV, DH = 16, 2, 8                 # attention whole: 16 over 2 of 8
MH, MP, MG, MN, CONV = 16, 4, 8, 8, 4    # Mamba-2 whole: 16 heads, 8 groups
SHARE_CFG = {**CFG, "heads": HEADS, "kv_heads": KV, "head_dim": DH,
             "mamba_heads": MH, "mamba_head_dim": MP, "mamba_groups": MG,
             "mamba_state": MN}


def block_of(kind, **kw):
    return Block(dim=D, heads=kw.pop("heads", HEADS), dtype=jnp.float32,
                 rms_norm_eps=1e-5, rope=False, attention="dense",
                 sublayers="mlp" if kind == "E" else "mixer", **kw)


def columns(w, rank, width, axis=-1):
    return jax.lax.slice_in_dim(w, rank * width, (rank + 1) * width, axis=axis)


def mamba_share(layer, rank):
    """Tensor rank ``rank``'s heads and group of an uncut ``M`` layer."""
    inner, bc = MH * MP, MG * MN
    hs, ps = MH // RANKS, MH // RANKS * MP      # heads, features a rank
    z, x, B, C, dt = jnp.split(
        layer["w_in"], [inner, 2 * inner, 2 * inner + bc, 2 * inner + 2 * bc],
        axis=1)
    cx, cB, cC = jnp.split(layer["conv_w"], [inner, inner + bc], axis=1)
    bx, bB, bC = jnp.split(layer["conv_b"], [inner, inner + bc])
    return {"norm": layer["norm"],
            "w_in": jnp.concatenate(
                [columns(z, rank, ps), columns(x, rank, ps),
                 columns(B, rank, MN), columns(C, rank, MN),
                 columns(dt, rank, hs)], axis=1),
            "conv_w": jnp.concatenate(
                [columns(cx, rank, ps), columns(cB, rank, MN),
                 columns(cC, rank, MN)], axis=1),
            "conv_b": jnp.concatenate(
                [columns(bx, rank, ps), columns(bB, rank, MN),
                 columns(bC, rank, MN)]),
            "dt_bias": columns(layer["dt_bias"], rank, hs),
            "A_log": columns(layer["A_log"], rank, hs),
            "D": columns(layer["D"], rank, hs),
            "gate_norm": columns(layer["gate_norm"], rank, ps),
            "w_out": columns(layer["w_out"], rank, ps, axis=0)}


def attention_share(layer, rank):
    """Tensor rank ``rank``'s query heads over the key/value head they read."""
    qs = HEADS // RANKS * DH
    kv = rank // (RANKS // KV)
    return {"norm": layer["norm"], "wq": columns(layer["wq"], rank, qs),
            "wk": columns(layer["wk"], kv, DH),
            "wv": columns(layer["wv"], kv, DH),
            "wo": columns(layer["wo"], rank, qs, axis=0)}


def test_the_shares_add_up_to_the_uncut_layers():
    """8 tensor ranks (an eighth of the Mamba heads with one group each, an
    eighth of the query heads over one of the two key/value heads, an eighth
    of the shared expert's columns) and 8 expert ranks (2 of 16 experts each):
    the branches they compute add up to the uncut reference layer's branch,
    the norm, the router and the latent projections being on every rank
    alike (their work is counted once: each rank's product with them is
    linear in what it holds)."""
    cfg = {**SHARE_CFG, "hidden": D, "vocab": 8, "experts": E,
           "latent": LATENT, "expert_width": W, "shared_width": WS,
           "conv": CONV, "held": (0, E), "layer_types": "M*E",
           "mtp_layer_types": ""}
    params, _ = nemotron3.init_params(jax.random.PRNGKey(0), cfg, scale=0.3)
    mamba, attention, experts = params["layers"]
    x, bias = seeded((2, 32, D), 1, 1.0), seeded((E,), 2, 0.2)

    def branch(block, layer, **variables):
        # a block is x + f(norm x): its branch is what it adds
        return block.apply({"params": layer_to_system(layer), **variables},
                           x, jnp.arange(x.shape[1])[None]) - x

    with jax.default_matmul_precision("highest"):
        h = nemotron3.rms(x, mamba["norm"], 1e-5)
        want = nemotron3.mamba_mixer(h, mamba, cfg, nemotron3.ssm_recurrence)
        got = sum(branch(block_of("M", mamba=Mamba2Dims(
            heads=MH // RANKS, head_dim=MP, state=MN, groups=1, conv=CONV,
            chunk=8)), mamba_share(mamba, r)) for r in range(RANKS))
        close(got, want, 2e-5)

        h = nemotron3.rms(x, attention["norm"], 1e-5)
        want = nemotron3.attention(h, attention, cfg)
        got = sum(branch(block_of("*", heads=HEADS // RANKS, kv_heads=1,
                                  head_dim=DH), attention_share(attention, r))
                  for r in range(RANKS))
        close(got, want, 2e-5)

        h = nemotron3.rms(x, experts["norm"], 1e-5)
        want, _ = nemotron3.latent_experts(h.reshape(-1, D), experts, bias, cfg)
        per, cols = E // RANKS, WS // RANKS
        got = sum(branch(
            block_of("E", moe_experts=E, moe_top_k=TOP_K, moe_hidden=W,
                     moe_router="sigmoid", moe_route_scale=SCALE,
                     moe_shared_hidden=cols, moe_held=(r * per, per),
                     moe_activation="relu2", moe_latent=LATENT),
            {**experts, "w_up": experts["w_up"][r * per:(r + 1) * per],
             "w_down": experts["w_down"][r * per:(r + 1) * per],
             "s_up": columns(experts["s_up"], r, cols),
             "s_down": columns(experts["s_down"], r, cols, axis=0)},
            **{BIAS_COLLECTION: {"moe": {"router_bias": bias}}})
            for r in range(RANKS))
        close(got, want.reshape(x.shape), 2e-5)
