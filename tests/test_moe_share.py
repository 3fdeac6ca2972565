"""The sigmoid router, the shared expert and the share of the experts a rank
holds, on the CPU at tiny sizes against ``tests/references/kanana2.py``: the
guide's share test (all shares' routed parts plus the shared expert counted
once are the uncut layer), no held pair dropped under the worst routing, the
bias rule, and that the optimizer never sees the bias."""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from references import kanana2  # noqa: E402

from horovod_tpu.models import BIAS_COLLECTION, MoEMLP, expert_counts  # noqa: E402
from horovod_tpu.ops import moe as ops_moe  # noqa: E402

E, TOP_K, D, W, WS, SCALE = 16, 3, 32, 16, 24, 2.448
CFG = {"top_k": TOP_K, "route_scale": SCALE}


def layer_of(held=None, **kw):
    return MoEMLP(dim=D, hidden=W, n_experts=E, top_k=TOP_K, dtype=jnp.float32,
                  router="sigmoid", route_scale=SCALE, shared_hidden=WS,
                  held=held, **kw)


def close(got, want, rel):
    """max|got - want| within ``rel`` of max|want|."""
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert got.shape == want.shape
    assert np.max(np.abs(got - want)) <= rel * max(np.max(np.abs(want)), 1e-30)


def seeded(shape, seed, scale=0.5):
    return scale * jax.random.normal(jax.random.PRNGKey(seed), shape)


@pytest.fixture(scope="module")
def whole():
    """(x, params of the layer holding all E experts, bias)."""
    x = seeded((2, 24, D), 0, 1.0)
    params = layer_of().init(jax.random.PRNGKey(1), x)["params"]
    params = jax.tree_util.tree_map(lambda p: seeded(p.shape, p.size % 97), params)
    return x, params, seeded((E,), 2, 0.2)


def reference_layer(params, first=0, count=E):
    return {"router": params["router"],
            "w_gate": params["w_gate"][first:first + count],
            "w_up": params["w_up"][first:first + count],
            "w_down": params["w_down"][first:first + count],
            "s_gate": params["shared_gate"]["kernel"],
            "s_up": params["shared_up"]["kernel"],
            "s_down": params["shared_down"]["kernel"]}


def share_params(params, first, count):
    return {**params, **{k: params[k][first:first + count]
                         for k in ("w_gate", "w_up", "w_down")}}


def test_router_against_the_reference(whole):
    x, params, bias = whole
    tokens = x.reshape(-1, D)
    logits = jnp.dot(tokens, params["router"], precision="highest")
    scores, weights, experts = ops_moe.sigmoid_route(logits, bias, TOP_K, SCALE)
    with jax.default_matmul_precision("highest"):
        want_w, chosen, want_s = kanana2.route(tokens, params["router"], bias, CFG)
    np.testing.assert_allclose(scores, want_s, atol=1e-6)
    got_chosen = jnp.any(experts[:, :, None] == jnp.arange(E), axis=1)
    assert bool(jnp.all(got_chosen == chosen))
    dense = jnp.zeros((tokens.shape[0], E)).at[
        jnp.arange(tokens.shape[0])[:, None], experts].set(weights)
    np.testing.assert_allclose(dense, want_w, atol=1e-6)
    np.testing.assert_allclose(weights.sum(-1), SCALE, rtol=1e-6)   # renormalised
    # the bias chooses and never weighs: without it, other experts somewhere
    _, _, plain_experts = ops_moe.sigmoid_route(logits, jnp.zeros(E), TOP_K, SCALE)
    assert bool(jnp.any(jnp.sort(plain_experts) != jnp.sort(experts)))


@pytest.mark.parametrize("held", [None, (4, 4), (0, 8), (12, 4)])
def test_layer_and_its_gradients_against_the_reference(whole, held):
    x, params, bias = whole
    first, count = held or (0, E)
    mine = share_params(params, first, count)
    layer = layer_of(held)
    cfg = {**CFG, "held": (first, count)}

    def got(p, x):
        return layer.apply({"params": p, BIAS_COLLECTION:
                            {"router_bias": bias}}, x)

    def want(p, x):
        with jax.default_matmul_precision("highest"):
            y, _ = kanana2.experts(reference_layer(p, 0, count), bias,
                                   x.reshape(-1, D), cfg)
        return y.reshape(x.shape)

    close(got(mine, x), want(mine, x), 2e-6)
    g = seeded(x.shape, 9, 1.0)
    got_grads = jax.grad(lambda p, x: jnp.sum(got(p, x) * g), argnums=(0, 1))(mine, x)
    want_grads = jax.grad(lambda p, x: jnp.sum(want(p, x) * g), argnums=(0, 1))(mine, x)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got_grads)[0],
                            jax.tree_util.tree_leaves(want_grads)):
        assert float(jnp.max(jnp.abs(a - b))) <= 2e-5 * float(
            jnp.max(jnp.abs(b))), path


def test_the_shares_add_up_to_the_uncut_layer(whole):
    """Four ranks of four experts each: their routed parts, plus the shared
    expert counted ONCE, are what the uncut reference gives for the whole
    layer; so are the gradients (a shared leaf's summed once, an expert's
    leaf from the rank that holds it)."""
    x, params, bias = whole
    g = seeded(x.shape, 9, 1.0)
    tokens = x.reshape(-1, D)

    def shared_part(p, x):
        s = reference_layer(p)
        with jax.default_matmul_precision("highest"):
            return kanana2.swiglu(x.reshape(-1, D), s["s_gate"], s["s_up"],
                                  s["s_down"]).reshape(x.shape)

    def share(p, x, first):
        out = layer_of((first, 4)).apply(
            {"params": share_params(p, first, 4),
             BIAS_COLLECTION: {"router_bias": bias}}, x)
        return out - shared_part(p, x)          # the routed part alone

    def summed(p, x):
        return sum(share(p, x, first) for first in range(0, E, 4)) \
            + shared_part(p, x)

    def uncut(p, x):
        with jax.default_matmul_precision("highest"):
            y, _ = kanana2.experts(reference_layer(p), bias, x.reshape(-1, D),
                                   {**CFG, "held": (0, E)})
        return y.reshape(x.shape)

    close(summed(params, x), uncut(params, x), 3e-6)
    got = jax.grad(lambda p, x: jnp.sum(summed(p, x) * g), argnums=(0, 1))(params, x)
    want = jax.grad(lambda p, x: jnp.sum(uncut(p, x) * g), argnums=(0, 1))(params, x)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        assert float(jnp.max(jnp.abs(a - b))) <= 3e-5 * float(jnp.max(jnp.abs(b)))
    assert tokens.shape[0] == 48


@pytest.mark.parametrize("interpret_kernels", [False, True],
                         ids=["ragged_dot", "kernels"])
def test_no_held_pair_is_dropped_when_every_token_picks_held_experts(
        interpret_kernels):
    """The worst routing for a share: a bias that sends EVERY token to held
    experts fills the whole N x top_k row buffer, and every pair is computed.
    With the kernels (bf16, aligned, interpreter) the plan's group sizes sum
    to the buffer; with a bias the other way they sum to 0 and the layer adds
    the shared expert alone."""
    if interpret_kernels:
        d, w, n, dtype, tol = 128, 128, 512, jnp.bfloat16, 1e-2
    else:
        d, w, n, dtype, tol = D, W, 48, jnp.float32, 2e-5
    layer = MoEMLP(dim=d, hidden=w, n_experts=E, top_k=TOP_K, dtype=dtype,
                   router="sigmoid", route_scale=SCALE, shared_hidden=0,
                   held=(4, 4), interpret=interpret_kernels)
    x = seeded((1, n, d), 3, 1.0)
    params = layer.init(jax.random.PRNGKey(1), x)["params"]
    params = jax.tree_util.tree_map(lambda p: seeded(p.shape, p.size % 89, 0.2),
                                    params)
    onto = jnp.where((jnp.arange(E) >= 4) & (jnp.arange(E) < 8), 10.0, 0.0)
    for bias, all_held in ((onto, True), (-onto, False)):
        out, state = layer.apply({"params": params, BIAS_COLLECTION:
                                  {"router_bias": bias}}, x,
                                 mutable=["intermediates"])
        counts = state["intermediates"]["moe_expert_counts"][0]
        assert int(counts.sum()) == n * TOP_K
        assert int(counts[4:8].sum()) == (n * TOP_K if all_held else 0)
        ref = {"router": params["router"], "w_gate": params["w_gate"],
               "w_up": params["w_up"], "w_down": params["w_down"]}
        tokens = x.reshape(-1, d).astype(dtype).astype(jnp.float32)
        with jax.default_matmul_precision("highest"):
            weights, _, _ = kanana2.route(tokens, ref["router"], bias, CFG)
            want = sum(kanana2.expert_term(
                tokens, weights[:, 4 + i],
                *(ref[k][i].astype(dtype).astype(jnp.float32)
                  for k in ("w_gate", "w_up", "w_down"))) for i in range(4))
        assert bool(jnp.all(jnp.isfinite(out.astype(jnp.float32))))
        # bf16 rounds gate, up and their product: by the Euclidean norm
        error = np.asarray(out.reshape(-1, d).astype(jnp.float32) - want)
        assert np.linalg.norm(error) <= tol * max(np.linalg.norm(want), 1e-30)
        if not all_held:
            assert float(jnp.max(jnp.abs(out.astype(jnp.float32)))) == 0.0
        # the gradient reaches x through held pairs only, and is finite
        dx = jax.grad(lambda x: jnp.sum(layer.apply(
            {"params": params, BIAS_COLLECTION: {"router_bias": bias}},
            x).astype(jnp.float32)))(x)
        assert bool(jnp.all(jnp.isfinite(dx.astype(jnp.float32))))


def ops_moe_gauges():
    from horovod_tpu.metrics import registry
    return registry().snapshot()["gauges"]


WINDOW = 32     # the tests' window: a tiny order is several of them


def routed(n, live, seed, first=4, count=4):
    """``experts`` (n, TOP_K) of E, a token's distinct, with EXACTLY ``live``
    pairs on the held experts ``[first, first + count)``, and weights."""
    rng = np.random.default_rng(seed)
    on_held = np.zeros(n * TOP_K, bool)
    on_held[rng.permutation(n * TOP_K)[:live]] = True
    held = np.arange(first, first + count)
    absent = np.setdiff1d(np.arange(E), held)
    experts = np.empty((n, TOP_K), np.int32)
    for token, flags in enumerate(on_held.reshape(n, TOP_K)):
        picks = iter(rng.permutation(held)), iter(rng.permutation(absent))
        experts[token] = [next(picks[0 if flag else 1]) for flag in flags]
    weights = rng.uniform(0.1, 0.9, (n, TOP_K)).astype(np.float32)
    return jnp.asarray(experts), jnp.asarray(weights)


def held_reference(x, weights, experts, w_gate, w_up, w_down, first):
    """The held experts' terms, each expert on every row (the file's
    reference): ``weights`` scattered to (N, E), 0 where not chosen."""
    dense = jnp.zeros((x.shape[0], E), weights.dtype).at[
        jnp.arange(x.shape[0])[:, None], experts].set(weights)
    x, w_gate, w_up, w_down = (a.astype(jnp.float32)
                               for a in (x, w_gate, w_up, w_down))
    with jax.default_matmul_precision("highest"):
        return sum(kanana2.expert_term(x, dense[:, first + i], w_gate[i],
                                       w_up[i], w_down[i])
                   for i in range(w_gate.shape[0]))


# (tokens, live pairs): a window is WINDOW sorted rows, the order 192
ROUTINGS = {"every_pair_held": (64, 192), "no_pair_held": (64, 0),
            "on_a_window_border": (64, 64), "one_row_past_it": (64, 65),
            "one_row_short_of_it": (64, 63), "a_share_of_0.19": (64, 36)}


@pytest.mark.parametrize("routing", list(ROUTINGS))
def test_the_held_path_follows_the_live_rows(monkeypatch, routing):
    """Several windows of the sorted order, the live rows ending anywhere in
    them: output, ``dx``, ``dweights`` and the three weight gradients are the
    reference's. Every pair held: every window runs, and the layer is
    ``held=None`` on the same weights; none held: no window runs."""
    monkeypatch.setattr(ops_moe, "_WINDOW_ROWS", WINDOW)
    n, live = ROUTINGS[routing]
    first, count = (0, E) if live == n * TOP_K else (4, 4)
    experts, weights = routed(n, live, 11, first, count)
    assert int(jnp.sum((experts >= first) & (experts < first + count))) == live
    x = seeded((n, D), 5, 1.0)
    w_gate, w_up, w_down = (seeded(shape, seed, 0.3) for shape, seed in (
        ((count, D, W), 6), ((count, D, W), 7), ((count, W, D), 8)))
    g = seeded((n, D), 9, 1.0)

    def system(held):
        return lambda x, weights, *w: ops_moe.dropless_experts(
            x, weights, experts, *w, held=held)

    def value_and_grads(f):
        return jax.value_and_grad(
            lambda *a: jnp.sum(f(*a) * g), argnums=(0, 1, 2, 3, 4))(
                x, weights, w_gate, w_up, w_down)

    got = value_and_grads(system((first, count, E)))
    want = value_and_grads(lambda x, weights, *w: held_reference(
        x, weights, experts, *w, first))
    close(system((first, count, E))(x, weights, w_gate, w_up, w_down),
          held_reference(x, weights, experts, w_gate, w_up, w_down, first), 2e-6)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert bool(jnp.all(jnp.isfinite(a)))
        close(a, b, 2e-5)
    if live == 0:
        assert all(float(jnp.max(jnp.abs(a))) == 0.0
                   for a in jax.tree_util.tree_leaves(got))
    if live == n * TOP_K:
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(value_and_grads(system(None)))):
            close(a, b, 2e-6)
    gauges = ops_moe_gauges()
    assert gauges["horovod_moe_dispatch_rows"] == (
        192 if count == E else 64)      # the balanced share 48, in windows


@pytest.mark.parametrize("live", [0, 512, 513, 700, 1536])
def test_the_held_path_through_the_kernels(monkeypatch, live):
    """The same with the grouped-product kernels in the interpreter (bf16,
    three windows of one row tile each): rows behind the live ones are never
    written by a kernel, and nothing of them reaches a result."""
    monkeypatch.setattr(ops_moe, "_WINDOW_ROWS", 512)
    n, d, w = 512, 128, 128
    experts, weights = routed(n, live, live)
    x = seeded((n, d), 5, 1.0).astype(jnp.bfloat16)
    w_gate, w_up, w_down = (seeded(shape, seed, 0.2).astype(jnp.bfloat16)
                            for shape, seed in (((4, d, w), 6), ((4, d, w), 7),
                                                ((4, w, d), 8)))
    g = seeded((n, d), 9, 1.0)

    def value_and_grads(f):
        return jax.value_and_grad(
            lambda *a: jnp.sum(f(*a).astype(jnp.float32) * g),
            argnums=(0, 1, 2, 3, 4))(x, weights, w_gate, w_up, w_down)

    got = value_and_grads(lambda x, weights, *ws: ops_moe.dropless_experts(
        x, weights, experts, *ws, interpret=True, held=(4, 4, E)))
    want = value_and_grads(lambda x, weights, *ws: held_reference(
        x, weights, experts, *ws, 4))
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        a, b = (np.asarray(v, np.float32) for v in (a, b))
        assert np.all(np.isfinite(a))
        # bf16 rounds gate, up, their product and dout: by the Euclidean norm
        assert np.linalg.norm(a - b) <= 2e-2 * max(np.linalg.norm(b), 1e-30)
        if live == 0:
            assert not a.any()
    assert ops_moe_gauges()["horovod_moe_grouped_border_overhead"] > 1.0


# ------------------------------ the float32 master weights under bf16 rows

MASTER_PATHS = {"held_kernels": ((4, 4, E), 128), "all_held_kernels": (None, 128),
                "held_ragged_dot": ((4, 4, E), 48)}
MASTER_LEAVES = ("y", "dx", "dweights", "dw_gate", "dw_up", "dw_down")


def _master_operands(held, width):
    """512 tokens of ``width`` (128: the kernels take the products; 48: they
    refuse them) routed over ``held``'s experts, float32 expert weights."""
    n, count = 512, E if held is None else held[1]
    experts, weights = routed(n, 700, 63) if held else routed(
        n, n * TOP_K, 63, 0, E)
    x = seeded((n, width), 5, 1.0).astype(jnp.bfloat16)
    ws = tuple(seeded(shape, seed, 0.2) for shape, seed in (
        ((count, width, width), 6), ((count, width, width), 7),
        ((count, width, width), 8)))
    return x, weights, experts, ws, seeded((n, width), 9, 1.0)


@pytest.fixture(scope="module")
def master_and_cast():
    """path -> ((output, gradients) on the float32 weights as they are, the
    same on the weights cast to bf16 BEFORE the call: the parent's program),
    the gradients in both w.r.t. the float32 weights."""
    cache = {}

    def get(path):
        if path not in cache:
            held, width = MASTER_PATHS[path]
            x, weights, experts, ws, g = _master_operands(held, width)

            def outputs(cast):
                def loss(x, weights, *ws):
                    y = ops_moe.dropless_experts(
                        x, weights, experts, *(cast(w) for w in ws),
                        interpret=True, held=held)
                    return jnp.sum(y.astype(jnp.float32) * g), y

                (_, y), grads = jax.value_and_grad(
                    loss, argnums=(0, 1, 2, 3, 4), has_aux=True)(
                        x, weights, *ws)
                return dict(zip(MASTER_LEAVES, (y, *grads)))

            cache[path] = (outputs(lambda w: w),
                           outputs(lambda w: w.astype(jnp.bfloat16)))
        return cache[path]

    return get


@pytest.mark.parametrize("leaf", MASTER_LEAVES)
@pytest.mark.parametrize("path", list(MASTER_PATHS))
def test_master_weights_give_the_cast_copys_bits(master_and_cast, path, leaf):
    """``dropless_experts`` on bf16 rows and the float32 parameters themselves
    (rounded in the kernels' VMEM, or cast inside before ``lax.ragged_dot``)
    is, bit for bit, the call on a bf16 copy cast beforehand: the output and
    every gradient, the weights' in float32."""
    master, cast = (np.asarray(side[leaf]) for side in master_and_cast(path))
    want = jnp.bfloat16 if leaf in ("y", "dx") else np.float32
    assert master.dtype == cast.dtype == want
    assert master.any() and np.isfinite(master.astype(np.float32)).all()
    np.testing.assert_array_equal(master.astype(np.float32),
                                  cast.astype(np.float32))


@pytest.mark.parametrize("width,cast,itemsize", [
    (128, False, 4),    # the kernels on the parameters, rounded in VMEM
    (128, True, 2),     # the kernels on a copy a caller cast
    (48, False, 0),     # lax.ragged_dot
], ids=["master", "cast_copy", "ragged_dot"])
def test_the_gauge_says_which_weights_the_kernels_read(width, cast, itemsize):
    x, weights, experts, ws, _ = _master_operands((4, 4, E), width)
    if cast:
        ws = tuple(w.astype(jnp.bfloat16) for w in ws)
    jax.eval_shape(lambda *a: ops_moe.dropless_experts(
        *a, interpret=True, held=(4, 4, E)), x, weights, experts, *ws)
    gauges = ops_moe_gauges()
    assert gauges["horovod_moe_grouped_weight_itemsize"] == itemsize
    assert (gauges["horovod_moe_grouped_border_overhead"] > 0) is (width == 128)
    # the kernels ask for all but a column tile's first block a group ahead
    # (the rank's 4 held experts are a call's groups)
    assert gauges["horovod_moe_grouped_weight_lookahead_share"] == (
        3 / 4 if width == 128 else 0)


@pytest.mark.parametrize("width", [128, 48], ids=["kernels", "ragged_dot"])
def test_the_held_paths_row_buffers_are_the_rows_dtype(width):
    """The dispatch, hidden and output buffers and what the backward returns
    for the rows are bf16 under float32 weights (never the weights' dtype:
    twice the bytes of every pass); the residuals hold the float32 parameters
    themselves, and their gradients are float32."""
    x, weights, experts, ws, g = _master_operands((4, 4, E), width)
    flat = jnp.where((experts >= 4) & (experts < 8), experts - 4, 4).reshape(-1)
    y, res = jax.eval_shape(
        lambda *a: ops_moe._held_forward(*a, True), x, weights, flat, *ws)
    _, _, rows, before, h, out, *kept = res
    assert all(a.dtype == jnp.bfloat16 for a in (y, rows, *before, h, out))
    assert rows.shape == (512 * TOP_K, width)
    assert all(w.dtype == jnp.float32 for w in kept) and len(kept) == 3
    dx, _, _, *dws = jax.eval_shape(
        lambda res, g: ops_moe._held_backward(True, res, g), res,
        g.astype(jnp.bfloat16))
    assert dx.dtype == jnp.bfloat16
    assert all(dw.dtype == jnp.float32 and dw.shape == w.shape
               for dw, w in zip(dws, ws)) and len(dws) == 3


def test_every_expert_held_by_all_lowers_to_what_it_did():
    """``held=None`` (OLMoE's layer) is the function it was before the held
    path followed the live rows: its lowered text, forward and backward, at a
    tiny float32 size. A digest that moves with an edit that was meant to
    change that path is pinned again; one that moves with the held path's
    edits is a fault."""
    import hashlib

    shape = jax.ShapeDtypeStruct

    def loss(x, weights, experts, *w):
        return jnp.sum(ops_moe.dropless_experts(x, weights, experts, *w))

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 3, 4, 5))).lower(
        shape((48, 32), jnp.float32), shape((48, 3), jnp.float32),
        shape((48, 3), jnp.int32), shape((8, 32, 16), jnp.float32),
        shape((8, 32, 16), jnp.float32), shape((8, 16, 32), jnp.float32)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "5b8f0009b29a8d1f8ff15dd7ce8672dd840b965be761dfdfa67d6a79265d16f3")


def test_the_layer_sows_the_rows_it_visits(whole):
    """``moe_live_rows`` beside ``moe_expert_counts``: the pairs on the held
    experts, which is how many sorted rows the layer's passes visit."""
    x, params, bias = whole
    _, state = layer_of((4, 4)).apply(
        {"params": share_params(params, 4, 4),
         BIAS_COLLECTION: {"router_bias": bias}}, x, mutable=["intermediates"])
    counts = state["intermediates"]["moe_expert_counts"][0]
    assert int(state["intermediates"]["moe_live_rows"][0]) == int(
        counts[4:8].sum()) > 0
    assert ops_moe_gauges()["horovod_moe_dispatch_rows"] == 144     # one window


def test_bias_rule_against_the_reference():
    counts = jnp.array([0, 5, 9, 9, 9, 20, 11, 9], jnp.int32)   # mean 9
    bias = seeded((8,), 4, 0.01)
    got = ops_moe.router_bias_update(bias, counts, 0.001)
    np.testing.assert_array_equal(got, kanana2.bias_update(bias, counts, 0.001))
    np.testing.assert_allclose(
        got - bias, 0.001 * np.array([1, 1, 0, 0, 0, -1, -1, 0]), atol=1e-9)


def test_the_bias_is_state_that_adamw_never_touches(whole):
    """The bias is a collection of its own: ``params`` has no such leaf, so
    the optimizer's state has no moment for it and weight decay cannot reach
    it; it receives no gradient; the rule alone moves it, by the sown counts."""
    x, _, _ = whole
    layer = layer_of((0, 4))
    variables = layer.init(jax.random.PRNGKey(1), x)
    assert set(variables) == {"params", BIAS_COLLECTION}
    assert "router_bias" not in jax.tree_util.tree_flatten_with_path(
        variables["params"])[1].__repr__()
    bias = {"router_bias": seeded((E,), 2, 0.2)}
    opt = optax.adamw(1e-2, weight_decay=0.1)
    opt_state = opt.init(variables["params"])

    def loss(params, bias):
        out, state = layer.apply({"params": params, BIAS_COLLECTION: bias}, x,
                                 mutable=["intermediates"])
        return jnp.sum(out ** 2), state["intermediates"]["moe_expert_counts"][0]

    (_, counts), (grads, bias_grad) = jax.value_and_grad(
        loss, argnums=(0, 1), has_aux=True)(variables["params"], bias)
    assert float(jnp.max(jnp.abs(bias_grad["router_bias"]))) == 0.0
    updates, _ = opt.update(grads, opt_state, variables["params"])
    new_params = optax.apply_updates(variables["params"], updates)
    assert jax.tree_util.tree_structure(new_params) == \
        jax.tree_util.tree_structure(variables["params"])
    moved = ops_moe.router_bias_update(bias["router_bias"], counts, 0.001)
    step = np.asarray(moved - bias["router_bias"])
    assert set(np.round(step / 0.001).astype(int)) <= {-1, 0, 1}
    assert int(counts.sum()) == x.shape[0] * x.shape[1] * TOP_K
    # expert_counts finds the sown counts by block
    inter = {"block_1": {"moe": {"moe_expert_counts": (counts,)}},
             "block_0": {"RMSNorm_0": {}}}
    assert list(expert_counts(inter)) == ["block_1"]


def test_softmax_layer_keeps_its_parameters_and_sows():
    """OLMoE's layer is what it was: no bias collection, the old sows."""
    layer = MoEMLP(dim=D, hidden=W, n_experts=4, top_k=2, dtype=jnp.float32)
    x = seeded((1, 8, D), 5)
    variables = layer.init(jax.random.PRNGKey(0), x)
    assert set(variables) == {"params"}
    assert sorted(variables["params"]) == ["router", "w_down", "w_gate", "w_up"]
    _, state = layer.apply(variables, x, mutable=["intermediates"])
    assert sorted(state["intermediates"]) == [
        "moe_chosen_experts", "moe_lb_loss", "moe_router_logits", "moe_z_loss"]
    with pytest.raises(ValueError, match="router"):
        MoEMLP(dim=D, hidden=W, n_experts=4, top_k=2, router="tanh").init(
            jax.random.PRNGKey(0), x)
    with pytest.raises(ValueError, match="held"):
        MoEMLP(dim=D, hidden=W, n_experts=4, top_k=2, held=(2, 4)).init(
            jax.random.PRNGKey(0), x)


@pytest.mark.parametrize("router", ["softmax", "sigmoid"])
def test_a_recomputed_block_keeps_the_forwards_chosen_experts(router):
    """``TransformerLM(remat=True)`` saves what the routers chose (the name
    ``ops.moe.CHOSEN_EXPERTS``) and recomputes no ``top_k``: a recomputed
    score that differs in its last bit cannot hand the backward another
    expert set than the forward ran (PERF.md, PR 36). The gradients are the
    unrecomputed model's."""
    from horovod_tpu.models import TransformerLM

    tokens = jnp.arange(32, dtype=jnp.int32).reshape(2, 16) % 64

    def model(remat):
        return TransformerLM(vocab=64, dim=32, heads=2, layers=3, moe_experts=8,
                             moe_top_k=2, moe_hidden=16, moe_every=1,
                             moe_router=router, dtype=jnp.float32, remat=remat)

    variables = model(False).init(jax.random.PRNGKey(0), tokens)

    def grad_of(remat):
        def loss(params):
            return jnp.sum(model(remat).apply({**variables, "params": params},
                                              tokens) ** 2)
        return jax.grad(loss)

    plain, recomputed = grad_of(False), grad_of(True)
    count = lambda f: str(jax.make_jaxpr(f)(variables["params"])).count(" top_k[")
    assert count(plain) == count(recomputed) == 3       # one a layer, forward only
    for got, want in zip(*(jax.tree_util.tree_leaves(f(variables["params"]))
                           for f in (recomputed, plain))):
        close(got, want, 1e-5)


# ----------------------------------------- the router, run once under remat

def _router_model(remat, router="sigmoid", **fields):
    from horovod_tpu.models import TransformerLM

    # 32 tokens over 8 experts: (32, 8) is the router's shape alone
    return TransformerLM(vocab=64, dim=32, heads=2, layers=3, moe_experts=8,
                         moe_top_k=2, moe_hidden=16, moe_every=1,
                         moe_router=router, dtype=jnp.float32, remat=remat,
                         **fields)


def _router_products(program):
    """The router's float32 products at ``highest``, its logits and its
    backward's two: in a jaxpr (walked through its sub-jaxprs) the products
    with an (N, E)- or (D, E)-shaped operand or result, in a compiled
    module's text the ``dot`` instructions under ``hvd_moe_logits``."""
    if isinstance(program, str):
        return sum(" dot(" in line and "hvd_moe_logits" in line
                   and "operand_precision={highest,highest}" in line
                   for line in program.splitlines())
    found = 0
    for eqn in program.eqns:
        if (eqn.primitive.name == "dot_general" and eqn.params["precision"]
                == (jax.lax.Precision.HIGHEST,) * 2):
            found += any(v.aval.shape == (32, 8)
                         for v in (*eqn.invars, *eqn.outvars))
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _router_products(sub)
    return found


ROUTER_CASES = {
    "as_published": {},
    "eps_1e-6": {"moe_route_eps": 1e-6},
    "scale_2.5": {"moe_route_scale": 2.5},
    "bias": {},
    "held": {"moe_held": (2, 4)},
    "latent": {"moe_latent": 16, "moe_activation": "relu2"},
}


@pytest.mark.parametrize("case", list(ROUTER_CASES))
def test_a_recomputed_sigmoid_layer_runs_its_router_once(monkeypatch, case):
    """``TransformerLM(remat=True)`` saves the sigmoid router's chosen scores
    and weights beside its chosen experts (``ops.moe.ROUTER_SAVED``), and the
    router's backward is formed from them: THREE float32 products at
    ``highest`` a layer (the forward, d-tokens, d-router) in ``jax.grad``'s
    jaxpr and in the compiled module, one fewer a layer than under a policy
    without the two new names; the gradients are the unrecomputed model's."""
    from horovod_tpu.models import transformer

    tokens = jnp.arange(32, dtype=jnp.int32).reshape(2, 16) % 64
    fields = ROUTER_CASES[case]
    variables = _router_model(False, **fields).init(jax.random.PRNGKey(0), tokens)
    if case == "bias":      # a bias that moves the choice
        variables = {**variables, BIAS_COLLECTION: jax.tree_util.tree_map(
            lambda b: seeded(b.shape, 3, 0.3), variables[BIAS_COLLECTION])}

    def grad_of(remat):
        def loss(params):
            return jnp.sum(_router_model(remat, **fields).apply(
                {**variables, "params": params}, tokens) ** 2)
        return jax.jit(jax.grad(loss))

    params = variables["params"]
    recomputed = grad_of(True)
    jaxpr = jax.make_jaxpr(recomputed)(params)
    assert _router_products(jaxpr.jaxpr) == 3 * 3
    # the sigmoid over (N, E) and the choice: the forward's alone
    assert str(jaxpr).count("f32[32,8] = logistic") == 3
    assert str(jaxpr).count(" top_k[") == 3
    if case == "as_published":
        compiled = recomputed.lower(params).compile().as_text()
        assert _router_products(compiled) == 3 * 3
        assert ops_moe_gauges()["horovod_moe_router_recomputed"] == 0
        assert ops_moe_gauges()["horovod_moe_router_saved_bytes_per_layer"] == (
            2 * 32 * 2 * 4)
    for got, want in zip(*(jax.tree_util.tree_leaves(f(params))
                           for f in (recomputed, grad_of(False)))):
        close(got, want, 1e-5)
    # the policy without the two new names: the router runs again
    monkeypatch.setattr(transformer, "ROUTER_SAVED", (ops_moe.CHOSEN_EXPERTS,))
    before = grad_of(True)
    jaxpr = jax.make_jaxpr(before)(params)
    assert _router_products(jaxpr.jaxpr) == 3 * 4
    assert str(jaxpr).count("f32[32,8] = logistic") == 3 * 2
    if case == "as_published":
        assert _router_products(before.lower(params).compile().as_text()) == 3 * 4
        assert ops_moe_gauges()["horovod_moe_router_recomputed"] == 1


def test_the_softmax_routers_recomputed_products_are_what_they_were():
    """``topk_route`` is not the unit's: a recomputed softmax-routed layer
    runs its router's product again (its losses read the whole
    probabilities), four a layer, whatever the policy's names."""
    tokens = jnp.arange(32, dtype=jnp.int32).reshape(2, 16) % 64
    variables = _router_model(False, "softmax").init(jax.random.PRNGKey(0), tokens)

    def grad_of(remat):
        def loss(params):
            return jnp.sum(_router_model(remat, "softmax").apply(
                {**variables, "params": params}, tokens) ** 2)
        return jax.grad(loss)

    count = lambda f: _router_products(jax.make_jaxpr(f)(variables["params"]).jaxpr)
    assert (count(grad_of(False)), count(grad_of(True))) == (3 * 3, 3 * 4)


def _written_out(tokens, router, bias, top_k, scale, eps):
    """What ``sigmoid_route_tokens`` is, for plain autodiff."""
    logits = jnp.dot(tokens.astype(jnp.float32), router,
                     precision=jax.lax.Precision.HIGHEST)
    _, weights, experts = ops_moe.sigmoid_route(logits, bias, top_k, scale, eps)
    return logits, weights, experts


@pytest.mark.parametrize("eps,scale", [(1e-20, 1.0), (1e-6, 2.5)])
def test_the_routers_rule_against_plain_autodiff(eps, scale):
    """``jax.grad`` through the unit is ``jax.grad`` through the product and
    ``sigmoid_route`` written out: for the tokens, the router and with a
    cotangent on the logits, to float32 rounding; the bias receives none.
    Token 0's last chosen expert and the next are TIED (two equal router
    columns, ``ops/moe.py`` over ``CHOSEN_EXPERTS``): both sides
    differentiate the experts the forward chose."""
    tokens, router, bias = seeded((24, D), 0, 1.0), seeded((D, E), 1), seeded((E,), 2, 0.2)
    first = jnp.argsort(-(jax.nn.sigmoid(tokens[0] @ router) + bias))
    router = router.at[:, first[TOP_K]].set(router[:, first[TOP_K - 1]])
    bias = bias.at[first[TOP_K]].set(bias[first[TOP_K - 1]])
    mix, on_logits = seeded((24, TOP_K), 3, 1.0), seeded((24, E), 4, 1.0)

    def loss(route):
        def f(tokens, router, bias):
            logits, weights, experts = route(tokens, router, bias)
            return (jnp.sum(weights * mix * (1 + experts))
                    + jnp.sum(jnp.tanh(logits) * on_logits)), experts
        return jax.jit(jax.grad(f, argnums=(0, 1, 2), has_aux=True))

    got, experts = loss(lambda *a: ops_moe.sigmoid_route_tokens(
        *a, TOP_K, scale, eps, ops_moe.sigmoid_route))(tokens, router, bias)
    want, reference = loss(lambda *a: _written_out(
        *a, TOP_K, scale, eps))(tokens, router, bias)
    np.testing.assert_array_equal(np.asarray(experts), np.asarray(reference))
    assert set(np.asarray(first[TOP_K - 1:TOP_K + 1])) & set(np.asarray(experts[0]))
    for g, w in zip(got[:2], want[:2]):
        assert g.dtype == w.dtype
        close(g, w, 2e-6)
    assert not np.any(np.asarray(got[2])) and not np.any(np.asarray(want[2]))


def test_the_routers_rule_has_no_forward_mode():
    """As any ``jax.custom_vjp`` (``chunked_lm_loss`` is another): ``jax.jvp``
    through the unit raises, and its docstring says so."""
    tokens, router, bias = seeded((8, D), 0), seeded((D, E), 1), jnp.zeros(E)
    route = lambda t: ops_moe.sigmoid_route_tokens(
        t, router, bias, TOP_K, 1.0, 1e-20, ops_moe.sigmoid_route)[1]
    with pytest.raises(TypeError, match="forward-mode"):
        jax.jvp(route, (tokens,), (tokens,))
    assert "Forward mode" in ops_moe.sigmoid_route_tokens.__doc__
