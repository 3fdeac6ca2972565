"""OLMoE through ``TransformerLM`` against ``tests/references/olmoe.py`` (plain
jax.numpy, float32 at ``highest``, a loop over the experts) on seeded weights
at tiny sizes: logits, loss with both auxiliary terms, ALL gradients; no
dropped pair under the worst imbalance; the layer alone against a per-token
loop at ``top_k`` 1, 2 and all experts; weights not renormalised; experts
without a ``top_k`` refused; QK-norm over the whole projection; data parallel on the 4-device CPU mesh through
``DistributedOptimizer`` against the reference played rank by rank; and the
placeholder ``lm217m`` model untouched by the new options at their defaults.

Tolerances, as shares of max|reference| per array:
* float32: 2e-5. System and reference do the same float32 arithmetic and
  differ only in the order of sums (pairs sorted by expert and grouped
  products against a loop over experts; one softmax); observed <= 2.5e-6.
* bfloat16 activations: held on the tokens whose experts agree with the
  reference's in every layer (a rounding of the hidden state flips an 8th
  expert against a 9th; at these sizes, 8 experts and 2 layers, up to a
  quarter of the tokens). One bf16 rounding is 4e-3 and a token passes
  through about ten of them: logits 6e-2, loss 2e-2. A renormalised top-k, a
  dropped pair or fp8 (6e-2 a rounding) is far outside.
"""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from references import olmoe as ref  # noqa: E402

from horovod_tpu.compat import shard_map  # noqa: E402
from horovod_tpu.models import (MoEMLP, TransformerLM, aux_losses,  # noqa: E402
                                ep_param_specs)
from horovod_tpu.ops import moe as moe_ops  # noqa: E402

CFG = dict(hidden=64, heads=2, experts=8, top_k=2, expert_width=32, vocab=128,
           eps=1e-5, rope_theta=10000.0, lb_coef=0.01, z_coef=0.001)
LAYERS = 2
F32_TOL = 2e-5


def model(**kw):
    return TransformerLM(**{**dict(
        vocab=CFG["vocab"], dim=CFG["hidden"], heads=CFG["heads"],
        layers=LAYERS, moe_experts=CFG["experts"], moe_every=1,
        moe_top_k=CFG["top_k"], moe_hidden=CFG["expert_width"], qk_norm=True,
        rms_norm_eps=CFG["eps"], dtype=jnp.float32), **kw})


def to_system(p):
    """The reference's parameter layout as ``TransformerLM``'s tree."""
    out = {"embed": {"embedding": p["embed"]},
           "RMSNorm_0": {"scale": p["final_norm"]},
           "lm_head": {"kernel": p["head"]}}
    for i, l in enumerate(p["layers"]):
        out[f"block_{i}"] = {
            "RMSNorm_0": {"scale": l["attn_norm"]},
            "RMSNorm_1": {"scale": l["mlp_norm"]},
            "qkv": {"kernel": jnp.concatenate([l["wq"], l["wk"], l["wv"]], 1)},
            "q_norm": {"scale": l["q_norm"]}, "k_norm": {"scale": l["k_norm"]},
            "o_proj": {"kernel": l["wo"]},
            "moe": {k: l[k] for k in ("router", "w_gate", "w_up", "w_down")}}
    return out


def system_loss(m):
    def loss(params, tokens):
        logits, state = m.apply({"params": params}, tokens,
                                mutable=["intermediates"])
        lb, z = aux_losses(state["intermediates"])
        ce = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), jnp.roll(tokens, -1, axis=1)).mean()
        total = ce + CFG["lb_coef"] * lb + CFG["z_coef"] * z
        return total, {"ce": ce, "lb": lb, "z": z, "logits": logits,
                       "intermediates": state["intermediates"]}
    return loss


def share(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


@pytest.fixture(scope="module")
def seeded():
    params = ref.init_params(jax.random.PRNGKey(0), CFG, layers=LAYERS, scale=0.1)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, CFG["vocab"])
    return params, tokens, ref.loss_and_grads(params, tokens, CFG)


def test_param_tree_is_olmoes(seeded):
    params, tokens, _ = seeded
    init = model().init(jax.random.PRNGKey(0), tokens)["params"]
    assert (jax.tree_util.tree_map(jnp.shape, init)
            == jax.tree_util.tree_map(jnp.shape, to_system(params)))
    # QK-norm: one weight per projected feature (all heads), not per head_dim
    assert init["block_0"]["q_norm"]["scale"].shape == (CFG["hidden"],)
    assert init["block_0"]["moe"]["w_gate"].shape == (
        CFG["experts"], CFG["hidden"], CFG["expert_width"])


def test_float32_matches_reference_everywhere(seeded):
    params, tokens, ((want_total, want), want_grads) = seeded
    with jax.default_matmul_precision("highest"):
        (total, got), grads = jax.jit(jax.value_and_grad(
            system_loss(model()), has_aux=True))(to_system(params), tokens)
    assert share(got["logits"], want["logits"]) <= F32_TOL
    for name, a, b in (("total", total, want_total), ("ce", got["ce"], want["ce"]),
                       ("lb", got["lb"], want["lb"]), ("z", got["z"], want["z"])):
        assert abs(float(a) - float(b)) <= F32_TOL * abs(float(b)), name
    flat, _ = jax.tree_util.tree_flatten_with_path(to_system(want_grads))
    assert len(flat) == 3 + 10 * LAYERS     # every leaf of the model
    for (path, b), a in zip(flat, jax.tree_util.tree_leaves(grads)):
        assert share(a, b) <= F32_TOL, jax.tree_util.keystr(path)


@pytest.mark.parametrize("attention", ["dense", "flash"])
def test_bfloat16_activations_stay_in_their_band(seeded, attention):
    params, tokens, ((want_total, want), _) = seeded
    m = model(dtype=jnp.bfloat16, attention=attention, flash_interpret=True)
    tokens = jnp.tile(tokens, (1, 4))       # 128 positions: one flash block
    (want_total, want), _ = ref.loss_and_grads(params, tokens, CFG)
    total, got = jax.jit(system_loss(m))(to_system(params), tokens)
    agree = np.ones(tokens.size, bool)
    for i, chosen in enumerate(want["chosen"]):
        picked = got["intermediates"][f"block_{i}"]["moe"]["moe_chosen_experts"][0]
        mine = np.zeros(chosen.shape, bool)
        np.put_along_axis(mine, picked, True, axis=-1)
        agree &= (mine == np.asarray(chosen)).all(axis=-1)
    assert agree.mean() >= 0.75
    v = CFG["vocab"]
    assert share(np.asarray(got["logits"], np.float32).reshape(-1, v)[agree],
                 np.asarray(want["logits"]).reshape(-1, v)[agree]) <= 6e-2
    assert abs(float(total) - float(want_total)) <= 2e-2 * float(want_total)


# Widths and rows the grouped-product kernels tile (ops/grouped_matmul.py):
# multiples of 128, and 128 tokens x top_k 2 = one f32 row tile of 256.
ALIGNED = dict(CFG, hidden=128, expert_width=128)


def one_layer(bias, cfg=CFG, tokens=96):
    """A seeded MoE layer in the reference's layout and inputs whose first
    feature is a constant 1, so that the router's first row is a bias."""
    layer = ref.init_params(jax.random.PRNGKey(2), cfg, scale=0.1)["layers"][0]
    layer["router"] = layer["router"].at[0].set(jnp.asarray(bias, jnp.float32))
    x = jax.random.normal(jax.random.PRNGKey(3), (tokens, cfg["hidden"]))
    return layer, x.at[:, 0].set(1.0)


def probe(y):
    """A scalar of the layer's output whose cotangent differs on every entry."""
    return jnp.sum(y * jnp.cos(jnp.arange(y.size).reshape(y.shape)))


def moe_system(layer, x, top_k=CFG["top_k"], cfg=CFG):
    params = {k: layer[k] for k in ("router", "w_gate", "w_up", "w_down")}
    m = MoEMLP(dim=cfg["hidden"], hidden=cfg["expert_width"],
               n_experts=cfg["experts"], top_k=top_k, dtype=jnp.float32,
               interpret=True)

    def f(params, x):
        y, state = m.apply({"params": params}, x[None],
                           mutable=["intermediates"])
        return probe(y), y[0]

    with jax.default_matmul_precision("highest"):
        (_, y), grads = jax.jit(jax.value_and_grad(f, argnums=(0, 1),
                                                   has_aux=True))(params, x)
    return y, grads


def moe_reference(layer, x):
    def f(layer, x):
        with jax.default_matmul_precision("highest"):
            y, stats = ref.experts(layer, x, CFG)
        return probe(y), (y, stats)

    (_, (y, stats)), grads = jax.value_and_grad(f, argnums=(0, 1),
                                                has_aux=True)(layer, x)
    return y, stats, grads


@pytest.mark.parametrize("bias", [
    pytest.param([0.0] * 8, id="seeded_router"),
    # every token picks experts 5 and 2, whatever else its features say:
    # 48 times the mean load on two experts, none on the other six
    pytest.param([0, 0, 40.0, 0, 0, 50.0, 0, 0], id="all_tokens_on_the_same_experts"),
])
def test_no_pair_is_dropped_under_any_imbalance(bias):
    layer, x = one_layer(bias)
    y, (g_params, g_x) = moe_system(layer, x)
    want_y, stats, (want_layer, want_x) = moe_reference(layer, x)
    counts = np.asarray(stats["chosen"]).sum(axis=0)
    assert counts.sum() == x.shape[0] * CFG["top_k"]
    if any(bias):
        assert sorted(counts) == [0] * 6 + [96, 96]
    # A bias of 40-50 puts the probabilities within 1e-17 of 0 and e^-10 of
    # 1: the softmax's backward then cancels to its last bits (observed 2.8e-5
    # on the router's gradient), so the biased case is held at 1e-4.
    tol = 1e-4 if any(bias) else F32_TOL
    assert share(y, want_y) <= F32_TOL
    assert share(g_x, want_x) <= tol
    for name in ("router", "w_gate", "w_up", "w_down"):
        assert share(g_params[name], want_layer[name]) <= tol, name


def per_token_loop(layer, x, top_k):
    """The layer one token at a time: its ``top_k`` most probable experts,
    each expert's SwiGLU on that token alone, summed with the probabilities.
    Same probe as ``moe_system``; (y, gradients of (layer, x))."""
    probs = np.asarray(jax.nn.softmax(x @ layer["router"], axis=-1))
    chosen = np.argsort(-probs, axis=-1, kind="stable")[:, :top_k]

    def f(layer, x):
        rows = []
        for n, row in enumerate(x):
            p = jax.nn.softmax(row @ layer["router"])
            rows.append(sum(
                p[e] * ((jax.nn.silu(row @ layer["w_gate"][e])
                         * (row @ layer["w_up"][e])) @ layer["w_down"][e])
                for e in chosen[n]))
        y = jnp.stack(rows)
        return probe(y), y

    with jax.default_matmul_precision("highest"):
        (_, y), grads = jax.value_and_grad(f, argnums=(0, 1),
                                           has_aux=True)(layer, x)
    return y, grads


@pytest.mark.parametrize("top_k, cfg, tokens, border_overhead", [
    (1, CFG, 24, 0.0), (2, CFG, 24, 0.0), (CFG["experts"], CFG, 24, 0.0),
    pytest.param(2, ALIGNED, 128, 4.5, id="2-aligned_takes_the_kernels"),
])
def test_layer_alone_matches_a_per_token_loop(top_k, cfg, tokens,
                                              border_overhead):
    """``top_k = 1`` is one expert a token, weighted by its probability;
    ``top_k = n_experts`` is every expert on every token. At the aligned
    widths the nine grouped products run through the repo's kernels (the
    gauge says so: 2 row blocks of work, 8 groups), at the others through
    ``lax.ragged_dot`` (0)."""
    from horovod_tpu.metrics import registry

    layer, x = one_layer([0.0] * 8, cfg, max(tokens, 96))
    x = x[:tokens]
    y, (g_params, g_x) = moe_system(layer, x, top_k, cfg)
    assert registry().gauge(
        "horovod_moe_grouped_border_overhead").value == border_overhead
    want_y, (want_layer, want_x) = per_token_loop(layer, x, top_k)
    assert share(y, want_y) <= F32_TOL
    assert share(g_x, want_x) <= F32_TOL
    for name in ("router", "w_gate", "w_up", "w_down"):
        assert share(g_params[name], want_layer[name]) <= F32_TOL, name


def test_weights_are_the_probabilities_not_renormalised():
    logits = jax.random.normal(jax.random.PRNGKey(4), (50, 8)) * 2.0
    probs, weights, experts = moe_ops.topk_route(logits, 3)
    want = np.sort(np.asarray(jax.nn.softmax(logits, axis=-1)), axis=-1)[:, ::-1][:, :3]
    np.testing.assert_allclose(np.asarray(weights), want, rtol=1e-6)
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(probs), np.asarray(experts), axis=-1),
        want, rtol=1e-6)
    assert np.all(np.asarray(weights).sum(axis=-1) < 1.0 - 1e-3)


def test_auxiliary_losses_by_hand():
    logits = jnp.log(jnp.asarray([[0.5, 0.3, 0.1, 0.1], [0.1, 0.2, 0.3, 0.4]]))
    probs, _, experts = moe_ops.topk_route(logits, 2)
    # assignments: {0, 1} and {3, 2}: a quarter each; P = column means
    want = 4 * sum(0.25 * p for p in (0.3, 0.25, 0.2, 0.25))
    assert float(moe_ops.topk_load_balancing_loss(probs, experts)) == pytest.approx(want)
    z = jnp.asarray([[1.0, 1.0], [0.0, 0.0]])
    assert float(moe_ops.router_z_loss(z)) == pytest.approx(
        ((1 + np.log(2)) ** 2 + np.log(2) ** 2) / 2, rel=1e-6)


def test_load_balancing_loss_of_a_balanced_router_is_one():
    """Token n picks experts n .. n + top_k - 1 (mod E), evenly: f_e = P_e =
    1 / E, so E * sum_e f_e P_e = 1 at every top_k, its minimum. (Counting
    f_e by N alone, as the paper's formula does, gives top_k times this.)"""
    n, e = 64, 8
    for top_k in (1, 2, 4):
        picks = (jnp.arange(n)[:, None] + jnp.arange(top_k)) % e
        logits = 20.0 * jnp.sum(jax.nn.one_hot(picks, e), axis=1)
        probs, _, experts = moe_ops.topk_route(logits, top_k)
        np.testing.assert_array_equal(np.sort(np.asarray(experts), axis=-1),
                                      np.sort(np.asarray(picks), axis=-1))
        assert float(moe_ops.topk_load_balancing_loss(probs, experts)) == \
            pytest.approx(1.0, abs=1e-5), top_k


def test_expert_load_gauge_is_set_by_the_helper_alone(hvd):
    logits = np.zeros((8, 4), np.float32)
    logits[:, 0], logits[:, 1] = 3.0, 2.0          # everyone picks 0 and 1
    assert moe_ops.record_expert_load(logits, 2) == pytest.approx(2.0)
    # what a sigmoid-routed layer of another test in this process set: a
    # softmax layer's trace sets neither
    for name in ("horovod_moe_router_recomputed",
                 "horovod_moe_router_saved_bytes_per_layer"):
        hvd.metrics.registry().remove(name)
    layer, x = one_layer([0.0] * 8)
    # tracing and running the layer leaves the load as the helper set it; the
    # two gauges a trace sets say which path the grouped products took
    moe_system(layer, x)
    gauges = hvd.metrics.registry().snapshot()["gauges"]
    assert gauges["horovod_moe_expert_load_max_over_mean"] == pytest.approx(2.0)
    assert gauges["horovod_moe_grouped_border_overhead"] == 0.0
    assert gauges["horovod_moe_grouped_weight_itemsize"] == 0      # ragged_dot
    assert gauges["horovod_moe_grouped_weight_lookahead_share"] == 0
    assert gauges["horovod_moe_dispatch_rows"] == 96 * CFG["top_k"]   # N x top_k
    assert gauges["horovod_moe_dispatch_row_bytes"] == x.shape[-1] * x.dtype.itemsize
    assert sorted(name for name in gauges if name.startswith("horovod_moe_")) == [
        "horovod_moe_dispatch_row_bytes",
        "horovod_moe_dispatch_rows",
        "horovod_moe_expert_load_max_over_mean",
        "horovod_moe_grouped_border_overhead",
        "horovod_moe_grouped_weight_itemsize",
        "horovod_moe_grouped_weight_lookahead_share"]


def test_data_parallel_through_distributed_optimizer(hvd, seeded):
    """Four ranks, one row each: every rank's auxiliary losses are over its
    own rows, the gradients are averaged. SGD, so that the update is the
    averaged gradient itself."""
    params, _, _ = seeded
    tokens = jax.random.randint(jax.random.PRNGKey(5), (4, 32), 0, CFG["vocab"])
    mesh = hvd.data_parallel_mesh(jax.devices()[:4])
    opt = hvd.jax.DistributedOptimizer(optax.sgd(1.0))
    loss_fn = system_loss(model())
    system = to_system(params)

    def train_step(params, opt_state, tokens):
        (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, tokens)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), jax.lax.pmean(loss, hvd.HVD_AXIS)

    with jax.default_matmul_precision("highest"):
        new, loss = jax.jit(shard_map(
            train_step, mesh=mesh, in_specs=(P(), P(), P(hvd.HVD_AXIS)),
            out_specs=(P(), P()), check_vma=False))(system, opt.init(system), tokens)
    played = [ref.loss_and_grads(params, tokens[r:r + 1], CFG) for r in range(4)]
    want_loss = np.mean([float(total) for (total, _), _ in played])
    want_grads = jax.tree_util.tree_map(lambda *g: sum(g) / 4,
                                        *[g for _, g in played])
    assert float(loss) == pytest.approx(want_loss, rel=F32_TOL)
    moved = jax.tree_util.tree_map(lambda a, b: a - b, system, new)
    for (path, b), a in zip(
            jax.tree_util.tree_flatten_with_path(to_system(want_grads))[0],
            jax.tree_util.tree_leaves(moved)):
        assert share(a, b) <= 5 * F32_TOL, jax.tree_util.keystr(path)


def test_the_placeholder_model_is_untouched_by_the_new_options():
    """``lm217m``'s model (every new option at its default) traces to a
    program with no QK-norm, no router and the old epsilon."""
    kw = dict(vocab=256, dim=64, heads=2, layers=2, mlp_ratio=4)
    tokens = jnp.zeros((2, 32), jnp.int32)
    plain = TransformerLM(**kw)
    spelled = TransformerLM(**kw, moe_top_k=0, moe_hidden=None, qk_norm=False,
                            rms_norm_eps=1e-6)
    params = plain.init(jax.random.PRNGKey(0), tokens)["params"]
    assert set(params["block_0"]) == {"RMSNorm_0", "RMSNorm_1", "qkv", "o_proj",
                                      "mlp_in", "mlp_out"}

    def program(m):
        return str(jax.make_jaxpr(jax.grad(lambda p: m.apply(
            {"params": p}, tokens).astype(jnp.float32).sum()))(params))

    text = program(plain)
    assert text == program(spelled)
    assert text.count("rsqrt") == 2 * 2 + 1       # two norms a layer + the last
    for absent in ("ragged_dot", "top_k", " sort[", "9.999999747378752e-06", "1e-05"):
        assert absent not in text, absent
    assert text.count("9.999999974752427e-07") == 2 * 2 + 1      # float32(1e-6)


def test_experts_without_a_top_k_are_refused():
    """``moe_experts > 0`` names no layer by itself: ``moe_top_k`` says how
    many experts a token uses, and its default of 0 is not a number of them."""
    tokens = jnp.zeros((1, 8), jnp.int32)
    for top_k in (0, 5):
        m = TransformerLM(vocab=32, dim=16, heads=2, layers=2, moe_experts=4,
                          moe_top_k=top_k)
        with pytest.raises(ValueError, match=f"top_k {top_k} of 4 swiglu experts"):
            m.init(jax.random.PRNGKey(0), tokens)


def test_every_second_block_holds_the_experts():
    params = jax.eval_shape(
        model(layers=4, moe_every=2).init, jax.random.PRNGKey(0),
        jnp.zeros((1, 8), jnp.int32))["params"]
    for i in range(4):
        mlp = set(params[f"block_{i}"]) - {"RMSNorm_0", "RMSNorm_1", "qkv",
                                           "q_norm", "k_norm", "o_proj"}
        assert mlp == ({"moe"} if i in (1, 3) else {"mlp_in", "mlp_out"}), i


def test_ep_param_specs_name_the_three_expert_tensors():
    params = jax.eval_shape(model().init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    specs = ep_param_specs(params, "ep")
    sharded = {jax.tree_util.keystr(path): spec for path, spec in
               jax.tree_util.tree_flatten_with_path(
                   specs, is_leaf=lambda s: isinstance(s, P))[0] if spec != P()}
    assert sharded == {
        f"['block_{i}']['moe']['{w}']": P("ep", None, None)
        for i in range(LAYERS) for w in ("w_gate", "w_up", "w_down")}
    assert specs["block_0"]["moe"]["router"] == P()
