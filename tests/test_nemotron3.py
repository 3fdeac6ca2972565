"""A Nemotron-3-class hybrid through ``TransformerLM`` on the CPU at tiny
sizes against ``tests/references/nemotron3.py`` on seeded weights: layers that
are ONE sub-layer of every kind, the multi-token-prediction module, both loss
terms and the gradient of every leaf, in float32 and as trained (bf16, the
flash, grouped-product and Mamba-2 kernels in the interpreter, recomputation);
what the module shares and what it shifts; and the sizes a layer kind has no
half for, which raise."""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from nemotron3_layout import biases_to_system, to_system  # noqa: E402
from references import nemotron3  # noqa: E402

from horovod_tpu.models import BIAS_COLLECTION, TransformerLM  # noqa: E402
from horovod_tpu.models.mamba import Mamba2Dims  # noqa: E402
from horovod_tpu.models.transformer import (Block, chunked_lm_loss,  # noqa: E402
                                            lm_loss_with_mtp)

KINDS = {"M": "mamba_only", "*": "attention_only", "E": "experts_only"}
# every width a multiple of 128 where a kernel wants one: a row of 128 tokens
# x 4 experts a token is one row tile of the grouped products in bf16
CFG = {"hidden": 128, "vocab": 256, "layer_types": "ME*E", "mtp_layer_types": "*E",
       "heads": 4, "kv_heads": 1, "head_dim": 32,
       "mamba_heads": 4, "mamba_head_dim": 32, "mamba_groups": 1,
       "mamba_state": 64, "conv": 4, "eps": 1e-5,
       "experts": 16, "top_k": 4, "held": (4, 4), "latent": 128,
       "expert_width": 128, "shared_width": 64, "route_scale": 5.0,
       "mtp_weight": 0.3}
T = 128


def model_of(cfg=CFG, **kw):
    args = dict(
        vocab=cfg["vocab"], dim=cfg["hidden"], heads=cfg["heads"],
        kv_heads=cfg["kv_heads"], head_dim=cfg["head_dim"],
        layers=len(cfg["layer_types"]),
        layer_types=tuple(KINDS[k] for k in cfg["layer_types"]),
        mtp_layer_types=tuple(KINDS[k] for k in cfg["mtp_layer_types"]),
        mamba=Mamba2Dims(heads=cfg["mamba_heads"], head_dim=cfg["mamba_head_dim"],
                         state=cfg["mamba_state"], groups=cfg["mamba_groups"],
                         conv=cfg["conv"], chunk=32),
        rope=False, moe_experts=cfg["experts"], moe_top_k=cfg["top_k"],
        moe_hidden=cfg["expert_width"], moe_router="sigmoid",
        moe_route_scale=cfg["route_scale"],
        moe_shared_hidden=cfg["shared_width"], moe_held=cfg["held"],
        moe_activation="relu2", moe_latent=cfg["latent"], rms_norm_eps=cfg["eps"],
        # these widths take the Pallas kernels in float32 too
        dtype=jnp.float32, flash_interpret=True)
    args.update(kw)
    return TransformerLM(**args)


@pytest.fixture(scope="module")
def seeded():
    params, biases = nemotron3.init_params(jax.random.PRNGKey(0), CFG, scale=0.05)
    biases = [0.05 * jax.random.normal(jax.random.PRNGKey(9 + i), b.shape)
              for i, b in enumerate(biases)]
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, T), 0, CFG["vocab"])
    return params, biases, tokens


def system_loss(model, chunk=64):
    """``(reference-layout params, biases, tokens) -> (loss, (main, mtp,
    chosen))`` through the model and ``lm_loss_with_mtp``."""
    def loss(params, biases, tokens):
        tree = to_system(params)
        hidden, state = model.apply(
            {"params": tree, BIAS_COLLECTION: biases_to_system(
                biases, CFG["layer_types"], CFG["mtp_layer_types"])},
            tokens, return_hidden=True, mutable=["intermediates"])
        total, (main, mtp) = lm_loss_with_mtp(
            *hidden, tree["lm_head"]["kernel"], tokens, CFG["mtp_weight"], chunk)
        inter = state["intermediates"]
        names = [f"block_{i}" for i, k in enumerate(CFG["layer_types"]) if k == "E"] \
            + [f"mtp_block_{i}" for i, k in enumerate(CFG["mtp_layer_types"])
               if k == "E"]
        chosen = [jnp.any(inter[n]["moe"]["moe_chosen_experts"][0][:, :, None]
                          == jnp.arange(CFG["experts"]), axis=1) for n in names]
        return total, (main, mtp, chosen)
    return loss


def leaves(tree):
    return {jax.tree_util.keystr(path): leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


def test_float32_model_both_losses_and_every_gradient(seeded):
    params, biases, tokens = seeded
    with jax.default_matmul_precision("highest"):
        (got, (main, mtp, chosen)), got_grads = jax.jit(jax.value_and_grad(
            system_loss(model_of()), has_aux=True))(params, biases, tokens)
    (want, parts), want_grads = nemotron3.loss_and_grads(params, biases, tokens,
                                                         CFG)
    for g, w in zip(chosen, parts["chosen"], strict=True):
        assert bool(jnp.all(g == w))
    assert float(main) == pytest.approx(float(parts["main"]), rel=2e-6)
    assert float(mtp) == pytest.approx(float(parts["mtp"]), rel=2e-6)
    assert float(got) == pytest.approx(float(want), rel=2e-6)
    assert float(main) != pytest.approx(float(mtp), rel=1e-4)
    got_grads, want_grads = leaves(got_grads), leaves(want_grads)
    assert set(got_grads) == set(want_grads) and len(want_grads) == 50
    for name, w in want_grads.items():
        assert float(jnp.max(jnp.abs(w))) > 0, name     # every leaf is reached
        assert float(jnp.max(jnp.abs(got_grads[name] - w))) <= 5e-5 * float(
            jnp.max(jnp.abs(w))), name


def test_logits_of_both_heads(seeded):
    params, biases, tokens = seeded
    model = model_of()
    with jax.default_matmul_precision("highest"):
        logits, mtp_logits = model.apply(
            {"params": to_system(params), BIAS_COLLECTION: biases_to_system(
                biases, CFG["layer_types"], CFG["mtp_layer_types"])}, tokens)
    want, want_mtp, _ = nemotron3.forward(params, biases, tokens, CFG)
    np.testing.assert_allclose(logits, want, atol=2e-5)
    np.testing.assert_allclose(mtp_logits, want_mtp, atol=2e-5)


def test_recurrence_and_quadratic_forms_of_the_reference_agree(seeded):
    params, biases, tokens = seeded
    a = nemotron3.loss_parts(params, biases, tokens, CFG)[1]
    b = nemotron3.loss_parts(params, biases, tokens, CFG,
                             ssm=nemotron3.ssm_quadratic)[1]
    np.testing.assert_allclose(a["logits"], b["logits"], atol=2e-5)
    np.testing.assert_allclose(a["mtp_logits"], b["mtp_logits"], atol=2e-5)


def test_as_trained_with_the_kernels_in_the_interpreter(seeded, hvd):
    """bf16 activations, flash attention, the grouped-product kernels and the
    Mamba-2 kernels in the interpreter, recomputation: against the float32
    reference computed under the SYSTEM's choice of experts."""
    params, biases, _ = seeded
    # a row of 512: one row tile of the Mamba-2 kernels in bf16
    tokens = jax.random.randint(jax.random.PRNGKey(4), (1, 512), 0, CFG["vocab"])
    model = model_of(dtype=jnp.bfloat16, attention="flash",
                     flash_interpret=True, remat=True)
    (got, (main, mtp, chosen)), got_grads = jax.jit(jax.value_and_grad(
        system_loss(model), has_aux=True))(params, biases, tokens)
    gauges = hvd.metrics.registry().snapshot()["gauges"]
    assert gauges["horovod_moe_grouped_border_overhead"] > 0     # the kernels
    assert gauges["horovod_mamba_fused_passes"] == 2
    assert gauges["horovod_moe_dispatch_row_bytes"] == CFG["latent"] * 2
    (want, parts), want_grads = nemotron3.loss_and_grads(
        params, biases, tokens, CFG, forced=chosen)
    assert float(main) == pytest.approx(float(parts["main"]), rel=2e-3)
    assert float(mtp) == pytest.approx(float(parts["mtp"]), rel=2e-3)
    got_grads, want_grads = leaves(got_grads), leaves(want_grads)
    for name, w in want_grads.items():
        assert float(jnp.linalg.norm(got_grads[name] - w)) <= 0.1 * float(
            jnp.linalg.norm(w)), name


def test_float32_where_bf16_is_stated_would_fail_the_float32_limit(seeded):
    """The float32 comparison is tight enough that bf16 hidden states fail it."""
    params, biases, tokens = seeded
    (_, (_, _, chosen)), got = jax.jit(jax.value_and_grad(
        system_loss(model_of(dtype=jnp.bfloat16)), has_aux=True))(
        params, biases, tokens)
    _, want = nemotron3.loss_and_grads(params, biases, tokens, CFG, forced=chosen)
    got, want = leaves(got), leaves(want)
    assert max(float(jnp.max(jnp.abs(got[n] - w)) / jnp.max(jnp.abs(w)))
               for n, w in want.items()) > 5e-4


# ------------------------------------------ what the module shares and shifts

def test_the_heads_gradient_is_the_sum_of_both_passes():
    hidden, mtp_hidden = (jax.random.normal(jax.random.PRNGKey(i), (2, 64, 16))
                          for i in (0, 1))
    head = 0.1 * jax.random.normal(jax.random.PRNGKey(2), (16, 32))
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 64), 0, 32)
    total, (main, mtp) = lm_loss_with_mtp(hidden, mtp_hidden, head, tokens,
                                          0.3, 32)
    assert float(total) == pytest.approx(float(main) + 0.3 * float(mtp))
    assert float(main) == pytest.approx(float(chunked_lm_loss(
        hidden, head, jnp.roll(tokens, -1, axis=1), 32)))
    assert float(mtp) == pytest.approx(float(chunked_lm_loss(
        mtp_hidden, head, jnp.roll(tokens, -2, axis=1), 32)))   # the token AFTER the next
    both = jax.grad(lambda h: lm_loss_with_mtp(hidden, mtp_hidden, h, tokens,
                                               0.3, 32)[0])(head)
    first = jax.grad(lambda h: chunked_lm_loss(
        hidden, h, jnp.roll(tokens, -1, axis=1), 32))(head)
    second = jax.grad(lambda h: chunked_lm_loss(
        mtp_hidden, h, jnp.roll(tokens, -2, axis=1), 32))(head)
    np.testing.assert_allclose(both, first + 0.3 * second, rtol=1e-5, atol=1e-8)


def test_the_module_has_its_own_layers_and_the_models_embedding_and_head():
    model = model_of()
    tokens = jnp.zeros((1, 32), jnp.int32)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)["params"]
    assert {"mtp_hidden_norm", "mtp_embed_norm", "mtp_proj", "mtp_block_0",
            "mtp_block_1", "mtp_norm", "embed", "lm_head"} <= set(shapes)
    assert shapes["mtp_proj"]["kernel"].shape == (2 * CFG["hidden"], CFG["hidden"])
    assert not [k for k in shapes if k.startswith("mtp") and (
        "embed" in shapes[k] or "lm_head" in k)]
    # a layer that is one sub-layer has ONE norm and no second half
    assert set(shapes["block_0"]) == {"RMSNorm_0", "mixer"}
    assert set(shapes["block_1"]) == {"RMSNorm_0", "moe"}
    assert set(shapes["block_2"]) == {"RMSNorm_0", "q_proj", "kv_proj", "o_proj"}
    assert set(shapes["block_1"]["moe"]) == {
        "router", "w_up", "w_down", "fc1_latent", "fc2_latent", "shared_up",
        "shared_down"}
    held = CFG["held"][1]
    assert shapes["block_1"]["moe"]["w_up"].shape == (
        held, CFG["latent"], CFG["expert_width"])
    # without the module the call returns one result, not a pair
    plain = model_of(mtp_layer_types=None)
    assert jax.eval_shape(lambda: plain.apply(
        plain.init(jax.random.PRNGKey(0), tokens), tokens)).shape == (
        1, 32, CFG["vocab"])


# ----------------------------------------------------- what raises, and says why

@pytest.mark.parametrize("kw,match", [
    (dict(layer_types=("mamba_only", "sparse_only", "attention_only",
                       "experts_only")), "must name"),
    (dict(mtp_layer_types=("attention_only", "moe")), "must name"),
    (dict(moe_experts=0), "'experts_only' layer needs its experts"),
    (dict(mamba=None), "needs the mixer's sizes"),
    (dict(mlp_hidden=64), "no layer of .* has a dense MLP half"),
    (dict(tie_embeddings=True), "shares an untied head"),
])
def test_transformer_lm_rejects(kw, match):
    model = model_of(**kw)
    with pytest.raises(ValueError, match=match):
        jax.eval_shape(model.init, jax.random.PRNGKey(0),
                       jnp.zeros((1, 32), jnp.int32))


@pytest.mark.parametrize("kw,match", [
    (dict(sublayers="mixer", mlp_hidden=32), "mlp_hidden stated"),
    (dict(sublayers="mixer", moe_experts=4, moe_top_k=2), "moe_experts stated"),
    (dict(sublayers="mlp", moe_experts=4, moe_top_k=2,
          mamba=Mamba2Dims(heads=2, head_dim=8, state=8)), "mamba stated"),
    (dict(sublayers="both", moe_shared_hidden=16), "moe_shared_hidden stated"),
    (dict(sublayers="mixer", moe_shared_hidden=16), "moe_shared_hidden stated"),
    (dict(sublayers="half"), "'both', 'mixer' or 'mlp'"),
])
def test_block_rejects_a_size_for_a_half_it_does_not_have(kw, match):
    block = Block(dim=16, heads=2, dtype=jnp.float32, **kw)
    with pytest.raises(ValueError, match=match):
        jax.eval_shape(block.init, jax.random.PRNGKey(0),
                       jnp.zeros((1, 8, 16)), jnp.arange(8)[None])


def test_a_mixed_model_keeps_the_shared_expert_off_its_dense_layers():
    """kanana-2's shape: a dense layer, then expert layers beside a shared
    expert; the dense layer is handed no shared width and builds none."""
    from horovod_tpu.models import LatentDims

    model = TransformerLM(
        vocab=64, dim=32, heads=4, layers=2, dtype=jnp.float32,
        mla=LatentDims(kv_rank=16, qk_nope=8, qk_rope=4, v=8),
        first_k_dense=1, mlp_hidden=48, moe_experts=8, moe_every=1,
        moe_top_k=2, moe_hidden=16, moe_router="sigmoid", moe_shared_hidden=24)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 16), jnp.int32))["params"]
    assert "moe" not in shapes["block_0"] and "mlp_gate" in shapes["block_0"]
    assert "shared_gate" in shapes["block_1"]["moe"]
