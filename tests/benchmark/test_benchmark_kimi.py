"""The ``kimi_linear_48b_a3b`` configuration at a tiny size on the 4-device
virtual CPU mesh: the cell end to end through ``run.run_cell``, the float32
leg against the plain reference with each mechanism the issue names left out
or changed (every one moves the logits or the choice past the file's limits),
latent attention with ``rope=False`` against Kanana's layer with the rotation
taken out, the experts' choice under ``remat`` on a seeded tie, the 32 ranks'
shares adding up to the uncut layer, the file's keys against the catalog's,
the parameter count of the cut, the cost functions against hand counts and
the new readers on a hand-made table."""

from __future__ import annotations

import copy
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests"))

from benchmarks import kda_cost, run  # noqa: E402

CELL = "kimi_linear_seq16384_1chip"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# Tiny sizes under the file's own keys: the model's own first five layers
# (kda, kda, kda, full, kda), a share of 4 experts (4-7) of 16, 3 a token,
# 2 heads of 32 in the KDA mixer (chunks of 16), q | k of 24 against v of 16.
TINY = {"vocab_held": 256, "hidden_size": 64, "num_attention_heads": 2,
        "num_key_value_heads": 2, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 16, "kv_lora_rank": 32,
        "intermediate_size": 96, "moe_intermediate_size": 32,
        "num_experts": 16, "num_experts_per_token": 3, "experts_held": 4,
        "experts_first": 4, "layers": 5, "kda_chunk": 16,
        "linear_attn_config": {"head_dim": 32, "num_heads": 2,
                               "short_conv_kernel_size": 4}}
TRAFFIC = {"seq": 128, "global_rows": 4, "fence_every": 2, "fence_lag": 1,
           "warmup_groups": 1, "trace_groups": 1, "reference_prefix": 96,
           "flash_slice": 64, "scan_slice": 32}


def resolved_tiny(**more):
    resolved = run.resolve_cell(run.load_manifest(), CELL)
    tiny = copy.deepcopy(TINY)
    tiny["linear_attn_config"] = {**resolved["config"]["linear_attn_config"],
                                  **tiny["linear_attn_config"]}
    resolved["config"] = {**resolved["config"], **tiny, **more}
    resolved["traffic"] = dict(TRAFFIC)
    return resolved


@pytest.fixture()
def cpu_memory(monkeypatch):
    monkeypatch.setattr(run, "hbm_bytes", lambda devices: 3 << 30)


def observed_of(out):
    return json.loads(out.split("kernels vs f32 reference (share of "
                                "max|ref|): ")[1].splitlines()[0])


def test_cell_end_to_end_tiny(hvd, cpu_memory, capsys):
    resolved = resolved_tiny()
    result = run.run_cell(resolved, jax.devices()[:4], seed=3, seconds=0.0,
                          trace=0, flash_interpret=True)
    out = capsys.readouterr().out
    assert result["correct"] is True, out
    assert "INCORRECT" not in out
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"train_tok_per_s_per_chip", "step_ms",
                                      "peak_hbm_gib", "setup_s"}
    for metric in result["metrics"].values():
        assert math.isfinite(metric["value"]) and metric["value"] > 0
    json.dumps(result)
    observed = observed_of(out)
    assert set(observed["flash"]) == {"out", "dq", "dk", "dv"}
    assert set(observed["scan"]) == {
        "bf16_as_initialised", "f32_as_initialised", "bf16_strongest",
        "f32_strongest"}
    for name, parts in observed["scan"].items():
        assert set(parts) == {"o", "dq", "dk", "dv", "dg", "dbeta"}
        assert max(parts.values()) <= (1e-5 if name.startswith("f32") else 3e-2)
    assert observed["f32"]["logits"] <= 2e-6 and observed["f32"]["loss"] <= 1e-6
    assert observed["f32"]["flipped_share"] == 0.0
    assert observed["f32"]["held_under"] == "the reference's own choice"
    assert observed["f32"]["bias_differ_share"] == 0.0
    leaves = observed["f32"]["grads_rel"]
    # four KDA layers of 17 leaves and a latent one of 7, a dense second half
    # of 3 and four expert ones of 7, embedding, head and the final norm
    assert len(leaves) == 4 * 17 + 7 + 3 + 4 * 7 + 3
    assert {"layer0.a_log", "layer1.dt_bias", "layer2.conv_q", "layer4.conv_v",
            "layer0.wf_a", "layer0.wf_b", "layer1.wg_a", "layer1.wg_b",
            "layer2.wb", "layer4.o_norm", "layer3.wkva", "layer3.kv_norm",
            "layer1.router", "layer4.s_down", "embed", "head"} <= set(leaves)
    assert max(leaves.values()) <= 2e-5
    assert set(observed["bf16"]["grads_l2_rel"]) == set(leaves)
    assert 1e-3 < max(observed["bf16"]["grads_l2_rel"].values()) <= 0.3
    assert 1e-4 < observed["bf16"]["logits"] <= 2e-2
    assert observed["bias_after_abs_max"] == pytest.approx(0.001)
    gauges = hvd.metrics.registry().snapshot()["gauges"]
    assert gauges["horovod_kda_chunk_len"] == 16
    # the step traced a row of 128 positions a rank: 8 chunks, two blocks
    assert gauges["horovod_kda_saved_state_bytes_per_layer"] == 2 * 2 * 32 * 32 * 4


# ------------------------------------------------ the float32 leg, forwards

def float32_leg(module, config, seed=3, tokens=96):
    """(logits on the agreeing tokens as a share of max|ref|, share of tokens
    whose experts differ in some layer): the system's model in float32 at
    ``highest`` against the plain reference, forwards, on seeded weights."""
    from horovod_tpu.models import BIAS_COLLECTION

    from benchmarks.reference import kimi_linear as plain

    model = module._model(config, dtype=jnp.float32, attention="dense",
                          remat=False)
    key = jax.random.PRNGKey(seed)
    params, bias = jax.jit(module._init_state(model, config))(key)
    ids = module._tokens_fn(config, 1, tokens)(jax.random.fold_in(key, 1))
    cfg = module.reference_config(config)
    with jax.default_matmul_precision("highest"):
        got, state = jax.jit(lambda p, b, t: model.apply(
            {"params": p, BIAS_COLLECTION: b}, t,
            mutable=["intermediates"]))(params, bias, ids)
        want, stats = jax.jit(lambda p, b, t: plain.forward(
            p, b, t, cfg))(module.to_reference(params),
                           module.biases_to_reference(bias), ids)
    agree = jnp.ones(tokens, bool)
    blocks = module._in_layer_order(bias)
    for block, s in zip(blocks, stats):
        chosen = module._chosen_mask(
            state["intermediates"][block]["moe"]["moe_chosen_experts"][0],
            config["num_experts"])
        agree &= jnp.all(chosen == s["chosen"], axis=-1)
    keep = agree[None, :, None]
    share = float(jnp.max(jnp.where(keep, jnp.abs(got - want), 0.0))
                  / jnp.max(jnp.where(keep, jnp.abs(want), 0.0)))
    return share, 1.0 - float(jnp.mean(agree))


# At this size on the CPU the float32 leg reads 2e-6 and no token flips. The
# file's limits (1e-4, four tokens in a thousand) are the CHIP's, where the
# reference's own recurrence accumulates the bias of the TPU's ``exp`` over a
# row (PERF.md §6, PR 48); here the leg is held five times tighter than it.
TINY_LOGITS_REL = 1e-5


def beyond(module, config):
    """Whether the float32 leg is outside the limits."""
    tol = config["tolerance"]
    share, flipped = float32_leg(module, config)
    return (not share <= min(TINY_LOGITS_REL, tol["f32_logits_rel"])
            or not flipped <= tol["f32_flipped_share"]), (share, flipped)


def test_the_float32_leg_agrees_forwards():
    resolved = resolved_tiny()
    wrong, (share, flipped) = beyond(resolved["module"], resolved["config"])
    assert not wrong and share <= 2e-6 and flipped == 0.0


def without_correction(q, k, v, g, beta, chunk):
    """Linear attention with a decay: ``S_t = Diag(alpha_t) S_{t-1} + beta_t
    k_t v_t^T``, the delta rule's ``- beta k k^T`` left out."""
    f32 = jnp.float32
    b, _, h, dk = k.shape

    def step(state, x):
        qt, kt, vt, gt, bt = x
        state = jnp.exp(gt)[..., None] * state + (
            bt[..., None] * kt)[..., None] * vt[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, qt)

    _, o = jax.lax.scan(step, jnp.zeros((b, h, dk, v.shape[-1]), f32), tuple(
        jnp.moveaxis(x.astype(f32), 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1).astype(v.dtype)


FAULTS = ["correction_left_out", "a_decay_a_head_not_a_channel",
          "l2_norm_of_q_left_out", "l2_norm_of_k_left_out",
          "convolution_of_q_left_out", "convolution_of_k_left_out",
          "convolution_of_v_left_out", "silu_after_the_convolutions_left_out",
          "sigmoid_gate_left_out", "gate_then_norm", "rotary_on_the_latent_layer",
          "shared_expert_left_out", "scale_left_out",
          "renormalisation_left_out"]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_wrong_variant_is_not_correct(monkeypatch, fault):
    """What the float32 leg exists for: a model that is not Kimi-Linear's. The
    reference stays what it is; the system's model is built wrong, one part
    at a time, and each moves the logits (or the choice) past the limit."""
    import flax.linen as nn

    from horovod_tpu.models import kda as kda_model, moe as models_moe
    from horovod_tpu.ops import kda as kda_ops

    resolved = resolved_tiny()
    module, config = resolved["module"], resolved["config"]
    real_model = module._model

    def with_fields(**fields):
        monkeypatch.setattr(module, "_model", lambda config, **kw: real_model(
            config, **{**kw, **fields}))

    def nth_call(name, n, wrong):
        """``kda_model.<name>`` with its ``n``-th call of each mixer (q, k, v
        in that order) answered by ``wrong``."""
        real, calls = getattr(kda_model, name), [0]

        def patched(*args):
            calls[0] += 1
            return (wrong if (calls[0] - 1) % 3 == n else real)(*args)

        return patched

    if fault == "correction_left_out":
        monkeypatch.setattr(kda_model, "kda", without_correction)
    elif fault == "a_decay_a_head_not_a_channel":
        monkeypatch.setattr(kda_model, "kda", lambda q, k, v, g, beta, chunk:
                            kda_ops.kda(q, k, v, jnp.broadcast_to(
                                jnp.mean(g, -1, keepdims=True), g.shape),
                                beta, chunk))
    elif fault in ("l2_norm_of_q_left_out", "l2_norm_of_k_left_out"):
        real, calls = kda_model.l2_norm, [0]

        def one_left_out(x):
            calls[0] += 1
            left_out = (calls[0] - 1) % 2 == (fault == "l2_norm_of_k_left_out")
            return x.astype(jnp.float32) if left_out else real(x)

        monkeypatch.setattr(kda_model, "l2_norm", one_left_out)
    elif fault.startswith("convolution_of_"):
        n = "qkv".index(fault[len("convolution_of_")])
        monkeypatch.setattr(kda_model, "conv_silu", nth_call(
            "conv_silu", n, lambda x, taps: nn.silu(x)))
    elif fault == "silu_after_the_convolutions_left_out":
        def conv_alone(x, taps):
            k, t = taps.shape[0], x.shape[1]
            padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
            return sum(taps[j] * padded[:, j:j + t] for j in range(k))

        monkeypatch.setattr(kda_model, "conv_silu", conv_alone)
    elif fault == "sigmoid_gate_left_out":
        real = kda_model.head_norm_then_gate
        monkeypatch.setattr(
            kda_model, "head_norm_then_gate", lambda o, gate, scale, eps:
            real(o, jnp.full_like(gate, 30.0), scale, eps))   # sigmoid = 1
    elif fault == "gate_then_norm":
        def gate_first(o, gate, scale, eps):    # Mamba-2's order, and its silu
            o = o.astype(jnp.float32) * nn.silu(gate).reshape(o.shape)
            o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps)
            return (o * scale).reshape(gate.shape)

        monkeypatch.setattr(kda_model, "head_norm_then_gate", gate_first)
    elif fault == "rotary_on_the_latent_layer":
        with_fields(rope=True)
    elif fault == "shared_expert_left_out":
        real_shared = models_moe.MoEMLP._shared
        monkeypatch.setattr(models_moe.MoEMLP, "_shared", lambda self, tokens:
                            0.0 * real_shared(self, tokens))
    elif fault == "scale_left_out":
        with_fields(moe_route_scale=1.0)
    elif fault == "renormalisation_left_out":
        real = models_moe.sigmoid_route

        def unnormalised(logits, bias, top_k, scale):
            scores, weights, experts = real(logits, bias, top_k, 1.0)
            onehot = experts[:, :, None] == jnp.arange(scores.shape[-1])
            raw = jnp.sum(jnp.where(onehot, scores[:, None, :], 0.0), axis=-1)
            return scores, scale * raw, experts

        monkeypatch.setattr(models_moe, "sigmoid_route", unnormalised)
    wrong, readings = beyond(module, config)
    assert wrong, readings


def test_latent_attention_without_rotary_is_kananas_with_the_rotation_out(
        monkeypatch):
    """``Block`` with ``mla`` and ``rope=False`` is Kanana's layer with the
    rotation taken out, to the bit; with ``rope=True`` it turns q's and k's
    64-wide parts as it always did (the plain reference of Kanana holds that
    layer in tests/benchmark/test_benchmark_kanana.py)."""
    from horovod_tpu.models import LatentDims, transformer

    dims = LatentDims(kv_rank=32, qk_nope=16, qk_rope=8, v=16)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 48, 64), jnp.float32)
    positions = jnp.arange(48)[None]

    def block(rope):
        return transformer.Block(dim=64, heads=2, dtype=jnp.float32, mla=dims,
                                 mlp_hidden=96, rope=rope, rms_norm_eps=1e-5)

    params = block(True).init(jax.random.PRNGKey(1), x, positions)
    turned = block(True).apply(params, x, positions)
    unturned = block(False).apply(params, x, positions)
    assert float(jnp.max(jnp.abs(turned - unturned))) > 1e-3
    assert (jax.tree_util.tree_structure(params)
            == jax.tree_util.tree_structure(
                block(False).init(jax.random.PRNGKey(1), x, positions)))
    monkeypatch.setattr(transformer, "_rope", lambda x, *a, **kw: x)
    np.testing.assert_array_equal(
        np.asarray(block(True).apply(params, x, positions)),
        np.asarray(unturned))
    # and rope=False traces no rotation at all: no cosine in its program
    traced = str(jax.make_jaxpr(lambda p: block(False).apply(p, x, positions))(
        params))
    assert "cos" not in traced and "sin" not in traced


def test_the_choice_under_remat_is_the_forwards_on_a_seeded_tie():
    """Every router weight 0: all 16 scores are 0.5, every choice is a tie.
    The gradients of the model with ``remat`` (whose backward recomputes each
    block, the routers' choice saved by name) are those without."""
    from horovod_tpu.models import BIAS_COLLECTION

    resolved = resolved_tiny(layers=2)
    module, config = resolved["module"], resolved["config"]
    tokens = module._tokens_fn(config, 1, 64)(jax.random.PRNGKey(2))
    grads = {}
    for remat in (False, True):
        model = module._model(config, dtype=jnp.float32, attention="dense",
                              remat=remat)
        params, bias = jax.jit(module._init_state(model, config))(
            jax.random.PRNGKey(5))
        params["block_1"]["moe"]["router"] = jnp.zeros_like(
            params["block_1"]["moe"]["router"])

        def loss(p):
            logits, state = model.apply({"params": p, BIAS_COLLECTION: bias},
                                        tokens, mutable=["intermediates"])
            return jnp.mean(logits ** 2), state["intermediates"]

        (_, inter), grads[remat] = jax.jit(jax.value_and_grad(
            loss, has_aux=True))(params)
        chosen = np.asarray(inter["block_1"]["moe"]["moe_chosen_experts"][0])
        assert chosen.shape == (64, 3)
    for a, b in zip(jax.tree_util.tree_leaves(grads[False]),
                    jax.tree_util.tree_leaves(grads[True])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-7)


def test_the_reference_under_a_forced_choice_is_the_same_program():
    """What the float32 leg does when a token breaks a tie the other way: the
    reference computed under the SYSTEM's sets. Under its own sets handed
    back it gives what it gave; under a set moved by hand it computes under
    that set; and both are one compiled program with the unforced call."""
    resolved = resolved_tiny(layers=2)
    module, config = resolved["module"], resolved["config"]
    plain, _, _ = module.check_programs(config)
    model = module._model(config, dtype=jnp.float32, attention="dense")
    params, bias = jax.jit(module._init_state(model, config))(
        jax.random.PRNGKey(5))
    tokens = module._tokens_fn(config, 1, 64)(jax.random.PRNGKey(2))
    rows = jnp.arange(module.SAMPLED_ROWS, dtype=jnp.int32)
    no_sets = [jnp.zeros((64, config["num_experts"]), bool)]
    own = plain(params, bias, tokens, rows, no_sets, False)
    (chosen,) = own["chosen"]
    assert chosen.shape == (64, 16) and int(chosen.sum()) == 64 * 3
    again = plain(params, bias, tokens, rows, own["chosen"], True)
    assert float(again["loss"]) == float(own["loss"])
    np.testing.assert_array_equal(np.asarray(again["logits"]),
                                  np.asarray(own["logits"]))
    # a token gives up a held expert (4-7) for one it did not choose
    chosen = np.asarray(chosen)
    t = int(np.flatnonzero(chosen[:, 4:8].any(axis=1))[0])
    moved = chosen.copy()
    moved[t, 4 + int(np.flatnonzero(chosen[t, 4:8])[0])] = False
    moved[t, int(np.flatnonzero(~chosen[t])[0])] = True
    under = plain(params, bias, tokens, rows, [jnp.asarray(moved)], True)
    np.testing.assert_array_equal(np.asarray(under["chosen"][0]), moved)
    assert float(jnp.max(jnp.abs(under["logits"][0, t]
                                 - own["logits"][0, t]))) > 0.0
    assert plain._cache_size() == 1


def test_the_32_shares_add_up_to_the_uncut_layer(hvd):
    """What ties the share to the model: the system's expert layer run as each
    of 32 ranks (2 of 64 experts each, all 64 router outputs, the shared
    expert on every rank) gives parts whose sum, the shared expert counted
    once, is the uncut reference's layer."""
    from horovod_tpu.models import BIAS_COLLECTION
    from horovod_tpu.models.moe import MoEMLP

    from benchmarks.reference import kimi_linear as plain

    d, e, width, top_k, ranks = 32, 64, 16, 8, 32
    keys = jax.random.split(jax.random.PRNGKey(5), 8)

    def normal(key, *shape):
        return 0.3 * jax.random.normal(key, shape, jnp.float32)

    whole = {"router": normal(keys[0], d, e), "w_gate": normal(keys[1], e, d, width),
             "w_up": normal(keys[2], e, d, width),
             "w_down": normal(keys[3], e, width, d),
             "s_gate": normal(keys[4], d, width), "s_up": normal(keys[5], d, width),
             "s_down": normal(keys[6], width, d)}
    x = jax.random.normal(keys[7], (2, 24, d), jnp.float32)
    cfg = {"top_k": top_k, "route_scale": 2.446, "held": (0, e)}
    zero = jnp.zeros((e,), jnp.float32)
    with jax.default_matmul_precision("highest"):
        uncut, stats = plain.experts(whole, zero, x.reshape(-1, d), cfg)
        shared = plain.swiglu(x.reshape(-1, d), whole["s_gate"], whole["s_up"],
                              whole["s_down"])
        total = 0.0
        for rank in range(ranks):
            first, count = rank * e // ranks, e // ranks
            layer = MoEMLP(dim=d, hidden=width, n_experts=e, top_k=top_k,
                           dtype=jnp.float32, router="sigmoid",
                           route_scale=2.446, shared_hidden=width,
                           held=(first, count))
            params = {"router": whole["router"],
                      **{k: whole[k][first:first + count]
                         for k in ("w_gate", "w_up", "w_down")},
                      **{f"shared_{k}": {"kernel": whole[f"s_{k}"]}
                         for k in ("gate", "up", "down")}}
            part = layer.apply({"params": params, BIAS_COLLECTION: {
                "router_bias": zero}}, x)
            total = total + (part.reshape(-1, d) - shared)
        total = total + shared
    assert int(stats["counts"].sum()) == 48 * top_k
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               atol=2e-5, rtol=2e-5)
    # and the shares differ: no rank's part alone is the layer
    assert float(jnp.max(jnp.abs(part.reshape(-1, d) - uncut))) > 1e-2


# ----------------------------------------------------- the file, the counts

def test_every_catalog_key_is_in_the_file_as_published():
    config = run.resolve_cell(run.load_manifest(), CELL)["config"]
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(json.loads(line) for line in f
                   if '"Kimi-Linear-48B-A3B-Instruct"' in line)
    for key, value in row["config"].items():
        assert config[key] == value, key
    entry = next(c for c in run.load_manifest()["configs"]
                 if c["name"] == "kimi_linear_48b_a3b")
    assert entry["reduced"] == ["layers", "num_experts", "vocab_size"]
    assert entry["source"].startswith(row["source_url"])
    assert len(entry["source"]) <= 200
    module = run.resolve_cell(run.load_manifest(), CELL)["module"]
    assert module.kinds(config) == ("kda", "kda", "kda", "full", "kda")
    assert (config["layers"], config["experts_held"], config["experts_first"],
            config["vocab_held"], config["kda_chunk"]) == (5, 8, 0, 20480, 64)
    assert config["vocab_held"] * 8 == config["vocab_size"]
    assert config["experts_held"] * 32 == config["num_experts"]
    assert config["vocab_held"] % 128 == 0
    for key, words in {"deployment_share": ("32 chips", "rank 0"),
                       "layers": ("27", "FOUR", "0.77"),
                       "num_experts": ("256", "8 held"),
                       "vocab_size": ("163,840", "20,480"),
                       "parameters": ("602,433,408", "8.98 GiB"),
                       "expert_load": ("512 rows", "32 x their share")}.items():
        for word in words:
            assert word in config["cut"][key], (key, word)
    assert len(config["assumed"]) >= 8
    assert set(config["tolerance"]) >= {
        "f32_logits_rel", "f32_grads_rel", "f32_loss_rel", "f32_flipped_share",
        "f32_bias_differ_share", "bf16_logits_rel", "bf16_grads_l2_rel",
        "bf16_loss_rel", "bf16_flipped_share", "bf16_bias_differ_share",
        "flash_rel", "f32_scan_rel", "bf16_scan_rel", "why"}
    traffic = run.resolve_cell(run.load_manifest(), CELL)["traffic"]
    assert {k: traffic[k] for k in (
        "seq", "global_rows", "fence_every", "fence_lag", "warmup_groups",
        "trace_groups", "reference_prefix", "flash_slice", "scan_slice")} == {
        "seq": 16384, "global_rows": 1, "fence_every": 5, "fence_lag": 1,
        "warmup_groups": 2, "trace_groups": 2, "reference_prefix": 2048,
        "flash_slice": 2048, "scan_slice": 512}


def test_the_manifest_holds_the_new_cell():
    manifest = run.load_manifest()
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert cell == {**cell, "config": "kimi_linear_48b_a3b",
                    "traffic": "seq16384x1_fence5_kda", "chips": 1}
    # by name, not by place: later cells come after it and join its lists
    assert [w["name"] for w in manifest["workloads"]].count(CELL) == 1
    resolved = run.resolve_cell(manifest, CELL)
    names = {m["name"] for m in resolved["per_layer"]}
    new = {"kda_mixer_ms_per_step", "kda_proj_ms_per_step",
           "kda_conv_ms_per_step", "kda_gate_ms_per_step",
           "kda_scan_ms_per_step", "kda_out_norm_ms_per_step",
           "kda_scan_roofline_pct", "kda_chunk_len"}
    assert new <= names
    assert {"flash_fwd_ms_per_step", "mla_proj_ms_per_step",
            "mla_rope_ms_per_step", "moe_shared_ms_per_step",
            "moe_dispatch_rows_per_layer", "unnamed_device_ms_per_step"} <= names
    assert not {"mla_flash_ms_per_step", "mla_flash_roofline_pct",
                "mla_ms_per_step", "ssd_ms_per_step"} & names
    for metric in manifest["per_layer"]:
        if metric["name"] in new:
            assert (metric["layer"], metric["moves"]) == ("KDA mixer",
                                                          "step_ms")
            assert CELL in metric["workloads"]
            assert os.path.exists(os.path.join(
                REPO, "benchmarks", "layer_metrics", metric["name"] + ".py"))
    assert {m["name"] for m in resolved["end_to_end"]} == {
        "train_tok_per_s_per_chip", "step_ms", "peak_hbm_gib", "setup_s"}


def test_parameter_count_of_the_cut_is_the_files():
    """The share's parameters, counted from the model's own shapes at the
    published widths (abstractly: nothing is allocated)."""
    resolved = run.resolve_cell(run.load_manifest(), CELL)
    module, config = resolved["module"], resolved["config"]
    params, bias = jax.eval_shape(module._init_state(module._model(
        config, attention="dense"), config), jax.random.PRNGKey(0))

    def count(tree):
        return sum(math.prod(p.shape) for p in jax.tree_util.tree_leaves(tree))

    assert count(params) == 602_433_408
    assert count(params["block_0"]["mixer"]) == 39_514_272
    assert count(params["block_0"]) == 103_219_872
    assert count(params["block_1"]) == 103_809_696
    assert count(params["block_3"]) == 93_410_304
    assert "mixer" not in params["block_3"]
    assert sorted(bias) == [f"block_{i}" for i in range(1, 5)]
    assert params["block_1"]["moe"]["w_gate"].shape == (8, 2304, 1024)
    assert params["block_1"]["moe"]["router"].shape == (2304, 256)
    assert params["block_0"]["mlp_gate"]["kernel"].shape == (2304, 9216)
    assert params["block_0"]["mixer"]["f_b_proj"]["kernel"].shape == (128, 4096)
    assert params["block_0"]["mixer"]["dt_bias"].shape == (4096,)
    assert params["block_0"]["mixer"]["A_log"].shape == (32,)
    assert params["block_3"]["q_proj"]["kernel"].shape == (2304, 32 * 192)
    assert params["lm_head"]["kernel"].shape == (2304, 20480)


def test_adamw_decays_matrices_and_not_the_taps():
    resolved = resolved_tiny()
    module, config = resolved["module"], resolved["config"]
    params, _ = jax.eval_shape(module._init_state(module._model(
        config, attention="dense"), config), jax.random.PRNGKey(0))
    decayed = jax.tree_util.tree_map_with_path(module._is_matrix, params)
    mixer = decayed["block_0"]["mixer"]
    assert not any(mixer[k] for k in ("A_log", "dt_bias", "o_norm", "q_conv",
                                      "k_conv", "v_conv"))
    assert all(mixer[k]["kernel"] for k in ("q_proj", "f_a_proj", "f_b_proj",
                                            "b_proj", "g_b_proj", "o_proj"))
    assert decayed["embed"]["embedding"] and decayed["lm_head"]["kernel"]
    assert decayed["block_1"]["moe"]["w_gate"]
    assert not decayed["block_1"]["RMSNorm_0"]["scale"]


def test_seeded_state_is_initialised_as_the_file_says():
    resolved = resolved_tiny()
    module, config = resolved["module"], resolved["config"]
    params, bias = jax.jit(module._init_state(module._model(
        config, attention="dense"), config))(jax.random.PRNGKey(4))
    mixer = params["block_2"]["mixer"]
    a = np.exp(np.asarray(mixer["A_log"]))
    assert a.min() >= 1.0 and a.max() <= 16.0
    dt = np.asarray(jax.nn.softplus(mixer["dt_bias"]))
    assert dt.min() >= 0.999e-3 and dt.max() <= 0.1001
    taps = np.asarray(mixer["q_conv"])
    assert np.abs(taps).max() <= 0.5 and np.abs(taps).max() > 0.4
    assert np.std(np.asarray(params["embed"]["embedding"])) == pytest.approx(
        config["init"]["embedding_std"], rel=0.05)
    assert np.std(np.asarray(mixer["q_proj"]["kernel"])) == pytest.approx(
        0.02, rel=0.1)
    np.testing.assert_array_equal(np.asarray(mixer["o_norm"]), 1.0)
    assert all(float(jnp.max(jnp.abs(b))) == 0.0
               for b in jax.tree_util.tree_leaves(bias))


def test_costs_against_hand_counts():
    resolved = run.resolve_cell(run.load_manifest(), CELL)
    cost = resolved["module"].cost(resolved["config"], resolved["traffic"], 1)
    # a chunk of 64 and a head: two (C x C x 128) products on the causal
    # half, W and U likewise, the scores times D, three whole (C x 128 x 128)
    chunk_head = (2 * 64 * 64 * 128 + 64 * 64 * 256 + 64 * 64 * 128
                  + 3 * 2 * 64 * 128 * 128)
    forward = 256 * 32 * chunk_head
    assert kda_cost.kda_forward_flops(16384, 32, 128, 128, 64) == forward
    # four KDA layers; the forward twice (remat), the backward at twice one
    assert cost["kda_scan"]["flops"] == 4 * 4 * forward
    one_pass = 16384 * 32 * (2 * 4 * 128 + 4 * 128 + 4)
    assert cost["kda_scan"]["bytes"] == 4 * 4 * one_pass
    assert kda_cost.kda_scan_step_cost(16384, 1, 4, 32, 128, 128, 64)[
        "flops"] == 4 * 3 * forward             # without remat
    assert kda_cost.kda_scan_step_cost(
        16384, 1, 1, 32, 128, 128, 64)["bytes"] == 3 * one_pass
    # no metric of this cell reads the flash calls' needed work
    assert set(cost) == {"model_flops", "kda_scan"}
    # a token, forward
    kda = 2 * (4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32)
    full = 2 * (2304 * 6144 + 2304 * 576 + 512 * 8192 + 4096 * 2304)
    dense = 6 * 2304 * 9216
    expert = 2 * 2304 * 256 + 6 * 2304 * 1024 + 6 * 2304 * 1024 * 8 * 8 / 256
    head = 2 * 2304 * 20480
    attn = 16384 * 16384 * (192 + 128) * 32
    want = 3 * (16384 * (4 * kda + full + dense + 4 * expert + head)
                + 4 * forward + attn)
    assert cost["model_flops"] == pytest.approx(want, rel=1e-12)
    assert 41e12 < want < 43e12


def test_readers_on_a_hand_made_table(hvd, monkeypatch):
    from benchmarks import named_device_time

    seconds = {"hvd_kda_proj": 0.130, "hvd_kda_conv": 0.001,
               "hvd_kda_conv_fwd": 0.012, "hvd_kda_conv_bwd": 0.010,
               "hvd_kda_gate": 0.015, "hvd_kda_scan": 0.200,
               "hvd_kda_out_norm": 0.008, "hvd_mla_proj": 0.02}
    table = {"seconds": dict(seconds), "unnamed": 0.05}
    monkeypatch.setattr(named_device_time, "_tables", [table])
    lines = []
    context = {"trace": {"steps": 10}, "log": lines.append,
               "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
               "cost": {"kda_scan": {"flops": 1.168e12, "bytes": 12.9e9}}}

    def read(name):
        return run.load_module(os.path.join(
            REPO, "benchmarks", "layer_metrics", name + ".py")).read(context)

    assert read("kda_proj_ms_per_step") == pytest.approx(130.0)
    assert read("kda_conv_ms_per_step") == pytest.approx(23.0)
    assert read("kda_gate_ms_per_step") == pytest.approx(15.0)
    assert read("kda_scan_ms_per_step") == pytest.approx(200.0)
    assert read("kda_out_norm_ms_per_step") == pytest.approx(8.0)
    assert read("kda_mixer_ms_per_step") == pytest.approx(376.0)
    # bound by HBM: 12.9e9 / 819e9 = 15.75 ms against 5.93 ms by operations
    assert read("kda_scan_roofline_pct") == pytest.approx(
        100 * 15.751 / 200.0, rel=1e-3)
    assert "bound by HBM bandwidth" in lines[-1]
    hvd.metrics.registry().gauge("horovod_kda_chunk_len").set(64)
    assert read("kda_chunk_len") == 64
    # a program without the names or the gauge (the parent): nothing, no raise
    table["seconds"] = {"hvd_mla_proj": 0.02}
    hvd.metrics.registry().gauge("horovod_kda_chunk_len").set(0)
    for name in ("kda_mixer_ms_per_step", "kda_proj_ms_per_step",
                 "kda_conv_ms_per_step", "kda_gate_ms_per_step",
                 "kda_scan_ms_per_step", "kda_out_norm_ms_per_step",
                 "kda_scan_roofline_pct", "kda_chunk_len"):
        assert read(name) is None
    table["seconds"] = dict(seconds)
    context["cost"] = {}
    assert read("kda_scan_roofline_pct") is None
