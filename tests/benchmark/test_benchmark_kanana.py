"""The ``kanana_2_30b_a3b`` configuration at a tiny size on the 4-device
virtual CPU mesh: the cell end to end through ``run.run_cell`` (the bias one
on every rank after the steps), wrong variants of the model that are not
``correct`` (each part the issue forbids leaving out), a lower precision in
the router that fails the float32 limit, the file's keys against the
catalog's, the cost functions against hand counts, the four new readers on a
hand-made ``breakdown``, and the two copies of the plain reference held to the
same outputs."""

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

from benchmarks import mla_cost, run  # noqa: E402

CELL = "kanana2_seq8192_1chip"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# Tiny sizes under the file's own keys: a dense layer and two expert layers,
# a share of 4 experts (4-7) of 16, 3 a token, q | k of 24 against v of 16.
TINY = {"vocab_held": 256, "hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 4, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "qk_head_dim": 24, "v_head_dim": 16,
        "kv_lora_rank": 32, "intermediate_size": 96,
        "moe_intermediate_size": 32, "n_routed_experts": 16,
        "num_experts_per_tok": 3, "experts_held": 4, "experts_first": 4,
        "layers": 3}
TRAFFIC = {"seq": 128, "global_rows": 4, "fence_every": 2, "fence_lag": 1,
           "warmup_groups": 1, "trace_groups": 1, "reference_prefix": 96,
           "flash_slice": 64}


def resolved_tiny():
    resolved = run.resolve_cell(run.load_manifest(), CELL)
    resolved["config"] = {**resolved["config"], **copy.deepcopy(TINY)}
    resolved["traffic"] = dict(TRAFFIC)
    return resolved


@pytest.fixture()
def cpu_memory(monkeypatch):
    monkeypatch.setattr(run, "hbm_bytes", lambda devices: 3 << 30)


def observed_of(out):
    return json.loads(out.split("kernels vs f32 reference (share of "
                                "max|ref|): ")[1].splitlines()[0])


def test_cell_end_to_end_tiny(hvd, cpu_memory, capsys, monkeypatch):
    resolved = resolved_tiny()
    built = {}
    real_build = resolved["module"].build

    def keep(*args, **kw):
        built.update(real_build(*args, **kw))
        return built

    monkeypatch.setattr(resolved["module"], "build", keep)
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
    assert observed["f32"]["logits"] <= 2e-6 and observed["f32"]["loss"] <= 1e-6
    assert observed["f32"]["flipped_share"] == 0.0
    assert observed["f32"]["held_under"] == "the reference's own choice"
    assert observed["f32"]["bias_differ_share"] == 0.0
    leaves = observed["f32"]["grads_rel"]
    assert len(leaves) == 10 + 2 * 14 + 3       # every leaf of every layer
    assert {"layer1.router", "layer2.w_gate", "layer1.s_down", "layer0.wkva",
            "layer2.kv_norm", "embed", "head"} <= set(leaves)
    assert max(leaves.values()) <= 2e-5
    assert set(observed["bf16"]["grads_l2_rel"]) == set(leaves)
    assert 1e-3 < max(observed["bf16"]["grads_l2_rel"].values()) <= 1e-1
    assert 1e-4 < observed["bf16"]["logits"] <= 2e-2
    assert observed["bias_after_abs_max"] == pytest.approx(0.001)
    # the rule moved the bias in the steps, by 0.001 a step at most, and the
    # summed load keeps it ONE bias on every rank
    bias = built["state"][2]
    assert sorted(bias) == ["block_1", "block_2"]
    for leaves in bias.values():
        b = leaves["moe"]["router_bias"]
        shards = [np.asarray(s.data) for s in b.addressable_shards]
        assert len(shards) == 4
        for other in shards[1:]:
            np.testing.assert_array_equal(shards[0], other)
        assert 0 < np.max(np.abs(shards[0])) <= 0.001 * 10 + 1e-9    # <= 10 steps
    # AdamW's state holds moments for params only: no leaf of the bias
    assert "router_bias" not in str(jax.tree_util.tree_structure(built["state"][1]))
    # the step traced the worst-case buffer: 128 tokens a rank x 3
    gauges = hvd.metrics.registry().snapshot()["gauges"]
    assert gauges["horovod_moe_dispatch_rows"] == 128 * 3


def check_alone(hvd, **tiny):
    """The configuration's checks without the step: a dense layer and one
    expert layer, so that a fault costs two compilations and not three."""
    resolved = resolved_tiny()
    resolved["config"].update(layers=2, **tiny)
    mesh = hvd.data_parallel_mesh(jax.devices()[:4])
    return resolved, lambda: resolved["module"].reference(
        resolved["config"], resolved["traffic"], mesh, 3, flash_interpret=True)


FAULTS = ["shared_expert_left_out", "renormalisation_left_out",
          "scale_left_out", "latent_norm_left_out", "rotary_left_out",
          "rotary_halves_not_pairs", "bias_not_in_the_choice"]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_wrong_variant_is_not_correct(hvd, monkeypatch, fault):
    """What the float32 leg exists for: a model that is not kanana-2's. The
    reference stays what it is; the system's model is built wrong, one part
    at a time, and each moves the logits (or the choice) past the limit."""
    import flax.linen as nn

    from horovod_tpu.models import moe as models_moe, transformer

    resolved, check = check_alone(hvd)
    module = resolved["module"]
    real_model = module._model

    def with_fields(**fields):
        monkeypatch.setattr(module, "_model", lambda config, **kw: real_model(
            config, **{**kw, **fields}))

    if fault == "shared_expert_left_out":
        real_shared = models_moe.MoEMLP._shared
        monkeypatch.setattr(models_moe.MoEMLP, "_shared", lambda self, tokens:
                            0.0 * real_shared(self, tokens))
    elif fault == "renormalisation_left_out":
        real = models_moe.sigmoid_route

        def unnormalised(logits, bias, top_k, scale):
            scores, weights, experts = real(logits, bias, top_k, 1.0)
            onehot = experts[:, :, None] == jnp.arange(scores.shape[-1])
            raw = jnp.sum(jnp.where(onehot, scores[:, None, :], 0.0), axis=-1)
            return scores, scale * raw, experts

        monkeypatch.setattr(models_moe, "sigmoid_route", unnormalised)
    elif fault == "scale_left_out":
        with_fields(moe_route_scale=1.0)
    elif fault == "latent_norm_left_out":
        real_norm = nn.RMSNorm.__call__

        def no_latent_norm(self, x, *a, **kw):
            normed = real_norm(self, x, *a, **kw)
            return x + 0.0 * normed if self.name == "kv_a_norm" else normed

        monkeypatch.setattr(nn.RMSNorm, "__call__", no_latent_norm)
    elif fault == "rotary_left_out":
        monkeypatch.setattr(transformer, "_rope", lambda x, *a, **kw: x)
    elif fault == "rotary_halves_not_pairs":
        with_fields(rope_interleave=False)
    elif fault == "bias_not_in_the_choice":
        real = models_moe.sigmoid_route
        monkeypatch.setattr(
            models_moe, "sigmoid_route", lambda logits, bias, top_k, scale:
            real(logits, jnp.zeros_like(bias), top_k, scale))
        # a bias that matters: the check starts from a seeded one
        real_init = module._init_state

        def biased(model):
            init = real_init(model)

            def with_bias(key):
                params, bias = init(key)
                return params, jax.tree_util.tree_map(
                    lambda b: 0.3 * jax.random.normal(key, b.shape), bias)
            return with_bias

        monkeypatch.setattr(module, "_init_state", biased)
    with pytest.raises(AssertionError, match="against its float32 references: "
                       ".*f32 (logits on the agreeing tokens|share of tokens "
                       "whose experts differ) = "):
        check()


def test_a_seeded_bias_is_still_correct(hvd, monkeypatch):
    """The control of ``bias_not_in_the_choice``: with the bias in the choice
    on both sides the float32 leg agrees from a bias that is not 0."""
    resolved, check = check_alone(hvd)
    module = resolved["module"]
    real_init = module._init_state

    def biased(model):
        init = real_init(model)

        def with_bias(key):
            params, bias = init(key)
            return params, jax.tree_util.tree_map(
                lambda b: 0.3 * jax.random.normal(key, b.shape), bias)
        return with_bias

    monkeypatch.setattr(module, "_init_state", biased)
    observed = check()["observed"]
    assert observed["f32"]["flipped_share"] == 0.0
    assert observed["f32"]["logits"] <= 2e-6


def test_a_tie_broken_the_other_way_is_held_under_the_systems_choice(
        hvd, monkeypatch):
    """In float32 on the chip one seed in five has a token whose 6th and 7th
    score + bias are tied within rounding; the system then takes the other
    one. Made here by hand on ONE token: its share is held as a share, and
    loss, logits, gradients and biases against the reference computed under
    the system's choice, at the float32 limits."""
    from horovod_tpu.models import moe as models_moe

    real = models_moe.sigmoid_route

    def other_way(logits, bias, top_k, scale):
        scores, _, experts = real(logits, bias, top_k, scale)
        _, wider = jax.lax.top_k(scores + bias, top_k + 1)
        first = jnp.arange(experts.shape[0])[:, None] == 0      # token 0 alone
        last = jnp.arange(top_k)[None, :] == top_k - 1
        experts = jnp.where(first & last, wider[:, top_k:], experts)
        onehot = experts[:, :, None] == jnp.arange(scores.shape[-1])
        weights = jnp.sum(jnp.where(onehot, scores[:, None, :], 0.0), axis=-1)
        return scores, scale * weights / (weights.sum(-1, keepdims=True)
                                          + 1e-20), experts

    monkeypatch.setattr(models_moe, "sigmoid_route", other_way)
    resolved, check = check_alone(hvd)
    with pytest.raises(AssertionError, match="f32 share of tokens whose "
                                             "experts differ = 1.042e-02"):
        check()                 # 1 of 96 tokens: over the file's 4 of 2048
    resolved["config"]["tolerance"] = {**resolved["config"]["tolerance"],
                                       "f32_flipped_share": 0.02,
                                       "bf16_flipped_share": 1.0,
                                       "bf16_bias_differ_share": 1.0}
    observed = check()["observed"]
    assert observed["f32"]["held_under"] == "the system's choice"
    assert observed["f32"]["flipped_share"] == pytest.approx(1 / 96, rel=1e-4)
    assert observed["f32"]["logits"] <= 2e-6 and observed["f32"]["loss"] <= 1e-6
    assert max(observed["f32"]["grads_rel"].values()) <= 2e-5
    assert observed["bf16"]["held_under"] == "the reference's own choice"


def test_bf16_router_scores_fail_the_float32_limit(hvd, monkeypatch):
    """The configuration states float32 router scores: rounded to bf16 they
    tie, other experts are chosen, and the float32 leg's share of flipped
    tokens (or, where no token flips, its logits) is beyond its limit."""
    from horovod_tpu.models import moe as models_moe

    real = models_moe.sigmoid_route

    def rounded(logits, bias, top_k, scale):
        return real(jax.lax.optimization_barrier(
            logits.astype(jnp.bfloat16)).astype(jnp.float32), bias, top_k, scale)

    monkeypatch.setattr(models_moe, "sigmoid_route", rounded)
    _, check = check_alone(hvd)
    with pytest.raises(AssertionError, match="f32 (logits on the agreeing "
                       "tokens|share of tokens whose experts differ) = "):
        check()


def test_every_catalog_key_is_in_the_file_as_published():
    config = run.resolve_cell(run.load_manifest(), CELL)["config"]
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(json.loads(line) for line in f
                   if '"kanana-2-30b-a3b-instruct-2601"' in line)
    for key, value in row["config"].items():
        assert config[key] == value, key
    entry = next(c for c in run.load_manifest()["configs"]
                 if c["name"] == "kanana_2_30b_a3b")
    assert entry["reduced"] == ["layers", "n_routed_experts", "vocab_size"]
    assert entry["source"].startswith(row["source_url"])
    assert len(entry["source"]) <= 200
    assert (config["layers"], config["experts_held"], config["experts_first"],
            config["vocab_held"]) == (5, 16, 0, 16032)
    assert config["vocab_held"] * 8 == config["vocab_size"]
    assert config["experts_held"] * 8 == config["n_routed_experts"]
    for key, words in {"deployment_share": ("8 chips", "rank 0"),
                       "layers": ("48", "FOUR"),
                       "n_routed_experts": ("128", "16 held"),
                       "vocab_size": ("128,256", "16,032"),
                       "parameters": ("576.0 M", "8.58 GiB"),
                       "expert_load": ("768 rows", "8 x their share")}.items():
        for word in words:
            assert word in config["cut"][key], (key, word)
    assert set(config["tolerance"]) >= {
        "f32_logits_rel", "f32_grads_rel", "f32_loss_rel", "f32_flipped_share",
        "f32_bias_differ_share", "bf16_logits_rel", "bf16_grads_l2_rel",
        "bf16_loss_rel", "bf16_flipped_share", "bf16_bias_differ_share",
        "flash_rel", "why"}


def test_parameter_count_of_the_cut_is_the_files():
    """The share's parameters, counted from the model's own shapes at the
    published widths (abstractly: nothing is allocated)."""
    resolved = run.resolve_cell(run.load_manifest(), CELL)
    module, config = resolved["module"], resolved["config"]
    params, bias = jax.eval_shape(module._init_state(module._model(
        config, attention="dense")), jax.random.PRNGKey(0))
    count = sum(math.prod(p.shape) for p in jax.tree_util.tree_leaves(params))
    assert 575.9e6 < count < 576.1e6
    assert sorted(bias) == [f"block_{i}" for i in range(1, 5)]
    assert params["block_1"]["moe"]["w_gate"].shape == (16, 2048, 768)
    assert params["block_1"]["moe"]["router"].shape == (2048, 128)
    assert params["block_0"]["mlp_gate"]["kernel"].shape == (2048, 6144)
    assert params["block_3"]["q_proj"]["kernel"].shape == (2048, 32 * 192)
    assert params["block_3"]["kv_b_proj"]["kernel"].shape == (512, 32 * 256)
    assert params["lm_head"]["kernel"].shape == (2048, 16032)


def test_costs_against_hand_counts():
    resolved = run.resolve_cell(run.load_manifest(), CELL)
    cost = resolved["module"].cost(resolved["config"], resolved["traffic"], 1)
    at_qk, at_v = 8192 * 8192 * 192 * 32, 8192 * 8192 * 128 * 32
    # the forward twice (remat), the backward's five once; 2 rows, 5 layers
    assert cost["mla_flash"]["flops"] == 2 * 5 * (2 * (at_qk + at_v)
                                                  + 3 * at_qk + 2 * at_v)
    assert 31.5e12 < cost["mla_flash"]["flops"] < 31.7e12
    wide, narrow, lse = (2 * 8192 * 32 * 192 * 2, 2 * 8192 * 32 * 128 * 2,
                         2 * 8192 * 32 * 4)
    assert cost["mla_flash"]["bytes"] == 5 * (
        2 * (2 * wide + 2 * narrow + lse) + 4 * wide + 4 * narrow + lse)
    assert mla_cost.flash_calls_step_cost(8192, 2, 32, 192, 128, 5)["flops"] == (
        2 * 5 * (4 * at_qk + 3 * at_v))     # without remat: flash_step_cost's 7
    # a token, forward: four projections; dense MLP; router + shared + 0.75
    # held pairs; head over the held rows
    proj = 2 * (2048 * 6144 + 2048 * 576 + 512 * 8192 + 4096 * 2048)
    dense = 6 * 2048 * 6144
    expert = 2 * 2048 * 128 + 6 * 2048 * 1536 + 6 * 2048 * 768 * 6 * 16 / 128
    head = 2 * 2048 * 16032
    attn = 8192 * 8192 * (192 + 128) * 32
    want = 3 * (16384 * (5 * proj + dense + 4 * expert + head) + 2 * 5 * attn)
    assert cost["model_flops"] == pytest.approx(want, rel=1e-12)
    # ISSUE 32 counts 49.8 TFLOP with the kernels' seven attention products
    # (24.7); flops.py's convention, forward x 3, counts six (20.6)
    assert 45.5e12 < want < 46e12


def test_readers_on_a_hand_made_breakdown(hvd):
    trace = {"steps": 4, "breakdown": {"device_ops": [
        ["kernel [bench_fwd_bwd] jit(_fwd_call)/hvd_flash_fwd/pallas_call", 0.48],
        ["kernel [bench_fwd_bwd] jit(_bwd_rule)/hvd_flash_bwd_dkv/pallas_call", 0.44],
        ["kernel [bench_fwd_bwd] jit(_bwd_rule)/hvd_flash_bwd_dq/pallas_call", 0.36],
        ["fusion [bench_fwd_bwd] hvd_mla_proj/q_proj/dot_general", 0.2],
        ["fusion [bench_fwd_bwd] block_N/hvd_mla_rope/concatenate", 0.12],
        ["fusion [bench_fwd_bwd] moe/hvd_moe_shared/shared_up/dot_general", 0.1]]}}
    context = {"trace": trace, "log": lambda *a: None,
               "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
               "cost": {"mla_flash": {"flops": 31.6e12, "bytes": 20e9}}}

    def read(name):
        return run.load_module(os.path.join(
            REPO, "benchmarks", "layer_metrics", name + ".py")).read(context)

    assert read("mla_flash_ms_per_step") == pytest.approx(320.0)
    assert read("mla_ms_per_step") == pytest.approx(400.0)
    # bound by compute: 31.6e12 / 197e12 = 160.4 ms against 24.4 ms by bytes
    assert read("mla_flash_roofline_pct") == pytest.approx(
        100 * 160.406 / 320.0, rel=1e-4)
    hvd.metrics.registry().gauge("horovod_moe_dispatch_rows").set(98304)
    assert read("moe_dispatch_rows_per_layer") == 98304
    # a program without the names or the gauge (the parent): nothing, no raise
    trace["breakdown"]["device_ops"] = [["fusion x/mlp_in/dot_general", 1.0]]
    hvd.metrics.registry().gauge("horovod_moe_dispatch_rows").set(0)
    for name in ("mla_flash_ms_per_step", "mla_ms_per_step",
                 "mla_flash_roofline_pct", "moe_dispatch_rows_per_layer"):
        assert read(name) is None
    context["cost"] = {}
    assert read("mla_flash_roofline_pct") is None


def test_the_two_reference_copies_agree():
    from benchmarks.reference import kanana2 as bench_copy
    from references import kanana2 as test_copy

    with open(bench_copy.__file__) as a, open(test_copy.__file__) as b:
        assert a.read() == b.read()
    cfg = {"hidden": 16, "heads": 2, "qk_nope": 4, "qk_rope": 4, "v_dim": 4,
           "kv_rank": 8, "eps": 1e-6, "rope_theta": 1e6, "top_k": 2,
           "route_scale": 2.448, "held": (2, 2), "experts": 8,
           "first_k_dense": 1, "dense_width": 24, "expert_width": 8,
           "shared_width": 12, "vocab": 32}
    tokens = jnp.arange(12).reshape(1, 12) % 32
    outs = []
    for m in (bench_copy, test_copy):
        params, biases = m.init_params(jax.random.PRNGKey(0), cfg, layers=3,
                                       scale=0.3)
        biases = [b + 0.1 * jnp.arange(8) for b in biases]
        (loss, parts), grads = m.loss_and_grads(params, biases, tokens, cfg)
        outs.append((loss, parts, grads,
                     [m.bias_update(b, c, 0.001)
                      for b, c in zip(biases, parts["counts"])]))
    for a, b in zip(*(jax.tree_util.tree_leaves(o) for o in outs)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    loss, parts, grads, after = outs[0]
    assert math.isfinite(float(loss)) and len(parts["chosen"]) == 2
    assert all(int(c.sum()) == 12 * 2 for c in parts["counts"])
    # only experts 2 and 3 are held: their leaves, and no other's, exist
    assert grads["layers"][1]["w_gate"].shape == (2, 16, 8)
    assert "router" not in grads["layers"][0]
