"""The ``laguna_xs_2`` configuration at a tiny size on the 4-device virtual CPU
mesh: the cell end to end through ``run.run_cell``, wrong variants of the model
that are not ``correct`` (each part the issue forbids leaving out), a lower
precision in the router that fails the float32 limit, the eight ranks' shares
of an expert layer adding up to the uncut reference's, the file's keys against
the catalog's, the cost functions against hand counts, the new readers on a
hand-made table, and the two copies of the plain reference held to the same
outputs."""

from __future__ import annotations

import copy
import dataclasses
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

from benchmarks import named_device_time, run, swa_cost  # noqa: E402

CELL = "laguna_xs2_seq16384_1chip"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# Tiny sizes under the file's own keys: a dense layer (full attention, 4
# heads) and two expert layers (sliding, 6 heads) over 2 key/value heads of
# 16, a window of 24, a share of 4 experts (4-7) of 16, 3 a token.
TINY = {"vocab_held": 256, "hidden_size": 64, "head_dim": 16,
        "num_key_value_heads": 2,
        "num_attention_heads_per_layer": [4, 6, 6, 6, 4] + [4] * 35,
        "intermediate_size": 96, "moe_intermediate_size": 32,
        "shared_expert_intermediate_size": 32, "num_experts": 16,
        "num_experts_per_tok": 3, "experts_held": 4, "experts_first": 4,
        "layers": 3, "sliding_window": 24}
# the reference's prefix is longer than four windows
TRAFFIC = {"seq": 128, "global_rows": 4, "fence_every": 2, "fence_lag": 1,
           "warmup_groups": 1, "trace_groups": 1, "reference_prefix": 112,
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
    for check in ("flash_window", "flash_full"):
        assert set(observed[check]) == {"out", "dq", "dk", "dv"}
        assert 0 < max(observed[check].values()) <= 1.2e-2
    assert observed["f32"]["logits"] <= 2e-6 and observed["f32"]["loss"] <= 1e-6
    assert observed["f32"]["flipped_share"] == 0.0
    assert observed["f32"]["held_under"] == "the reference's own choice"
    leaves = observed["f32"]["grads_rel"]
    assert len(leaves) == 10 + 2 * 14 + 3       # every leaf of every layer
    assert {"layer1.router", "layer2.w_gate", "layer1.s_down", "layer0.wg",
            "layer2.wk", "layer1.wv", "embed", "head"} <= set(leaves)
    assert max(leaves.values()) <= 2e-5
    assert set(observed["bf16"]["grads_l2_rel"]) == set(leaves)
    assert 1e-3 < max(observed["bf16"]["grads_l2_rel"].values()) <= 2e-1
    assert 1e-4 < observed["bf16"]["logits"] <= 2e-2
    # what the layers sowed: the held experts' share of 112 tokens x 3 x 2
    # layers, about a quarter
    assert 0 < observed["f32"]["live_rows"] < 112 * 3 * 2
    assert observed["f32"]["live_rows"] == round(sum(
        share * 112 * 3 for share in observed["f32"]["held_share"]))
    # the step traced the held share's windows (one window here: 128 x 3) and
    # the sliding layers' band: 128 positions in blocks of 128 are one block
    gauges = hvd.metrics.registry().snapshot()["gauges"]
    assert gauges["horovod_moe_dispatch_rows"] == 128 * 3


def check_alone(hvd, **tiny):
    """The configuration's checks without the step."""
    resolved = resolved_tiny()
    resolved["config"].update(**tiny)
    mesh = hvd.data_parallel_mesh(jax.devices()[:4])
    return resolved, lambda: resolved["module"].reference(
        resolved["config"], resolved["traffic"], mesh, 3, flash_interpret=True)


FAULTS = ["window_left_out", "head_counts_left_out", "gate_left_out",
          "partial_rotation_left_out", "yarn_blend_left_out",
          "attention_factor_left_out", "sliding_base_not_10000",
          "renormalisation_left_out", "scale_left_out",
          "shared_expert_left_out"]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_wrong_variant_is_not_correct(hvd, monkeypatch, fault):
    """What the float32 leg exists for: a model that is not Laguna-XS.2. The
    reference stays what it is; the system's model is built wrong, one part
    at a time, and each moves the logits (or the choice) past the limit. The
    head counts are shapes: a model with ONE head count has other parameters
    than the file's, and the reference says so before any leg."""
    import flax.linen as nn

    from horovod_tpu.models import moe as models_moe

    resolved, check = check_alone(hvd)
    module = resolved["module"]
    real_model = module._model

    def with_fields(**fields):
        monkeypatch.setattr(module, "_model", lambda config, **kw: real_model(
            config, **{**kw, **fields}))

    def full_rotary(**changed):
        with_fields(full_rotary=dataclasses.replace(
            module._rotary(resolved["config"], "full_attention"), **changed))

    match = ("against its float32 references: .*f32 (logits on the agreeing "
             "tokens|share of tokens whose experts differ) = ")
    if fault == "window_left_out":
        with_fields(sliding_window=TRAFFIC["seq"])
    elif fault == "head_counts_left_out":
        with_fields(heads_per_layer=None, heads=4)
        match = "layer 1: wq is 64 wide, 6 query heads of 16 are 96"
    elif fault == "gate_left_out":
        monkeypatch.setattr(nn, "sigmoid", lambda x: jnp.ones_like(x))
    elif fault == "partial_rotation_left_out":
        full_rotary(dims=None)
    elif fault == "yarn_blend_left_out":
        full_rotary(factor=None)
    elif fault == "attention_factor_left_out":
        full_rotary(attention_factor=1.0)
    elif fault == "sliding_base_not_10000":
        with_fields(sliding_rotary=dataclasses.replace(
            module._rotary(resolved["config"], "sliding_attention"),
            theta=500000.0))
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
    elif fault == "shared_expert_left_out":
        real_shared = models_moe.MoEMLP._shared
        monkeypatch.setattr(models_moe.MoEMLP, "_shared", lambda self, tokens:
                            0.0 * real_shared(self, tokens))
    with pytest.raises(AssertionError, match=match):
        check()


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


def test_a_tie_broken_the_other_way_is_held_under_the_systems_choice(
        hvd, monkeypatch):
    """A token whose 3rd and 4th score are tied within rounding, made by
    hand on ONE token: its share is held as a share, and loss, logits and
    gradients against the reference computed under the system's choice
    (``route(forced=)``), at the float32 limits."""
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
    resolved, check = check_alone(hvd, layers=2)
    resolved["config"]["tolerance"] = {**resolved["config"]["tolerance"],
                                       "f32_flipped_share": 0.02,
                                       "bf16_flipped_share": 1.0}
    observed = check()["observed"]
    assert observed["f32"]["held_under"] == "the system's choice"
    assert observed["f32"]["flipped_share"] == pytest.approx(1 / 112, rel=1e-4)
    assert observed["f32"]["logits"] <= 2e-6 and observed["f32"]["loss"] <= 1e-6
    assert max(observed["f32"]["grads_rel"].values()) <= 2e-5
    assert observed["bf16"]["held_under"] == "the reference's own choice"


def test_the_eight_shares_add_up_to_the_uncut_layer(hvd):
    """What ties the share to the model: the system's expert layer run as each
    of 8 ranks (4 of 32 experts each, all 32 router outputs, the shared
    expert on every rank) gives parts whose sum, the shared expert counted
    once, is the uncut reference's layer."""
    from horovod_tpu.models import BIAS_COLLECTION
    from horovod_tpu.models.moe import MoEMLP

    from benchmarks.reference import laguna as plain

    d, e, width, top_k, ranks = 32, 32, 16, 3, 8
    keys = jax.random.split(jax.random.PRNGKey(5), 8)

    def normal(key, *shape):
        return 0.3 * jax.random.normal(key, shape, jnp.float32)

    whole = {"router": normal(keys[0], d, e), "w_gate": normal(keys[1], e, d, width),
             "w_up": normal(keys[2], e, d, width),
             "w_down": normal(keys[3], e, width, d),
             "s_gate": normal(keys[4], d, width), "s_up": normal(keys[5], d, width),
             "s_down": normal(keys[6], width, d)}
    x = jax.random.normal(keys[7], (2, 24, d), jnp.float32)
    cfg = {"top_k": top_k, "route_scale": 2.5, "held": (0, e)}
    with jax.default_matmul_precision("highest"):
        uncut, stats = plain.experts(whole, x.reshape(-1, d), cfg)
        shared = plain.swiglu(x.reshape(-1, d), whole["s_gate"], whole["s_up"],
                              whole["s_down"])
        total = 0.0
        for rank in range(ranks):
            first, count = rank * e // ranks, e // ranks
            layer = MoEMLP(dim=d, hidden=width, n_experts=e, top_k=top_k,
                           dtype=jnp.float32, router="sigmoid",
                           route_scale=2.5, shared_hidden=width,
                           held=(first, count))
            params = {"router": whole["router"],
                      **{k: whole[k][first:first + count]
                         for k in ("w_gate", "w_up", "w_down")},
                      **{f"shared_{k}": {"kernel": whole[f"s_{k}"]}
                         for k in ("gate", "up", "down")}}
            part = layer.apply({"params": params, BIAS_COLLECTION: {
                "router_bias": jnp.zeros((e,), jnp.float32)}}, x)
            total = total + (part.reshape(-1, d) - shared)
        total = total + shared
    assert int(stats["counts"].sum()) == 48 * top_k
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               atol=2e-5, rtol=2e-5)
    # and the shares differ: no rank's part alone is the layer
    assert float(jnp.max(jnp.abs(part.reshape(-1, d) - uncut))) > 1e-2


def test_every_catalog_key_is_in_the_file_as_published():
    config = run.resolve_cell(run.load_manifest(), CELL)["config"]
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(json.loads(line) for line in f if '"Laguna-XS.2"' in line)
    for key, value in row["config"].items():
        assert config[key] == value, key
    entry = next(c for c in run.load_manifest()["configs"]
                 if c["name"] == "laguna_xs_2")
    assert entry["reduced"] == ["layers", "num_experts", "vocab_size"]
    assert entry["source"].startswith(row["source_url"])
    assert len(entry["source"]) <= 200
    assert (config["layers"], config["experts_held"], config["experts_first"],
            config["vocab_held"]) == (5, 32, 0, 12544)
    assert config["vocab_held"] * 8 == config["vocab_size"]
    assert config["experts_held"] * 8 == config["num_experts"]
    assert config["vocab_held"] % 128 == 0
    for key, words in {"deployment_share": ("8 chips", "rank 0"),
                       "layers": ("40", "whole period", "1.6 x"),
                       "num_experts": ("256", "32 held"),
                       "vocab_size": ("100,352", "12,544"),
                       "parameters": ("691.6 M", "10.31 GiB"),
                       "expert_load": ("512 rows", "8 x their share")}.items():
        for word in words:
            assert word in config["cut"][key], (key, word)
    assumed = " ".join(config["assumed"])
    for word in ("per head", "renormalises", "NO bias", "no auxiliary loss",
                 "no QK-norm", "p - 512 < j <= p", "(i, i + half)", "truncate"):
        assert word in assumed, word
    assert set(config["tolerance"]) >= {
        "f32_logits_rel", "f32_grads_rel", "f32_loss_rel", "f32_flipped_share",
        "bf16_logits_rel", "bf16_grads_l2_rel", "bf16_loss_rel",
        "bf16_flipped_share", "flash_rel", "why"}
    # the cell's lists: the throughput, the per-name readers the model emits
    manifest = run.load_manifest()
    listed = {m["name"] for kind in ("end_to_end", "per_layer")
              for m in manifest[kind] if CELL in m.get("workloads", [])}
    assert listed == {
        "train_tok_per_s_per_chip", "flash_fwd_ms_per_step",
        "flash_bwd_dq_ms_per_step", "flash_bwd_dkv_ms_per_step",
        "moe_route_ms_per_step", "moe_dispatch_ms_per_step",
        "moe_combine_ms_per_step", "moe_grouped_ms_per_step",
        "moe_shared_ms_per_step", "unnamed_device_ms_per_step",
        "swa_flash_ms_per_step", "swa_flash_roofline_pct",
        "attn_gate_ms_per_step", "attn_rope_ms_per_step", "swa_block_share",
        "full_flash_roofline_pct"}


def test_parameter_count_of_the_cut_is_the_files():
    """The share's parameters, counted from the model's own shapes at the
    published widths (abstractly: nothing is allocated)."""
    resolved = run.resolve_cell(run.load_manifest(), CELL)
    module, config = resolved["module"], resolved["config"]
    model = module._model(config, attention="dense")
    assert model.layer_types == ("full_attention", "sliding_attention",
                                 "sliding_attention", "sliding_attention",
                                 "full_attention")
    assert model.heads_per_layer == (48, 64, 64, 64, 48)
    shapes = module._shapes(model)
    params = shapes["params"]
    count = sum(math.prod(p.shape) for p in jax.tree_util.tree_leaves(params))
    assert 691.5e6 < count < 691.8e6
    assert sorted(shapes["moe_bias"]) == [f"block_{i}" for i in range(1, 5)]
    assert params["block_1"]["moe"]["w_gate"].shape == (32, 2048, 512)
    assert params["block_1"]["moe"]["router"].shape == (2048, 256)
    assert params["block_0"]["mlp_gate"]["kernel"].shape == (2048, 8192)
    assert params["block_0"]["q_proj"]["kernel"].shape == (2048, 48 * 128)
    assert params["block_2"]["q_proj"]["kernel"].shape == (2048, 64 * 128)
    assert params["block_2"]["o_proj"]["kernel"].shape == (64 * 128, 2048)
    assert params["block_2"]["kv_proj"]["kernel"].shape == (2048, 2 * 8 * 128)
    assert params["block_2"]["gate_proj"]["kernel"].shape == (2048, 64)
    assert params["block_4"]["gate_proj"]["kernel"].shape == (2048, 48)
    assert params["lm_head"]["kernel"].shape == (2048, 12544)
    # the two rotary schemes as the file states them
    full, sliding = model.full_rotary, model.sliding_rotary
    assert (full.dims, full.theta, full.factor, full.original_max) == (
        64, 500000.0, 64.0, 4096)
    assert full.scale() == 1.4158883083359672
    assert (sliding.dims, sliding.theta, sliding.factor) == (128, 10000.0, None)
    # and the reference reads the same numbers
    cfg = module.reference_config(config)
    assert cfg["rope_full"]["dims"] == 64 and cfg["window"] == 512
    assert cfg["heads"] == (48, 64, 64, 64, 48) and cfg["held"] == (0, 32)


def test_costs_against_hand_counts():
    resolved = run.resolve_cell(run.load_manifest(), CELL)
    cost = resolved["module"].cost(resolved["config"], resolved["traffic"], 1)
    t, w = 16384, 512
    band = t * w - w * (w - 1) // 2         # sum over p of min(p + 1, 512)
    assert swa_cost.attended_pairs(t, w) == band == sum(
        min(p + 1, w) for p in range(t))
    assert swa_cost.attended_pairs(t) == t * t // 2
    assert swa_cost.attended_pairs(t, t) == t * t // 2
    assert swa_cost.attended_pairs(8, 3) == 1 + 2 + 3 * 6
    # three sliding layers at 64 heads: the forward twice (remat), the
    # backward's five once; a product is 2 x pairs x 128 a head
    assert cost["swa_flash"]["flops"] == 3 * (2 * band * 128 * 64) * (2 * 2 + 5)
    assert 3.6e12 < cost["swa_flash"]["flops"] < 3.7e12
    by_q, by_kv, lse = t * 64 * 128 * 2, t * 8 * 128 * 2, t * 64 * 4
    assert cost["swa_flash"]["bytes"] == 3 * (
        2 * (2 * by_q + 2 * by_kv + lse) + 4 * by_q + 4 * by_kv + lse)
    # two full layers at 48 heads on the causal half
    assert cost["full_flash"]["flops"] == 2 * (2 * (t * t // 2) * 128 * 48) * 9
    assert 29.6e12 < cost["full_flash"]["flops"] < 29.8e12
    by_q, lse = t * 48 * 128 * 2, t * 48 * 4
    assert cost["full_flash"]["bytes"] == 2 * (
        2 * (2 * by_q + 2 * by_kv + lse) + 4 * by_q + 4 * by_kv + lse)
    assert swa_cost.flash_calls_step_cost(t, 1, 48, 8, 128, 2)["flops"] == (
        2 * (2 * (t * t // 2) * 128 * 48) * 7)      # without remat: seven
    # a token, forward: the projections and the gate; dense MLP; router +
    # shared + one held pair (8 x 32 / 256); head over the held rows
    full = 2 * 2048 * (2 * 48 * 128 + 2 * 8 * 128 + 48)
    sliding = 2 * 2048 * (2 * 64 * 128 + 2 * 8 * 128 + 64)
    dense = 6 * 2048 * 8192
    expert = 2 * 2048 * 256 + 6 * 2048 * 512 + 6 * 2048 * 512 * 8 * 32 / 256
    head = 2 * 2048 * 12544
    attn = 2 * (4 * (t * t // 2) * 128 * 48) + 3 * (4 * band * 128 * 64)
    want = 3 * (t * (2 * full + 3 * sliding + dense + 4 * expert + head) + attn)
    assert cost["model_flops"] == pytest.approx(want, rel=1e-12)
    assert 49e12 < want < 50e12


def test_readers_on_a_hand_made_table(hvd, monkeypatch):
    seconds = {"hvd_flash_win_fwd": 0.010, "hvd_flash_win_bwd_dq": 0.012,
               "hvd_flash_win_bwd_dkv": 0.018, "hvd_flash_fwd": 0.080,
               "hvd_flash_bwd_dq": 0.060, "hvd_flash_bwd_dkv": 0.070,
               "hvd_attn_gate": 0.004, "hvd_attn_rope": 0.011}
    monkeypatch.setattr(named_device_time, "_tables",
                        [{"seconds": seconds, "unnamed": 0.2}])
    logged = []
    context = {"trace": {"steps": 10}, "log": logged.append,
               "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
               "cost": {"swa_flash": {"flops": 3.65e12, "bytes": 2.0e9},
                        "full_flash": {"flops": 29.7e12, "bytes": 1.1e9}}}

    def read(name):
        return run.load_module(os.path.join(
            REPO, "benchmarks", "layer_metrics", name + ".py")).read(context)

    assert read("swa_flash_ms_per_step") == pytest.approx(40.0)
    assert read("attn_gate_ms_per_step") == pytest.approx(4.0)
    assert read("attn_rope_ms_per_step") == pytest.approx(11.0)
    # bound by compute: 3.65e12 / 197e12 = 18.53 ms against 2.44 by bytes
    assert read("swa_flash_roofline_pct") == pytest.approx(
        100 * 18.528 / 40.0, rel=1e-4)
    assert read("full_flash_roofline_pct") == pytest.approx(
        100 * 150.761 / 210.0, rel=1e-4)
    assert len(logged) == 2 and "bound by compute" in logged[0]
    hvd.metrics.registry().gauge("horovod_flash_window_block_share").set(
        63 / 528)
    assert read("swa_block_share") == pytest.approx(0.1193, abs=1e-4)
    # a program without the names or the gauge (the parent): nothing, no raise
    monkeypatch.setattr(named_device_time, "_tables", [
        {"seconds": {"hvd_flash_fwd": 0.08}, "unnamed": 0.2}])
    hvd.metrics.registry().gauge("horovod_flash_window_block_share").set(0)
    for name in ("swa_flash_ms_per_step", "swa_flash_roofline_pct",
                 "attn_gate_ms_per_step", "attn_rope_ms_per_step",
                 "swa_block_share", "full_flash_roofline_pct"):
        assert read(name) is None, name
    monkeypatch.setattr(named_device_time, "_tables", [None])
    assert read("swa_flash_ms_per_step") is None
    context["cost"] = {}
    monkeypatch.setattr(named_device_time, "_tables",
                        [{"seconds": seconds, "unnamed": 0.2}])
    assert read("swa_flash_roofline_pct") is None


def test_the_two_reference_copies_agree():
    from benchmarks.reference import laguna as bench_copy
    from references import laguna as test_copy

    with open(bench_copy.__file__) as a, open(test_copy.__file__) as b:
        assert a.read() == b.read()
    cfg = {"hidden": 16, "head_dim": 8, "kv_heads": 2, "heads": (2, 4, 2),
           "kinds": ("full_attention", "sliding_attention", "full_attention"),
           "eps": 1e-6, "window": 5,
           "rope_full": {"theta": 500000.0, "dims": 4, "factor": 64.0,
                         "original_max": 4096, "beta_fast": 64.0,
                         "beta_slow": 1.0, "attention_factor": 1.4},
           "rope_sliding": {"theta": 10000.0}, "top_k": 2, "route_scale": 2.5,
           "held": (2, 2), "experts": 8, "first_k_dense": 1, "dense_width": 24,
           "expert_width": 8, "shared_width": 12, "vocab": 32}
    tokens = jnp.arange(12).reshape(1, 12) % 32
    outs = []
    for m in (bench_copy, test_copy):
        params = m.init_params(jax.random.PRNGKey(0), cfg, scale=0.3)
        outs.append(m.loss_and_grads(params, tokens, cfg))
    for a, b in zip(*(jax.tree_util.tree_leaves(o) for o in outs)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    (loss, parts), grads = outs[0]
    assert math.isfinite(float(loss)) and len(parts["chosen"]) == 2
    assert all(int(c.sum()) == 12 * 2 for c in parts["counts"])
    # only experts 2 and 3 are held: their leaves, and no other's, exist
    assert grads["layers"][1]["w_gate"].shape == (2, 16, 8)
    assert "router" not in grads["layers"][0]
    assert grads["layers"][1]["wq"].shape == (16, 4 * 8)
    assert grads["layers"][1]["wg"].shape == (16, 4)
    # the window: a key farther back than 5 positions does not move a
    # sliding layer's output, and moves a full layer's
    layer = params["layers"][1]
    h = jax.random.normal(jax.random.PRNGKey(1), (1, 12, 16))
    moved = h.at[0, 2].add(1.0)
    for kind, same in (("sliding_attention", True), ("full_attention", False)):
        a, b = (bench_copy.attention(layer, x, cfg, kind, 4) for x in (h, moved))
        assert bool(jnp.allclose(a[0, 7:], b[0, 7:], atol=1e-6)) is same
        assert not jnp.allclose(a[0, 2:7], b[0, 2:7], atol=1e-6)
    # YaRN's frequencies at Laguna-XS.2's numbers: the program's are the same
    from horovod_tpu.models import RotaryScheme

    want = bench_copy.yarn_inv_freq(64, 500000.0, 64.0, 4096, 64.0, 1.0)
    got = RotaryScheme(theta=500000.0, dims=64, factor=64.0, original_max=4096,
                       beta_fast=64.0, beta_slow=1.0).inv_freq(64)
    np.testing.assert_allclose(got, want.astype(np.float32), rtol=1e-7)
