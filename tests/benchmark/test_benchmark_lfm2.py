"""The ``lfm2_24b_a2b`` configuration at a tiny size on the virtual CPU mesh:
the cell end to end through ``run.run_cell``; the share adding up (8 expert
ranks x 1 expert, and 2 x 4: the sum over ranks of the held experts' parts is
what the uncut reference gives for the whole expert layer, behind a conv
mixer and behind an attention mixer); the reference under a forced choice;
the file's keys against the catalog's; the parameter count of the cut term by
term and the whole model's from the published keys; the cost functions
against hand counts and the new readers on hand-made gauges and tables. It
asserts that the manifest HOLDS the cell and its metrics, not that either is
last or alone in a list: the next configuration must not break it."""

from __future__ import annotations

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

from benchmarks import lfm2_cost, run  # noqa: E402

CELL = "lfm2_seq8192_1chip"
CONFIG = "lfm2_24b_a2b"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# Tiny sizes under the file's own keys: the cut's seven layers at a width of
# 128, 4 | 2 heads of 32, experts 4-7 of 16 (expert rank 1 of 4), 3 a token,
# a quarter of a vocabulary of 1,024.
TINY = {"vocab_held": 256, "vocab_size": 1024, "hidden_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "intermediate_size": 192, "moe_intermediate_size": 64,
        "num_experts": 16, "expert_parallel": 4, "experts_held": 4,
        "experts_first": 4, "num_experts_per_tok": 3}
TRAFFIC = {"seq": 512, "global_rows": 4, "fence_every": 2, "fence_lag": 1,
           "warmup_groups": 1, "trace_groups": 1, "reference_prefix": 256,
           "flash_slice": 128, "conv_slice": 512}
CONV_LEAVES = ("taps", "w_in", "w_out")
ATTENTION_LEAVES = ("k_norm", "q_norm", "wkv", "wo", "wq")
EXPERT_LEAVES = ("router", "w_down", "w_gate", "w_up")
DENSE_LEAVES = ("w_down", "w_gate", "w_up")
CUT = ("conv", "full_attention", "conv", "conv", "conv", "full_attention",
       "conv")


def resolved_tiny(**more):
    resolved = run.resolve_cell(run.load_manifest(), CELL)
    resolved["config"] = {**resolved["config"], **TINY, **more}
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
    result = run.run_cell(resolved, jax.devices()[:2], seed=3, seconds=0.0,
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
    assert max(observed["flash"].values()) <= 1e-2
    assert set(observed["conv"]) == {"bf16", "f32"}
    for name, parts in observed["conv"].items():
        assert set(parts) == {"y", "d_bcx", "d_taps"}
        # bf16: one rounding of values up to the array's largest (2^-9 of it)
        assert max(parts.values()) <= (2e-6 if name == "f32" else 8e-3)
    assert observed["f32"]["logits"] <= 2e-6 and observed["f32"]["loss"] <= 1e-6
    # the loss starts near ln(held rows): logits of a seeded model are small
    assert observed["f32"]["loss_value"] == pytest.approx(math.log(256), rel=0.02)
    assert observed["f32"]["flipped_share"] == 0.0
    assert observed["f32"]["held_under"] == "the reference's own choice"
    assert observed["f32"]["bias_differ_share"] == 0.0
    leaves = observed["f32"]["grads_rel"]
    kinds = dict(enumerate(CUT))
    assert set(leaves) == (
        {f"layer{i}.{leaf}" for i, k in kinds.items() if k == "conv"
         for leaf in CONV_LEAVES}
        | {f"layer{i}.{leaf}" for i, k in kinds.items() if k != "conv"
           for leaf in ATTENTION_LEAVES}
        | {f"layer{i}.{leaf}" for i in range(1, 7) for leaf in EXPERT_LEAVES}
        | {f"layer0.{leaf}" for leaf in DENSE_LEAVES}
        | {f"layer{i}.{leaf}" for i in range(7)
           for leaf in ("attn_norm", "mlp_norm")}
        | {"embed", "final_norm"})      # tied: no head of its own
    assert max(leaves.values()) <= 2e-5
    assert set(observed["bf16"]["grads_l2_rel"]) == set(leaves)
    assert 1e-3 < max(observed["bf16"]["grads_l2_rel"].values()) <= 0.3
    assert 1e-4 < observed["bf16"]["logits"] <= 2e-2
    assert observed["bias_after_abs_max"] == pytest.approx(0.001)
    assert len(observed["f32"]["held_share"]) == 6      # the expert layers
    assert all(0.1 < s < 0.45 for s in observed["f32"]["held_share"])
    gauges = hvd.metrics.registry().snapshot()["gauges"]
    assert gauges["horovod_short_conv_taps"] == 3
    assert gauges["horovod_short_conv_kernel_passes"] >= 1


def test_a_limit_that_is_passed_makes_the_run_incorrect(hvd, cpu_memory, capsys):
    """Each of the cell's limits decides ``correct`` by itself: with the
    convolution's bf16 limit below its sound reading the run reports
    ``correct`` false and still reports."""
    resolved = resolved_tiny()
    resolved["config"]["tolerance"] = {**resolved["config"]["tolerance"],
                                       "bf16_conv_rel": 1e-6}
    result = run.run_cell(resolved, jax.devices()[:1], seed=5, seconds=0.0,
                          trace=0, flash_interpret=True)
    out = capsys.readouterr().out
    assert result["correct"] is False
    assert "INCORRECT" in out and "conv bf16" in out


# ------------------------------------------------------ the share adds up

def normal(key, *shape):
    return 0.5 * jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)


@pytest.mark.parametrize("ranks,held", [(8, 1), (2, 4)])
@pytest.mark.parametrize("kind", ["conv", "attention"])
def test_the_ranks_expert_parts_add_up_to_the_uncut_layer(hvd, kind, ranks,
                                                          held):
    """One layer of the SYSTEM a rank (``Block`` with ``moe_held``), every
    rank the same mixer, router and bias and its own ``held`` of 8 experts:
    the ranks' outputs less the stream after the mixer sum to the uncut
    reference's whole expert layer."""
    from horovod_tpu.models import BIAS_COLLECTION, ShortConvDims
    from horovod_tpu.models.transformer import Block

    from benchmarks.reference import lfm2_moe as plain

    dim, width, n, top_k, t = 128, 64, ranks * held, 3, 48
    cfg = {"heads": 4, "kv_heads": 2, "head_dim": 32, "eps": 1e-5,
           "theta": 1e6, "top_k": top_k, "route_scale": 1.0,
           "route_eps": 1e-6, "held": (0, n)}
    whole = {"attn_norm": 1.0 + 0.1 * normal(1, dim),
             "mlp_norm": 1.0 + 0.1 * normal(2, dim),
             "router": normal(3, dim, n), "w_gate": normal(4, n, dim, width),
             "w_up": normal(5, n, dim, width), "w_down": normal(6, n, width, dim)}
    if kind == "conv":
        whole.update(w_in=0.2 * normal(7, dim, 3 * dim), taps=normal(8, 3, dim),
                     w_out=0.2 * normal(9, dim, dim))
        mixer = {"mixer": {"in_proj": {"kernel": whole["w_in"]},
                           "conv_kernel": whole["taps"],
                           "out_proj": {"kernel": whole["w_out"]}}}
    else:
        whole.update(wq=0.2 * normal(7, dim, dim), wkv=0.2 * normal(8, dim, dim),
                     q_norm=1.0 + 0.1 * normal(9, 32),
                     k_norm=1.0 + 0.1 * normal(10, 32),
                     wo=0.2 * normal(11, dim, dim))
        mixer = {"q_proj": {"kernel": whole["wq"]},
                 "kv_proj": {"kernel": whole["wkv"]},
                 "q_head_norm": {"scale": whole["q_norm"]},
                 "k_head_norm": {"scale": whole["k_norm"]},
                 "o_proj": {"kernel": whole["wo"]}}
    bias = 0.05 * normal(12, n)
    x = normal(13, 1, t, dim)
    with jax.default_matmul_precision("highest"):
        op = plain.short_conv if kind == "conv" else plain.attention
        mid = x + op(whole, plain.rms(x, whole["attn_norm"], 1e-5), cfg)
        h2 = plain.rms(mid, whole["mlp_norm"], 1e-5).reshape(-1, dim)
        uncut, stats = plain.experts(whole, bias, h2, cfg)
        parts = []
        for rank in range(ranks):
            mine = slice(rank * held, (rank + 1) * held)
            block = Block(
                dim=dim, heads=4, kv_heads=2, dtype=jnp.float32,
                conv=ShortConvDims(3) if kind == "conv" else None,
                qk_head_norm=True, rope_theta=1e6, rms_norm_eps=1e-5,
                moe_experts=n, moe_top_k=top_k, moe_hidden=width,
                moe_router="sigmoid", moe_route_eps=1e-6,
                moe_held=(rank * held, held))
            params = {"RMSNorm_0": {"scale": whole["attn_norm"]},
                      "RMSNorm_1": {"scale": whole["mlp_norm"]}, **mixer,
                      "moe": {"router": whole["router"],
                              **{k: whole[k][mine]
                                 for k in ("w_gate", "w_up", "w_down")}}}
            out = block.apply(
                {"params": params,
                 BIAS_COLLECTION: {"moe": {"router_bias": bias}}},
                x, jnp.arange(t)[None], mutable=["intermediates"])[0]
            parts.append((out - mid).reshape(-1, dim))
    assert int(stats["counts"].sum()) == t * top_k
    largest = float(jnp.max(jnp.abs(uncut)))
    # float32 sums in another order: 1e-5 of the layer's largest output
    assert float(jnp.max(jnp.abs(sum(parts) - uncut))) <= 1e-5 * largest
    # no rank alone is the layer
    assert all(float(jnp.max(jnp.abs(p - uncut))) > 1e-2 * largest
               for p in parts)


def test_the_reference_under_a_forced_choice_is_the_same_program():
    """``route(forced=)``: under its own choice handed back the reference
    gives what it gave; under another set the weights follow that set."""
    from benchmarks.reference import lfm2_moe as plain

    cfg = {"top_k": 3, "route_scale": 1.0, "route_eps": 1e-6}
    h2, router = normal(1, 40, 32), normal(2, 32, 16)
    bias = 0.05 * normal(3, 16)
    with jax.default_matmul_precision("highest"):
        w, chosen, s = plain.route(h2, router, bias, cfg)
        again, same, _ = plain.route(h2, router, bias, cfg, forced=chosen)
        unused, kept, _ = plain.route(h2, router, bias, cfg,
                                      forced=(False, ~chosen))
        other = jnp.roll(chosen, 1, axis=1)
        moved, taken, _ = plain.route(h2, router, bias, cfg,
                                      forced=(True, other))
    np.testing.assert_array_equal(np.asarray(w), np.asarray(again))
    np.testing.assert_array_equal(np.asarray(chosen), np.asarray(same))
    np.testing.assert_array_equal(np.asarray(w), np.asarray(unused))
    np.testing.assert_array_equal(np.asarray(chosen), np.asarray(kept))
    np.testing.assert_array_equal(np.asarray(taken), np.asarray(other))
    np.testing.assert_allclose(
        np.asarray(moved), np.asarray(jnp.where(other, s, 0.0) / (
            jnp.sum(jnp.where(other, s, 0.0), -1, keepdims=True) + 1e-6)),
        rtol=1e-6)
    assert int(chosen.sum()) == 40 * 3


# ----------------------------------------------------- the file, the counts

def test_every_catalog_key_is_in_the_file_as_published():
    resolved = run.resolve_cell(run.load_manifest(), CELL)
    config, module = resolved["config"], resolved["module"]
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(json.loads(line) for line in f
                   if '"name": "LFM2-24B-A2B"' in line)
    for key, value in row["config"].items():
        assert config[key] == value, key
    entry = next(c for c in run.load_manifest()["configs"]
                 if c["name"] == CONFIG)
    assert entry["reduced"] == ["layers", "num_dense_layers", "num_experts",
                                "vocab_size"]
    assert entry["source"].startswith(row["source_url"])
    assert entry["file"] == "benchmarks/configs/lfm2_24b_a2b.json"
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    # the cut's kinds out of the published list: the model's own layers
    published = config["layer_types"]
    assert len(published) == 40 and published.count("full_attention") == 10
    assert [i for i, k in enumerate(published) if k == "full_attention"] == list(
        range(2, 40, 4))
    assert config["layers_run"] == [0, 2, 3, 4, 5, 6, 7]
    assert module.kinds(config) == CUT
    assert {k: config[k] for k in (
        "layers", "dense_layers_held", "expert_parallel", "experts_held",
        "experts_first", "vocab_held", "tie_word_embeddings",
        "router_norm_eps", "attention", "remat", "throughput_metric")} == {
        "layers": 7, "dense_layers_held": 1, "expert_parallel": 8,
        "experts_held": 8, "experts_first": 0, "vocab_held": 8192,
        "tie_word_embeddings": True, "router_norm_eps": 1e-6,
        "attention": "flash", "remat": True,
        "throughput_metric": "train_tok_per_s_per_chip"}
    assert config["vocab_held"] * 8 == config["vocab_size"]
    assert config["vocab_held"] % 128 == 0
    assert set(entry["reduced"]) | {"deployment_share", "parameters",
                                    "expert_load"} == set(config["cut"])
    for key, words in {"deployment_share": ("Expert parallel 8", "rank 0",
                                            "no code stands in"),
                       "layers": ("40 published", "7 run", "1.14 x"),
                       "num_dense_layers": ("2 published", "1 held"),
                       "num_experts": ("64 published", "8 held", "9.66 GB"),
                       "vocab_size": ("65,536", "8,192", "tied"),
                       "parameters": ("647,819,520", "9.65 GiB", "16,783,360",
                                      "10,485,888", "23.84 B"),
                       "expert_load": ("1,024 rows", "8 x their share")}.items():
        for word in words:
            assert word in config["cut"][key], (key, word)
    assert "expert parallel 8" in config["deployment"]
    assert len(config["assumed"]) >= 7
    for n, word in enumerate(("TIED", "1e-6", "sign rule", "intermediate_size",
                              "BEFORE the rotation")):
        assert word in config["assumed"][n], (n, word)
    assert set(config["tolerance"]) >= {
        "f32_logits_rel", "f32_grads_rel", "f32_loss_rel", "f32_flipped_share",
        "f32_bias_differ_share", "bf16_logits_rel", "bf16_grads_l2_rel",
        "bf16_loss_rel", "bf16_flipped_share", "bf16_bias_differ_share",
        "flash_rel", "f32_conv_rel", "bf16_conv_rel", "why"}
    traffic = resolved["traffic"]
    assert {k: traffic[k] for k in (
        "seq", "global_rows", "fence_every", "fence_lag", "warmup_groups",
        "trace_groups", "reference_prefix", "flash_slice", "conv_slice")} == {
        "seq": 8192, "global_rows": 2, "fence_every": 5, "fence_lag": 1,
        "warmup_groups": 2, "trace_groups": 2, "reference_prefix": 2048,
        "flash_slice": 2048, "conv_slice": 8192}


@pytest.mark.parametrize("change", [
    {"layers_run": [0, 1, 2, 3, 4, 5, 6]},      # a second dense layer as experts
    {"layers_run": [2, 3, 4, 5, 6, 7, 8]},      # no leading dense layer
    {"layers_run": [0, 2, 3]},                  # not `layers` long
    {"experts_held": 16}, {"vocab_held": 4096}, {"conv_bias": True},
    {"tie_word_embeddings": False}, {"use_expert_bias": False},
])
def test_a_share_that_is_not_the_layouts_is_refused(change):
    resolved = run.resolve_cell(run.load_manifest(), CELL)
    with pytest.raises(ValueError, match="lfm2_24b_a2b"):
        resolved["module"]._model({**resolved["config"], **change})


def test_the_manifest_holds_the_new_cell():
    manifest = run.load_manifest()
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert cell == {**cell, "config": CONFIG,
                    "traffic": "seq8192x2_fence5_sconv", "chips": 1}
    assert len(cell["why"]) <= 200
    resolved = run.resolve_cell(manifest, CELL)
    names = {m["name"] for m in resolved["per_layer"]}
    new = {"sconv_proj_ms_per_step", "sconv_conv_ms_per_step", "sconv_taps",
           "sconv_conv_roofline_pct"}
    joined = {"flash_fwd_ms_per_step", "flash_bwd_dq_ms_per_step",
              "flash_bwd_dkv_ms_per_step", "attn_proj_ms_per_step",
              "attn_other_ms_per_step", "norm_add_ms_per_step",
              "embed_ms_per_step", "lm_head_ms_per_step", "mlp_ms_per_step",
              "moe_route_ms_per_step", "moe_dispatch_ms_per_step",
              "moe_combine_ms_per_step", "moe_grouped_ms_per_step",
              "moe_logits_ms_per_step", "moe_weight_cast_ms_per_step",
              "moe_dispatch_rows_per_layer", "unnamed_device_ms_per_step",
              "named_device_share_pct"}
    assert new | joined <= names
    assert not {"ssd_roofline_pct", "mamba_conv_ms_per_step",
                "kda_conv_ms_per_step", "attn_rope_ms_per_step",
                "moe_shared_ms_per_step", "mla_proj_ms_per_step"} & names
    for metric in manifest["per_layer"]:
        if metric["name"] in new | joined:
            assert CELL in metric["workloads"]
            assert os.path.exists(os.path.join(
                REPO, "benchmarks", "layer_metrics", metric["name"] + ".py"))
        if metric["name"] in new:
            assert (metric["layer"], metric["moves"], metric["workloads"]) == (
                "Short-convolution mixer", "step_ms", [CELL])
            assert metric["source"] == ("program_counter" if metric["name"]
                                        == "sconv_taps" else "device_trace")
    assert {m["name"] for m in resolved["end_to_end"]} == {
        "train_tok_per_s_per_chip", "step_ms", "peak_hbm_gib", "setup_s"}


def count(tree):
    return sum(math.prod(p.shape) for p in jax.tree_util.tree_leaves(tree))


def test_parameter_count_of_the_cut_is_the_files():
    """The share's parameters, counted from the model's own shapes at the
    published widths (abstractly: nothing is allocated), term by term."""
    resolved = run.resolve_cell(run.load_manifest(), CELL)
    module, config = resolved["module"], resolved["config"]
    params, bias = jax.eval_shape(module._init_state(module._model(
        config, attention="dense"), config), jax.random.PRNGKey(0))
    assert count(params) == 647_819_520
    conv = params["block_0"]["mixer"]
    assert count(conv) == 16_783_360
    assert conv["in_proj"]["kernel"].shape == (2048, 6144)
    assert conv["conv_kernel"].shape == (3, 2048)
    assert conv["out_proj"]["kernel"].shape == (2048, 2048)
    attention = {k: params["block_1"][k] for k in (
        "q_proj", "kv_proj", "q_head_norm", "k_head_norm", "o_proj")}
    assert count(attention) == 10_485_888
    assert attention["kv_proj"]["kernel"].shape == (2048, 2 * 8 * 64)
    assert attention["q_head_norm"]["scale"].shape == (64,)
    assert "mixer" not in params["block_1"]
    dense = [params["block_0"][k] for k in ("mlp_gate", "mlp_up", "mlp_down")]
    assert count(dense) == 72_351_744 and "moe" not in params["block_0"]
    moe = params["block_1"]["moe"]
    assert moe["router"].shape == (2048, 64)
    assert moe["w_gate"].shape == (8, 2048, 1536)
    assert count([moe[k] for k in ("w_gate", "w_up", "w_down")]) == 75_497_472
    assert count(params["block_0"]) == 89_139_200
    assert all(count(params[f"block_{i}"]) == 92_416_000 for i in (2, 3, 4, 6))
    assert all(count(params[f"block_{i}"]) == 86_118_528 for i in (1, 5))
    assert count(params["embed"]) == 16_777_216 and "lm_head" not in params
    assert count(params["RMSNorm_0"]) == 2048
    assert sorted(bias) == [f"block_{i}" for i in range(1, 7)]
    # 16 B a parameter: f32 value, gradient, two AdamW moments
    assert 16 * 647_819_520 / 2 ** 30 == pytest.approx(9.65, abs=0.01)


def test_the_whole_models_count_from_the_published_keys():
    """23.84 B parameters, 2.33 B of them active a token: the model's name."""
    config = run.resolve_cell(run.load_manifest(), CELL)["config"]
    d, e = config["hidden_size"], config["num_experts"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    hd = d // heads
    conv = d * 3 * d + config["conv_L_cache"] * d + d * d
    attention = 2 * d * heads * hd + 2 * d * kv * hd + 2 * hd
    mixers = {k: v for k, v in (("conv", conv), ("full_attention", attention))}
    expert = 3 * d * config["moe_intermediate_size"]
    dense = 3 * d * config["intermediate_size"]
    table = config["vocab_size"] * d
    total = active = table + d
    for i, kind in enumerate(config["layer_types"]):
        layer = mixers[kind] + 2 * d
        if i < config["num_dense_layers"]:
            total, active = total + layer + dense, active + layer + dense
        else:
            total += layer + d * e + e * expert
            active += layer + d * e + config["num_experts_per_tok"] * expert
    assert (conv, attention, expert, dense) == (16_783_360, 10_485_888,
                                                9_437_184, 72_351_744)
    assert total == pytest.approx(23.84e9, rel=5e-4)
    assert active == pytest.approx(2.33e9, rel=5e-3)


def test_adamw_decays_matrices_and_the_seeded_state_is_the_files():
    resolved = resolved_tiny()
    module, config = resolved["module"], resolved["config"]
    params, bias = jax.jit(module._init_state(module._model(
        config, attention="dense"), config))(jax.random.PRNGKey(4))
    decayed = jax.tree_util.tree_map_with_path(module._is_matrix, params)
    mixer = decayed["block_0"]["mixer"]
    assert not mixer["conv_kernel"]
    assert mixer["in_proj"]["kernel"] and mixer["out_proj"]["kernel"]
    assert all(decayed["block_1"][k]["kernel"] for k in (
        "q_proj", "kv_proj", "o_proj"))
    assert not decayed["block_1"]["q_head_norm"]["scale"]
    assert decayed["embed"]["embedding"] and decayed["block_1"]["moe"]["w_gate"]
    assert decayed["block_1"]["moe"]["router"]
    assert not decayed["block_0"]["RMSNorm_0"]["scale"]
    # the tied table at the other matrices' scale: the logits start small
    assert np.std(np.asarray(params["embed"]["embedding"])) == pytest.approx(
        0.02, rel=0.05)
    taps = np.asarray(params["block_2"]["mixer"]["conv_kernel"])
    assert np.abs(taps).max() <= 3 ** -0.5 and np.abs(taps).max() > 0.5
    assert float(jnp.min(params["block_1"]["k_head_norm"]["scale"])) == 1.0
    assert all(float(jnp.max(jnp.abs(b))) == 0.0
               for b in jax.tree_util.tree_leaves(bias))


def test_costs_against_hand_counts():
    resolved = run.resolve_cell(run.load_manifest(), CELL)
    module, config, traffic = (resolved[k] for k in ("module", "config",
                                                     "traffic"))
    cost = module.cost(config, traffic, 1)
    assert set(cost) == {"model_flops", "sconv_conv"}
    tokens, seq = 2 * 8192, 8192
    parts = lfm2_cost.share_forward_parts(*module._cost_args(config, traffic, 1))
    want = {"conv_mixers": tokens * 5 * 2 * 4 * 2048 * 2048,
            "attention_scores": 2 * 2 * seq * seq * 2 * 64 * 32,
            "attention_projections": tokens * 2 * 2 * (
                2 * 2048 * 2048 + 2 * 2048 * 512),
            "dense": tokens * 6 * 2048 * 11776,
            "experts": tokens * 6 * (2 * 2048 * 64
                                     + 6 * 2048 * 1536 * 4 * 8 / 64),
            "head": tokens * 2 * 2048 * 8192}
    assert parts == pytest.approx(want, rel=1e-12)
    total = sum(want.values())
    assert cost["model_flops"] == pytest.approx(3 * total, rel=1e-12)
    assert 3 * total == pytest.approx(25.2e12, rel=5e-3)    # ISSUE 62's
    shares = {k: round(100 * v / total) for k, v in want.items()}
    assert shares == {"conv_mixers": 33, "dense": 28, "attention_scores": 13,
                      "attention_projections": 8, "experts": 11, "head": 7}
    # the pass: forward 4 runs of T x 2,048 x 2 B twice (remat), backward 7
    run_bytes = tokens * 2048 * 2
    assert cost["sconv_conv"] == {"bytes": 5 * (2 * 4 + 7) * run_bytes}
    assert cost["sconv_conv"]["bytes"] == pytest.approx(5.03e9, rel=2e-3)
    assert lfm2_cost.sconv_conv_step_cost(8192, 2, 5, 2048)["bytes"] == (
        5 * 11 * run_bytes)


def test_readers_on_hand_made_gauges_and_tables(hvd, monkeypatch):
    from benchmarks import named_device_time

    def read(name, context):
        return run.load_module(os.path.join(
            REPO, "benchmarks", "layer_metrics", name + ".py")).read(context)

    logged = []
    context = {"log": logged.append, "cost": {"sconv_conv": {"bytes": 5.03e9}},
               "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}}
    registry = hvd.metrics.registry()
    registry.gauge("horovod_short_conv_taps").set(3)
    assert read("sconv_taps", context) == 3
    # a program that traced no such mixer (or the parent, without the gauge)
    registry.gauge("horovod_short_conv_taps").set(0)
    assert read("sconv_taps", context) is None
    table = {"seconds": {"hvd_sconv_proj": 0.09, "hvd_sconv_conv": 0.0,
                         "hvd_sconv_conv_fwd": 0.004,
                         "hvd_sconv_conv_bwd": 0.006}, "unnamed": 0.01}
    monkeypatch.setattr(named_device_time, "_tables", [table])
    assert read("sconv_proj_ms_per_step", context) == pytest.approx(90.0)
    assert read("sconv_conv_ms_per_step", context) == pytest.approx(10.0)
    assert read("sconv_conv_roofline_pct", context) == pytest.approx(
        100 * 5.03e9 / 819e9 / 0.010)
    assert "HBM bandwidth" in logged[-1]
    # the parent's table has no such names: nothing, no raise
    monkeypatch.setattr(named_device_time, "_tables",
                        [{"seconds": {"hvd_mlp": 0.07}, "unnamed": 0.0}])
    for name in ("sconv_proj_ms_per_step", "sconv_conv_ms_per_step",
                 "sconv_conv_roofline_pct"):
        assert read(name, context) is None
    monkeypatch.setattr(named_device_time, "_tables", [None])
    assert read("sconv_conv_roofline_pct", context) is None
