"""The ``nemotron_3_super_120b_a12b`` configuration at a tiny size on the
4-device virtual CPU mesh: the cell end to end through ``run.run_cell`` (both
loss terms, every leaf's gradient, the biases one on every rank after the
steps), wrong variants of the model that are not ``correct``, a lower
precision in the router that fails the float32 limit, a tie broken the other
way held under the system's choice, the file's keys against the catalog's,
the parameter count of the cut, the cost functions against hand counts, the
four new readers on a hand-made table, the manifest's entries for the new
cell, and the two copies of the plain reference held to the same outputs."""

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

from benchmarks import latent_moe_cost, named_device_time, run  # noqa: E402

CELL = "nemotron3s_seq8192_1chip"
CONFIG = "nemotron_3_super_120b_a12b"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# Tiny sizes under the file's own keys: the pattern ME*E and the module's *E,
# a share of 4 experts (4-7) of 32, 5 a token, in a latent of 32.
TINY = {"vocab_held": 256, "hidden_size": 64, "head_dim": 16,
        "attention_heads_held": 4, "kv_heads_held": 1,
        "mamba_heads_held": 4, "mamba_head_dim": 8, "mamba_groups_held": 1,
        "ssm_state_size": 16, "chunk_size": 16, "mamba_num_heads": 16,
        "n_routed_experts": 32, "num_experts_per_tok": 5, "experts_held": 4,
        "experts_first": 4, "moe_latent_size": 32, "moe_intermediate_size": 48,
        "shared_width_held": 24, "layers": 4, "loss_chunk": 32,
        "hybrid_override_pattern": "ME*E" + "M" * 84}
TRAFFIC = {"seq": 128, "global_rows": 4, "fence_every": 2, "fence_lag": 1,
           "warmup_groups": 1, "trace_groups": 1, "reference_prefix": 64,
           "scan_slice": 32, "flash_slice": 64}


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
    kept = []
    real_build = resolved["module"].build

    def keep(*args, **kw):      # the dict itself: the registry's collector
        kept.append(real_build(*args, **kw))    # reads the state through it
        return kept[0]

    monkeypatch.setattr(resolved["module"], "build", keep)
    result = run.run_cell(resolved, jax.devices()[:4], seed=3, seconds=0.0,
                          trace=0, flash_interpret=True)
    built = kept[0]
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
    assert set(observed["scan"]) == {"f32", "bf16"}
    assert observed["scan"]["f32"] <= 1e-6 < observed["scan"]["bf16"]
    assert set(observed["flash"]) == {"out", "dq", "dk", "dv"}
    f32 = observed["f32"]
    assert max(f32["main_loss"], f32["mtp_loss"], f32["loss"]) <= 1e-6
    assert max(f32["logits"], f32["mtp_logits"]) <= 2e-6
    assert f32["flipped_share"] == 0.0 and f32["bias_differ_share"] == 0.0
    assert f32["pairs_differ_share"] == 0.0
    assert observed["bf16"]["bias_differ_share"] == 0.0     # held exactly
    assert 0.0 < observed["bf16"]["pairs_differ_share"] < observed["bf16"][
        "flipped_share"] <= 1.0
    assert f32["held_under"] == "the system's choice"
    assert observed["reference_forms"]["logits"] <= 2e-6
    leaves = f32["grads_rel"]
    # embed, head, final norm; M 9 leaves, E 8, * 5, E 8; the module's 4 + 5 + 8
    assert len(leaves) == 3 + 9 + 8 + 5 + 8 + 4 + 5 + 8
    assert {"layer0.A_log", "layer1.router", "layer1.w_fc1", "layer1.w_fc2",
            "layer3.w_up", "layer3.s_down", "layer2.wk", "mtp.proj",
            "mtp.hidden_norm", "mtp.embed_norm", "mtp.layer1.w_down",
            "mtp.layer0.wq", "mtp.norm", "embed", "head"} <= set(leaves)
    assert max(leaves.values()) <= 2e-5
    assert set(observed["bf16"]["grads_l2_rel"]) == set(leaves)
    assert 1e-3 < max(observed["bf16"]["grads_l2_rel"].values()) <= 1e-1
    assert 1e-4 < observed["bf16"]["logits"] <= 2e-2
    assert len(f32["held_share"]) == 3      # two expert layers and the module's
    assert observed["bias_after_abs_max"] == pytest.approx(0.001)
    # the rule moved every expert layer's bias in the steps, the module's too,
    # and the summed load keeps it ONE bias on every rank
    bias = built["state"][2]
    assert sorted(bias) == ["block_1", "block_3", "mtp_block_1"]
    for leaves in bias.values():
        b = leaves["moe"]["router_bias"]
        shards = [np.asarray(s.data) for s in b.addressable_shards]
        assert len(shards) == 4
        for other in shards[1:]:
            np.testing.assert_array_equal(shards[0], other)
        assert 0 < np.max(np.abs(shards[0])) <= 0.001 * 10 + 1e-9    # <= 10 steps
    # AdamW's state holds moments for params only: no leaf of the bias
    assert "router_bias" not in str(jax.tree_util.tree_structure(built["state"][1]))
    gauges = hvd.metrics.registry().snapshot()["gauges"]
    assert gauges["horovod_moe_dispatch_rows"] == 128 * 5    # a rank's one window
    assert gauges["horovod_moe_dispatch_row_bytes"] == 32 * 2
    assert gauges["horovod_ssd_chunk_len"] == 16
    # what the three expert layers' passes visited in the latest steps (the
    # fourth carry's ring: trace_groups x fence_every = 2 steps), from the
    # rows each layer sowed; a rank's 128 x 5 pairs are one window a layer
    live = np.asarray(built["state"][3]["live"])
    assert live.shape == (2, 3) and int(built["state"][3]["steps"]) >= 4
    assert (live > 0).all() and (live < 128 * 5).all()
    assert gauges["horovod_moe_live_rows_per_step"] == pytest.approx(
        live.sum() / 2)
    assert gauges["horovod_moe_live_windows_per_step"] == 3.0
    resolved["module"].forget_live_rows()
    registry = hvd.metrics.registry()
    for name in ("horovod_moe_live_rows_per_step",
                 "horovod_moe_live_windows_per_step"):
        assert registry.remove(name)
    assert "horovod_moe_live_rows_per_step" not in registry.snapshot()["gauges"]


def check_alone(hvd, **tiny):
    """The configuration's checks without the step: one Mamba-2 and one
    expert layer beside the module, so that a fault costs three compilations
    and not four."""
    resolved = resolved_tiny()
    resolved["config"].update(layers=2, **tiny)
    mesh = hvd.data_parallel_mesh(jax.devices()[:4])
    return resolved, lambda: resolved["module"].reference(
        resolved["config"], resolved["traffic"], mesh, 3, flash_interpret=True)


FAULTS = ["scale_left_out", "shared_expert_left_out", "relu_not_squared",
          "latent_left_out_of_the_sum", "mtp_target_not_shifted",
          "mtp_weight_left_out", "bias_not_in_the_choice",
          "bias_rule_the_other_way"]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_wrong_variant_is_not_correct(hvd, monkeypatch, fault):
    """What the float32 leg exists for: a model that is not Nemotron-3's. The
    reference stays what it is; the system's model is built wrong, one part
    at a time, and each moves a loss term, the logits, a gradient or the
    choice past its limit."""
    from horovod_tpu.models import moe as models_moe, transformer
    from horovod_tpu.ops import moe as ops_moe

    resolved, check = check_alone(hvd)
    module = resolved["module"]
    real_model = module._model
    if fault == "scale_left_out":
        monkeypatch.setattr(module, "_model", lambda config, **kw: real_model(
            config, **{**kw, "moe_route_scale": 1.0}))
    elif fault == "shared_expert_left_out":
        real_shared = models_moe.MoEMLP._shared
        monkeypatch.setattr(models_moe.MoEMLP, "_shared", lambda self, tokens:
                            0.0 * real_shared(self, tokens))
    elif fault == "relu_not_squared":
        monkeypatch.setattr(ops_moe, "_relu2", jax.nn.relu)
    elif fault == "latent_left_out_of_the_sum":
        real = models_moe.dropless_experts
        monkeypatch.setattr(models_moe, "dropless_experts",
                            lambda x, *a, **kw: real(x, *a, **kw) + x)
    elif fault == "mtp_target_not_shifted":
        def next_token_twice(hidden, mtp_hidden, head, tokens, weight,
                             chunk=2048):
            main, mtp = (transformer.chunked_lm_loss(
                h, head, jnp.roll(tokens, -1, axis=1), chunk)
                for h in (hidden, mtp_hidden))
            return main + weight * mtp, (main, mtp)

        monkeypatch.setattr(transformer, "lm_loss_with_mtp", next_token_twice)
    elif fault == "mtp_weight_left_out":
        real_loss = transformer.lm_loss_with_mtp
        monkeypatch.setattr(
            transformer, "lm_loss_with_mtp",
            lambda hidden, mtp_hidden, head, tokens, weight, chunk=2048:
            real_loss(hidden, mtp_hidden, head, tokens, 1.0, chunk))
    elif fault == "bias_not_in_the_choice":
        real = models_moe.sigmoid_route
        monkeypatch.setattr(
            models_moe, "sigmoid_route", lambda logits, bias, top_k, scale:
            real(logits, jnp.zeros_like(bias), top_k, scale))
        seeded_bias(monkeypatch, module)
    elif fault == "bias_rule_the_other_way":    # held exactly, with no limit
        real_rule = ops_moe.router_bias_update
        monkeypatch.setattr(ops_moe, "router_bias_update",
                            lambda bias, counts, rate: real_rule(bias, counts,
                                                                 -rate))
    with pytest.raises(AssertionError,
                       match="against its float32 references: .*f32 "):
        check()


def seeded_bias(monkeypatch, module):
    """A bias that matters: the check starts from a seeded one."""
    real_init = module._init_state

    def biased(model, config):
        init = real_init(model, config)

        def with_bias(key):
            params, bias = init(key)
            return params, jax.tree_util.tree_map(
                lambda b: 0.3 * jax.random.normal(key, b.shape), bias)
        return with_bias

    monkeypatch.setattr(module, "_init_state", biased)


def test_a_seeded_bias_is_still_correct(hvd, monkeypatch):
    """The control of ``bias_not_in_the_choice``: with the bias in the choice
    on both sides the float32 leg agrees from a bias that is not 0."""
    resolved, check = check_alone(hvd)
    seeded_bias(monkeypatch, resolved["module"])
    observed = check()["observed"]
    assert observed["f32"]["flipped_share"] == 0.0
    assert observed["f32"]["logits"] <= 2e-6


def test_a_tie_broken_the_other_way_is_held_under_the_systems_choice(
        hvd, monkeypatch):
    """With 22 of 512 a token, float32 ties between a token's last chosen
    expert and the next are common on the chip. Made here by hand on ONE
    token: its share is held as a share, under a limit of its own, and both
    loss terms, both sets of logits, every gradient and the biases against
    the reference computed under the system's choice, at the float32 limits:
    a tie early in the row fails nothing downstream."""
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
    resolved["config"]["tolerance"] = {**resolved["config"]["tolerance"],
                                       "f32_flipped_share": 0.01}
    with pytest.raises(AssertionError, match="f32 share of tokens whose "
                                             "experts differ = 1.562e-02"):
        check()                 # 1 of 64 tokens: over a limit of its own
    resolved["config"]["tolerance"] = {**resolved["config"]["tolerance"],
                                       "f32_flipped_share": 0.02,
                                       "f32_pairs_differ_share": 0.01,
                                       "bf16_pairs_differ_share": 1.0}
    observed = check()["observed"]
    assert observed["f32"]["held_under"] == "the system's choice"
    assert observed["f32"]["flipped_share"] == pytest.approx(1 / 64, rel=1e-4)
    # one pair of the token's five, in each of the two layers
    assert observed["f32"]["pairs_differ_share"] == pytest.approx(
        1 / (64 * 5), rel=1e-4)
    assert max(observed["f32"][k] for k in ("logits", "mtp_logits", "main_loss",
                                            "mtp_loss")) <= 2e-6
    assert max(observed["f32"]["grads_rel"].values()) <= 2e-5


def test_bf16_router_scores_fail_the_float32_limit(hvd, monkeypatch):
    """The configuration states float32 router scores: rounded to bf16 they
    tie, other experts are chosen, and the float32 leg's share of flipped
    tokens is beyond its limit."""
    from horovod_tpu.models import moe as models_moe

    real = models_moe.sigmoid_route

    def rounded(logits, bias, top_k, scale):
        return real(jax.lax.optimization_barrier(
            logits.astype(jnp.bfloat16)).astype(jnp.float32), bias, top_k, scale)

    monkeypatch.setattr(models_moe, "sigmoid_route", rounded)
    resolved, check = check_alone(hvd)
    # the file's limit is 61 of 1,024 tokens; of this size's 64, under one
    resolved["config"]["tolerance"] = {**resolved["config"]["tolerance"],
                                       "f32_flipped_share": 0.01}
    with pytest.raises(AssertionError, match="f32 (logits|share of tokens "
                                             "whose experts differ) = "):
        check()


def test_every_catalog_key_is_in_the_file_as_published():
    config = run.resolve_cell(run.load_manifest(), CELL)["config"]
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(json.loads(line) for line in f
                   if '"NVIDIA-Nemotron-3-Super-120B-A12B-BF16"' in line)
    for key, value in row["config"].items():
        assert config[key] == value, key
    entry = next(c for c in run.load_manifest()["configs"]
                 if c["name"] == CONFIG)
    assert entry["reduced"] == ["layers", "mamba_num_heads", "n_groups",
                                "num_attention_heads", "num_key_value_heads",
                                "n_routed_experts", "vocab_size"]
    assert entry["source"] == row["source_url"]
    # no width is cut, and none is named as reduced
    for key in ("hidden_size", "head_dim", "mamba_head_dim", "ssm_state_size",
                "conv_kernel", "chunk_size", "moe_latent_size",
                "moe_intermediate_size", "num_experts_per_tok",
                "moe_shared_expert_intermediate_size", "expand"):
        assert key not in entry["reduced"] and key not in config["cut"] or (
            key == "moe_shared_expert_intermediate_size")
    tp = config["tensor_parallel"]
    assert (tp, config["expert_parallel"]) == (8, 64)
    assert (config["layers"], config["experts_held"], config["experts_first"],
            config["vocab_held"]) == (11, 8, 0, 16384)
    assert config["mamba_heads_held"] * tp == config["mamba_num_heads"]
    assert config["mamba_groups_held"] * tp == config["n_groups"]
    assert config["attention_heads_held"] * tp == config["num_attention_heads"]
    assert config["kv_heads_held"] == max(1, config["num_key_value_heads"] // tp)
    assert config["shared_width_held"] * tp == config[
        "moe_shared_expert_intermediate_size"]
    assert config["experts_held"] * config["expert_parallel"] == config[
        "n_routed_experts"]
    assert config["vocab_held"] * tp == config["vocab_size"]
    assert config["hybrid_override_pattern"][:11] == "MEMEMEM*EME"
    for key, words in {"deployment_share": ("tensor parallel 8 x expert parallel 64",
                                            "PARTIAL", "rank 0"),
                       "layers": ("88", "MEMEMEM*EME"),
                       "mamba_num_heads": ("128", "16 held"),
                       "n_groups": ("8 published", "1 held"),
                       "num_attention_heads": ("32", "4 held"),
                       "num_key_value_heads": ("2 published", "1 held"),
                       "moe_shared_expert_intermediate_size": ("5,376", "672"),
                       "n_routed_experts": ("512", "8 held"),
                       "vocab_size": ("131,072", "16,384"),
                       "parameters": ("607,035,888", "9.05 GiB"),
                       "expert_load": ("704 rows", "1/8")}.items():
        for word in words:
            assert word in config["cut"][key], (key, word)
    for key in entry["reduced"]:
        assert key in config["cut"], key
    assumed = " ".join(config["assumed"])
    for word in ("rotary", "full hidden state", "0.3", "0.001 a step",
                 "rescale_prenorm_residual", "AdamW", "recomputation"):
        assert word in assumed, word
    assert set(config["tolerance"]) >= {
        "f32_logits_rel", "f32_grads_rel", "f32_loss_rel", "f32_flipped_share",
        "f32_pairs_differ_share", "f32_scan_rel", "bf16_logits_rel",
        "bf16_grads_l2_rel", "bf16_loss_rel", "bf16_pairs_differ_share",
        "bf16_scan_rel", "flash_rel", "why"}
    # near 1 by nature in the as-trained leg: logged, held by the pairs; the
    # biases after the rule are held exactly, with no limit of the file's
    assert not {"bf16_flipped_share", "f32_bias_differ_share",
                "bf16_bias_differ_share"} & set(config["tolerance"])
    assert config["router_bias"]["update_rate"] == 0.001      # the sourced one


def test_the_file_states_the_rate_and_the_draw_each_with_its_grounds():
    """What steadies the cell (PR 58) is two values of the configuration's
    file, as keye's, kimi's and solar's run: each is there with a ``why`` that
    gives its grounds and its reading, and the rest of the job is as it was."""
    config = run.resolve_cell(run.load_manifest(), CELL)["config"]
    optimizer = config["optimizer"]
    assert optimizer["learning_rate"] == 7.3e-6
    assert {k: optimizer[k] for k in ("name", "b1", "b2", "eps",
                                      "weight_decay")} == {
        "name": "adamw", "b1": 0.9, "b2": 0.95, "eps": 1e-8,
        "weight_decay": 0.1}
    for other in ("keye_vl_2_0_30b_a3b", "kimi_linear_48b_a3b",
                  "solar_open2_250b"):
        theirs = run.load_json(os.path.join(
            REPO, "benchmarks", "configs", other + ".json"))
        assert theirs["optimizer"]["learning_rate"] == optimizer["learning_rate"]
    assert set(config["init"]) == {"embedding_std", "why"}
    assert config["init"]["embedding_std"] == 3.0
    assert config["initializer_std"] == 0.02
    assert config["router_bias"]["update_rate"] == 0.001
    for why in (optimizer["learning_rate_why"], config["init"]["why"],
                config["router_bias"]["why_this_rate"]):
        assert "my chip runs, PR 58" in why and "TODO" not in why
    # the seeds a reading names are the six every steadied cell was read on
    for seed in ("1700000077", "2600000033", "45000017", "3900000091",
                 "808080809", "2147483693"):
        assert seed in optimizer["learning_rate_why"]
    assert "8 / 512" in config["init"]["why"]


def _matrices(params):
    """{name: leaf} of the leaves ``_init_state`` draws normal: two or more
    axes, not the convolution's taps."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    named = {"/".join(str(getattr(k, "key", k)) for k in path): leaf
             for path, leaf in flat}
    return {name: leaf for name, leaf in named.items()
            if leaf.ndim >= 2 and not name.endswith("conv_kernel")}


def test_build_draws_the_embedding_at_its_own_std_and_the_rest_as_before():
    """``init.embedding_std`` reaches the embedding's rows and nothing else:
    every other leaf is, bit for bit, what the file drew before it had the
    key (the same key a leaf), and a seeded draw's std is the file's within
    2%: 3.0 for the rows, ``initializer_std`` for the pooled matrices, the
    mixers' out-projections at theirs / sqrt(2 x 88)."""
    resolved = resolved_tiny()
    module = resolved["module"]
    config = {**resolved["config"], "vocab_held": 1024, "hidden_size": 128,
              "mamba_num_heads": 32}    # expand x hidden = heads x 8
    assert config["init"]["embedding_std"] == 3.0
    before = {**config, "init": {"embedding_std": config["initializer_std"]}}
    model = module._model(config, attention="dense")
    key = jax.random.PRNGKey(58)
    params, bias = jax.jit(module._init_state(model, config))(key)
    old, _ = jax.jit(module._init_state(model, before))(key)
    flat, old_flat = (jax.tree_util.tree_flatten_with_path(t)[0]
                      for t in (params, old))
    for (path, leaf), (_, was) in zip(flat, old_flat):
        if getattr(path[-1], "key", None) == "embedding":
            np.testing.assert_allclose(
                np.asarray(leaf), np.asarray(was) * (
                    config["init"]["embedding_std"] / config["initializer_std"]),
                rtol=1e-6)
        else:
            np.testing.assert_array_equal(np.asarray(leaf), np.asarray(was))
    matrices = _matrices(params)
    rows = matrices.pop("embed/embedding")
    assert rows.shape == (1024, 128)
    assert float(jnp.std(rows)) == pytest.approx(3.0, rel=0.02)
    assert abs(float(jnp.mean(rows))) < 0.05
    rescale = (2 * config["num_hidden_layers"]) ** -0.5
    out = {n: m for n, m in matrices.items()
           if n.endswith(("out_proj/kernel", "o_proj/kernel"))}
    rest = {n: m for n, m in matrices.items() if n not in out}
    assert out and "lm_head/kernel" in rest and "block_1/moe/w_up" in rest
    for group, std in ((out, 0.02 * rescale), (rest, 0.02)):
        pooled = jnp.concatenate([m.reshape(-1) for m in group.values()])
        assert pooled.size >= 20_000
        assert float(jnp.std(pooled)) == pytest.approx(std, rel=0.02)
    assert all(float(jnp.max(jnp.abs(b))) == 0.0
               for b in jax.tree_util.tree_leaves(bias))


HELD_SHARE_BAND = 0.3   # of a balanced router's share, at the test's size


@pytest.fixture(scope="module")
def seeded_share():
    """The model at the share test's size with its two programs, compiled
    once for the four seeds."""
    resolved = resolved_tiny()
    module = resolved["module"]
    config = {**resolved["config"], "n_routed_experts": 64, "experts_held": 8,
              "experts_first": 0, "num_experts_per_tok": 6}
    model = module._model(config, attention="dense")
    return (module, config, jax.jit(module._init_state(model, config)),
            jax.jit(module._loss_fn(model, config)))


@pytest.mark.parametrize("seed", [1, 2, 3, 4])
def test_a_seeded_models_held_experts_draw_their_balanced_share(
        seeded_share, seed):
    """The property the cell now rests on (the file's ``init.why``): on a
    seeded model, before any update, the held experts draw about held / all of
    every expert layer's pairs, the module's too, whatever the seed. At the
    test's size (8 of 64, 6 a token, 512 tokens) within 30% of 8 / 64; on the
    chip at the published widths the file's ``init.why`` has the readings
    against 8 / 512."""
    module, config, init, loss_fn = seeded_share
    key = jax.random.PRNGKey(seed)
    params, bias = init(key)
    tokens = module._tokens_fn(config, 2, 256)(jax.random.fold_in(key, 1))
    _, (counts, live) = loss_fn(params, bias, tokens)
    first, held = module._held(config)
    balanced = held / config["n_routed_experts"]
    blocks = module._in_layer_order(counts)
    assert blocks == ["block_1", "block_3", "mtp_block_1"]
    for block, rows in zip(blocks, np.asarray(live)):
        pairs = np.asarray(counts[block])
        assert pairs.sum() == 512 * 6
        on_held = pairs[first:first + held].sum()
        assert abs(on_held / pairs.sum() / balanced - 1.0) <= HELD_SHARE_BAND, (
            block, on_held)
        assert rows == on_held      # what the layer's passes visit, no more


def test_the_files_limits_are_the_ones_the_reference_reads(hvd):
    """Every limit of the file's ``tolerance`` is read by ``reference`` and
    ``reference`` reads no other: a limit renamed or left behind in either
    place shows here, not as a default on the chip."""
    resolved, check = check_alone(hvd)

    class Recording(dict):
        """The limits, with the names that were asked for."""

        def __init__(self, limits):
            super().__init__(limits)
            self.read = set()

        def __getitem__(self, key):
            self.read.add(key)
            return super().__getitem__(key)

        def get(self, key, default=None):   # a limit the file may leave out
            if key in self:
                self.read.add(key)
            return super().get(key, default)

    limits = resolved["config"]["tolerance"]
    seen = resolved["config"]["tolerance"] = Recording(limits)
    assert check()["kind"] == "kernel"
    # (``bf16_flipped_share`` is asked for with a default and the file has
    # none: that leg's share is logged, not held)
    assert "bf16_flipped_share" not in limits
    assert seen.read == set(limits) - {"why"}
    assert all(isinstance(limits[name], float) for name in seen.read)
    assert "my chip runs, PR 58" in limits["why"]


def test_parameter_count_of_the_cut_is_the_files():
    """The share's parameters, counted from the model's own shapes at the
    published widths (abstractly: nothing is allocated)."""
    resolved = run.resolve_cell(run.load_manifest(), CELL)
    module, config = resolved["module"], resolved["config"]
    params, bias = jax.eval_shape(module._init_state(module._model(
        config, attention="dense"), config), jax.random.PRNGKey(0))
    count = sum(math.prod(p.shape) for p in jax.tree_util.tree_leaves(params))
    assert count == 607_035_888
    assert set(bias) == {"block_1", "block_3", "block_5", "block_8",
                         "block_10", "mtp_block_1"}
    moe = params["block_1"]["moe"]
    assert moe["w_up"].shape == (8, 1024, 2688)
    assert moe["w_down"].shape == (8, 2688, 1024)
    assert moe["router"].shape == (4096, 512) and "w_gate" not in moe
    assert moe["fc1_latent"]["kernel"].shape == (4096, 1024)
    assert moe["shared_up"]["kernel"].shape == (4096, 672)
    assert params["block_0"]["mixer"]["in_proj"]["kernel"].shape == (4096, 2320)
    assert params["block_0"]["mixer"]["conv_kernel"].shape == (4, 1280)
    assert params["block_7"]["q_proj"]["kernel"].shape == (4096, 512)
    assert params["block_7"]["kv_proj"]["kernel"].shape == (4096, 256)
    assert params["mtp_proj"]["kernel"].shape == (8192, 4096)
    assert params["lm_head"]["kernel"].shape == (4096, 16384)
    assert sum(1 for k in params if k.startswith("block_")) == 11


def test_costs_against_hand_counts():
    resolved = run.resolve_cell(run.load_manifest(), CELL)
    cost = resolved["module"].cost(resolved["config"], resolved["traffic"], 1)
    # held grouped products: 5,632 rows a layer under a balanced router, six
    # layers, two products forward twice (remat) and four backward
    rows = 16384 * 22 * 8 / 512
    assert rows == 5632
    product = 2 * rows * 1024 * 2688
    assert cost["latent_moe"]["flops"] == 6 * 8 * product
    assert cost["latent_moe"]["bytes"] == 6 * 8 * 2 * (
        rows * 1024 + rows * 2688 + 8 * 1024 * 2688)
    assert latent_moe_cost.grouped_step_cost(
        16384, 6, 512, 22, 8, 1024, 2688)["flops"] == 6 * 6 * product
    # affine in the rows a step really sends: balanced, and a quarter more
    assert cost["latent_moe"]["rows"] == 6 * rows
    assert latent_moe_cost.at_rows(cost["latent_moe"], 6 * rows) == {
        "flops": cost["latent_moe"]["flops"], "bytes": cost["latent_moe"]["bytes"]}
    more = latent_moe_cost.at_rows(cost["latent_moe"], 6 * rows * 1.25)
    assert more["flops"] == pytest.approx(1.25 * cost["latent_moe"]["flops"])
    assert more["bytes"] == pytest.approx(6 * 8 * 2 * (
        1.25 * rows * (1024 + 2688) + 8 * 1024 * 2688))
    # a token, forward
    mamba = 2 * 4096 * 2320 + 2 * 1024 * 4096
    attention = 2 * 4096 * 128 * (2 * 4 + 2 * 1)
    experts = (2 * 4096 * 512 + 2 * 2 * 4096 * 1024 + 2 * 2 * 4096 * 672
               + 2 * 2 * 1024 * 2688 * 22 * 8 / 512)
    head, w_eh = 2 * 4096 * 16384, 2 * 8192 * 4096
    # a row: the scan at chunk 128 and the causal half of attention
    scan = 8192 // 128 * (1 * 128 * 128 * 128 + 16 * 128 * 128 * 64
                          + 2 * 2 * 128 * 16 * 64 * 128)
    attn = 2 * 8192 * 8192 * 128 * 4
    want = 3 * (16384 * (5 * mamba + 2 * attention + 6 * experts + 2 * head
                         + w_eh) + 2 * (5 * scan + 2 * attn))
    assert cost["model_flops"] == pytest.approx(want, rel=1e-12)
    assert 35e12 < want < 37e12


def test_readers_on_a_hand_made_table(hvd, monkeypatch):
    seconds = {"hvd_moe_latent": 0.031, "hvd_mtp": 0.052,
               "hvd_moe_experts_gmm": 0.020, "hvd_moe_experts_tgmm": 0.010}
    monkeypatch.setattr(named_device_time, "_tables",
                        [{"seconds": seconds, "unnamed": 0.2}])
    logged = []
    context = {"trace": {"steps": 10}, "log": logged.append,
               "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
               "cost": {"latent_moe": {"flops": 1.488e12, "bytes": 4.12e9,
                                       "rows": 33792.0,
                                       "flops_per_row": 44040192,
                                       "bytes_per_row": 59392}}}
    registry = hvd.metrics.registry()

    def read(name):
        return run.load_module(os.path.join(
            REPO, "benchmarks", "layer_metrics", name + ".py")).read(context)

    assert read("moe_latent_ms_per_step") == pytest.approx(31.0)
    assert read("mtp_ms_per_step") == pytest.approx(52.0)
    # without the live rows of the window's steps: nothing, not a balanced guess
    assert read("latent_moe_roofline_pct") is None
    assert read("moe_live_rows_per_step") is None
    hvd.metrics.record_moe_live_rows([[5632] * 6, [5632] * 6], 2048)
    assert read("moe_live_rows_per_step") == 33792
    assert read("moe_live_windows_per_step") == 18
    # bound by compute: 1.488e12 / 197e12 = 7.553 ms against 5.03 by bytes
    assert read("latent_moe_roofline_pct") == pytest.approx(
        100 * 7.5533 / 30.0, rel=1e-4)
    assert len(logged) == 1 and "bound by compute" in logged[0]
    # a router that sends this rank a quarter more rows: the needed work
    # follows (the same kernels' time then reads the higher share), and a
    # layer past 6,144 rows runs a fourth window
    hvd.metrics.record_moe_live_rows([[7040] * 6], 2048)
    assert read("moe_live_windows_per_step") == 24
    assert read("latent_moe_roofline_pct") == pytest.approx(
        100 * (1.488e12 + 8448 * 44040192) / 197e12 * 1e3 / 30.0, rel=1e-4)
    for name in ("horovod_moe_live_rows_per_step",
                 "horovod_moe_live_windows_per_step"):
        assert registry.remove(name)
    registry.gauge("horovod_moe_dispatch_row_bytes").set(2048)
    assert read("moe_dispatch_row_bytes") == 2048
    # a program without the names or the gauge (the parent): nothing, no raise
    monkeypatch.setattr(named_device_time, "_tables", [
        {"seconds": {"hvd_flash_fwd": 0.08}, "unnamed": 0.2}])
    hvd.metrics.registry().gauge("horovod_moe_dispatch_row_bytes").set(0)
    for name in ("moe_latent_ms_per_step", "mtp_ms_per_step",
                 "latent_moe_roofline_pct", "moe_dispatch_row_bytes",
                 "moe_live_rows_per_step", "moe_live_windows_per_step"):
        assert read(name) is None, name
    monkeypatch.setattr(named_device_time, "_tables", [None])
    assert read("mtp_ms_per_step") is None
    context["cost"] = {}
    monkeypatch.setattr(named_device_time, "_tables",
                        [{"seconds": seconds, "unnamed": 0.2}])
    assert read("latent_moe_roofline_pct") is None


def test_the_manifest_holds_the_new_cell():
    manifest = run.load_manifest()
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert cell == {**cell, "config": CONFIG, "traffic": "seq8192x2_fence5_mtp",
                    "chips": 1}
    assert "1/8 of deployed load" in cell["why"] and len(cell["why"]) <= 200
    # by name, not by place: later cells and configurations come after it
    assert [c["name"] for c in manifest["configs"]].count(CONFIG) == 1
    for path in ("benchmarks/configs/%s.json" % CONFIG,
                 "benchmarks/configs/%s.py" % CONFIG,
                 "benchmarks/reference/nemotron3.py",
                 "benchmarks/traffic/seq8192x2_fence5_mtp.json",
                 "benchmarks/latent_moe_cost.py"):
        assert os.path.exists(os.path.join(REPO, path)), path
    resolved = run.resolve_cell(manifest, CELL)
    traffic = resolved["traffic"]
    assert {k: traffic[k] for k in (
        "seq", "global_rows", "fence_every", "fence_lag", "warmup_groups",
        "trace_groups", "reference_prefix", "flash_slice")} == {
        "seq": 8192, "global_rows": 2, "fence_every": 5, "fence_lag": 1,
        "warmup_groups": 2, "trace_groups": 2, "reference_prefix": 1024,
        "flash_slice": 2048}
    assert {m["name"] for m in resolved["end_to_end"]} == {
        "train_tok_per_s_per_chip", "step_ms", "peak_hbm_gib", "setup_s"}
    listed = {m["name"] for m in manifest["per_layer"]
              if CELL in m.get("workloads", [])}
    assert listed >= {      # later tracing PRs append their names' readers
        "flash_fwd_ms_per_step", "flash_bwd_dq_ms_per_step",
        "flash_bwd_dkv_ms_per_step", "ssd_scan_ms_per_step",
        "mamba_proj_ms_per_step", "mamba_conv_ms_per_step",
        "mamba_gate_norm_ms_per_step", "moe_route_ms_per_step",
        "moe_dispatch_ms_per_step", "moe_combine_ms_per_step",
        "moe_grouped_ms_per_step", "moe_shared_ms_per_step",
        "unnamed_device_ms_per_step", "moe_dispatch_rows_per_layer",
        "ssd_chunk_len", "moe_latent_ms_per_step", "mtp_ms_per_step",
        "moe_dispatch_row_bytes", "latent_moe_roofline_pct",
        "moe_live_rows_per_step", "moe_live_windows_per_step"}
    new = [m for m in manifest["per_layer"] if m["name"] in (
        "moe_latent_ms_per_step", "mtp_ms_per_step", "moe_dispatch_row_bytes",
        "latent_moe_roofline_pct", "moe_live_rows_per_step",
        "moe_live_windows_per_step")]
    assert len(new) == 6
    assert all(CELL in m["workloads"] and m["moves"] == "step_ms"
               for m in new)
    assert [m["layer"] for m in new] == ["Experts", "Models", "Experts",
                                         "Experts", "Experts", "Experts"]
    for m in new:
        assert os.path.exists(os.path.join(
            REPO, "benchmarks", "layer_metrics", m["name"] + ".py"))
    # the readers that stand on the reduction's ten longest labels stay off
    for name in ("ssd_ms_per_step", "ssd_roofline_pct", "flash_ms_per_step",
                 "mamba_mixer_ms_per_step", "moe_experts_ms_per_step",
                 "moe_experts_roofline_pct", "flash_roofline_pct"):
        assert name not in listed


def test_the_two_reference_copies_agree():
    from benchmarks.reference import nemotron3 as bench_copy
    from references import nemotron3 as test_copy

    with open(bench_copy.__file__) as a, open(test_copy.__file__) as b:
        assert a.read() == b.read()
    cfg = {"hidden": 16, "vocab": 32, "layer_types": "ME*", "mtp_layer_types": "*E",
           "heads": 2, "kv_heads": 1, "head_dim": 8, "mamba_heads": 2,
           "mamba_head_dim": 4, "mamba_groups": 1, "mamba_state": 4, "conv": 4,
           "eps": 1e-5, "experts": 8, "top_k": 3, "held": (2, 2), "latent": 8,
           "expert_width": 12, "shared_width": 6, "route_scale": 5.0,
           "mtp_weight": 0.3}
    tokens = jnp.arange(12).reshape(1, 12) % 32
    outs = []
    for m in (bench_copy, test_copy):
        params, biases = m.init_params(jax.random.PRNGKey(0), cfg, scale=0.3)
        biases = [b + 0.1 * jnp.arange(8) for b in biases]
        (loss, parts), grads = m.loss_and_grads(params, biases, tokens, cfg)
        outs.append((loss, parts, grads,
                     [m.bias_update(b, c, 0.001)
                      for b, c in zip(biases, parts["counts"])]))
    for a, b in zip(*(jax.tree_util.tree_leaves(o) for o in outs)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    loss, parts, grads, after = outs[0]
    assert math.isfinite(float(loss)) and len(parts["chosen"]) == 2
    assert float(loss) == pytest.approx(float(parts["main"])
                                        + 0.3 * float(parts["mtp"]))
    assert all(int(c.sum()) == 12 * 3 for c in parts["counts"])
    # only experts 2 and 3 are held: their leaves, and no other's, exist
    assert grads["layers"][1]["w_up"].shape == (2, 8, 12)
    assert grads["mtp"]["layers"][1]["w_down"].shape == (2, 12, 8)
    # the shared head receives both passes' gradient, the embedding the
    # module's lookups too
    assert float(jnp.max(jnp.abs(grads["mtp"]["proj"]))) > 0
