"""The ``keye_vl_2_0_30b_a3b`` configuration at a tiny size on the 4-device
virtual CPU mesh: the cell end to end through ``run.run_cell``, wrong variants
of the model that are not ``correct`` (each part the issue forbids leaving
out), unequal rotary streams, a selection and an expert moved by hand held
under the system's choice, the eight ranks' shares of an expert layer adding
up to the uncut reference's, the file's keys against the catalog's, the cost
functions against hand counts and the new readers on a hand-made table."""

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

from benchmarks import dsa_cost, named_device_time, run  # noqa: E402

CELL = "keye_vl2_seq16384_1chip"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# Tiny sizes under the file's own keys: 4 query heads over 2 key/value heads
# of 16 (8 rotary pairs in sections of 2, 3 and 3), an indexer of 2 heads of
# 8 that keeps 24 keys a query in chunks of 16, a share of 4 experts (2-5) of
# 8, 2 a token.
TINY = {"vocab_held": 96, "hidden_size": 64, "head_dim": 16,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "moe_intermediate_size": 32, "num_experts": 8,
        "num_experts_per_tok": 2, "experts_held": 4, "experts_first": 2,
        "layers": 2,
        "rope_scaling": {"mrope_section": [2, 3, 3], "rope_type": "default",
                         "type": "default"},
        "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 2,
                      "indexer_num_kv_heads": 1, "kv_chunk_size": 16,
                      "q_chunk_size": 16, "topk": 24}}
# the reference's prefix is three times the selection: most queries select
TRAFFIC = {"seq": 128, "global_rows": 4, "fence_every": 2, "fence_lag": 1,
           "warmup_groups": 1, "trace_groups": 1, "reference_prefix": 96,
           "flash_slice": 64}
OVERRIDES = dict(flash_interpret=True, block_q=32, block_k=32)


def resolved_tiny(**tiny):
    """The cell at the tiny sizes. The float32 leg keeps the file's limits;
    the as-trained leg's were read at the published widths, where 32 heads
    and 2,048 keys average bf16's rounding out: sixteen keys do not, and its
    limits here are wide (they are not what these tests are about)."""
    resolved = run.resolve_cell(run.load_manifest(), CELL)
    resolved["config"] = {**resolved["config"], **copy.deepcopy(TINY), **tiny}
    resolved["config"]["tolerance"] = {
        **resolved["config"]["tolerance"], "bf16_logits_rel": 0.3,
        "bf16_grads_l2_rel": 2.0, "bf16_index_grads_l2_rel": 2.0,
        "bf16_align_rel": 1.0, "bf16_balance_rel": 1.0,
        "bf16_flipped_share": 1.0, "bf16_selection_share": 1.0}
    resolved["traffic"] = dict(TRAFFIC)
    return resolved


@pytest.fixture()
def cpu_memory(monkeypatch):
    monkeypatch.setattr(run, "hbm_bytes", lambda devices: 3 << 30)


@pytest.fixture()
def fresh_traces():
    """The ops' jitted calls keep their traces: a test that patches what they
    close over starts and ends without them."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def observed_of(out):
    return json.loads(out.split("kernels vs f32 reference (share of "
                                "max|ref|): ")[1].splitlines()[0])


def test_cell_end_to_end_tiny(hvd, cpu_memory, capsys):
    resolved = resolved_tiny()
    # blocks of 32 in the step too: the float32 leg's 512 clamp to the prefix
    result = run.run_cell(resolved, jax.devices()[:4], seed=3, seconds=0.0,
                          trace=0, **OVERRIDES)
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
    assert set(observed["flash"]) == {"out", "dq", "dk", "dv", "align_terms",
                                      "align_dqi", "align_dw"}
    assert 0 < max(observed["flash"].values()) <= 1.2e-2
    f32 = observed["f32"]
    assert f32["logits"] <= 2e-6 and f32["lm"] <= 1e-6
    assert f32["align"] <= 4e-5 and f32["balance"] <= 5e-6
    assert f32["flipped_share"] == 0.0 and f32["selection_share"] == 0.0
    assert f32["held_under"] == "the reference's own choice"
    leaves = f32["grads_rel"]
    assert len(leaves) == 2 * 17 + 3        # every leaf of every layer
    assert {"layer0.index_q", "layer1.index_k", "layer0.index_k_norm_b",
            "layer1.index_k_norm_w", "layer0.index_w", "layer1.q_norm",
            "layer0.k_norm", "layer1.router", "layer0.w_gate", "layer1.wk",
            "embed", "head"} <= set(leaves)
    assert max(leaves.values()) <= 3e-5
    assert set(observed["bf16"]["grads_l2_rel"]) == set(leaves)
    assert 1e-3 < max(observed["bf16"]["grads_l2_rel"].values()) <= 2.0
    assert 1e-4 < observed["bf16"]["logits"] <= 0.3
    assert all(v > 0 for v in f32["losses"].values())
    # the step's census of its own selection, from the data: 128 positions in
    # blocks of 32 are 10 causal block steps a row and layer; one row a chip
    gauges = hvd.metrics.registry().snapshot()["gauges"]
    kept = sum(min(t + 1, 24) for t in range(128))
    assert gauges["horovod_dsa_selected_pairs_per_step"] == 2 * kept
    assert gauges["horovod_dsa_dense_block_steps_per_step"] == 2 * 10
    assert 2 * 4 <= gauges["horovod_dsa_live_block_steps_per_step"] <= 2 * 10
    assert gauges["horovod_moe_dispatch_rows"] > 0
    resolved["module"].forget_census()


def check_alone(hvd, **tiny):
    """The configuration's checks without the step."""
    resolved = resolved_tiny(**tiny)
    mesh = hvd.data_parallel_mesh(jax.devices()[:4])
    return resolved, lambda: resolved["module"].reference(
        resolved["config"], resolved["traffic"], mesh, 3, **OVERRIDES)


FAULTS = ["relu_left_out", "token_weights_left_out", "shared_key_left_out",
          "renormalisation_left_out", "head_norm_left_out",
          "indexer_reads_the_gradient", "alignment_loss_left_out",
          "indexer_rotary_left_out"]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_wrong_variant_is_not_correct(hvd, monkeypatch, fresh_traces, fault):
    """What the float32 leg exists for: a model that is not this one. The
    reference stays what it is; the system's model is built wrong, one part
    at a time, and each moves the selection, the logits or the indexer's
    gradient past the limit."""
    import flax.linen as nn

    from horovod_tpu.models import transformer
    from horovod_tpu.ops import sparse_attention as dsa

    resolved, check = check_alone(hvd)
    module = resolved["module"]
    real_model = module._model

    def with_fields(**fields):
        monkeypatch.setattr(module, "_model", lambda config, **kw: real_model(
            config, **{**kw, **fields}))

    def with_indexer_operands(change):
        """``change(qi, ki, w)`` before the selection and the alignment."""
        real_select, real_align = dsa.select, dsa.align_loss
        monkeypatch.setattr(dsa, "select", lambda qi, ki, w, *a: real_select(
            *change(qi, ki, w), *a))
        monkeypatch.setattr(
            dsa, "align_loss", lambda q, k, lse, qi, ki, w, *a: real_align(
                q, k, lse, *change(qi, ki, w), *a))

    moved = ("against its float32 references: .*f32 (share of selected pairs "
             "that differ|logits on the agreeing tokens|share of tokens whose "
             "experts differ) = ")
    gradient = "f32 gradient of layer[01].index_"
    match = moved
    if fault == "relu_left_out":
        monkeypatch.setattr(dsa, "_relu", lambda z: z)
    elif fault == "token_weights_left_out":
        with_indexer_operands(lambda qi, ki, w: (
            qi, ki, jnp.full_like(w, 2 ** -0.5 * 8 ** -0.5)))
    elif fault == "shared_key_left_out":
        # head j against the key turned j places: a key a head, of the same
        # parameters
        with_indexer_operands(lambda qi, ki, w: (jnp.stack([
            jnp.roll(qi[:, :, j], -j, axis=-1) for j in range(qi.shape[2])],
            axis=2), ki, w))
    elif fault == "renormalisation_left_out":
        with_fields(moe_norm_topk=False)
    elif fault == "head_norm_left_out":
        real_call = nn.RMSNorm.__call__

        def call(self, x, *a, **kw):
            out = real_call(self, x, *a, **kw)
            return x if self.name in ("q_head_norm", "k_head_norm") else out

        monkeypatch.setattr(nn.RMSNorm, "__call__", call)
    elif fault == "indexer_reads_the_gradient":
        # the indexer's input not detached: its parameters' gradient is
        # unchanged (the alignment loss's), the norm's and every earlier
        # leaf's then hold an alignment term
        real_stop = jax.lax.stop_gradient

        def stop(x):
            inside = sys._getframe(1).f_code.co_name == "_selected_attention"
            if inside and getattr(x, "ndim", 0) == 3 and x.shape[-1] == 64:
                return x
            return real_stop(x)

        monkeypatch.setattr(transformer.jax.lax, "stop_gradient", stop)
        match = "f32 gradient of (embed|layer[01].(attn_norm|w[qkvo]|mlp_norm))"
    elif fault == "alignment_loss_left_out":
        real_parts = module._loss_parts
        monkeypatch.setattr(module, "_loss_parts", lambda model, config:
                            real_parts(model, {**config, "loss_weights": {
                                **config["loss_weights"], "align": 0.0}}))
        match = gradient
    elif fault == "indexer_rotary_left_out":
        real_scheme = transformer._rope_scheme
        monkeypatch.setattr(
            transformer, "_rope_scheme", lambda x, positions, scheme:
            x if scheme.sections is None else real_scheme(x, positions, scheme))
    with pytest.raises(AssertionError, match=match):
        check()


def _legs(resolved, positions, **fields):
    """(system's float32 leg, reference) on one row at ``positions``."""
    module, config = resolved["module"], resolved["config"]
    plain, exact, _ = module.check_programs(config, **OVERRIDES, **fields)
    key = jax.random.PRNGKey(5)
    params = jax.jit(module._init_params(module._model(config, **OVERRIDES)))(key)
    # norm weights off 1 and the bias off 0, so that each one matters
    params = jax.tree_util.tree_map(
        lambda x: x + 0.1 * jax.random.normal(key, x.shape) if x.ndim < 2
        else 3 * x, params)
    tokens = jax.random.randint(key, (1, 96), 0, config["vocab_held"])
    rows = jnp.arange(64, dtype=jnp.int32)
    with jax.default_matmul_precision("highest"):
        return (exact(params, tokens, rows, positions),
                plain(params, tokens, rows, positions))


def test_unequal_rotary_streams_and_the_sections(hvd):
    """An image's rows: three position streams that differ. The system's
    float32 leg follows the reference; with the sections left out (every pair
    by the temporal stream) it does not."""
    t = jnp.arange(96)
    positions = jnp.stack([t, t // 8, t % 8])[:, None, :]
    resolved = resolved_tiny()
    got, want = _legs(resolved, positions)
    assert all(bool(jnp.array_equal(g, w)) for g, w in zip(
        got["selection"], want["selection"]))
    scale = float(jnp.max(jnp.abs(want["logits"])))
    assert float(jnp.max(jnp.abs(got["logits"] - want["logits"]))) <= 2e-6 * scale
    for name, (distance, _) in resolved["module"]._distances(
            got["grads"], want["grads"]).items():
        assert float(distance) <= 3e-5, name
    # a text row's streams are equal: the same as one stream
    text, _ = _legs(resolved, jnp.broadcast_to(t, (3, 1, 96)))
    alone, _ = _legs(resolved, None)
    np.testing.assert_array_equal(text["logits"], alone["logits"])
    from horovod_tpu.models import RotaryScheme

    wrong, _ = _legs(resolved, positions, rotary=RotaryScheme(theta=1e7))
    assert float(jnp.max(jnp.abs(wrong["logits"] - want["logits"]))) > 1e-3 * scale


def test_a_choice_moved_by_hand_is_held_under_the_systems_choice(
        hvd, monkeypatch, fresh_traces):
    """A query whose last kept key is exchanged for the first it left out
    (as a float32 tie between its 24th and 25th score would), and a token
    whose 2nd expert is exchanged for its 3rd: both shares are held as
    shares, and losses, logits and gradients against the reference computed
    under the system's choices (``selection(forced=)``, ``route(forced=)``),
    at the float32 limits."""
    from horovod_tpu.models import moe as models_moe
    from horovod_tpu.ops import sparse_attention as dsa

    # the selection's one kernel call since PR 46 (the XLA path's
    # ``select_rows`` went with it): the seam tests/test_sparse_attention.py
    # takes, the two bits flipped in ``words``
    real_call, real_route = dsa._select_call, models_moe.topk_route

    def other_key(scores, row0, topk, chunk, interpret):
        words, _ = real_call(scores, row0, topk, chunk, interpret)
        rows, t = scores.shape
        mask = dsa.unpack(words, t, chunk)
        pos = jnp.arange(t)
        last = jnp.max(jnp.where(mask, pos, -1), axis=1)
        free = jnp.min(jnp.where((scores > -jnp.inf) & ~mask, pos, t), axis=1)
        here = (row0 + jnp.arange(rows) == 90) & (free < t)
        mask ^= here[:, None] & ((pos == last[:, None]) | (pos == free[:, None]))
        kept = jnp.where(mask, scores, -jnp.inf)
        return dsa.pack(mask, chunk), jax.nn.logsumexp(kept, axis=1)

    def other_expert(logits, top_k, renormalise=False):
        probs, _, experts = real_route(logits, top_k, renormalise)
        _, wider = jax.lax.top_k(probs, top_k + 1)
        first = jnp.arange(experts.shape[0])[:, None] == 0      # token 0 alone
        last = jnp.arange(top_k)[None, :] == top_k - 1
        experts = jnp.where(first & last, wider[:, top_k:], experts)
        onehot = experts[:, :, None] == jnp.arange(probs.shape[-1])
        weights = jnp.sum(jnp.where(onehot, probs[:, None, :], 0.0), axis=-1)
        return probs, weights / weights.sum(-1, keepdims=True), experts

    monkeypatch.setattr(dsa, "_select_call", other_key)
    monkeypatch.setattr(models_moe, "topk_route", other_expert)
    resolved, check = check_alone(hvd)
    resolved["config"]["tolerance"] = {
        **resolved["config"]["tolerance"], "f32_flipped_share": 0.05,
        "f32_selection_share": 0.01, "bf16_logits_rel": 1.0}
    observed = check()["observed"]
    f32 = observed["f32"]
    assert f32["held_under"] == "the system's choice"
    # token 0 by hand; the query whose key moved may choose others after it
    assert 1 / 96 - 1e-6 <= f32["flipped_share"] <= 3 / 96 + 1e-6
    kept = sum(min(t + 1, 24) for t in range(96))
    # a pair a layer by hand; the query's later selection may follow it
    assert 2 / (2 * kept) - 1e-9 <= f32["selection_share"] <= 8 / (2 * kept)
    assert f32["logits"] <= 2e-6 and f32["lm"] <= 1e-6 and f32["align"] <= 4e-5
    assert max(f32["grads_rel"].values()) <= 3e-5
    assert observed["bf16"]["held_under"] == "the reference's own choice"


def test_the_eight_shares_add_up_to_the_uncut_layer(hvd):
    """What ties the share to the model: the system's expert layer run as each
    of 8 ranks (4 of 32 experts each, all 32 router outputs, the chosen 3
    renormalised over all 3 whoever holds them) gives parts whose sum is the
    uncut reference's layer; the router and attention are counted once, being
    the same on every rank."""
    from horovod_tpu.models.moe import MoEMLP

    from benchmarks.reference import keye_vl2 as plain

    d, e, width, top_k, ranks = 32, 32, 16, 3, 8
    keys = jax.random.split(jax.random.PRNGKey(5), 5)

    def normal(key, *shape):
        return 0.3 * jax.random.normal(key, shape, jnp.float32)

    whole = {"router": normal(keys[0], d, e), "w_gate": normal(keys[1], e, d, width),
             "w_up": normal(keys[2], e, d, width),
             "w_down": normal(keys[3], e, width, d)}
    x = jax.random.normal(keys[4], (2, 24, d), jnp.float32)
    with jax.default_matmul_precision("highest"):
        uncut, balance, stats = plain.experts(
            whole, x.reshape(-1, d), {"top_k": top_k, "held": (0, e)})
        total, losses = 0.0, []
        for rank in range(ranks):
            first, count = rank * e // ranks, e // ranks
            layer = MoEMLP(dim=d, hidden=width, n_experts=e, top_k=top_k,
                           dtype=jnp.float32, router="softmax", norm_topk=True,
                           held=(first, count))
            params = {"router": whole["router"],
                      **{k: whole[k][first:first + count]
                         for k in ("w_gate", "w_up", "w_down")}}
            part, state = layer.apply({"params": params}, x,
                                      mutable=["intermediates"])
            losses.append(float(state["intermediates"]["moe_lb_loss"][0]))
            total = total + part.reshape(-1, d)
    assert int(stats["counts"].sum()) == 48 * top_k
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               atol=2e-5, rtol=2e-5)
    # every rank's balancing loss is the whole router's, over all 32 outputs
    np.testing.assert_allclose(losses, float(balance), rtol=1e-5)
    # and the shares differ: no rank's part alone is the layer
    assert float(jnp.max(jnp.abs(part.reshape(-1, d) - uncut))) > 1e-2


def test_every_catalog_key_is_in_the_file_as_published():
    config = run.resolve_cell(run.load_manifest(), CELL)["config"]
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(json.loads(line) for line in f
                   if '"Keye-VL-2.0-30B-A3B"' in line)
    for key, value in row["config"].items():
        assert config[key] == value, key
    entry = next(c for c in run.load_manifest()["configs"]
                 if c["name"] == "keye_vl_2_0_30b_a3b")
    assert entry["reduced"] == ["layers", "num_experts", "vocab_size"]
    assert entry["source"] == row["source_url"] == config["source"]
    assert (config["layers"], config["experts_held"], config["experts_first"],
            config["vocab_held"]) == (5, 16, 0, 18992)
    assert config["vocab_held"] * 8 == config["vocab_size"]
    assert config["experts_held"] * 8 == config["num_experts"]
    for key, words in {"deployment_share": ("8 chips", "rank 0"),
                       "layers": ("48", "whole period"),
                       "num_experts": ("128", "16 held"),
                       "vocab_size": ("151,936", "18,992"),
                       "parameters": ("562.3 M", "8.38 GiB", "96.90 M"),
                       "expert_load": ("1,024 rows", "8 x their share")}.items():
        for word in words:
            assert word in config["cut"][key], (key, word)
    assert "vision tower" in config["left_out"]
    assert len(config["assumed"]) == 8
    assumed = " ".join(config["assumed"])
    for word in ("per-head RMSNorm", "normed hidden state", "LayerNorm",
                 "16^-0.5 x 64^-0.5", "temporal stream", "per QUERY TOKEN",
                 "no FP8", "0.001", "recomputation"):
        assert word in assumed, word
    assert set(config["tolerance"]) >= {
        "flash_rel", "align_rel", "f32_logits_rel", "f32_grads_rel",
        "f32_index_grads_rel", "f32_lm_rel", "f32_align_rel",
        "f32_balance_rel", "f32_flipped_share", "f32_selection_share",
        "bf16_logits_rel", "bf16_grads_l2_rel", "bf16_index_grads_l2_rel",
        "bf16_lm_rel", "bf16_align_rel", "bf16_balance_rel",
        "bf16_flipped_share", "bf16_selection_share", "why"}
    # the cell's lists: the throughput, the per-name readers the model emits
    manifest = run.load_manifest()
    listed = {m["name"] for kind in ("end_to_end", "per_layer")
              for m in manifest[kind] if CELL in m.get("workloads", [])}
    assert listed == {
        "train_tok_per_s_per_chip", "moe_route_ms_per_step",
        "moe_dispatch_ms_per_step", "moe_combine_ms_per_step",
        "moe_grouped_ms_per_step", "moe_dispatch_rows_per_layer",
        "unnamed_device_ms_per_step", "dsa_indexer_ms_per_step",
        "dsa_select_ms_per_step", "dsa_flash_ms_per_step",
        "dsa_align_ms_per_step", "dsa_flash_roofline_pct",
        "dsa_selected_share", "dsa_live_block_share"}
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["traffic"]) == (1, "seq16384x1_fence5_dsa")


def test_parameter_count_of_the_cut_is_the_files():
    """The share's parameters, counted from the model's own shapes at the
    published widths (abstractly: nothing is allocated)."""
    resolved = run.resolve_cell(run.load_manifest(), CELL)
    module, config = resolved["module"], resolved["config"]
    model = module._model(config)
    params = module._shapes(model)["params"]
    count = sum(math.prod(p.shape) for p in jax.tree_util.tree_leaves(params))
    assert count == 562290560       # 562.3 M: 8.38 GiB at 16 B a parameter
    block = params["block_3"]
    assert sum(math.prod(p.shape) for p in jax.tree_util.tree_leaves(block)
               ) == 96899456
    assert block["q_proj"]["kernel"].shape == (2048, 32 * 128)
    assert block["kv_proj"]["kernel"].shape == (2048, 2 * 4 * 128)
    assert block["q_head_norm"]["scale"].shape == (128,)
    assert block["index_q"]["kernel"].shape == (2048, 16 * 64)
    assert block["index_k"]["kernel"].shape == (2048, 64)
    assert block["index_w"]["kernel"].shape == (2048, 16)
    assert sorted(block["index_k_norm"]) == ["bias", "scale"]
    assert block["moe"]["w_gate"].shape == (16, 2048, 768)
    assert block["moe"]["router"].shape == (2048, 128)
    assert params["lm_head"]["kernel"].shape == (2048, 18992)
    sparse = model.sparse
    assert (sparse.index_heads, sparse.index_dim, sparse.topk, sparse.kv_chunk,
            sparse.q_chunk) == (16, 64, 2048, 512, 512)
    assert model.rotary.sections == (16, 24, 24) and model.rotary.theta == 1e7
    cfg = module.reference_config(config)
    assert cfg["sections"] == (16, 24, 24) and cfg["held"] == (0, 16)
    assert (cfg["align_weight"], cfg["balance_weight"]) == (1.0, 0.001)


def test_costs_against_hand_counts():
    resolved = run.resolve_cell(run.load_manifest(), CELL)
    cost = resolved["module"].cost(resolved["config"], resolved["traffic"], 1)
    t, k = 16384, 2048
    kept = sum(min(p + 1, k) for p in range(t))
    assert dsa_cost.selected_pairs(t, k) == kept == 31458304
    assert dsa_cost.causal_pairs(t) == t * (t + 1) // 2
    assert dsa_cost.selected_pairs(100, k) == dsa_cost.causal_pairs(100)
    assert dsa_cost.selected_pairs(8, 3) == 1 + 2 + 3 * 6
    assert cost["dsa_pairs"] == {"selected": 5 * kept,
                                 "causal": 5 * t * (t + 1) // 2}
    assert kept / (t * (t + 1) // 2) == pytest.approx(0.2344, abs=1e-4)
    # five layers at 32 heads over 4: the forward twice (remat), the
    # backward's five once; a product is 2 x pairs x 128 a head
    assert cost["dsa_flash"]["flops"] == 5 * (2 * kept * 128 * 32) * (2 * 2 + 5)
    assert 11.5e12 < cost["dsa_flash"]["flops"] < 11.7e12
    by_q, by_kv, lse, bits = (t * 32 * 128 * 2, t * 4 * 128 * 2, t * 32 * 4,
                              t * t // 8)
    assert cost["dsa_flash"]["bytes"] == 5 * (
        2 * (2 * by_q + 2 * by_kv + lse + bits)
        + 4 * by_q + 4 * by_kv + lse + 2 * bits)
    assert dsa_cost.flash_calls_step_cost(t, 1, 32, 4, 128, 5, k)["flops"] == (
        5 * (2 * kept * 128 * 32) * 7)          # without remat: seven
    # a token, forward: q, o, k, v; the indexer's three; router + one held
    # pair (8 x 16 / 128); the head over the held rows
    attention = 2 * 2048 * (2 * 32 * 128 + 2 * 4 * 128)
    indexer = 2 * 2048 * (16 * 64 + 64 + 16)
    expert = 2 * 2048 * 128 + 6 * 2048 * 768 * 8 * 16 / 128
    head = 2 * 2048 * 18992
    pairs = 4 * kept * 128 * 32                 # QK^T and PV, selected pairs
    scores = 2 * (t * (t + 1) // 2) * 16 * 64   # once: no gradient through it
    align = 2 * kept * 128 * 32 + 3 * 2 * kept * 16 * 64
    want = (5 * (3 * (t * (attention + indexer + expert) + pairs) + scores
                 + align) + 3 * t * head)
    assert cost["model_flops"] == pytest.approx(want, rel=1e-12)
    assert 27e12 < want < 29e12


def test_readers_on_a_hand_made_table(hvd, monkeypatch):
    seconds = {"hvd_flash_sel_fwd": 0.140, "hvd_flash_sel_bwd_dq": 0.090,
               "hvd_flash_sel_bwd_dkv": 0.110, "hvd_dsa_indexer": 0.011,
               "hvd_dsa_indexer_scores": 0.017, "hvd_dsa_select": 0.069,
               "hvd_dsa_align": 0.002, "hvd_dsa_align_tiles": 0.104}
    monkeypatch.setattr(named_device_time, "_tables",
                        [{"seconds": seconds, "unnamed": 0.2}])
    logged = []
    context = {"trace": {"steps": 10}, "log": logged.append,
               "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
               "cost": {"dsa_flash": {"flops": 11.6e12, "bytes": 6.7e9},
                        "dsa_pairs": {"selected": 100, "causal": 400}}}

    def read(name):
        return run.load_module(os.path.join(
            REPO, "benchmarks", "layer_metrics", name + ".py")).read(context)

    assert read("dsa_flash_ms_per_step") == pytest.approx(340.0)
    assert read("dsa_indexer_ms_per_step") == pytest.approx(28.0)
    assert read("dsa_select_ms_per_step") == pytest.approx(69.0)
    assert read("dsa_align_ms_per_step") == pytest.approx(106.0)
    # bound by compute: 11.6e12 / 197e12 = 58.88 ms against 8.18 by bytes
    assert read("dsa_flash_roofline_pct") == pytest.approx(
        100 * 58.883 / 340.0, rel=1e-4)
    assert len(logged) == 1 and "bound by compute" in logged[0]
    hvd.metrics.record_dsa_census(np.asarray([[90, 60], [110, 64]]), 68)
    assert read("dsa_selected_share") == pytest.approx(0.25)
    assert read("dsa_live_block_share") == pytest.approx(62 / 68)
    # a program without the names or the gauges (the parent): nothing, no raise
    monkeypatch.setattr(named_device_time, "_tables", [
        {"seconds": {"hvd_flash_fwd": 0.08}, "unnamed": 0.2}])
    for gauge in ("horovod_dsa_selected_pairs_per_step",
                  "horovod_dsa_live_block_steps_per_step",
                  "horovod_dsa_dense_block_steps_per_step"):
        hvd.metrics.registry().gauge(gauge).set(0)
    for name in ("dsa_flash_ms_per_step", "dsa_indexer_ms_per_step",
                 "dsa_select_ms_per_step", "dsa_align_ms_per_step",
                 "dsa_flash_roofline_pct", "dsa_selected_share",
                 "dsa_live_block_share"):
        assert read(name) is None, name
    monkeypatch.setattr(named_device_time, "_tables", [None])
    assert read("dsa_flash_ms_per_step") is None
    context["cost"] = {}
    monkeypatch.setattr(named_device_time, "_tables",
                        [{"seconds": seconds, "unnamed": 0.2}])
    assert read("dsa_flash_roofline_pct") is None


def test_the_reference_alone():
    """The plain reference by itself: what it holds, and that each of its
    own parts matters to it."""
    from benchmarks.reference import keye_vl2 as plain

    cfg = {"hidden": 16, "head_dim": 8, "heads": 4, "kv_heads": 2,
           "eps": 1e-6, "theta": 1e7, "sections": (1, 1, 2), "index_heads": 2,
           "index_dim": 4, "topk": 5, "top_k": 2, "held": (2, 2),
           "experts": 8, "expert_width": 8, "vocab": 32, "layers": 2,
           "align_weight": 1.0, "balance_weight": 0.001}
    tokens = jnp.arange(12).reshape(1, 12) % 32
    params = plain.init_params(jax.random.PRNGKey(0), cfg, scale=0.3)
    (loss, parts), grads = plain.loss_and_grads(params, tokens, cfg)
    assert math.isfinite(float(loss)) and len(parts["chosen"]) == 2
    assert float(loss) == pytest.approx(float(
        parts["lm"] + parts["align"] + 0.001 * parts["balance"]), rel=1e-6)
    assert all(int(c.sum()) == 12 * 2 for c in parts["counts"])
    # each query keeps min(t + 1, 5) of its causal keys
    for sel in parts["selection"]:
        np.testing.assert_array_equal(sel.sum(-1)[0], np.minimum(
            np.arange(12) + 1, 5))
        assert not bool(jnp.any(sel & ~jnp.tril(jnp.ones((12, 12), bool))))
    # only experts 2 and 3 are held: their leaves, and no other's, exist
    assert grads["layers"][1]["w_gate"].shape == (2, 16, 8)
    # the indexer's leaves move by the alignment loss alone
    lm_only = jax.grad(lambda p: plain.loss_parts(p, tokens, cfg)[1]["lm"])(params)
    assert float(jnp.max(jnp.abs(lm_only["layers"][0]["index_q"]))) == 0.0
    assert float(jnp.max(jnp.abs(grads["layers"][0]["index_q"]))) > 0.0
    assert float(jnp.max(jnp.abs(grads["layers"][0]["index_k_norm_b"]))) > 0.0
    # a forced selection IS the selection
    forced = [jnp.tril(jnp.ones((1, 12, 12), bool))] * 2
    _, dense = plain.loss_parts(params, tokens, cfg, None,
                                {"selection": forced})
    assert all(bool(jnp.array_equal(s, f)) for s, f in zip(
        dense["selection"], forced))
    assert float(jnp.max(jnp.abs(dense["logits"] - parts["logits"]))) > 1e-4


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_seeded_weights_keep_the_tokens_apart(seed):
    """What every seed's amount of work stands on: with embedding rows drawn
    at the module's ``EMBED_STD`` a layer's held experts draw about their
    balanced share of the pairs; at the other leaves' 0.02 randomly
    initialised attention averages the tokens into one direction and some
    layer's held experts draw half or double it (the plain reference, small
    widths). The file states the rate it trains at and why."""
    from benchmarks.reference import keye_vl2 as plain

    resolved = run.resolve_cell(run.load_manifest(), CELL)
    module, config = resolved["module"], resolved["config"]
    assert (module.INIT_STD, module.EMBED_STD) == (0.02, 3.0)
    assert config["optimizer"]["learning_rate"] == 7.3e-6
    assert "SPARSE TRAINING STAGE" in config["optimizer"]["learning_rate_why"]
    assert "normal(3)" in config["assumed"][7]

    cfg = {"hidden": 256, "head_dim": 32, "heads": 4, "kv_heads": 2,
           "eps": 1e-6, "theta": 1e7, "sections": (4, 6, 6), "index_heads": 2,
           "index_dim": 16, "topk": 128, "top_k": 4, "held": (0, 8),
           "experts": 32, "expert_width": 64, "vocab": 512, "layers": 4,
           "align_weight": 1.0, "balance_weight": 0.001}
    seq = 384
    balanced = seq * cfg["top_k"] * cfg["held"][1] // cfg["experts"]
    key = jax.random.PRNGKey(seed)
    tokens = jax.random.randint(jax.random.fold_in(key, 1), (1, seq), 0,
                                cfg["vocab"])

    def held_rows(embed_std):
        params = plain.init_params(key, cfg, scale=module.INIT_STD)
        params["embed"] = params["embed"] * (embed_std / module.INIT_STD)
        counts = plain.forward(params, tokens, cfg)[1]["counts"]
        return np.array([int(c[:cfg["held"][1]].sum()) for c in counts])

    apart = held_rows(module.EMBED_STD)
    assert np.all(np.abs(apart / balanced - 1) < 0.2), apart
    collapsed = held_rows(module.INIT_STD)
    assert np.max(np.abs(np.log2(collapsed / balanced))) > 0.4, collapsed
