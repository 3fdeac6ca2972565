"""The ``olmoe_1b_7b`` configuration at a tiny size on the 4-device virtual CPU
mesh: the cell end to end through ``run.run_cell``, a wrong router that is not
``correct``, the cost functions against hand counts, the three ``Experts``
readers on a hand-made ``breakdown``, and the two copies of the plain
reference held to the same outputs."""

from __future__ import annotations

import copy
import json
import math
import os
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests"))

from benchmarks import moe_cost, run  # noqa: E402

CELL = "olmoe_seq4096_1chip"
# Tiny sizes under the file's own keys; one layer, as the cell runs (with two,
# a flipped expert in the first reaches every later token of the second
# through attention, and no token "agrees" cleanly). With 8 experts and 128
# tokens a bf16 rounding of the hidden state flips a 2nd expert against a 3rd
# for several tokens in a hundred, so the share of differing tokens is held
# at a quarter here, not at the file's 3% of 4096 tokens over 64 experts.
TINY = {"vocab_size": 256, "hidden_size": 64, "num_attention_heads": 2,
        "num_key_value_heads": 2, "num_experts": 8, "num_experts_per_tok": 2,
        "intermediate_size": 32, "layers": 1}
TRAFFIC = {"seq": 128, "global_rows": 4, "fence_every": 2, "fence_lag": 1,
           "warmup_groups": 1, "trace_groups": 1}


def resolved_tiny():
    resolved = run.resolve_cell(run.load_manifest(), CELL)
    config = {**resolved["config"], **copy.deepcopy(TINY)}
    config["tolerance"] = {**config["tolerance"], "bf16_flipped_share": 0.25}
    resolved["config"], resolved["traffic"] = config, dict(TRAFFIC)
    return resolved


@pytest.fixture()
def cpu_memory(monkeypatch):
    monkeypatch.setattr(run, "hbm_bytes", lambda devices: 3 << 30)


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
    observed = json.loads(out.split("kernels vs f32 reference (share of "
                                    "max|ref|): ")[1].splitlines()[0])
    assert observed["f32"]["flipped_share"] == 0.0
    assert observed["f32"]["logits"] <= 2e-5
    assert max(observed["f32"]["grads_rel"].values()) <= 2e-5
    assert len(observed["f32"]["grads_rel"]) == 8 + 1
    # every token agrees in float32: the gradients are the step's own, through
    # the chunked loss; as trained some tokens flip (held at a quarter here),
    # and the gradients are held on those that agree
    assert observed["f32"]["grads_through"] == "chunked_lm_loss"
    assert 0.0 < observed["bf16"]["flipped_share"] <= 0.25
    assert observed["bf16"]["grads_through"] == "agreeing tokens"
    assert set(observed["bf16"]["grads_l2_rel"]) == set(observed["f32"]["grads_rel"])
    assert 1e-3 < max(observed["bf16"]["grads_l2_rel"].values()) <= 3e-2
    # the check set the gauge the third reader reports, the trace the others
    gauges = hvd.metrics.registry().snapshot()["gauges"]
    assert gauges["horovod_moe_expert_load_max_over_mean"] == pytest.approx(
        observed["expert_load_max_over_mean"][-1])


@pytest.mark.parametrize("fault", ["renormalised", "dropped_pair"])
def test_a_wrong_router_is_not_correct(hvd, cpu_memory, capsys, monkeypatch, fault):
    """What the float32 leg exists for: a top-k whose weights are renormalised
    (Mixtral's router, not OLMoE's), or one that loses its last pair."""
    from horovod_tpu.models import moe

    real = moe.topk_route

    def wrong(logits, top_k):
        probs, weights, experts = real(logits, top_k)
        if fault == "renormalised":
            return probs, weights / weights.sum(axis=-1, keepdims=True), experts
        return probs, weights.at[:, -1].set(0.0), experts

    monkeypatch.setattr(moe, "topk_route", wrong)
    result = run.run_cell(resolved_tiny(), jax.devices()[:4], seed=3,
                          seconds=0.0, trace=0, flash_interpret=True)
    assert result["correct"] is False
    assert "against its float32 reference: f32" in capsys.readouterr().out


@pytest.mark.parametrize("leg", ["f32", "bf16"])
def test_a_wrong_backward_is_not_correct(hvd, cpu_memory, capsys, monkeypatch, leg):
    """The forward is right and a backward is not: the chunked loss hands the
    hidden states 0.9 of their gradient (the float32 leg takes its gradients
    through it), or the as-trained gradients are held to a limit they do not
    meet (that they are held at all: a coarser backward than bf16 reads 7e-2
    and more where this path reads 1e-2, PERF.md PR 26)."""
    from horovod_tpu.models import transformer

    resolved = resolved_tiny()
    if leg == "f32":
        real = transformer.chunked_lm_loss

        @jax.custom_vjp
        def wrong(hidden, head, targets):
            return real(hidden, head, targets)

        def fwd(hidden, head, targets):
            return jax.vjp(lambda h, w: real(h, w, targets), hidden, head)

        def bwd(pull, g):
            d_hidden, d_head = pull(g)
            return 0.9 * d_hidden, d_head, None

        wrong.defvjp(fwd, bwd)
        monkeypatch.setattr(transformer, "chunked_lm_loss", wrong)
    else:
        resolved["config"]["tolerance"]["bf16_grads_l2_rel"] = 1e-3
    result = run.run_cell(resolved, jax.devices()[:4], seed=3, seconds=0.0,
                          trace=0, flash_interpret=True)
    out = capsys.readouterr().out
    assert result["correct"] is False
    message = out.split("against its float32 reference: ")[1].splitlines()[0]
    held = message.split("; observed ")[0]
    assert f"{leg} gradient of" in held and "logits" not in held
    assert " total" not in held and "flipped" not in held


def test_a_near_tie_is_weighted_out_on_both_sides(hvd, cpu_memory, capsys, monkeypatch):
    """One token whose last expert differs between system and reference (at
    the published widths: a tie within float32 rounding, one seed in fourteen)
    must not make the run incorrect: its position is weighted 0 in the cross
    entropy of both sides and the gradients are compared again. Here the tie
    is made by hand: token 5 takes its 3rd choice in place of its 2nd."""
    import jax.numpy as jnp
    from horovod_tpu.models import moe

    real = moe.topk_route

    def tied(logits, top_k):
        probs, weights, experts = real(logits, top_k)
        if logits.shape[0] != 128:       # the 4-row step: leave it alone
            return probs, weights, experts
        third = jnp.argsort(-probs[5])[top_k]
        return (probs, weights.at[5, -1].set(probs[5, third]),
                experts.at[5, -1].set(third))

    monkeypatch.setattr(moe, "topk_route", tied)
    resolved = resolved_tiny()
    # 1 of 128 tokens, where the file allows 4 of 4096. The load-balancing
    # term still counts the token's assignment (1 of 256 here, 1 of 32,768 in
    # the cell), which moves lb by 9e-4 and the router's gradient by 1e-3;
    # left in the cross entropy the token would move gradients by 7e-3..3e-2.
    resolved["config"]["tolerance"].update(f32_flipped_share=0.01,
                                           f32_loss_rel=0.01, f32_grads_rel=2e-3)
    result = run.run_cell(resolved, jax.devices()[:4], seed=3, seconds=0.0,
                          trace=0, flash_interpret=True)
    out = capsys.readouterr().out
    assert result["correct"] is True, out
    observed = json.loads(out.split("max|ref|): ")[1].splitlines()[0])
    assert observed["f32"]["flipped_share"] == pytest.approx(1 / 128)
    assert observed["f32"]["logits"] <= 2e-5
    assert observed["f32"]["grads_through"] == "agreeing tokens"
    assert max(observed["f32"]["grads_rel"].values()) <= 2e-3


def test_costs_against_hand_counts():
    """OLMoE-1B-7B, one layer, 4 rows of 4096 on one chip, by hand."""
    resolved = run.resolve_cell(run.load_manifest(), CELL)
    cost = resolved["module"].cost(resolved["config"], resolved["traffic"], 1)
    tokens = 4 * 4096
    head = 2 * 2048 * 50304
    projections = 2 * 2048 * 4 * 2048
    router = 2 * 2048 * 64
    experts = 8 * 3 * 2 * 2048 * 1024
    attention = 4 * 2 * (2 * 4096 * 4096 * 128) * 16 // 2   # 4 rows, causal half
    assert cost["model_flops"] == 3 * (
        tokens * (head + projections + router + experts) + attention)
    assert cost["model_flops"] == pytest.approx(17.56e12, rel=1e-3)
    rows = tokens * 8
    assert cost["experts"]["flops"] == 9 * 2 * rows * 2048 * 1024
    assert cost["experts"]["flops"] == 3 * tokens * experts
    # each of gate, up, down touches its input, output and weights three times
    # (forward, input gradient, weight gradient), in bf16
    one = rows * 2048 + rows * 1024 + 64 * 2048 * 1024
    assert cost["experts"]["bytes"] == 3 * 3 * one * 2
    assert cost["kernel"]["flops"] == 4 * 7 * 4096 * 4096 * 128 * 16
    # two layers double the layer's part, not the head's
    two = resolved["module"].cost({**resolved["config"], "layers": 2},
                                  resolved["traffic"], 1)
    assert two["model_flops"] - cost["model_flops"] == 3 * (
        tokens * (projections + router + experts) + attention)
    assert two["experts"]["flops"] == 2 * cost["experts"]["flops"]


def test_experts_readers_on_a_hand_made_breakdown(hvd):
    readers = os.path.join(REPO, "benchmarks", "layer_metrics")

    def read(name, context):
        return run.load_module(os.path.join(readers, name + ".py")).read(context)

    trace = {"steps": 20, "breakdown": {"device_ops": [
        ["fusion [bench_fwd_bwd] jvp()/while/body/dot_general", 2.0],
        ["kernel ragged-dot-none", 0.8],
        ["fusion [bench_fwd_bwd] moe._topk_swiglu/hvd_moe_experts/mul", 0.1],
        ["fusion [bench_fwd_bwd] moe._topk_swiglu/hvd_moe_experts/jit(silu)/mul", 0.1],
        ["fusion [bench_fwd_bwd] moe._topk_swiglu/hvd_moe_combine/gather", 0.3],
    ]}}
    logged = []
    context = {"trace": trace, "log": lambda *a: logged.append(a),
               "peaks": {"bf16_flops_per_s": 100e12, "hbm_bytes_per_s": 1e12},
               "cost": {"experts": {"flops": 2e12, "bytes": 30e9}}}
    assert moe_cost.experts_seconds_per_step(trace) == pytest.approx(0.05)
    assert read("moe_experts_ms_per_step", context) == pytest.approx(50.0)
    # least time: max(2e12 / 100e12 = 20 ms, 30e9 / 1e12 = 30 ms) over 50 ms
    assert read("moe_experts_roofline_pct", context) == pytest.approx(60.0)
    assert "HBM bandwidth" in logged[0][0]
    # a program without the experts (the parent, or another cell): nothing
    bare = {**context, "trace": {"steps": 20, "breakdown": {"device_ops": [
        ["fusion [bench_fwd_bwd] dot_general", 2.0]]}}, "cost": {"kernel": None}}
    assert read("moe_experts_ms_per_step", bare) is None
    assert read("moe_experts_roofline_pct", bare) is None
    from horovod_tpu.ops.moe import record_expert_load

    logits = np.zeros((6, 4), np.float32)
    logits[:, 2] = 1.0                                  # all six rows pick 2
    logits[np.arange(6), [0, 0, 0, 1, 1, 3]] = 0.5      # second choices
    record_expert_load(logits, 2)
    # 12 assignments over 4 experts: mean 3, the fullest has 6
    assert read("moe_expert_load_max_over_mean", context) == pytest.approx(2.0)


def test_the_two_reference_copies_agree():
    from benchmarks.reference import olmoe as benchmark_copy
    from references import olmoe as test_copy

    cfg = dict(hidden=32, heads=2, experts=4, top_k=2, expert_width=16,
               vocab=64, eps=1e-5, rope_theta=10000.0, lb_coef=0.01, z_coef=0.001)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64)
    results = []
    for copy_ in (benchmark_copy, test_copy):
        params = copy_.init_params(jax.random.PRNGKey(0), cfg, layers=2, scale=0.1)
        (total, parts), grads = copy_.loss_and_grads(params, tokens, cfg)
        results.append(jax.tree_util.tree_leaves(
            (total, parts["logits"], parts["lb"], parts["z"], grads)))
    assert len(results[0]) == len(results[1]) > 20
    for a, b in zip(*results):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
