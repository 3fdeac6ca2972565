"""The ``ouro_2_6b`` configuration at a tiny size on the virtual CPU mesh: the
cell end to end through ``run.run_cell``; the system (``TransformerLM`` with
``passes``, ``exit_gate`` and ``sandwich_norm`` under ``loop_lm_loss``)
against the plain reference - four passes' logits, four gates, the exit
distribution, the loss and every leaf's gradient; the loop tied to its
weights (a shared leaf's gradient is the sum of the gradients of four
independent copies of the stack); one pass with the gate ignored against the
plain model under ``chunked_lm_loss``; the weighted chunked loss against
``optax`` on materialised logits, ``d_weights`` included; the file's keys
against the catalog's; the parameter count of the cut from the model's own
shapes; the cost functions against hand counts and the new readers on
hand-made gauges and tables. It asserts that the manifest HOLDS the cell and
its metrics, not that either is last or alone in a list."""

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

from benchmarks import loop_cost, run  # noqa: E402
from benchmarks.reference import ouro as plain  # noqa: E402

CELL = "ouro_seq8192_1chip"
CONFIG = "ouro_2_6b"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# Tiny sizes under the file's own keys: two layers x four passes at a width
# of 128, 4 heads of 32, an MLP of 192 columns, a vocabulary of 256.
TINY = {"vocab_size": 256, "hidden_size": 128, "num_attention_heads": 4,
        "num_key_value_heads": 4, "head_dim": 32, "intermediate_size": 192,
        "layers": 2}
TRAFFIC = {"seq": 256, "global_rows": 2, "fence_every": 2, "fence_lag": 1,
           "warmup_groups": 1, "trace_groups": 1, "reference_prefix": 128,
           "flash_slice": 128}
LAYER_LEAVES = ("attn_in_norm", "attn_out_norm", "mlp_in_norm", "mlp_out_norm",
                "w_down", "w_gate", "w_up", "wk", "wo", "wq", "wv")
LEAVES = ({f"layer{i}.{leaf}" for i in range(2) for leaf in LAYER_LEAVES}
          | {"embed", "final_norm", "head", "gate"})
PASSES = 4


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
    try:
        result = run.run_cell(resolved, jax.devices()[:2], seed=3, seconds=0.0,
                              trace=0, flash_interpret=True)
        gauges = hvd.metrics.registry().snapshot()["gauges"]
    finally:
        resolved["module"].forget_exit_mass()
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
    f32, bf16 = observed["f32"], observed["bf16"]
    assert f32["logits"] <= 2e-5 and f32["loss"] <= 1e-6
    assert f32["gates"] <= 1e-5
    assert set(f32["grads_rel"]) == LEAVES
    assert max(f32["grads_rel"].values()) <= 1e-4
    assert set(bf16["grads_l2_rel"]) == LEAVES
    assert 1e-3 < max(bf16["grads_l2_rel"].values()) <= 0.5
    assert 1e-4 < bf16["logits"] <= 0.25 and 1e-5 < bf16["gates"] <= 0.05
    for mass in (f32["exit_mass"], bf16["exit_mass"],
                 observed["exit_mass_reference"]):
        assert len(mass) == PASSES and sum(mass) == pytest.approx(1, abs=1e-5)
    assert gauges["horovod_loop_passes"] == PASSES
    assert gauges["horovod_loop_block_applications"] == 2 * PASSES
    assert gauges["horovod_chunked_loss_products_per_chunk"] == 3
    # the steps' own exit mass, from the ring the step writes
    masses = [gauges[f'horovod_loop_exit_mass{{loop_pass="{t}"}}']
              for t in range(1, PASSES + 1)]
    assert sum(masses) == pytest.approx(1, abs=1e-5)
    assert all(0.02 < m < 0.98 for m in masses)


@pytest.mark.parametrize("limit,said", [("f32_gates_abs", "f32 gates"),
                                        ("bf16_logits_rel", "bf16 logits"),
                                        ("f32_grads_rel", "f32 gradient of")])
def test_a_limit_that_is_passed_makes_the_run_incorrect(hvd, cpu_memory, capsys,
                                                        limit, said):
    """Each of the cell's limits decides ``correct`` by itself."""
    resolved = resolved_tiny()
    resolved["config"]["tolerance"] = {**resolved["config"]["tolerance"],
                                       limit: 1e-12}
    try:
        result = run.run_cell(resolved, jax.devices()[:1], seed=5, seconds=0.0,
                              trace=0, flash_interpret=True)
    finally:    # the build's collector, and with it the build's state
        resolved["module"].forget_exit_mass()
    out = capsys.readouterr().out
    assert result["correct"] is False
    assert "INCORRECT" in out and said in out


# ------------------------------------------- system against the reference

def with_live_gates(params, bias=-0.6, scale=8.0):
    """The gate's bias and weights moved so that every ``p_t`` is away from 0
    and 1 AND differs a token (at the file's initialisation the gates sit at
    1/2 within a few percent)."""
    gate = params["exit_gate"]
    return {**params, "exit_gate": {"kernel": gate["kernel"] * scale,
                                    "bias": gate["bias"] + bias}}


@pytest.fixture(scope="module")
def seeded():
    """(config, params, tokens, rows, the reference's result, the module, the
    check's programs)."""
    resolved = resolved_tiny()
    module, config = resolved["module"], resolved["config"]
    model = module._model(config, flash_interpret=True)
    params = with_live_gates(jax.jit(module._init_state(model, config))(
        jax.random.PRNGKey(11)))
    tokens = jax.random.randint(jax.random.PRNGKey(12), (2, 128), 0, 256)
    rows = jnp.arange(module.SAMPLED_ROWS, dtype=jnp.int32)
    programs = module.check_programs(config, flash_interpret=True)
    with jax.default_matmul_precision("highest"):
        want = programs[0](params, tokens, rows)
    return config, params, tokens, rows, want, module, programs


def worst(got, want):
    return max(float(jnp.max(jnp.abs(got[k] - want[k]))
                     / jnp.maximum(jnp.max(jnp.abs(want[k])), 1e-30))
               for k in want)


@pytest.mark.parametrize("leg,limits", [("f32", (1e-6, 2e-5, 1e-5, 1e-4)),
                                        ("bf16", (1e-3, 0.1, 0.05, 0.5))])
def test_system_against_reference(seeded, leg, limits):
    """Loss, the four passes' logits, the four gates, the exit mass and every
    leaf's gradient (64 rows of a matrix)."""
    config, params, tokens, rows, want, module, programs = seeded
    program, precision = {"f32": (programs[1], "highest"),
                          "bf16": (programs[2], None)}[leg]
    with jax.default_matmul_precision(precision):
        got = program(params, tokens, rows)
        logits = programs[3](got["streams"], params["lm_head"]["kernel"],
                             want["logits"])
    assert abs(float(got["loss"]) - float(want["loss"])) <= (
        limits[0] * float(want["loss"]))
    assert got["streams"].shape == (PASSES, 2, 128, 128)
    assert want["logits"].shape == (PASSES, 2, 128, 256)
    assert float(logits) <= limits[1]
    assert got["gates"].shape == want["gates"].shape == (PASSES, 2, 128)
    assert float(jnp.max(jnp.abs(got["gates"] - want["gates"]))) <= limits[2]
    np.testing.assert_allclose(got["exit_mass"], want["exit_mass"],
                               atol=10 * limits[2])
    assert set(got["grads"]) == set(want["grads"]) == LEAVES
    assert worst(got["grads"], want["grads"]) <= limits[3]
    # the gates are live: no p_t near 0 or 1, and they differ a token
    assert 0.05 < float(want["exit_mass"].min()) < float(
        want["exit_mass"].max()) < 0.7
    assert float(jnp.std(want["gates"])) > 0.05
    # and every gradient is there: none is identically zero
    for name, leaf in want["grads"].items():
        assert float(jnp.max(jnp.abs(leaf))) > 0, name


def test_the_exit_distribution_against_the_reference(seeded):
    """``p`` whole (P, B, T), from the system's own gates and streams: the
    log form against the reference's products, and against the reference's
    own forward pass."""
    from horovod_tpu.models.transformer import exit_log_distribution

    config, params, tokens, rows, want, module, programs = seeded
    model = module._model(config, flash_interpret=True, dtype=jnp.float32,
                          block_q=64, block_k=64)
    with jax.default_matmul_precision("highest"):
        hidden, gates = model.apply({"params": params}, tokens,
                                    return_hidden=True)
        _, (_, _, p) = plain.loss_parts(module.to_reference(params), tokens,
                                        module.reference_config(config))
    assert hidden.shape == (PASSES, 2, 128, 128) and gates.dtype == jnp.float32
    got = jnp.exp(exit_log_distribution(gates))
    np.testing.assert_allclose(got, p, atol=2e-6)
    np.testing.assert_allclose(
        got, plain.exit_distribution(jax.nn.sigmoid(gates)), atol=1e-6)
    np.testing.assert_allclose(got.sum(0), 1.0, atol=1e-6)
    # finite wherever the gates are: no 0 x inf in p log p
    extreme = jnp.array([[-200.0, 200.0, 0.0]] * 3 + [[0.0] * 3])
    log_p = exit_log_distribution(extreme)
    assert bool(jnp.all(jnp.isfinite(log_p)))
    assert bool(jnp.all(jnp.isfinite(jnp.exp(log_p) * log_p)))


def test_the_references_scanned_passes_are_its_python_passes(seeded):
    """The reference's two forms of the loop: four Python passes over the one
    parameter tree against the same pass as the body of its ``lax.scan``
    (the form the chip's check runs): the same loss, logits, gates and
    gradients. (The system's loop is a scan alone; what ties IT to its
    weights is the test of the four independent copies below.)"""
    config, params, tokens, rows, want, module, programs = seeded
    cfg = module.reference_config(config)
    assert cfg["scan_passes"] is True
    ref = module.to_reference(params)
    with jax.default_matmul_precision("highest"):
        unrolled = jax.value_and_grad(plain.loss_parts, has_aux=True)(
            ref, tokens, {**cfg, "scan_passes": False})
        scanned = jax.value_and_grad(plain.loss_parts, has_aux=True)(
            ref, tokens, cfg)
    for a, b in zip(jax.tree_util.tree_leaves(unrolled),
                    jax.tree_util.tree_leaves(scanned)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=5e-6)


def independent_copies_loss(copies, shared, tokens, cfg):
    """The reference's loss with the stack's parameters handed in a PASS:
    ``copies[t]`` is the list of layers pass ``t`` runs."""
    x = shared["embed"][tokens].astype(jnp.float32)
    targets = jnp.roll(tokens, -1, axis=1)
    gates, ces = [], []
    for layers in copies:
        for layer in layers:
            x = plain.layer_forward(layer, x, cfg)
        x = plain.rms(x, shared["final_norm"], cfg["eps"])
        _, gate, ce = plain.read(shared, x, targets)
        gates.append(gate)
        ces.append(ce)
    p = plain.exit_distribution(jnp.stack(gates))
    entropy = -jnp.sum(p * jnp.log(p), axis=0)
    return jnp.mean(jnp.sum(p * jnp.stack(ces), axis=0)
                    - cfg["beta"] * entropy)


def test_a_shared_leafs_gradient_is_the_sum_of_four_independent_copies(seeded):
    """THE TEST THAT TIES THE LOOP TO ITS WEIGHTS: the system's gradient of
    every leaf of the stack, through ``TransformerLM(passes=4)`` and
    ``loop_lm_loss``, is the sum of the four gradients of a model with four
    independent copies of the stack set to the same values; no single copy's
    gradient, and no three of them, would pass."""
    config, params, tokens, rows, want, module, programs = seeded
    cfg = module.reference_config(config)
    shared = module.to_reference(params)
    layers = shared.pop("layers")
    with jax.default_matmul_precision("highest"):
        by_pass = jax.grad(independent_copies_loss)(
            [layers] * PASSES, shared, tokens, cfg)
        got = programs[1](params, tokens, rows)["grads"]
    for i in range(len(layers)):
        for leaf in LAYER_LEAVES:
            each = [by_pass[t][i][leaf] for t in range(PASSES)]
            each = [g if g.ndim < 2 else g[rows % g.shape[0]] for g in each]
            total, mine = sum(each), got[f"layer{i}.{leaf}"]
            scale = float(jnp.max(jnp.abs(total)))
            assert float(jnp.max(jnp.abs(mine - total))) <= 1e-4 * scale
            for t in range(PASSES):     # every pass's share is needed
                assert float(jnp.max(jnp.abs(mine - (total - each[t])))) > (
                    1e-2 * scale), (i, leaf, t)


def test_one_pass_with_the_gate_ignored_is_the_plain_model(seeded):
    """``passes=1``: ``p_1 = 1`` whatever the gate says, the entropy is 0 and
    the weighted loss with weights of 1 is ``chunked_lm_loss``, bit for bit,
    value and gradients; the gate takes no gradient."""
    from horovod_tpu.models.transformer import chunked_lm_loss, loop_lm_loss

    config, params, tokens, rows, want, module, programs = seeded
    once = module._model(config, flash_interpret=True, passes=1)
    flat = module._model(config, flash_interpret=True, passes=1,
                         exit_gate=False)
    targets = jnp.roll(tokens, -1, axis=1)
    ungated = {k: v for k, v in params.items() if k != "exit_gate"}

    def looped(p):
        hidden, gates = once.apply({"params": p}, tokens, return_hidden=True)
        assert hidden.shape == (1, 2, 128, 128) and gates.shape == (1, 2, 128)
        loss, parts = loop_lm_loss(hidden, gates, p["lm_head"]["kernel"],
                                   targets, 0.1, 64)
        return loss, parts

    def plainly(p):
        hidden = flat.apply({"params": p}, tokens, return_hidden=True)
        assert hidden.shape == (2, 128, 128)
        return chunked_lm_loss(hidden, p["lm_head"]["kernel"], targets, 64)

    (loss, parts), grads = jax.jit(jax.value_and_grad(looped, has_aux=True))(
        params)
    want_loss, want_grads = jax.jit(jax.value_and_grad(plainly))(ungated)
    assert float(loss) == float(want_loss) == float(parts["expected"])
    assert float(parts["entropy"]) == 0.0 and parts["exit_mass"].tolist() == [1.0]
    gate = grads.pop("exit_gate")
    assert not np.any(np.asarray(gate["kernel"])) and not np.any(gate["bias"])
    jax.tree_util.tree_map(np.testing.assert_array_equal, grads, want_grads)


# ------------------------------------------------- the weighted chunked loss

def materialised(hidden, kernel, targets, weights):
    """``(1 / N) sum weights CE`` on whole float32 logits, by ``optax``."""
    import optax

    logits = hidden.astype(jnp.float32) @ kernel
    ce = optax.softmax_cross_entropy_with_integer_labels(
        logits, jnp.broadcast_to(targets, logits.shape[:-1]))
    return jnp.sum(weights * ce) / targets.size


@pytest.mark.parametrize("passes", [None, 1, 3])
def test_the_weighted_loss_against_optax(passes):
    """Value, ``d_hidden``, ``d_kernel`` AND ``d_weights``, with weights that
    differ a row, on (B, T, d) and on (P, B, T, d); the call that is not
    differentiated gives the same value."""
    from horovod_tpu.models.transformer import chunked_lm_loss

    lead = () if passes is None else (passes,)
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    hidden = jax.random.normal(ks[0], lead + (2, 96, 32), jnp.float32)
    kernel = 0.3 * jax.random.normal(ks[1], (32, 160), jnp.float32)
    targets = jax.random.randint(ks[2], (2, 96), 0, 160)
    weights = jax.random.uniform(ks[3], lead + (2, 96), jnp.float32, 0.1, 2.0)

    def chunked(h, k, w):
        return chunked_lm_loss(h, k, targets, 32, weights=w)

    with jax.default_matmul_precision("highest"):
        got, grads = jax.value_and_grad(chunked, argnums=(0, 1, 2))(
            hidden, kernel, weights)
        want, want_grads = jax.value_and_grad(
            lambda h, k, w: materialised(h, k, targets, w), argnums=(0, 1, 2))(
            hidden, kernel, weights)
        alone = chunked(hidden, kernel, weights)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    assert float(alone) == pytest.approx(float(want), rel=1e-6)
    for g, w, name in zip(grads, want_grads, ("hidden", "kernel", "weights")):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=1e-8, err_msg=name)
    # the weights' gradient is the per-row cross entropy over N: it differs a
    # row, and a loss that dropped the weights from d_kernel would not pass
    assert float(jnp.std(grads[2])) > 0
    unweighted = jax.grad(lambda k: materialised(
        hidden, k, targets, jnp.ones_like(weights)))(kernel)
    assert float(jnp.max(jnp.abs(grads[1] - unweighted))) > 1e-3 * float(
        jnp.max(jnp.abs(unweighted)))


def test_weights_of_one_are_the_unweighted_loss_bit_for_bit():
    from horovod_tpu.models.transformer import chunked_lm_loss

    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    hidden = jax.random.normal(ks[0], (3, 96, 32), jnp.bfloat16)
    kernel = 0.3 * jax.random.normal(ks[1], (32, 160), jnp.float32)
    targets = jax.random.randint(ks[2], (3, 96), 0, 160)
    ones = jnp.ones((3, 96), jnp.float32)
    got, grads = jax.jit(jax.value_and_grad(
        lambda h, k: chunked_lm_loss(h, k, targets, 32, weights=ones),
        argnums=(0, 1)))(hidden, kernel)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda h, k: chunked_lm_loss(h, k, targets, 32), argnums=(0, 1)))(
        hidden, kernel)
    assert float(got) == float(want)
    for g, w in zip(grads, want_grads):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32))


def test_the_weighted_loss_says_what_it_cannot_take():
    from horovod_tpu.models.transformer import chunked_lm_loss

    hidden = jnp.zeros((2, 1, 64, 8))
    kernel, targets = jnp.zeros((8, 16)), jnp.zeros((1, 64), jnp.int32)
    with pytest.raises(ValueError, match="need their weights"):
        chunked_lm_loss(hidden, kernel, targets, 32)
    with pytest.raises(ValueError, match="a number a row"):
        chunked_lm_loss(hidden, kernel, targets, 32, weights=jnp.ones((1, 64)))
    with pytest.raises(ValueError, match="not divisible"):
        chunked_lm_loss(hidden, kernel, targets, 48, weights=jnp.ones((2, 1, 64)))


@pytest.mark.parametrize("fields,said", [
    (dict(sandwich_norm=True, norm_after=True), "two placements"),
    (dict(sandwich_norm=True, moe_experts=4, moe_top_k=2, moe_every=1),
     "dense MLP halves"),
    (dict(passes=2, mtp_layer_types=("attention",)), "looped model"),
    (dict(exit_gate=True, logits_scaling=2.0), "looped model"),
    (dict(passes=2, tie_embeddings=True), "looped model"),
    (dict(passes=0), "at least once")])
def test_the_model_refuses_what_it_does_not_build(fields, said):
    from horovod_tpu.models import TransformerLM

    model = TransformerLM(vocab=64, dim=32, heads=2, layers=2, mlp_hidden=48,
                          **fields)
    with pytest.raises(ValueError, match=said):
        jax.eval_shape(model.init, jax.random.PRNGKey(0),
                       jnp.zeros((1, 16), jnp.int32))


def test_a_looped_model_returns_every_passs_logits(hvd):
    """Without ``return_hidden``: (P, B, T, vocab) logits through the one
    head and the gates' logits; the parameter tree does not depend on the
    flag, and holds ONE stack; the trace records its plan."""
    from horovod_tpu.models import TransformerLM

    model = TransformerLM(vocab=64, dim=32, heads=2, layers=2, mlp_hidden=48,
                          sandwich_norm=True, passes=3, exit_gate=True,
                          remat=True)
    tokens = jnp.zeros((1, 16), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    assert set(params) == {"embed", "block_0", "block_1", "RMSNorm_0",
                           "exit_gate", "lm_head"}
    assert set(params["block_0"]) == {"RMSNorm_0", "RMSNorm_1", "RMSNorm_2",
                                      "RMSNorm_3", "qkv", "o_proj", "mlp_gate",
                                      "mlp_up", "mlp_down"}
    logits, gates = model.apply({"params": params}, tokens)
    hidden, same = model.apply({"params": params}, tokens, return_hidden=True)
    assert logits.shape == (3, 1, 16, 64) and gates.shape == (3, 1, 16)
    assert hidden.shape == (3, 1, 16, 32)
    np.testing.assert_array_equal(gates, same)
    np.testing.assert_allclose(
        logits, hidden.astype(jnp.float32) @ params["lm_head"]["kernel"],
        rtol=1e-5, atol=1e-6)
    gauges = hvd.metrics.registry().snapshot()["gauges"]
    assert gauges["horovod_loop_passes"] == 3
    assert gauges["horovod_loop_block_applications"] == 6
    ungated = TransformerLM(vocab=64, dim=32, heads=2, layers=2, passes=2)
    streams, none = ungated.apply(
        {"params": ungated.init(jax.random.PRNGKey(0), tokens)["params"]},
        tokens, return_hidden=True)
    assert streams.shape == (2, 1, 16, 32) and none is None


# ------------------------------------------------- the file and the count

def catalog_entry():
    with open(CATALOG) as f:
        for line in f:
            row = json.loads(line)
            if row["name"] == "Ouro-2.6B":
                return row
    raise AssertionError("the catalog has no Ouro-2.6B")


def test_the_file_keeps_every_published_key():
    manifest = run.load_manifest()
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    config = run.load_json(os.path.join(REPO, entry["file"]))
    row = catalog_entry()
    assert entry["source"].startswith(row["source_url"])
    for key, value in row["config"].items():
        assert config[key] == value, key
    for key in ("equations", "assumed", "cut", "deployment", "tolerance"):
        assert config[key], key
    assert entry["reduced"] == ["layers"]
    assert config["layers"] == 8 and config["num_hidden_layers"] == 48
    assert config["total_ut_steps"] == PASSES       # the mechanism is not cut
    said = " ".join(config["assumed"])
    for reading in ("sandwich", "NORMED", "lambda_4", "beta = 0.1", "AdamW",
                    "initialisation"):
        assert reading in said, reading
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "seq8192x1_fence3_loop", 1)
    traffic = run.load_json(os.path.join(
        REPO, "benchmarks", "traffic", cell["traffic"] + ".json"))
    assert {k: v for k, v in traffic.items() if k != "why"} == {
        "seq": 8192, "global_rows": 1, "fence_every": 3, "fence_lag": 1,
        "warmup_groups": 2, "trace_groups": 2, "reference_prefix": 2048,
        "flash_slice": 2048}
    tolerance = config["tolerance"]
    for key in ("f32_logits_rel", "f32_gates_abs", "f32_grads_rel",
                "f32_loss_rel", "bf16_logits_rel", "bf16_gates_abs",
                "bf16_grads_l2_rel", "bf16_loss_rel", "flash_rel"):
        assert 0 < tolerance[key] < 1
    for part in ("logits_rel", "gates_abs", "loss_rel"):    # each leg its own
        assert tolerance[f"f32_{part}"] < tolerance[f"bf16_{part}"]


def test_the_cuts_parameter_count_from_the_models_own_shapes():
    """612.4 M: the arithmetic of the file's ``cut.parameters``, term by
    term, against the shapes ``TransformerLM.init`` gives (traced, never
    run)."""
    resolved = run.resolve_cell(run.load_manifest(), CELL)
    module, config = resolved["module"], resolved["config"]
    shapes = jax.eval_shape(module._model(config).init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 128), jnp.int32))["params"]

    def count(tree):
        return sum(math.prod(x.shape) for x in jax.tree_util.tree_leaves(tree))

    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
    assert layer == 51_388_416
    for i in range(8):
        assert count(shapes[f"block_{i}"]) == layer
    assert "block_8" not in shapes
    assert count(shapes["embed"]) == count(shapes["lm_head"]) == 49152 * 2048
    assert count(shapes["RMSNorm_0"]) == 2048
    assert count(shapes["exit_gate"]) == 2049
    total = count(shapes)
    assert total == 8 * layer + 201_326_592 + 2048 + 2049 == 612_438_017
    assert 9.12 < total * 16 / 2 ** 30 < 9.14


def test_costs_against_hand_counts():
    resolved = run.resolve_cell(run.load_manifest(), CELL)
    cost = resolved["module"].cost(resolved["config"], resolved["traffic"], 1)
    t, d, v = 8192, 2048, 49152
    layer = t * 2 * 4 * d * d + t * t * 256 * 16 + t * 6 * d * 5632
    assert loop_cost.layer_forward_flops(t, d, 16, 128, 5632) == layer
    assert cost["model_flops"] == 3 * 4 * (8 * layer + t * 2 * d * v
                                           + t * 2 * d)
    assert 126.9e12 < cost["model_flops"] < 127.1e12
    assert cost["loop_head"]["flops"] == 4 * 3 * 2 * t * d * v
    assert cost["loop_head"]["bytes"] == 2 * d * v * 4 + 4 * 2 * t * d * 2
    # compute-bound on the v5e: 100.5 ms at the peak against 1.3 ms of bytes
    peaks = run.load_json(os.path.join(REPO, "benchmarks", "peaks.json"))[
        "TPU v5 lite"]
    assert cost["loop_head"]["flops"] / peaks["bf16_flops_per_s"] > 50 * (
        cost["loop_head"]["bytes"] / peaks["hbm_bytes_per_s"])


def test_the_new_readers_on_hand_made_tables_and_gauges(hvd, monkeypatch):
    from benchmarks import named_device_time
    from horovod_tpu.common import device_names

    manifest = run.load_manifest()
    mine = {m["name"]: m for m in manifest["per_layer"]
            if m.get("workloads") == [CELL]}
    assert set(mine) == {"loop_exit_ms_per_step", "loop_head_roofline_pct",
                         "loop_passes", "loop_block_applications",
                         "loop_mean_exit_pass"}
    assert {m["layer"] for m in mine.values()} == {"Loop"}
    assert {m["moves"] for m in mine.values()} == {"step_ms"}
    joined = {"train_tok_per_s_per_chip", "flash_fwd_ms_per_step",
              "flash_bwd_dq_ms_per_step", "flash_bwd_dkv_ms_per_step",
              "mlp_ms_per_step", "attn_proj_ms_per_step",
              "attn_other_ms_per_step", "norm_add_ms_per_step",
              "embed_ms_per_step", "lm_head_ms_per_step",
              "unnamed_device_ms_per_step", "named_device_share_pct"}
    for entry in manifest["end_to_end"] + manifest["per_layer"]:
        if entry["name"] in joined:
            assert CELL in entry["workloads"], entry["name"]
    assert set(loop_cost.EXIT + loop_cost.HEAD) <= set(device_names.ALL)
    seconds = {"hvd_loop_exit": 0.004, "hvd_lm_head": 0.25, "hvd_mlp": 1.0}
    monkeypatch.setattr(named_device_time, "_tables",
                        [{"seconds": seconds, "unnamed": 0.5}])
    readers = os.path.join(REPO, "benchmarks", "layer_metrics")

    def read(name, context):
        return run.load_module(os.path.join(readers, name + ".py")).read(context)

    context = {"cost": {"loop_head": {"flops": 197e12 * 0.1,
                                      "bytes": 819e9 * 0.001}},
               "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
               "log": lambda *a: None, "trace": {}}
    assert read("loop_exit_ms_per_step", context) == pytest.approx(4.0)
    assert read("loop_head_roofline_pct", context) == pytest.approx(40.0)
    # a program without the names (the parent): nothing, and no raise
    monkeypatch.setattr(named_device_time, "_tables",
                        [{"seconds": {"hvd_mlp": 1.0}, "unnamed": 0.0}])
    assert read("loop_exit_ms_per_step", context) is None
    assert read("loop_head_roofline_pct", context) is None
    assert read("loop_head_roofline_pct", {"log": lambda *a: None}) is None
    registry = hvd.metrics.registry()
    for t in range(1, 5):
        registry.remove("horovod_loop_exit_mass", loop_pass=str(t))
    hvd.metrics.record_loop_plan(0, 0)
    assert read("loop_passes", {}) is None
    assert read("loop_block_applications", {}) is None
    assert read("loop_mean_exit_pass", {}) is None
    hvd.metrics.record_loop_plan(4, 32)
    assert read("loop_passes", {}) == 4
    assert read("loop_block_applications", {}) == 32
    hvd.metrics.record_loop_exit_mass([])           # no step yet: nothing
    assert read("loop_mean_exit_pass", {}) is None
    hvd.metrics.record_loop_exit_mass([[0.5, 0.25, 0.125, 0.125],
                                       [0.3, 0.25, 0.125, 0.325]])
    assert read("loop_mean_exit_pass", {}) == pytest.approx(
        0.4 * 1 + 0.25 * 2 + 0.125 * 3 + 0.225 * 4)
    hvd.metrics.record_loop_exit_mass([[1.0, 0.0, 0.0, 0.0]])    # a dead gate
    assert read("loop_mean_exit_pass", {}) == 1.0
    hvd.metrics.record_loop_exit_mass([[0.0, 0.0, 0.0, 1.0]])
    assert read("loop_mean_exit_pass", {}) == 4.0
    for t in range(1, 5):
        registry.remove("horovod_loop_exit_mass", loop_pass=str(t))
