"""The ``olmo_hybrid_7b`` configuration at a tiny size on the virtual CPU mesh:
the cell end to end through ``run.run_cell``; the system against the plain
reference with each piece of the mathematics shown NOT left out (the float32
leg fails without it); the chunked rule and its kernels against the
token-by-token recurrence at key != value width; the share adding up (the two
tensor ranks' partial sums give the uncut layer's); the file's keys against
the catalog's; the parameter count of the cut from the model's own shapes; the
cost functions against hand counts and the new readers on hand-made gauges
and tables. It asserts that the manifest HOLDS the cell and its metrics, not
that either is last or alone in a list."""

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

from benchmarks import gdn_cost, run  # noqa: E402
from benchmarks.reference import olmo_hybrid as plain  # noqa: E402

CELL = "olmo_hybrid_seq16384_1chip"
CONFIG = "olmo_hybrid_7b"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# Tiny sizes under the file's own keys: the cut's four layers at a width of
# 128, 2 of 4 heads (keys of 24 under values of 48; attention's of 32), 96 of
# 192 MLP columns, an eighth of a vocabulary of 2,048.
TINY = {"vocab_held": 256, "vocab_size": 2048, "hidden_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 4,
        "attention_heads_held": 2, "kv_heads_held": 2,
        "linear_num_key_heads": 4, "linear_num_value_heads": 4,
        "linear_heads_held": 2, "linear_key_head_dim": 24,
        "linear_value_head_dim": 48, "intermediate_size": 192,
        "mlp_columns_held": 96}
TRAFFIC = {"seq": 256, "global_rows": 2, "fence_every": 2, "fence_lag": 1,
           "warmup_groups": 1, "trace_groups": 1, "reference_prefix": 128,
           "flash_slice": 128, "scan_slice": 64}
LINEAR_LEAVES = ("a_log", "conv_k", "conv_q", "conv_v", "dt_bias", "o_norm",
                 "wa", "wb", "wg", "wk", "wo", "wq", "wv")
FULL_LEAVES = ("k_norm", "q_norm", "wk", "wo", "wq", "wv")
SHARED_LEAVES = ("attn_norm", "mlp_norm", "w_down", "w_gate", "w_up")
CUT = ("linear_attention",) * 3 + ("full_attention",)


# At a width of 128 the as-trained leg reads several times what it reads at
# 3,840 (a norm on every half's OUTPUT hands each half's rounding on at full
# size: 1% of the hidden state a layer here): its limits at this size.
TINY_BF16 = {"bf16_logits_rel": 0.3, "bf16_loss_rel": 1e-3,
             "bf16_grads_l2_rel": 0.5}


def resolved_tiny(**more):
    resolved = run.resolve_cell(run.load_manifest(), CELL)
    resolved["config"] = {**resolved["config"], **TINY, **more}
    resolved["config"]["tolerance"] = {**resolved["config"]["tolerance"],
                                       **TINY_BF16}
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
    assert set(observed["scan"]) == {
        f"{leg}_{gates}" for leg in ("bf16", "f32")
        for gates in ("as_initialised", "strongest")}
    for name, parts in observed["scan"].items():
        assert set(parts) == {"o", "dq", "dk", "dv", "dg", "dbeta"}
        assert max(parts.values()) <= (2e-2 if name.startswith("bf16") else 1e-4)
    assert observed["f32"]["logits"] <= 2e-5 and observed["f32"]["loss"] <= 1e-6
    leaves = observed["f32"]["grads_rel"]
    assert set(leaves) == (
        {f"layer{i}.{leaf}" for i, k in enumerate(CUT)
         for leaf in (LINEAR_LEAVES if k == "linear_attention" else FULL_LEAVES)}
        | {f"layer{i}.{leaf}" for i in range(4) for leaf in SHARED_LEAVES}
        | {"embed", "final_norm", "head"})
    assert max(leaves.values()) <= 1e-4
    assert set(observed["bf16"]["grads_l2_rel"]) == set(leaves)
    assert 1e-3 < max(observed["bf16"]["grads_l2_rel"].values()) <= 0.5
    assert 1e-4 < observed["bf16"]["logits"] <= 0.3
    gauges = hvd.metrics.registry().snapshot()["gauges"]
    assert gauges["horovod_gdn_chunk_len"] == 64
    # every linear layer's scan on the kernels (in the interpreter here), in
    # each of the programs that hold the model and in their recomputation
    assert gauges["horovod_gdn_kernel_scans"] == 3
    assert gauges["horovod_gdn_key_lanes_padded"] == 128


def test_a_limit_that_is_passed_makes_the_run_incorrect(hvd, cpu_memory, capsys):
    """Each of the cell's limits decides ``correct`` by itself."""
    resolved = resolved_tiny()
    resolved["config"]["tolerance"] = {**resolved["config"]["tolerance"],
                                       "bf16_scan_rel": 1e-6}
    result = run.run_cell(resolved, jax.devices()[:1], seed=5, seconds=0.0,
                          trace=0, flash_interpret=True)
    out = capsys.readouterr().out
    assert result["correct"] is False
    assert "INCORRECT" in out and "scan bf16" in out


# ------------------------------------------- system against the reference

@pytest.fixture(scope="module")
def seeded():
    """(config, params, tokens, the reference's result, the module)."""
    resolved = resolved_tiny()
    module, config = resolved["module"], resolved["config"]
    model = module._model(config, flash_interpret=True)
    params = jax.jit(module._init_state(model, config))(jax.random.PRNGKey(11))
    tokens = jax.random.randint(jax.random.PRNGKey(12), (1, 128), 0, 256)
    rows = jnp.arange(module.SAMPLED_ROWS, dtype=jnp.int32)
    programs = module.check_programs(config, flash_interpret=True)
    with jax.default_matmul_precision("highest"):
        want = programs[0](params, tokens, rows)
    return config, params, tokens, rows, want, module, programs


def worst(got, want):
    return max(float(jnp.max(jnp.abs(got[k] - want[k]))
                     / jnp.maximum(jnp.max(jnp.abs(want[k])), 1e-30))
               for k in want)


@pytest.mark.parametrize("leg,limits", [("f32", (1e-6, 2e-5, 1e-4)),
                                        ("bf16", (1e-3, 0.3, 0.8))])
def test_system_against_reference(seeded, leg, limits):
    config, params, tokens, rows, want, module, programs = seeded
    program, precision = {"f32": (programs[1], "highest"),
                          "bf16": (programs[2], None)}[leg]
    with jax.default_matmul_precision(precision):
        got = program(params, tokens, rows)
    assert abs(float(got["loss"]) - float(want["loss"])) <= (
        limits[0] * float(want["loss"]))
    assert float(want["loss"]) == pytest.approx(math.log(256), rel=0.02)
    assert worst({"l": got["logits"]}, {"l": want["logits"]}) <= limits[1]
    assert worst(got["grads"], want["grads"]) <= limits[2]


PIECES = ("convolutions", "l2_norms", "decay", "beta_factor", "head_norm",
          "silu_gate", "block_norms", "qk_norm")


def forward_without(piece, params, tokens, cfg):
    """``plain.forward`` from the reference's own pieces with ONE of them left
    out (``None``: nothing, and then it IS ``plain.forward``): the three
    convolutions, both L2 norms, the decay (alpha = 1), the factor 2 on
    beta, the head norm, the silu gate (KDA's sigmoid in its place), the
    output-side block norms (pre-norm in their place), the QK-norm."""
    eps = cfg["eps"]
    conv = ((lambda x, taps: jax.nn.silu(x)) if piece == "convolutions"
            else plain.conv_silu)
    unit = (lambda x: x) if piece == "l2_norms" else plain.unit

    def linear(layer, x):
        b, t, _ = x.shape
        heads, dk, dv = cfg["linear_heads"], cfg["key_dim"], cfg["value_dim"]
        q = conv(x @ layer["wq"], layer["conv_q"]).reshape(b, t, heads, dk)
        k = conv(x @ layer["wk"], layer["conv_k"]).reshape(b, t, heads, dk)
        v = conv(x @ layer["wv"], layer["conv_v"]).reshape(b, t, heads, dv)
        g = -jnp.exp(layer["a_log"]) * jax.nn.softplus(
            x @ layer["wa"] + layer["dt_bias"])
        beta = jax.nn.sigmoid(x @ layer["wb"]) * (
            1.0 if piece == "beta_factor" else 2.0)
        o = plain.delta_rule(unit(q) * dk ** -0.5, unit(k), v,
                             jnp.zeros_like(g) if piece == "decay" else g, beta)
        if piece != "head_norm":
            o = plain.rms(o, layer["o_norm"], eps)
        gate = (jax.nn.sigmoid if piece == "silu_gate" else jax.nn.silu)(
            x @ layer["wg"])
        return (o.reshape(b, t, heads * dv) * gate) @ layer["wo"]

    def full(layer, x):
        if piece != "qk_norm":
            return plain.full_attention(layer, x, cfg)
        b, t, _ = x.shape
        q, k, v = (x @ layer[w] for w in ("wq", "wk", "wv"))
        shape = (b, t, cfg["heads"], cfg["head_dim"])
        s = jnp.einsum("bqhd,bkhd->bhqk", q.reshape(shape),
                       k.reshape(shape)) * cfg["head_dim"] ** -0.5
        causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v.reshape(shape)).reshape(
            b, t, -1) @ layer["wo"]

    mixers = {"linear_attention": linear, "full_attention": full}
    x = params["embed"][tokens]
    for kind, layer in zip(cfg["kinds"], params["layers"]):
        mlp = lambda h: plain.swiglu(h, layer["w_gate"], layer["w_up"],  # noqa: E731
                                     layer["w_down"])
        if piece == "block_norms":
            x = x + mixers[kind](layer, plain.rms(x, layer["attn_norm"], eps))
            x = x + mlp(plain.rms(x, layer["mlp_norm"], eps))
        else:
            x = x + plain.rms(mixers[kind](layer, x), layer["attn_norm"], eps)
            x = x + plain.rms(mlp(x), layer["mlp_norm"], eps)
    return plain.rms(x, params["final_norm"], eps) @ params["head"]


def test_the_pieces_together_are_the_reference(seeded):
    config, params, tokens, rows, want, module, programs = seeded
    cfg = module.reference_config(config)
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(lambda p, t: forward_without(
            None, module.to_reference(p), t, cfg))(params, tokens)
    np.testing.assert_allclose(logits, want["logits"], rtol=1e-5, atol=2e-5)


@pytest.mark.parametrize("piece", PIECES)
def test_no_piece_of_the_mathematics_is_left_out(seeded, piece):
    """The float32 leg is held against the reference WITHOUT the piece: it
    must then fail by the logits' limit of the cell's own file, ten times
    over, where the whole reference holds it."""
    config, params, tokens, rows, want, module, programs = seeded
    cfg = module.reference_config(config)
    with jax.default_matmul_precision("highest"):
        got = programs[1](params, tokens, rows)
        logits = jax.jit(lambda p, t: forward_without(
            piece, module.to_reference(p), t, cfg))(params, tokens)
    sound = worst({"l": got["logits"]}, {"l": want["logits"]})
    without = worst({"l": got["logits"]}, {"l": logits})
    limit = config["tolerance"]["f32_logits_rel"]
    assert sound <= limit < without / 10, (piece, sound, without)


# ------------------------------------------------ the rule and its kernels

def scan_inputs(seed, t, h, dk, dv, strongest=False, beta_near_two=False):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)

    def unit(k):
        x = jax.random.normal(k, (1, t, h, dk))
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    g = -jax.nn.softplus(jax.random.normal(ks[3], (1, t, h)))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (1, t, h))
                              + (6.0 if beta_near_two else 0.0))
    return (unit(ks[0]) * dk ** -0.5, unit(ks[1]),
            jax.random.normal(ks[2], (1, t, h, dv)),
            jnp.where(strongest, -320.0, g), beta), jax.random.normal(
                ks[5], (1, t, h, dv))


def with_grads(fn, args, do):
    out, vjp = jax.vjp(fn, *args)
    return (out,) + vjp(do)


def rel(got, want):
    return [float(jnp.max(jnp.abs(g - w)) / jnp.maximum(jnp.max(jnp.abs(w)), 1e-30))
            for g, w in zip(got, want)]


@pytest.mark.parametrize("case", ["as_drawn", "strongest_decay", "beta_near_2"])
@pytest.mark.parametrize("path", ["jax_numpy", "kernels"])
def test_chunked_rule_against_recurrence_at_key_ne_value_width(case, path):
    """``ops.gdn.gdn`` (keys of 24 under values of 48 on a row of three
    chunks, which is no whole pair of them, through ``jax.numpy``; 96 under
    192 through the kernels in the interpreter, five blocks of a pair of
    chunks: four borders between blocks) against the recurrence a step a
    token: o and all five gradients, float32."""
    from horovod_tpu.ops import gdn as ops

    kernels = path == "kernels"
    t, h, dk, dv = (640, 2, 96, 192) if kernels else (192, 3, 24, 48)
    args, do = scan_inputs(5, t, h, dk, dv, case == "strongest_decay",
                           case == "beta_near_2")
    assert ops.takes_kernel(*args[:3], 64) == kernels
    if not kernels:     # widths a lane block does not hold stay jax.numpy
        assert not ops.takes_kernel(*(jnp.zeros((1, 256, 2, d)) for d in
                                      (160, 160, 192)), 64)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda *a: with_grads(
            lambda *x: ops.gdn(*x, interpret=kernels, neg_eigval=True),
            a[:-1], a[-1]))(*args, do)
        want = jax.jit(lambda *a: with_grads(ops.gdn_recurrence, a[:-1],
                                             a[-1]))(*args, do)
    assert all(bool(jnp.all(jnp.isfinite(x))) for x in got)
    assert max(rel(got, want)) <= 2e-4, rel(got, want)


def test_the_scan_records_its_plan(hvd):
    from horovod_tpu.ops import gdn as ops

    args, _ = scan_inputs(1, 256, 2, 96, 192)
    jax.eval_shape(lambda *a: ops.gdn(*a, interpret=True), *args)
    jax.eval_shape(lambda *a: ops.gdn(*a, interpret=True), *args)
    gauges = hvd.metrics.registry().snapshot()["gauges"]
    assert gauges["horovod_gdn_key_lanes_padded"] == 128
    assert gauges["horovod_gdn_chunk_len"] == 64
    # one block of 256 positions: one saved state of 2 heads x 128 x 256 f32
    assert gauges["horovod_gdn_saved_state_bytes_per_layer"] == 2 * 128 * 256 * 4
    small, _ = scan_inputs(1, 128, 2, 24, 48)
    jax.eval_shape(lambda *a: ops.gdn(*a[:3], a[3], a[4], 32), *small)
    gauges = hvd.metrics.registry().snapshot()["gauges"]
    assert gauges["horovod_gdn_key_lanes_padded"] == 24
    assert gauges["horovod_gdn_chunk_len"] == 32


# --------------------------------------------------- the share adds up

def normal(key, *shape):
    return 0.3 * jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)


def cut_columns(w, heads, width, rank, ranks=2):
    """Columns of a matrix (or entries of a vector) laid out heads x width:
    the rank's heads."""
    held = heads // ranks
    return w[..., rank * held * width:(rank + 1) * held * width]


def test_the_two_ranks_partial_sums_add_up_to_the_uncut_layer():
    """Heads 0-1 and 2-3 of both mixers and the MLP's two halves of columns:
    the partial sums BEFORE each half's norm add up to the uncut reference
    layer's, with the two ranks' QK-norm sums of squares added as the
    deployment's all-reduce would (here, in the test alone)."""
    d, heads, dk, dv, hd, width, t = 64, 4, 24, 48, 32, 96, 128
    x = normal(0, 1, t, d)
    cfg = {"linear_heads": heads, "key_dim": dk, "value_dim": dv,
           "neg_eigval": True, "eps": 1e-6, "heads": heads, "head_dim": hd}
    half = {**cfg, "linear_heads": heads // 2, "heads": heads // 2}
    linear = {"wq": normal(1, d, heads * dk), "wk": normal(2, d, heads * dk),
              "wv": normal(3, d, heads * dv), "conv_q": normal(4, 4, heads * dk),
              "conv_k": normal(5, 4, heads * dk),
              "conv_v": normal(6, 4, heads * dv), "wa": normal(7, d, heads),
              "wb": normal(8, d, heads), "a_log": normal(9, heads),
              "dt_bias": normal(10, heads), "wg": normal(11, d, heads * dv),
              "o_norm": 1.0 + normal(12, dv), "wo": normal(13, heads * dv, d)}
    by_width = {"wq": dk, "wk": dk, "wv": dv, "conv_q": dk, "conv_k": dk,
                "conv_v": dv, "wa": 1, "wb": 1, "a_log": 1, "dt_bias": 1,
                "wg": dv}

    def linear_rank(rank):
        layer = {k: cut_columns(v, heads, by_width[k], rank)
                 for k, v in linear.items() if k in by_width}
        layer["o_norm"] = linear["o_norm"]
        layer["wo"] = cut_columns(linear["wo"].T, heads, dv, rank).T
        return plain.linear_attention(layer, x, half)

    with jax.default_matmul_precision("highest"):
        whole = plain.linear_attention(linear, x, cfg)
        parts = linear_rank(0) + linear_rank(1)
    np.testing.assert_allclose(parts, whole, rtol=1e-4, atol=1e-5)

    full = {"wq": normal(20, d, heads * hd), "wk": normal(21, d, heads * hd),
            "wv": normal(22, d, heads * hd), "wo": normal(23, heads * hd, d),
            "q_norm": 1.0 + normal(24, heads * hd),
            "k_norm": 1.0 + normal(25, heads * hd)}

    def full_rank(rank, mean_square):
        """The rank's partial sum with the WHOLE projection's mean square
        under its QK-norm (the all-reduced one), from the reference's own
        layer: its ``rms`` divides by the held channels' mean square, so q
        and k are scaled back to the whole's before it, exactly."""
        layer = {k: cut_columns(v, heads, hd, rank) for k, v in full.items()
                 if k != "wo"}
        layer["wo"] = cut_columns(full["wo"].T, heads, hd, rank).T
        for name in ("q", "k"):
            own = jnp.mean(jnp.square(x @ layer[f"w{name}"]), -1, keepdims=True)
            scale = jnp.sqrt((own + 1e-6) / (mean_square[name] + 1e-6))
            layer[f"w{name}"] = layer[f"w{name}"], scale
        b, t_, _ = x.shape
        shape = (b, t_, heads // 2, hd)
        q = plain.rms(x @ layer["wq"][0], layer["q_norm"], 1e-6) * layer["wq"][1]
        k = plain.rms(x @ layer["wk"][0], layer["k_norm"], 1e-6) * layer["wk"][1]
        v = x @ layer["wv"]
        s = jnp.einsum("bqhd,bkhd->bhqk", q.reshape(shape),
                       k.reshape(shape)) * hd ** -0.5
        causal = jnp.arange(t_)[:, None] >= jnp.arange(t_)[None, :]
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v.reshape(shape)).reshape(
            b, t_, -1) @ layer["wo"]

    with jax.default_matmul_precision("highest"):
        whole = plain.full_attention(full, x, cfg)
        # the all-reduce: each rank's sum of squares over its channels, added
        sums = {name: sum(jnp.sum(jnp.square(
            x @ cut_columns(full[f"w{name}"], heads, hd, r)), -1, keepdims=True)
            for r in range(2)) / (heads * hd) for name in ("q", "k")}
        parts = full_rank(0, sums) + full_rank(1, sums)
    np.testing.assert_allclose(parts, whole, rtol=1e-4, atol=1e-5)

    w_gate, w_up, w_down = (normal(30, d, width), normal(31, d, width),
                            normal(32, width, d))
    with jax.default_matmul_precision("highest"):
        whole = plain.swiglu(x, w_gate, w_up, w_down)
        parts = sum(plain.swiglu(x, w_gate[:, c], w_up[:, c], w_down[c])
                    for c in (slice(0, width // 2), slice(width // 2, width)))
    np.testing.assert_allclose(parts, whole, rtol=1e-4, atol=1e-5)


# ------------------------------------------------- the file and the count

def catalog_entry():
    with open(CATALOG) as f:
        for line in f:
            row = json.loads(line)
            if row["name"] == "Olmo-Hybrid-7B":
                return row
    raise AssertionError("the catalog has no Olmo-Hybrid-7B")


def test_the_file_keeps_every_published_key():
    manifest = run.load_manifest()
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    config = run.load_json(os.path.join(REPO, entry["file"]))
    row = catalog_entry()
    assert entry["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert config[key] == value, key
    for key in ("equations", "assumed", "cut", "deployment", "tolerance"):
        assert config[key], key
    assert len(config["assumed"]) >= 6
    assert config["tensor_parallel"] == 2 and config["vocab_shards"] == 8
    assert set(entry["reduced"]) - {"layers"} <= set(row["config"])
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "seq16384x1_fence5_gdn", 1)
    tolerance = config["tolerance"]
    for key in ("f32_logits_rel", "f32_grads_rel", "f32_loss_rel",
                "bf16_logits_rel", "bf16_grads_l2_rel", "flash_rel",
                "f32_scan_rel", "bf16_scan_rel"):
        assert 0 < tolerance[key] < 1


def test_the_cuts_parameter_count_from_the_models_own_shapes():
    """512.6 M: the arithmetic of the file's ``cut.parameters``, term by
    term, against the shapes ``TransformerLM.init`` gives (traced, never
    run)."""
    resolved = run.resolve_cell(run.load_manifest(), CELL)
    module, config = resolved["module"], resolved["config"]
    shapes = jax.eval_shape(module._model(config).init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 128), jnp.int32))["params"]

    def count(tree):
        return sum(math.prod(x.shape) for x in jax.tree_util.tree_leaves(tree))

    linear_mixer = (2 * 3840 * 1440 + 3 * 3840 * 2880 + 2 * 3840 * 15
                    + 4 * 5760 + 30 + 192)
    attention = 4 * 3840 * 1920 + 2 * 1920
    mlp, norms = 3 * 3840 * 5504, 2 * 3840
    assert linear_mixer == 44_375_262 and attention == 29_495_040
    assert mlp == 63_406_080
    for i in range(3):
        assert count(shapes[f"block_{i}"]["mixer"]) == linear_mixer
        assert count(shapes[f"block_{i}"]) == linear_mixer + mlp + norms
    assert count(shapes["block_3"]) == attention + mlp + norms == 92_908_800
    assert count(shapes["embed"]) == count(shapes["lm_head"]) == 12544 * 3840
    total = count(shapes)
    assert total == 3 * 107_789_022 + 92_908_800 + 2 * 48_168_960 + 3840
    assert total == 512_617_626
    assert 7.63 < total * 16 / 2 ** 30 < 7.65


def test_costs_against_hand_counts():
    resolved = run.resolve_cell(run.load_manifest(), CELL)
    cost = resolved["module"].cost(resolved["config"], resolved["traffic"], 1)
    t, c, h, dk, dv = 16384, 64, 15, 96, 192
    per_chunk_head = (2 * c * c * dk + c * c * (dk + dv) + c * c * dv
                      + 3 * 2 * c * dk * dv)
    forward = t // c * h * per_chunk_head
    assert gdn_cost.gdn_forward_flops(t, h, dk, dv, c) == forward
    assert cost["gdn_scan"]["flops"] == 3 * 4 * forward     # remat: 2 + 2
    assert cost["gdn_scan"]["bytes"] == 3 * 4 * t * h * (
        2 * (2 * dk + 2 * dv) + 8)
    linear = t * 2 * 3840 * h * (2 * dk + 3 * dv + 2) + forward
    full = t * 2 * 4 * 3840 * 15 * 128 + t * t * 256 * 15
    mlp = t * 6 * 3840 * 5504
    assert cost["model_flops"] == 3 * (3 * linear + full + 4 * mlp
                                       + t * 2 * 3840 * 12544)


def test_the_new_readers_on_hand_made_tables_and_gauges(hvd, monkeypatch):
    from benchmarks import named_device_time

    manifest = run.load_manifest()
    mine = {m["name"]: m for m in manifest["per_layer"]
            if m.get("workloads") == [CELL]}
    assert set(mine) == {
        "gdn_mixer_ms_per_step", "gdn_proj_ms_per_step",
        "gdn_conv_ms_per_step", "gdn_gate_ms_per_step", "gdn_scan_ms_per_step",
        "gdn_out_norm_ms_per_step", "gdn_scan_roofline_pct", "gdn_chunk_len",
        "gdn_kernel_scans"}
    assert {m["layer"] for m in mine.values()} == {"GDN mixer"}
    for name in ("train_tok_per_s_per_chip",):
        entry = next(m for m in manifest["end_to_end"] if m["name"] == name)
        assert CELL in entry["workloads"]
    seconds = {name: 0.001 * (i + 1) for i, name in enumerate(gdn_cost.MIXER)}
    monkeypatch.setattr(named_device_time, "_tables",
                        [{"seconds": seconds, "unnamed": 0.5}])
    readers = os.path.join(REPO, "benchmarks", "layer_metrics")

    def read(name, context):
        return run.load_module(os.path.join(readers, name + ".py")).read(context)

    context = {"cost": {"gdn_scan": {"flops": 197e12 * 0.002,
                                     "bytes": 819e9 * 0.001}},
               "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
               "log": lambda *a: None, "trace": {}}
    assert read("gdn_mixer_ms_per_step", context) == pytest.approx(
        sum(seconds.values()) * 1e3)
    scan_ms = sum(seconds[n] for n in gdn_cost.SCAN) * 1e3
    assert read("gdn_scan_ms_per_step", context) == pytest.approx(scan_ms)
    assert read("gdn_scan_roofline_pct", context) == pytest.approx(
        100 * 2.0 / scan_ms)
    for part, names in (("proj", gdn_cost.PROJ), ("conv", gdn_cost.CONV),
                        ("gate", gdn_cost.GATE), ("out_norm", gdn_cost.OUT_NORM)):
        assert read(f"gdn_{part}_ms_per_step", context) == pytest.approx(
            sum(seconds[n] for n in names) * 1e3)
    # a program without the names (the parent): nothing, and no raise
    monkeypatch.setattr(named_device_time, "_tables",
                        [{"seconds": {"hvd_mlp": 1.0}, "unnamed": 0.0}])
    for name in mine:
        if name.endswith("_ms_per_step") or name.endswith("_pct"):
            assert read(name, context) is None
    registry = hvd.metrics.registry()
    registry.gauge("horovod_gdn_chunk_len").set(0)
    registry.gauge("horovod_gdn_kernel_scans").set(0)
    assert read("gdn_chunk_len", {}) is None
    assert read("gdn_kernel_scans", {}) is None
    hvd.metrics.record_gdn_plan(64, 1 << 20, 128)
    assert read("gdn_chunk_len", {}) == 64
    from horovod_tpu.metrics import overlap
    monkeypatch.setattr(overlap, "_GDN_LAYERS", {})
    for layer, kernel in (("block_0/mixer", True), ("block_1/mixer", True),
                          ("block_0/mixer", True), ("block_2/mixer", False)):
        hvd.metrics.record_gdn_kernel_scan(layer, kernel)
    assert read("gdn_kernel_scans", {}) == 2
    from horovod_tpu.common import device_names
    assert set(gdn_cost.MIXER) <= set(device_names.ALL)
