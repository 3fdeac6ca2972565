"""The Mamba-2 layer and the Granite 4.0-H hybrid through ``TransformerLM``
against ``tests/references/granite_hybrid.py`` (plain jax.numpy, float32 at
``highest``, the state-space layer a step per token) on seeded weights at
tiny sizes: ``ops.ssd.ssd`` against the recurrence, forward and gradients,
over several chunks, ragged rows and groups; the causal convolution; the
gate-then-norm order; the whole model's logits, loss and EVERY leaf's
gradient, the tied embedding included; what the options change and what they
leave alone.

Tolerances, as shares of max|reference| per array:
* float32: 2e-5. System and reference do the same float32 arithmetic in
  another order (chunked sums against a step per token); observed <= 7e-6,
  the smallest leaves (``A_log``, ``dt_bias``: 2 numbers here) worst.
* bfloat16 activations: one rounding is 4e-3 and a token passes through a
  dozen of them in six layers: logits 5e-2, gradients' norm 2.5e-1 of the
  reference's (observed 1.6e-1 on a leaf of two numbers).
"""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from references import granite_hybrid as ref  # noqa: E402

from horovod_tpu.models import TransformerLM  # noqa: E402
from horovod_tpu.models.mamba import Mamba2Dims, Mamba2Mixer  # noqa: E402
from horovod_tpu.models.transformer import chunked_lm_loss  # noqa: E402
from horovod_tpu.ops import ssd as ssd_ops  # noqa: E402

KINDS = ("mamba", "mamba", "attention", "mamba", "mamba", "mamba")
DIMS = Mamba2Dims(heads=2, head_dim=8, state=16, groups=1, conv=4, chunk=8)
CFG = dict(layer_types=KINDS, heads=4, kv_heads=2, mamba_heads=DIMS.heads,
           mamba_head_dim=DIMS.head_dim, mamba_state=DIMS.state,
           mamba_groups=DIMS.groups, eps=1e-5, emb_mult=12.0,
           attn_mult=0.0625, res_mult=0.22, logits_scaling=8.0)
VOCAB, DIM, WIDTH, SEQ = 96, 32, 48, 40     # 40 tokens = 5 chunks of 8
F32_TOL = 2e-5


def model(**kw):
    return TransformerLM(**{**dict(
        vocab=VOCAB, dim=DIM, heads=CFG["heads"], kv_heads=CFG["kv_heads"],
        layers=len(KINDS), layer_types=KINDS, mamba=DIMS, mlp_hidden=WIDTH,
        rope=False, tie_embeddings=True, rms_norm_eps=CFG["eps"],
        embedding_multiplier=CFG["emb_mult"],
        attention_multiplier=CFG["attn_mult"],
        residual_multiplier=CFG["res_mult"],
        logits_scaling=CFG["logits_scaling"], dtype=jnp.float32), **kw})


def to_reference(tree):
    """``TransformerLM``'s tree (or its gradients) in the reference's layout."""
    layers = []
    for i in range(len(KINDS)):
        block = tree[f"block_{i}"]
        layer = {"norm": block["RMSNorm_0"]["scale"],
                 "mlp_norm": block["RMSNorm_1"]["scale"],
                 "w_gate": block["mlp_gate"]["kernel"],
                 "w_up": block["mlp_up"]["kernel"],
                 "w_down": block["mlp_down"]["kernel"]}
        if "mixer" in block:
            mixer = block["mixer"]
            layer.update(w_in=mixer["in_proj"]["kernel"],
                         conv_w=mixer["conv_kernel"], conv_b=mixer["conv_bias"],
                         dt_bias=mixer["dt_bias"], A_log=mixer["A_log"],
                         D=mixer["D"], gate_norm=mixer["gate_norm"],
                         w_out=mixer["out_proj"]["kernel"])
        else:
            wk, wv = jnp.split(block["kv_proj"]["kernel"], 2, axis=1)
            layer.update(wq=block["q_proj"]["kernel"], wk=wk, wv=wv,
                         wo=block["o_proj"]["kernel"])
        layers.append(layer)
    return {"embed": tree["embed"]["embedding"], "layers": layers,
            "final_norm": tree["RMSNorm_0"]["scale"]}


def share(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


def l2_share(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


def leaves(tree):
    return {jax.tree_util.keystr(path): leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


@pytest.fixture(scope="module")
def seeded():
    """Parameters with every leaf away from its initial constant (norm
    weights, D and the convolution's bias start at 1 / 1 / 0), and tokens."""
    key = jax.random.PRNGKey(7)
    params = model().init(key, jnp.zeros((1, 8), jnp.int32))["params"]
    noise = jax.random.split(jax.random.fold_in(key, 1),
                             len(jax.tree_util.tree_leaves(params)))
    flat, tree = jax.tree_util.tree_flatten(params)
    flat = [x + 0.1 * jax.random.normal(k, x.shape) if x.ndim == 1 else x
            for x, k in zip(flat, noise)]
    tokens = jax.random.randint(jax.random.fold_in(key, 2), (2, SEQ), 0, VOCAB)
    return jax.tree_util.tree_unflatten(tree, flat), tokens


def system_loss(m):
    def loss(params, tokens):
        logits = m.apply({"params": params}, tokens)
        targets = jnp.roll(tokens, -1, axis=1)
        nll = (jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
            logits, targets[..., None], axis=-1)[..., 0])
        return jnp.mean(nll), logits
    return loss


# ------------------------------------------------------------ the scan alone

def scan_inputs(b, t, h, p, g, n, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(ks[0], (b, t, h, p)),
            jax.nn.softplus(jax.random.normal(ks[1], (b, t, h)) - 2.0),
            -jnp.exp(jax.random.uniform(ks[2], (h,), minval=0.0, maxval=2.7)),
            jax.random.normal(ks[3], (b, t, g, n)),
            jax.random.normal(ks[4], (b, t, g, n)),
            jax.random.normal(ks[5], (h,)))


SCAN_CASES = {      # (b, t, heads, head_dim, groups, state, chunk)
    "four_chunks": (2, 32, 2, 8, 1, 16, 8),
    "ragged_row": (1, 27, 4, 8, 2, 16, 8),          # 27 = 3 chunks + 3
    "row_shorter_than_a_chunk": (1, 5, 2, 8, 1, 16, 8),
    "one_chunk": (1, 16, 2, 4, 1, 8, 16),
    "chunk_blocks_in_two_groups": (1, 64, 4, 4, 2, 8, 4),    # 16 chunks: the
    "chunk_blocks_one_group": (1, 128, 2, 4, 1, 8, 8),       # lax.map path
    "twelve_chunks_do_not_block": (1, 48, 2, 4, 1, 8, 4),
}


@pytest.mark.parametrize("case", sorted(SCAN_CASES))
def test_ssd_matches_the_recurrence(case):
    *shape, chunk = SCAN_CASES[case]
    args = scan_inputs(*shape)
    with jax.default_matmul_precision("highest"):
        got = ssd_ops.ssd(*args, chunk)
        want = ref.ssm_recurrence(*args)
    assert got.shape == want.shape
    assert share(got, want) <= F32_TOL


@pytest.mark.parametrize("case", sorted(SCAN_CASES))
def test_ssd_gradients_match_the_recurrences(case):
    *shape, chunk = SCAN_CASES[case]
    args = scan_inputs(*shape, seed=1)

    def scalar(fn):
        return lambda *a: jnp.sum(jnp.sin(fn(*a)))

    with jax.default_matmul_precision("highest"):
        got = jax.grad(scalar(lambda *a: ssd_ops.ssd(*a, chunk)),
                       argnums=range(6))(*args)
        want = jax.grad(scalar(ref.ssm_recurrence), argnums=range(6))(*args)
    for name, g, w in zip(("u", "dt", "A", "B", "C", "D"), got, want):
        assert share(g, w) <= F32_TOL, name


@pytest.mark.parametrize("case", sorted(SCAN_CASES))
def test_the_two_reference_forms_agree(case):
    args = scan_inputs(*SCAN_CASES[case][:-1], seed=2)
    with jax.default_matmul_precision("highest"):
        assert share(ref.ssm_quadratic(*args),
                     ref.ssm_recurrence(*args)) <= F32_TOL


def test_ssd_sets_the_chunk_gauge_and_refuses_bad_shapes(hvd):
    gauge = hvd.metrics.registry().gauge("horovod_ssd_chunk_len")
    args = scan_inputs(1, 32, 2, 8, 1, 16)
    ssd_ops.ssd(*args, 8)
    assert gauge.value == 8
    ssd_ops.ssd(*scan_inputs(1, 5, 2, 8, 1, 16), 8)
    assert gauge.value == 5         # a row shorter than the chunk is one chunk
    with pytest.raises(ValueError, match="chunk must be positive"):
        ssd_ops.ssd(*args, 0)
    with pytest.raises(ValueError, match="do not divide into"):
        ssd_ops.ssd(*scan_inputs(1, 8, 3, 8, 2, 16), 8)


def test_bf16_inputs_keep_decays_and_state_in_float32():
    """As trained: u, B, C in bf16; a bf16 decay or a bf16 carried state
    would err by 4e-3 a chunk and more over 16 chunks."""
    u, dt, A, B, C, D = scan_inputs(1, 128, 2, 8, 1, 16, seed=3)
    rounded = [x.astype(jnp.bfloat16) for x in (u, B, C)]
    got = ssd_ops.ssd(rounded[0], dt, A, rounded[1], rounded[2], D, 8)
    assert got.dtype == jnp.bfloat16
    with jax.default_matmul_precision("highest"):
        want = ref.ssm_recurrence(rounded[0].astype(jnp.float32), dt, A,
                                  *(x.astype(jnp.float32) for x in rounded[1:]),
                                  D)
    assert share(got.astype(jnp.float32), want) <= 2e-2


# ----------------------------------------------------------- the convolution

@pytest.mark.parametrize("width", [2, 4])
def test_causal_depthwise_conv_by_hand(width):
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (2, 9, 5))
    w = jax.random.normal(jax.random.fold_in(key, 1), (width, 5))
    b = jax.random.normal(jax.random.fold_in(key, 2), (5,))
    got = np.asarray(ssd_ops.causal_depthwise_conv(x, w, b))
    xn, wn, bn = (np.asarray(a, np.float64) for a in (x, w, b))
    want = np.zeros_like(xn)
    for t in range(9):
        for j in range(width):
            src = t - (width - 1) + j
            if src >= 0:                    # zeros before the row's start
                want[:, t] += wn[j] * xn[:, src]
    want += bn
    assert share(got, want) <= 1e-6
    assert share(ref.causal_conv(x, w, b), want) <= 1e-6
    # causal: position t does not read position t + 1
    moved = ssd_ops.causal_depthwise_conv(x.at[:, 5].add(1.0), w, b)
    assert np.array_equal(np.asarray(moved)[:, :5], got[:, :5])


# ------------------------------------------------------------- the mixer

@pytest.fixture(scope="module")
def mixer_case():
    dims = Mamba2Dims(heads=4, head_dim=8, state=16, groups=2, conv=4, chunk=8)
    layer = Mamba2Mixer(dim=DIM, dims=dims, dtype=jnp.float32)
    key = jax.random.PRNGKey(3)
    h = jax.random.normal(key, (2, 24, DIM))
    params = layer.init(jax.random.fold_in(key, 1), h)["params"]
    params = {**params, "gate_norm": params["gate_norm"] + 0.3 * jax.random.normal(
        jax.random.fold_in(key, 2), params["gate_norm"].shape)}
    cfg = dict(mamba_heads=4, mamba_head_dim=8, mamba_groups=2, mamba_state=16,
               eps=1e-5)
    plain = dict(w_in=params["in_proj"]["kernel"], conv_w=params["conv_kernel"],
                 conv_b=params["conv_bias"], dt_bias=params["dt_bias"],
                 A_log=params["A_log"], D=params["D"],
                 gate_norm=params["gate_norm"],
                 w_out=params["out_proj"]["kernel"])
    return layer, params, h, plain, cfg


def test_mixer_matches_the_reference_with_two_groups(mixer_case):
    layer, params, h, plain, cfg = mixer_case
    with jax.default_matmul_precision("highest"):
        got = layer.apply({"params": params}, h)
        want = ref.mamba_mixer(h, plain, cfg, ref.ssm_recurrence)
    assert share(got, want) <= F32_TOL


def test_mixer_gates_before_it_norms(mixer_case):
    """Mamba-2's gated norm is ``rms(y * silu(z))``; ``rms(y) * silu(z)``
    (Mamba-1's order, ``norm_before_gate``) is another layer."""
    layer, params, h, plain, cfg = mixer_case

    def norm_first(hh, p):
        b, t, _ = hh.shape
        z, xbc, dt = jnp.split(hh @ p["w_in"], [32, 32 + 32 + 2 * 2 * 16], axis=-1)
        xbc = jax.nn.silu(ref.causal_conv(xbc, p["conv_w"], p["conv_b"]))
        u, B, C = jnp.split(xbc, [32, 32 + 2 * 16], axis=-1)
        y = ref.ssm_recurrence(
            u.reshape(b, t, 4, 8), jax.nn.softplus(dt + p["dt_bias"]),
            -jnp.exp(p["A_log"]), B.reshape(b, t, 2, 16), C.reshape(b, t, 2, 16),
            p["D"]).reshape(b, t, 2, 16)
        normed = ref.rms(y, p["gate_norm"].reshape(2, 16), cfg["eps"])
        return (normed.reshape(b, t, 32) * jax.nn.silu(z)) @ p["w_out"]

    with jax.default_matmul_precision("highest"):
        got = layer.apply({"params": params}, h)
        wrong = norm_first(h, plain)
    assert share(got, wrong) > 1e-1


def test_mixer_initialises_as_mamba2_does():
    dims = Mamba2Dims(heads=64, head_dim=4, state=8)
    params = Mamba2Mixer(dim=16, dims=dims).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8, 16)))["params"]
    a = np.exp(np.asarray(params["A_log"]))
    assert a.min() >= 1.0 and a.max() <= 16.0 and a.std() > 1.0
    dt = np.asarray(jax.nn.softplus(params["dt_bias"]))
    assert dt.min() >= 1e-3 * 0.999 and dt.max() <= 1e-1 * 1.001
    assert np.array_equal(np.asarray(params["D"]), np.ones(64))
    assert np.array_equal(np.asarray(params["gate_norm"]), np.ones(256))
    assert params["in_proj"]["kernel"].shape == (16, 2 * 256 + 2 * 8 + 64)
    assert params["conv_kernel"].shape == (4, 256 + 2 * 8)
    assert all(x.dtype == jnp.float32 for x in jax.tree_util.tree_leaves(params))
    with pytest.raises(ValueError, match="do not divide into"):
        Mamba2Mixer(dim=16, dims=Mamba2Dims(heads=3, head_dim=4, state=8,
                                            groups=2)).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8, 16)))


# ------------------------------------------------------------ the whole model

def test_param_tree_is_the_hybrids(seeded):
    params, _ = seeded
    assert "lm_head" not in params                  # tied: one leaf
    assert set(params["block_0"]) == {"RMSNorm_0", "RMSNorm_1", "mixer",
                                      "mlp_gate", "mlp_up", "mlp_down"}
    assert set(params["block_0"]["mixer"]) == {
        "in_proj", "conv_kernel", "conv_bias", "A_log", "dt_bias", "D",
        "gate_norm", "out_proj"}
    assert set(params["block_2"]) == {"RMSNorm_0", "RMSNorm_1", "q_proj",
                                      "kv_proj", "o_proj", "mlp_gate",
                                      "mlp_up", "mlp_down"}
    assert params["block_2"]["kv_proj"]["kernel"].shape == (DIM, 2 * 2 * 8)
    assert params["block_0"]["mlp_gate"]["kernel"].shape == (DIM, WIDTH)


@pytest.fixture(scope="module")
def reference_run(seeded):
    params, tokens = seeded
    (loss, logits), grads = ref.loss_and_grads(
        to_reference(params), tokens, CFG, ssm=ref.ssm_recurrence)
    return loss, logits, grads


def test_float32_matches_reference_everywhere(seeded, reference_run):
    params, tokens = seeded
    want_loss, want_logits, want_grads = reference_run
    with jax.default_matmul_precision("highest"):
        (loss, logits), grads = jax.value_and_grad(
            system_loss(model()), has_aux=True)(params, tokens)
    assert abs(float(loss) - float(want_loss)) <= 1e-5 * abs(float(want_loss))
    assert share(logits, want_logits) <= F32_TOL
    got, want = leaves(to_reference(grads)), leaves(want_grads)
    assert set(got) == set(want) and len(want) == 5 * 13 + 9 + 2
    for name in want:
        assert share(got[name], want[name]) <= F32_TOL, name


def test_quadratic_form_gives_the_reference_the_same_gradients(seeded,
                                                               reference_run):
    params, tokens = seeded
    want_loss, want_logits, want_grads = reference_run
    (loss, logits), grads = ref.loss_and_grads(to_reference(params), tokens, CFG)
    assert abs(float(loss) - float(want_loss)) <= 1e-6 * abs(float(want_loss))
    assert share(logits, want_logits) <= F32_TOL
    for name, want in leaves(want_grads).items():
        assert share(leaves(grads)[name], want) <= F32_TOL, name


@pytest.mark.parametrize("attention", ["dense", "flash"])
@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_the_step_loss_through_the_tied_head(seeded, reference_run, attention,
                                             remat):
    """As the benchmark's step takes it: hidden states (already divided by
    ``logits_scaling``) and the TRANSPOSED embedding through
    ``chunked_lm_loss``; the tied leaf receives the embedding's scatter-add
    and the head's product."""
    params, tokens = seeded
    want_loss, _, want_grads = reference_run
    m = model(attention=attention, flash_interpret=True, remat=remat)

    def loss(params, tokens):
        hidden = m.apply({"params": params}, tokens, return_hidden=True)
        return chunked_lm_loss(hidden, params["embed"]["embedding"].T,
                               jnp.roll(tokens, -1, axis=1), chunk=8)

    with jax.default_matmul_precision("highest"):
        got_loss, grads = jax.value_and_grad(loss)(params, tokens)
    assert abs(float(got_loss) - float(want_loss)) <= 1e-5 * abs(float(want_loss))
    got, want = leaves(to_reference(grads)), leaves(want_grads)
    for name in want:
        assert share(got[name], want[name]) <= F32_TOL, name


@pytest.mark.parametrize("attention", ["dense", "flash"])
def test_bfloat16_activations_stay_in_their_band(seeded, reference_run,
                                                 attention):
    params, tokens = seeded
    want_loss, want_logits, want_grads = reference_run
    m = model(dtype=jnp.bfloat16, attention=attention, flash_interpret=True)
    (loss, logits), grads = jax.value_and_grad(
        system_loss(m), has_aux=True)(params, tokens)
    assert logits.dtype == jnp.float32
    assert abs(float(loss) - float(want_loss)) <= 2e-2 * abs(float(want_loss))
    assert 1e-4 < share(logits, want_logits) <= 5e-2
    got, want = leaves(to_reference(grads)), leaves(want_grads)
    worst = max(l2_share(got[name], want[name]) for name in want)
    assert 1e-3 < worst <= 2.5e-1      # worst: A_log / dt_bias, 2 numbers each


WRONG = {
    "rotary_left_on": dict(rope=True),
    "logits_scaling_dropped": dict(logits_scaling=1.0),
    "residual_multiplier_dropped": dict(residual_multiplier=1.0),
    "embedding_multiplier_dropped": dict(embedding_multiplier=1.0),
    "softmax_scale_head_dim": dict(attention_multiplier=None),
}


@pytest.mark.parametrize("fault", sorted(WRONG))
def test_a_wrong_variant_is_far_outside_the_float32_band(seeded,
                                                         reference_run,
                                                         fault):
    """Each of the model's stated departures from a plain transformer moves
    the float32 logits by 100 x the float32 tolerance or more (the dropped
    multipliers by 1e-1..1; the one attention layer's rotary embedding or
    softmax scale, behind a residual multiplier of 0.22, by 1e-2)."""
    params, tokens = seeded
    _, want_logits, _ = reference_run
    with jax.default_matmul_precision("highest"):
        logits = model(**WRONG[fault]).apply({"params": params}, tokens)
    assert share(logits, want_logits) > 100 * F32_TOL


def test_layer_types_and_sizes_are_checked():
    tokens = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError, match="layer_types"):
        model(layers=5).init(jax.random.PRNGKey(0), tokens)
    with pytest.raises(ValueError, match="layer_types"):
        model(layer_types=("mamba",) * 5 + ("linear",)).init(
            jax.random.PRNGKey(0), tokens)
    with pytest.raises(ValueError, match="needs the mixer's sizes"):
        model(mamba=None).init(jax.random.PRNGKey(0), tokens)
    with pytest.raises(ValueError, match="attention_scale needs sp_axis=None"):
        model(sp_axis="sp", layer_types=("attention",) * 6).init(
            jax.random.PRNGKey(0), tokens)


def test_the_older_models_are_untouched_by_the_new_options():
    """At their defaults the new fields add no leaf and no operation: the
    parameter tree of the plain model is what it was."""
    plain = TransformerLM(vocab=64, dim=32, heads=2, layers=2, dtype=jnp.float32)
    params = plain.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    assert set(params) == {"embed", "block_0", "block_1", "RMSNorm_0", "lm_head"}
    assert set(params["block_0"]) == {"RMSNorm_0", "RMSNorm_1", "qkv", "o_proj",
                                      "mlp_in", "mlp_out"}
    text = jax.jit(lambda p, t: plain.apply({"params": p}, t)).lower(
        params, jnp.zeros((1, 8), jnp.int32)).as_text()
    assert "cosine" in text         # rotary embedding still on by default
    assert Mamba2Dims(heads=1, head_dim=1, state=1).chunk == 256
