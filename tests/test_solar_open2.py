"""What Solar-Open2-250B forced in the program (ISSUE 53): the delta rule with
beta in (0, 2) - the mixer's setting, the block-by-block solve of a chunk's
triangular system in ``jax.numpy`` and in the kernels (interpreter) against
the token-by-token recurrence at beta near 2 and with all keys of a chunk
equal - and the sigmoid gate an ELEMENT of softmax attention's output beside
the gate a head, with the errors of the combinations that make no sense and
the two gauges. The configuration, its reference and the share's arithmetic
are held in tests/benchmark/test_benchmark_solar_open2.py.

Tolerances, as shares of max|reference| per array (observed on the CPU):
* float32 under ``highest``: 2e-5 (``jax.numpy`` <= 1.8e-6, kernels <=
  3.3e-6: the same float32 arithmetic in another order; a reflection a token
  keeps every rounding error at its size, it damps none).
* bf16 q, k, v: 6e-2 where every beta is 2 and a chunk's keys are equal
  (observed <= 1.6e-2 here and 3.9e-2 on the benchmark's tiny check: ``W``
  and ``U`` are sums of up to 64 terms of alternating sign and size 2, each
  rounded to bf16 before the next product), 3e-2 at beta drawn in (1.5, 2)
  on keys drawn apart (observed <= 7.6e-3).
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import kda as kda_model
from horovod_tpu.models.kda import KDADims, KDAMixer
from horovod_tpu.models.transformer import Block, TransformerLM, gate_form
from horovod_tpu.ops import kda as kda_ops
from test_kda_kernels import share, value_and_grads

NAMES = ("o", "dq", "dk", "dv", "dg", "dbeta")


def gauge(hvd, name):
    return hvd.metrics.registry().snapshot()["gauges"].get(name)


# ------------------------------------------------------- the mixer's setting

def mixer_case(allow, dtype=jnp.float32):
    dims = KDADims(heads=2, head_dim=16, conv=4, chunk=16,
                   allow_neg_eigval=allow)
    mixer = KDAMixer(dim=24, dims=dims, rms_norm_eps=1e-5, dtype=dtype)
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 80, 24), jnp.float32)
    params = mixer.init(jax.random.PRNGKey(2), h)["params"]
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: x if x.ndim < 2 or path[-1].key.endswith("_conv")
        else 3.0 * x, params)
    return mixer, params, h


@pytest.mark.parametrize("allow", [False, True])
def test_the_mixer_hands_the_scan_the_stated_beta(hvd, monkeypatch, allow):
    """``allow_neg_eigval`` doubles the sigmoid and asks for the block-by-block
    solve; off, the call the mixer makes IS the one it made before the
    setting existed (six operands, no keyword), with the sigmoid itself to
    the bit: Kimi-Linear's output cannot have moved. Either way the output
    through the chunked form is the recurrence's on that beta."""
    mixer, params, h = mixer_case(allow)
    seen = {}

    def stand_in(q, k, v, g, beta, chunk, **keywords):
        seen.update(beta=beta, keywords=keywords)
        return kda_ops.kda_recurrence(q, k, v, g, beta).astype(v.dtype)

    with jax.default_matmul_precision("highest"):
        chunked = mixer.apply({"params": params}, h)
        monkeypatch.setattr(kda_model, "kda", stand_in)
        stepped = mixer.apply({"params": params}, h)
    sigmoid = nn.sigmoid(h @ params["b_proj"]["kernel"])
    if allow:
        assert seen["keywords"] == {"neg_eigval": True}
        np.testing.assert_array_equal(np.asarray(seen["beta"]),
                                      np.asarray(2.0 * sigmoid))
        assert float(jnp.max(seen["beta"])) > 1.0
    else:
        assert seen["keywords"] == {}
        np.testing.assert_array_equal(np.asarray(seen["beta"]),
                                      np.asarray(sigmoid))
    assert float(jnp.max(jnp.abs(chunked - stepped))
                 / jnp.max(jnp.abs(stepped))) <= 2e-5
    assert gauge(hvd, "horovod_kda_beta_range") == (2 if allow else 1)


def test_the_settings_differ_and_the_dims_are_counts():
    (on, params, h), (off, _, _) = mixer_case(True), mixer_case(False)
    a, b = (m.apply({"params": params}, h) for m in (on, off))
    assert float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b))) > 1e-2
    assert KDADims(2, 16) == KDADims(2, 16, allow_neg_eigval=False)
    with pytest.raises(ValueError, match="at least 1"):
        KDADims(heads=0, head_dim=16)


# ------------------------------------------------- the solve, beta in (0, 2)

def test_the_solve_by_halves_is_exact_where_the_powers_are_not():
    """All keys of a chunk equal and every beta 2: ``N = 2 strict_lower(1)``,
    whose inverse ``(I + N)^-1`` alternates between -2 and 2. By halves every
    factor is the inverse of a block of ``I + N``: exact. The product of
    ``(I + N^(2^p))`` sums powers of size ``2^32 binom(62, 31)`` to reach it,
    and float32 keeps nothing of it - at beta 1 as at beta 2."""
    for size in (64, 48, 2, 1):
        for beta in (2.0, 1.0):
            n = jnp.tril(jnp.full((size, size), beta, jnp.float32), -1)
            exact = np.linalg.inv(np.eye(size) + np.asarray(n, np.float64))
            by_halves = kda_ops._unit_lower_inverse_by_halves(n)
            assert float(np.max(np.abs(np.asarray(by_halves) - exact))) <= 1e-6
    doubling = kda_ops._unit_lower_inverse(
        jnp.tril(jnp.full((64, 64), 2.0, jnp.float32), -1))
    assert not np.max(np.abs(np.asarray(doubling))) <= 1e3
    # keys drawn apart: both solves are the inverse
    n = 0.19 * jnp.tril(jax.random.normal(jax.random.PRNGKey(0), (3, 64, 64)), -1)
    with jax.default_matmul_precision("highest"):
        product = (jnp.eye(64) + n) @ kda_ops._unit_lower_inverse_by_halves(n)
    np.testing.assert_allclose(np.asarray(product),
                               np.broadcast_to(np.eye(64), (3, 64, 64)),
                               atol=2e-6)


def scan_inputs(rows, t, heads, d, chunk, case, dtype, seed=0):
    """q, k as the mixer hands them over, v normal, g as the layer
    initialises it; ``"beta_near_2"``: beta drawn in (1.5, 2);
    ``"beta_2_equal_keys"``: every beta 2 and every key of a chunk its first."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    shape = (rows, t, heads, d)

    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    q = unit(jax.random.normal(ks[0], shape)) * d ** -0.5
    k = unit(jax.random.normal(ks[1], shape))
    v = jax.random.normal(ks[2], shape)
    g = -jnp.exp(jax.random.uniform(ks[3], shape, minval=np.log(1e-3),
                                    maxval=np.log(1.6)))
    beta = jax.random.uniform(ks[4], shape[:3], minval=1.5, maxval=2.0)
    if case == "beta_2_equal_keys":
        k = k[:, jnp.arange(t) // chunk * chunk]
        beta = jnp.full_like(beta, 2.0)
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


PATHS = {   # (rows, t, heads, d, chunk, interpret)
    # a block of four chunks of 16; no kernel at this width
    "jax_numpy": (2, 64, 3, 32, 16, False),
    # a block of four chunks of 64, two heads of 128: the kernels
    "kernels": (1, 256, 2, 128, 64, True),
}
LIMITS = {("f32", "beta_near_2"): 2e-5, ("f32", "beta_2_equal_keys"): 2e-5,
          ("bf16", "beta_near_2"): 3e-2, ("bf16", "beta_2_equal_keys"): 6e-2}


@pytest.mark.parametrize("precision", ["f32", "bf16"])
@pytest.mark.parametrize("case", ["beta_near_2", "beta_2_equal_keys"])
@pytest.mark.parametrize("path", sorted(PATHS))
def test_chunked_form_is_the_recurrence_at_beta_up_to_2(path, case, precision):
    rows, t, heads, d, chunk, interpret = PATHS[path]
    dtype, matmul = {"f32": (jnp.float32, "highest"),
                     "bf16": (jnp.bfloat16, None)}[precision]
    args = scan_inputs(rows, t, heads, d, chunk, case, dtype)
    plan = kda_ops.plan(t, chunk)
    assert kda_ops.takes_kernel(*args[:3], plan[0], plan[2]) == interpret
    weights = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    with jax.default_matmul_precision(matmul):
        got = value_and_grads(lambda *a: kda_ops.kda(
            *a, chunk, interpret=interpret, neg_eigval=True), args, weights)
    want = value_and_grads(kda_ops.kda_recurrence, args, weights)
    for name, g, w in zip(NAMES, got, want):
        assert np.isfinite(np.asarray(g, np.float32)).all(), name
        assert share(g, w) <= LIMITS[precision, case], (name, share(g, w))


def test_the_other_solve_loses_a_chunk_of_equal_keys():
    """The finding the setting's own solve answers: on the same operands the
    product of powers gives nothing like the recurrence."""
    rows, t, heads, d, chunk, _ = PATHS["jax_numpy"]
    args = scan_inputs(rows, t, heads, d, 64, "beta_2_equal_keys", jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda *a: kda_ops.kda(*a, 64))(*args)
        right = jax.jit(lambda *a: kda_ops.kda(*a, 64, neg_eigval=True))(*args)
    want = kda_ops.kda_recurrence(*args)
    assert share(right, want) <= 2e-5
    assert not share(got, want) <= 1e-1


# ------------------------------------------------ the gate on attention's output

def gated_block(form, **more):
    return Block(dim=32, heads=4, kv_heads=2, head_dim=16, dtype=jnp.float32,
                 rope=False, attn_gate=form, sublayers="mixer",
                 rms_norm_eps=1e-5, **more)


def by_definition(params, x, form):
    """``x + (a * sigmoid(h Wg)) Wo`` written out: 4 query heads of 16 on 2
    key/value heads, no rotation; the gate a head or an element."""
    h = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5) * params[
        "RMSNorm_0"]["scale"]
    b, t, _ = x.shape
    q = (h @ params["q_proj"]["kernel"]).reshape(b, t, 4, 16)
    k, v = (jnp.repeat(part.reshape(b, t, 2, 16), 2, axis=2) for part in
            jnp.split(h @ params["kv_proj"]["kernel"], 2, axis=-1))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * 16 ** -0.5
    seen = jnp.arange(t)[:, None] >= jnp.arange(t)
    a = jnp.einsum("bhqk,bkhd->bqhd",
                   jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1), v)
    z = jax.nn.sigmoid(h @ params["gate_proj"]["kernel"])
    a = a * (z[..., None] if form == "head" else z.reshape(b, t, 4, 16))
    return x + a.reshape(b, t, 64) @ params["o_proj"]["kernel"]


@pytest.mark.parametrize("form,stated", [("element", "element"),
                                         ("head", "head"), ("head", True)])
def test_the_gate_is_its_definition_in_both_forms(hvd, form, stated):
    block = gated_block(stated)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 32), jnp.float32)
    positions = jnp.arange(24)[None]
    params = block.init(jax.random.PRNGKey(1), x, positions)["params"]
    params = jax.tree_util.tree_map(lambda p: 2.0 * p if p.ndim == 2 else p,
                                    params)
    width = {"head": 4, "element": 64}[form]
    assert params["gate_proj"]["kernel"].shape == (32, width)
    with jax.default_matmul_precision("highest"):
        got = block.apply({"params": params}, x, positions)
        want = by_definition(params, x, form)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-5)
    assert gauge(hvd, "horovod_attn_gate_width") == width
    ungated = gated_block(False)
    plain = {k: v for k, v in params.items() if k != "gate_proj"}
    assert float(jnp.max(jnp.abs(
        ungated.apply({"params": plain}, x, positions) - got))) > 1e-3


def test_true_is_the_gate_a_head_and_nothing_else_is_a_form():
    assert [gate_form(x) for x in (False, None, True, "head", "element")] == [
        None, None, "head", "head", "element"]
    for wrong in ("elementwise", 1, 2.0, ("head",)):
        with pytest.raises(ValueError, match="attn_gate"):
            gate_form(wrong)


def test_the_element_gate_runs_through_the_flash_kernels():
    """Grouped-query, no rotation, gated an element: the flash path (in the
    interpreter) gives what the dense path gives."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 128, 32), jnp.float32)
    positions = jnp.arange(128)[None]
    dense = gated_block("element")
    params = dense.init(jax.random.PRNGKey(1), x, positions)["params"]
    flash = gated_block("element", attention="flash", flash_interpret=True)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            np.asarray(flash.apply({"params": params}, x, positions)),
            np.asarray(dense.apply({"params": params}, x, positions)),
            atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("fields,message", [
    (dict(kda=KDADims(2, 16)), "gates softmax attention.*'kda'"),
    (dict(sublayers="mlp", moe_experts=4, moe_top_k=2),
     "gates softmax attention.*mixer none"),
])
def test_a_gate_on_a_layer_without_softmax_attention_is_an_error(fields,
                                                                  message):
    block = Block(dim=32, heads=4, head_dim=16, dtype=jnp.float32,
                  **{"attn_gate": "element", "sublayers": "mixer", **fields})
    x = jnp.zeros((1, 16, 32))
    with pytest.raises(ValueError, match=message):
        block.init(jax.random.PRNGKey(0), x, jnp.arange(16)[None])


def test_the_model_gates_its_softmax_layers_alone(hvd):
    """A period of one gated grouped-query layer FIRST and delta-rule layers
    after it, experts from layer 0: the gate's leaf is in the softmax layer
    and in no other; a model with no softmax layer that states a gate, or
    one whose attention is latent, raises."""
    def model(**more):
        return TransformerLM(**{**dict(
            vocab=64, dim=32, heads=4, kv_heads=1, head_dim=16, layers=4,
            layer_types=("full_attention", "kda", "kda", "kda"),
            kda=KDADims(2, 16, chunk=16, allow_neg_eigval=True), rope=False,
            attn_gate="element", moe_experts=8, moe_every=1, moe_top_k=2,
            moe_hidden=16, moe_router="sigmoid", moe_shared_hidden=8,
            moe_held=(0, 4), rms_norm_eps=1e-5, dtype=jnp.float32), **more})

    tokens = jnp.arange(32)[None] % 64
    variables = model().init(jax.random.PRNGKey(0), tokens)
    params = variables["params"]
    assert params["block_0"]["gate_proj"]["kernel"].shape == (32, 64)
    assert params["block_0"]["kv_proj"]["kernel"].shape == (32, 2 * 16)
    assert all("gate_proj" not in params[f"block_{i}"] and "mixer" in params[
        f"block_{i}"] for i in (1, 2, 3))
    assert all("moe" in params[f"block_{i}"] for i in range(4))
    logits = model().apply(variables, tokens)
    assert logits.shape == (1, 32, 64) and bool(jnp.isfinite(logits).all())
    assert gauge(hvd, "horovod_kda_beta_range") == 2
    assert gauge(hvd, "horovod_attn_gate_width") == 64
    with pytest.raises(ValueError, match="gates softmax attention"):
        model(layer_types=("kda",) * 4).init(jax.random.PRNGKey(0), tokens)
    from horovod_tpu.models import LatentDims

    with pytest.raises(ValueError, match="latent attention"):
        model(mla=LatentDims(16, 8, 8, 8), kv_heads=None, head_dim=None).init(
            jax.random.PRNGKey(0), tokens)
