"""The state-space scan's kernels (``ops/ssd.py`` ``hvd_ssd_scan_fwd`` /
``_bwd``) in the Pallas interpreter, at the two Mamba cells' widths cut in T
and heads: ``y`` and the gradients of ``u``, ``dt``, ``A``, ``B``, ``C``,
``D`` against the ``jax.numpy`` scan of the same file (the definition and the
fallback) AND against the token-by-token recurrence of
``tests/references/granite_hybrid.py``; the shapes that must fall back; the
gauge that says which of the two a traced scan took; the mixer handing its
``interpret`` down.

Tolerances, as shares of max|reference| per array:
* float32 under ``highest``: 2e-5, ``tests/test_mamba2.py``'s (kernels and
  ``jax.numpy`` scan do the same float32 arithmetic, sums in another order;
  observed <= 4.6e-6 against the recurrence, 2.4e-6 against the scan).
* bf16 ``u``, ``B``, ``C``: against the ``jax.numpy`` scan, which rounds the
  same three operands at the same places, y 4e-3 (one bf16 step; observed
  3.8e-4: a float32 sum in another order now and then falls on the other
  side of a rounding) and gradients 2e-2 (observed <= 7.0e-3: the backward's
  cotangents are rounded where JAX's transposes keep float32 ones); against
  the float32 recurrence on the same rounded inputs y 1.2e-2 and gradients
  2e-2 (observed 3.0e-3 and 5.7e-3), the benchmark's own ``bf16_scan_rel``
  being 0.02 on y.
"""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from references import granite_hybrid as ref  # noqa: E402

from horovod_tpu.models.mamba import Mamba2Dims, Mamba2Mixer  # noqa: E402
from horovod_tpu.ops import ssd as ssd_ops  # noqa: E402

NAMES = ("y", "u", "dt", "A", "B", "C", "D")
KERNEL_CASES = {    # (b, t, heads, head_dim, state, chunk)
    "granite_one_chunk": (1, 256, 4, 64, 128, 256),
    "granite_nine_chunks": (1, 2304, 4, 64, 128, 256),
    "nemotron_one_chunk": (2, 128, 2, 64, 128, 128),
    "nemotron_nine_chunks": (2, 1152, 2, 64, 128, 128),
}
PRECISIONS = {      # dtype of u, B, C; matmul precision; limits (y, gradients)
    "f32_highest": (jnp.float32, "highest", {"scan": (2e-5, 2e-5),
                                             "recurrence": (2e-5, 2e-5)}),
    "bf16": (jnp.bfloat16, None, {"scan": (4e-3, 2e-2),
                                  "recurrence": (1.2e-2, 2e-2)}),
}


def scan_inputs(b, t, h, p, n, dtype, groups=1, seed=0):
    """Mamba-2's ranges: dt log-uniform in [0.001, 0.1] with a spread per
    position, A in [-16, -1]; u, B, C rounded to ``dtype``."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    dt0 = jnp.exp(jax.random.uniform(ks[1], (h,)) * jnp.log(100.0)
                  + jnp.log(1e-3))
    return (jax.random.normal(ks[0], (b, t, h, p)).astype(dtype),
            dt0 * jnp.exp(0.5 * jax.random.normal(ks[2], (b, t, h))),
            -jax.random.uniform(ks[3], (h,), minval=1.0, maxval=16.0),
            (jax.random.normal(ks[4], (b, t, groups, n)) * n ** -0.25
             ).astype(dtype),
            (jax.random.normal(ks[5], (b, t, groups, n)) * n ** -0.25
             ).astype(dtype),
            jax.random.normal(ks[6], (h,)))


def share(got, want):
    got, want = (jnp.asarray(x, jnp.float32) for x in (got, want))
    return float(jnp.max(jnp.abs(got - want))
                 / (jnp.max(jnp.abs(want)) + 1e-30))


def value_and_grads(fn, args, weights):
    """y and the gradients of a fixed weighted sum of it, all six."""
    y, vjp = jax.vjp(fn, *args)
    return (y,) + vjp(weights.astype(y.dtype))


@pytest.fixture()
def plain_scan(monkeypatch):
    """``ssd`` with the shape rule answering no: the ``jax.numpy`` scan."""
    def run(*args):
        with monkeypatch.context() as m:
            m.setattr(ssd_ops, "takes_kernel", lambda *a: False)
            return ssd_ops.ssd(*args)
    return run


@pytest.fixture(scope="module")
def kernel_results():
    """What the kernels give a case, computed once for both comparisons."""
    cache = {}

    def get(case, precision):
        if (case, precision) not in cache:
            *shape, chunk = KERNEL_CASES[case]
            dtype, matmul, _ = PRECISIONS[precision]
            args = scan_inputs(*shape, dtype)
            assert ssd_ops.takes_kernel(args[0], args[1], args[3], args[4],
                                        chunk)
            weights = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
            with jax.default_matmul_precision(matmul):
                got = value_and_grads(
                    lambda *a: ssd_ops.ssd(*a, chunk, True), args, weights)
            cache[case, precision] = (args, weights, chunk, got)
        return cache[case, precision]
    return get


@pytest.mark.parametrize("precision", sorted(PRECISIONS))
@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_kernels_match_the_jax_numpy_scan(case, precision, kernel_results,
                                          plain_scan):
    args, weights, chunk, got = kernel_results(case, precision)
    _, matmul, limits = PRECISIONS[precision]
    with jax.default_matmul_precision(matmul):
        want = value_and_grads(lambda *a: plain_scan(*a, chunk), args, weights)
    for name, g, w in zip(NAMES, got, want):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        assert share(g, w) <= limits["scan"][name != "y"], name


@pytest.mark.parametrize("precision", sorted(PRECISIONS))
@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_kernels_match_the_token_by_token_recurrence(case, precision,
                                                     kernel_results):
    args, weights, chunk, got = kernel_results(case, precision)
    limits = PRECISIONS[precision][2]
    exact = tuple(x.astype(jnp.float32) for x in args)
    with jax.default_matmul_precision("highest"):
        want = value_and_grads(ref.ssm_recurrence, exact, weights)
    for name, g, w in zip(NAMES, got, want):
        assert share(g, w) <= limits["recurrence"][name != "y"], name


# ----------------------------------------------------- which shapes take them

FALLBACKS = {       # (b, t, heads, head_dim, groups, state, chunk), dtypes
    "a_padded_row": ((1, 300, 2, 64, 1, 128, 128), jnp.float32),
    "heads_that_fill_no_lane_block": ((1, 256, 1, 64, 1, 128, 128),
                                      jnp.float32),
    "a_head_wider_than_a_lane_block": ((1, 256, 1, 256, 1, 128, 128),
                                       jnp.float32),
    "groups_between_one_and_the_heads": ((1, 256, 4, 64, 2, 128, 128),
                                         jnp.float32),
    "a_state_of_half_a_lane_block": ((1, 256, 2, 64, 1, 64, 128), jnp.float32),
    "a_chunk_of_half_a_lane_block": ((1, 256, 2, 64, 1, 128, 64), jnp.float32),
    "a_row_shorter_than_the_chunk": ((1, 96, 2, 64, 1, 128, 128), jnp.float32),
    "half_precision_of_another_kind": ((1, 256, 2, 64, 1, 128, 128),
                                       jnp.float16),
}


@pytest.mark.parametrize("case", sorted(FALLBACKS))
def test_other_shapes_keep_the_jax_numpy_scan(case, hvd):
    """No ``interpret`` and no TPU: a kernel would raise at lowering. The
    gauge goes back to 0 and the result is the recurrence's."""
    (b, t, h, p, g, n, chunk), dtype = FALLBACKS[case]
    args = scan_inputs(b, t, h, p, n, dtype, groups=g)
    assert not ssd_ops.takes_kernel(args[0], args[1], args[3], args[4],
                                    min(chunk, t))
    gauge = hvd.metrics.registry().gauge("horovod_ssd_kernel_scans")
    gauge.set(3)
    with jax.default_matmul_precision("highest"):
        got = ssd_ops.ssd(*args, chunk)
        want = ref.ssm_recurrence(*(x.astype(jnp.float32) for x in args))
    assert gauge.value == 0
    assert got.dtype == dtype
    assert share(got, want) <= (2e-5 if dtype == jnp.float32 else 3e-3)


def test_operands_of_two_dtypes_keep_the_jax_numpy_scan():
    u, dt, A, B, C, D = scan_inputs(1, 256, 2, 64, 128, jnp.bfloat16)
    assert ssd_ops.takes_kernel(u, dt, B, C, 128)
    assert not ssd_ops.takes_kernel(u, dt, B.astype(jnp.float32), C, 128)
    assert not ssd_ops.takes_kernel(u, dt, B, C.astype(jnp.float32), 128)


def test_the_gauge_counts_the_traced_scans_that_took_the_kernels(hvd):
    gauge = hvd.metrics.registry().gauge("horovod_ssd_kernel_scans")
    chunks = hvd.metrics.registry().gauge("horovod_ssd_chunk_len")
    tiled = scan_inputs(1, 256, 2, 64, 128, jnp.float32)
    ssd_ops.ssd(*scan_inputs(1, 300, 2, 64, 128, jnp.float32), 128)
    assert gauge.value == 0
    for traced in (1, 2):
        jax.jit(lambda *a: ssd_ops.ssd(*a, 128, True)).lower(*tiled)
        assert (gauge.value, chunks.value) == (traced, 128)
    ssd_ops.ssd(*scan_inputs(1, 300, 2, 64, 128, jnp.float32), 128)
    assert gauge.value == 0


def test_a_machine_without_a_tpu_raises_without_interpret():
    """As ``flash_attention``: the interpreter is asked for, never inferred."""
    args = scan_inputs(1, 256, 2, 64, 128, jnp.float32)
    with pytest.raises(Exception, match="(?i)interpret|tpu|cpu"):
        jax.block_until_ready(ssd_ops.ssd(*args, 128))


# ----------------------------------------------------------------- the mixer

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_the_mixer_hands_its_interpret_to_the_scan(dtype, plain_scan,
                                                   monkeypatch, hvd):
    """2 heads x 64, state 128, chunk 128 over 512 rows: the mixer's three
    kernel paths (convolution + silu, the scan, the gated norm) at once, and
    the same mixer with the scan in ``jax.numpy``."""
    mixer = Mamba2Mixer(dim=64, dims=Mamba2Dims(heads=2, head_dim=64,
                                                state=128, chunk=128),
                        dtype=dtype, interpret=True)
    h = jax.random.normal(jax.random.PRNGKey(0), (1, 512, 64)).astype(dtype)
    params = mixer.init(jax.random.PRNGKey(1), h)["params"]
    gauge = hvd.metrics.registry().gauge("horovod_ssd_kernel_scans")

    def loss(p, x):
        return jnp.sum(jnp.sin(mixer.apply({"params": p}, x)
                               .astype(jnp.float32)))

    matmul = "highest" if dtype == jnp.float32 else None
    with jax.default_matmul_precision(matmul):
        got = jax.grad(loss, argnums=(0, 1))(params, h)
        assert gauge.value > 0
        monkeypatch.setattr(ssd_ops, "takes_kernel", lambda *a: False)
        want = jax.grad(loss, argnums=(0, 1))(params, h)
        assert gauge.value == 0
    limit = 2e-5 if dtype == jnp.float32 else 4e-2
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert share(g, w) <= limit
