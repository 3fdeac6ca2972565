"""The gated delta rule's kernels (``ops/kda.py`` ``hvd_kda_scan_fwd`` /
``_bwd``) in the Pallas interpreter, at the Kimi cell's widths (heads of 128
| 128, chunks of 64) cut in T and heads: ``o`` and the gradients of ``q``,
``k``, ``v``, ``g`` and ``beta`` against the ``jax.numpy`` scan of the same
file (the definition of the chunked form, and the fallback) AND against the
token-by-token recurrence ``kda_recurrence``; the strongest decay; the shapes
that must fall back; the gauge that says which of the two a traced scan
took; a machine without a TPU; the mixer handing its ``interpret`` down.

Tolerances, as shares of max|reference| per array:
* float32 under ``highest``: 5e-6, ``tests/test_kda.py``'s (the same float32
  arithmetic in another order; observed <= 8e-7 against the scan and against
  the recurrence; the scan itself stands 6e-7 from the recurrence).
* bf16 q, k, v: 2e-2 (observed <= 7.4e-3 against the scan, which rounds the
  same operands at the same places but beta INTO ``K+`` and ``V`` where the
  kernels round it into the solve's result, and <= 6.3e-3 against the float32
  recurrence on the same rounded inputs, where the scan itself reads 6.3e-3;
  one bf16 step of ``o`` is 4e-3), the benchmark's own ``bf16_scan_rel`` on o.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models.kda import KDADims, KDAMixer
from horovod_tpu.ops import kda as kda_ops

NAMES = ("o", "dq", "dk", "dv", "dg", "dbeta")
KERNEL_CASES = {    # (rows, t, heads): 128 | 128 a head, chunks of 64
    # two blocks of four chunks: the state and its cotangent cross a block
    "two_blocks_two_heads": (1, 512, 2),
    # a block of ONE pair of chunks, a head that fills no group of two, the
    # batch axis: every row starts from a zero state
    "one_pair_three_heads_two_rows": (2, 128, 3),
}
PRECISIONS = {      # dtype of q, k, v; matmul precision; the limit
    "f32_highest": (jnp.float32, "highest", 5e-6),
    "bf16": (jnp.bfloat16, None, 2e-2),
}


def scan_inputs(rows, t, heads, dtype, d=128, seed=0, g=None):
    """q, k as the mixer hands them over (unit length a head, q times
    d^-0.5), v normal, g = -exp(uniform log) in [-1.6, -0.001] as the layer
    initialises it (or one number), beta a sigmoid; q, k, v in ``dtype``."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    shape = (rows, t, heads, d)

    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    q = unit(jax.random.normal(ks[0], shape)) * d ** -0.5
    k = unit(jax.random.normal(ks[1], shape))
    v = jax.random.normal(ks[2], shape)
    if g is None:
        g = -jnp.exp(jax.random.uniform(ks[3], shape, minval=np.log(1e-3),
                                        maxval=np.log(1.6)))
    else:
        g = jnp.full(shape, g, jnp.float32)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], shape[:3]))
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


def share(got, want):
    got, want = (jnp.asarray(x, jnp.float32) for x in (got, want))
    return float(jnp.max(jnp.abs(got - want))
                 / (jnp.max(jnp.abs(want)) + 1e-30))


def value_and_grads(fn, args, weights):
    """o and the gradients of a fixed weighted sum of it, all five; ONE
    jitted program (run op by op, the interpreter compiles thousands)."""
    def both(*args):
        o, vjp = jax.vjp(fn, *args)
        return (o,) + vjp(weights.astype(o.dtype))

    return jax.jit(both)(*args)


def takes(args, chunk=kda_ops.CHUNK):
    q, k, v = args[:3]
    chunk, _, block_len = kda_ops.plan(q.shape[1], chunk)
    return kda_ops.takes_kernel(q, k, v, chunk, block_len)


@pytest.fixture()
def plain_scan(monkeypatch):
    """``kda`` with the shape rule answering no: the ``jax.numpy`` scan."""
    def run(*args):
        with monkeypatch.context() as m:
            m.setattr(kda_ops, "takes_kernel", lambda *a: False)
            return kda_ops.kda(*args)
    return run


@pytest.fixture(scope="module")
def kernel_results():
    """What the kernels give a case, computed once for both comparisons."""
    cache = {}

    def get(case, precision, g=None):
        if (case, precision, g) not in cache:
            dtype, matmul, _ = PRECISIONS[precision]
            args = scan_inputs(*KERNEL_CASES[case], dtype, g=g)
            assert takes(args)
            weights = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
            with jax.default_matmul_precision(matmul):
                got = value_and_grads(
                    lambda *a: kda_ops.kda(*a, interpret=True), args, weights)
            cache[case, precision, g] = (args, weights, got)
        return cache[case, precision, g]
    return get


@pytest.mark.parametrize("precision", sorted(PRECISIONS))
@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_kernels_match_the_jax_numpy_scan(case, precision, kernel_results,
                                          plain_scan):
    args, weights, got = kernel_results(case, precision)
    _, matmul, limit = PRECISIONS[precision]
    with jax.default_matmul_precision(matmul):
        want = value_and_grads(plain_scan, args, weights)
    for name, g, w in zip(NAMES, got, want):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        assert share(g, w) <= limit, name


@pytest.mark.parametrize("precision", sorted(PRECISIONS))
@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_kernels_match_the_token_by_token_recurrence(case, precision,
                                                     kernel_results):
    args, weights, got = kernel_results(case, precision)
    exact = tuple(x.astype(jnp.float32) for x in args)
    with jax.default_matmul_precision("highest"):
        want = value_and_grads(kda_ops.kda_recurrence, exact, weights)
    for name, g, w in zip(NAMES, got, want):
        assert share(g, w) <= PRECISIONS[precision][2], name


@pytest.mark.parametrize("precision", sorted(PRECISIONS))
def test_the_strongest_decay_stays_finite_and_right(precision, kernel_results):
    """g = -320 a token (16 x softplus(20)): exp(-G) overflows float32 inside
    one SUB-BLOCK, so any factorised form is inf or nan; the kernels bound
    every exponent as the module says, forward and backward."""
    assert not np.isfinite(np.exp(np.float32(320.0 * kda_ops.SUB)))
    args, weights, got = kernel_results("two_blocks_two_heads", precision,
                                        g=-320.0)
    exact = tuple(x.astype(jnp.float32) for x in args)
    with jax.default_matmul_precision("highest"):
        want = value_and_grads(kda_ops.kda_recurrence, exact, weights)
    for name, g, w in zip(NAMES, got, want):
        assert bool(jnp.all(jnp.isfinite(g.astype(jnp.float32)))), name
        assert share(g, w) <= PRECISIONS[precision][2], name


# ----------------------------------------------------- which shapes take them

FALLBACKS = {       # (rows, t, heads, d, chunk), dtype
    "a_head_of_half_a_lane_block": ((1, 256, 2, 64, 64), jnp.float32),
    "a_head_of_two_lane_blocks": ((1, 128, 1, 256, 64), jnp.float32),
    "a_chunk_of_32": ((1, 256, 2, 128, 32), jnp.float32),
    "a_chunk_of_128": ((1, 256, 2, 128, 128), jnp.float32),
    "blocks_of_three_chunks": ((1, 192, 2, 128, 64), jnp.float32),
    "a_row_of_one_chunk": ((1, 64, 2, 128, 64), jnp.float32),
    "a_row_shorter_than_a_chunk": ((1, 48, 2, 128, 64), jnp.float32),
    "half_precision_of_another_kind": ((1, 256, 2, 128, 64), jnp.float16),
}


@pytest.mark.parametrize("case", sorted(FALLBACKS))
def test_other_shapes_keep_the_jax_numpy_scan(case, hvd):
    """No ``interpret`` and no TPU: a kernel would raise at lowering. The
    gauge goes back to 0 and the result is the recurrence's."""
    (rows, t, heads, d, chunk), dtype = FALLBACKS[case]
    args = scan_inputs(rows, t, heads, dtype, d=d)
    assert not takes(args, chunk)
    gauge = hvd.metrics.registry().gauge("horovod_kda_kernel_scans")
    gauge.set(3)
    with jax.default_matmul_precision("highest"):
        got = kda_ops.kda(*args, chunk)
        want = kda_ops.kda_recurrence(*args)
    assert gauge.value == 0
    assert got.dtype == dtype
    assert share(got, want) <= (5e-6 if dtype == jnp.float32 else 3e-3)


def test_operands_of_two_dtypes_keep_the_jax_numpy_scan():
    q, k, v, g, beta = scan_inputs(1, 256, 2, jnp.bfloat16)
    assert takes((q, k, v))
    f32 = jnp.float32
    assert not takes((q.astype(f32), k, v))
    assert not takes((q, k.astype(f32), v))
    assert not takes((q, k, v.astype(f32)))
    with jax.default_matmul_precision("highest"):
        got = kda_ops.kda(q, k, v.astype(f32), g, beta)   # no interpret: falls back
        want = kda_ops.kda_recurrence(q, k, v, g, beta)
    assert got.dtype == f32 and share(got, want) <= 2e-2


def test_the_gauge_counts_the_traced_scans_that_took_the_kernels(hvd):
    gauge = hvd.metrics.registry().gauge("horovod_kda_kernel_scans")
    chunks = hvd.metrics.registry().gauge("horovod_kda_chunk_len")
    kept = hvd.metrics.registry().gauge(
        "horovod_kda_saved_state_bytes_per_layer")
    tiled = scan_inputs(1, 512, 2, jnp.float32)
    other = scan_inputs(1, 192, 2, jnp.float32)
    kda_ops.kda(*other)
    assert gauge.value == 0
    for traced in (1, 2):
        jax.jit(lambda *a: kda_ops.kda(*a, interpret=True)).lower(*tiled)
        assert (gauge.value, chunks.value) == (traced, 64)
        # a state a BLOCK of four chunks, the kernels' as the scan's
        assert kept.value == 2 * 2 * 128 * 128 * 4
    kda_ops.kda(*other)
    assert gauge.value == 0


def test_a_machine_without_a_tpu_raises_without_interpret():
    """As ``flash_attention``: the interpreter is asked for, never inferred."""
    args = scan_inputs(1, 256, 2, jnp.float32)
    with pytest.raises(Exception, match="(?i)interpret|tpu|cpu"):
        jax.block_until_ready(kda_ops.kda(*args))


# ----------------------------------------------------------------- the mixer

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_the_mixer_hands_its_interpret_to_the_scan(dtype, monkeypatch, hvd):
    """2 heads x 128 over 128 rows: the mixer's two kernel paths (convolution
    + silu, the scan) at once, and the same mixer with the scan in
    ``jax.numpy``."""
    mixer = KDAMixer(dim=64, dims=KDADims(heads=2, head_dim=128), dtype=dtype,
                     interpret=True)
    h = jax.random.normal(jax.random.PRNGKey(0), (1, 128, 64)).astype(dtype)
    params = mixer.init(jax.random.PRNGKey(1), h)["params"]
    gauge = hvd.metrics.registry().gauge("horovod_kda_kernel_scans")

    def loss(p, x):
        return jnp.sum(jnp.sin(mixer.apply({"params": p}, x)
                               .astype(jnp.float32)))

    matmul = "highest" if dtype == jnp.float32 else None
    with jax.default_matmul_precision(matmul):
        got = jax.jit(jax.grad(loss, argnums=(0, 1)))(params, h)
        assert gauge.value > 0
        monkeypatch.setattr(kda_ops, "takes_kernel", lambda *a: False)
        # (a jit of its own: the first one's trace holds the kernels)
        want = jax.jit(jax.grad(loss, argnums=(0, 1)))(params, h)
        assert gauge.value == 0
    limit = 2e-5 if dtype == jnp.float32 else 4e-2
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert share(g, w) <= limit
