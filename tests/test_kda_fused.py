"""The KDA mixer's fused elementwise passes (``ops/kda_fused.py``: the L2
norms with the log-decay before the scan, the head norm then the sigmoid
gate after it) in the Pallas interpreter, at heads of 128 lanes: each pair
against the ``jax.numpy`` lines of ``models/kda.py`` it replaces, values and
every gradient; the whole mixer on the (B, T, H x 128) form against the same
parameters on the ``jax.numpy`` form; the shapes that must keep ``jax.numpy``
and what the mixer then calls; the names a device profile reads the kernels
under; no (B, T, H, 128) array on the fused path; the gauge.

Tolerances, as shares of max|reference| per array:
* float32: 2e-6 on values and activations' gradients (the same float32
  arithmetic, a head's sum in another order), 2e-5 on parameter gradients
  (sums over B x T rows in another order).
* bf16: 1e-2 (both sides round a float32 value ONCE to bf16, from values a
  few float32 ulps apart: a rounding flips here and there, one bf16 step of
  4e-3 .. 8e-3 of the entry), parameter gradients 1e-3 (float32 sums of terms
  that hold those flips).
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import pytest

from horovod_tpu.common import device_names as names
from horovod_tpu.models import kda as kda_model
from horovod_tpu.models.kda import KDADims, KDAMixer
from horovod_tpu.ops import kda as kda_ops
from horovod_tpu.ops import kda_fused
from horovod_tpu.ops.mamba_fused import row_tile

HEADS, D = 2, 128
DTYPES = {"f32": (jnp.float32, 2e-6, 2e-5), "bf16": (jnp.bfloat16, 1e-2, 1e-3)}


def share(got, want):
    got, want = (jnp.asarray(x, jnp.float32) for x in (got, want))
    return float(jnp.max(jnp.abs(got - want))
                 / (jnp.max(jnp.abs(want)) + 1e-30))


def value_and_grads(fn, args, weights):
    """The outputs and the gradients of a fixed weighted sum of them, ONE
    jitted program (run op by op, the interpreter compiles thousands)."""
    def both(*args):
        out, vjp = jax.vjp(fn, *args)
        return out, vjp(jax.tree_util.tree_map(
            lambda w, o: w.astype(o.dtype), weights, out))

    return jax.jit(both)(*args)


def rows(dtype):
    """B > 1 and more than one block of rows of either pair."""
    return 2, 2 * row_tile(jnp.dtype(dtype).itemsize)


def normal(seed, shape, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(seed), shape).astype(dtype)


# ------------------------------------------------------- the pairs alone

def gate_definition(q, k, decay, a_log, dt_bias):
    """``KDAMixer``'s lines under ``hvd_kda_gate`` as they stand, on the 4-D
    form, handed back as (B, T, H x 128)."""
    b, t, inner = q.shape
    q4, k4 = (x.reshape(b, t, HEADS, D) for x in (q, k))
    qn = (kda_model.l2_norm(q4) * D ** -0.5).astype(q.dtype)
    kn = kda_model.l2_norm(k4).astype(k.dtype)
    g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
        decay.astype(jnp.float32) + dt_bias).reshape(b, t, HEADS, D)
    return tuple(x.reshape(b, t, inner) for x in (qn, kn, g))


@pytest.mark.parametrize("precision", sorted(DTYPES))
def test_the_gate_pair_matches_the_jax_numpy_lines(precision):
    dtype, limit, param_limit = DTYPES[precision]
    b, t = rows(dtype)
    shape = (b, t, HEADS * D)
    # decay + dt_bias over both of softplus' tails and its knee
    args = (normal(0, shape, dtype), normal(1, shape, dtype),
            (4.0 * normal(2, shape)).astype(dtype),
            jnp.log(jnp.linspace(1.0, 16.0, HEADS)),
            normal(3, (HEADS * D,)) - 2.0)
    weights = (normal(4, shape), normal(5, shape), normal(6, shape))
    got = value_and_grads(lambda *a: kda_fused.gate(*a, interpret=True),
                          args, weights)
    want = value_and_grads(gate_definition, args, weights)
    for name, g, w in zip(("q", "k", "g"), got[0], want[0]):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert share(g, w) <= limit, name
    for name, g, w in zip(("dq", "dk", "ddecay", "dA_log", "ddt_bias"),
                          got[1], want[1]):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert share(g, w) <= (param_limit if name[1:2].isupper()
                               or name == "ddt_bias" else limit), name


def out_norm_definition(o, gate, scale, eps=1e-5):
    b, t, _ = o.shape
    return kda_model.head_norm_then_gate(
        o.reshape(b, t, HEADS, D), gate, scale, eps).astype(o.dtype)


@pytest.mark.parametrize("precision", sorted(DTYPES))
def test_the_out_norm_pair_matches_head_norm_then_gate(precision):
    dtype, limit, param_limit = DTYPES[precision]
    b, t = rows(dtype)
    shape = (b, t, HEADS * D)
    args = (normal(0, shape, dtype), (3.0 * normal(1, shape)).astype(dtype),
            1.0 + 0.1 * normal(2, (D,)))
    weights = normal(3, shape)
    got = value_and_grads(
        lambda *a: kda_fused.out_norm(*a, 1e-5, interpret=True), args, weights)
    want = value_and_grads(out_norm_definition, args, weights)
    assert got[0].dtype == want[0].dtype
    assert share(got[0], want[0]) <= limit
    for name, g, w in zip(("do", "dgate", "dscale"), got[1], want[1]):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert share(g, w) <= (param_limit if name == "dscale" else limit), name


def test_the_order_is_norm_then_gate_with_one_weight_a_head():
    """Mamba-2's order (gate, with silu, THEN the norm, a weight a feature)
    is another function: the kernel is not ``mamba_fused.gate_norm``'s."""
    from horovod_tpu.ops import mamba_fused

    shape = (1, row_tile(4), HEADS * D)
    o, gate = normal(0, shape), normal(1, shape)
    ours = kda_fused.out_norm(o, gate, jnp.ones((D,)), 1e-5, interpret=True)
    theirs = mamba_fused.gate_norm(o, gate, jnp.ones((HEADS * D,)), HEADS,
                                   1e-5, interpret=True)
    assert share(ours, theirs) > 0.1


# ------------------------------------------------------------- the mixer

def mixer_and_inputs(dtype, heads=HEADS, d=D, t=None):
    mixer = KDAMixer(dim=64, dims=KDADims(heads=heads, head_dim=d),
                     dtype=dtype, interpret=True)
    t = t or row_tile(jnp.dtype(dtype).itemsize)
    h = normal(0, (1, t, 64), dtype)
    params = jax.eval_shape(
        lambda: mixer.init(jax.random.PRNGKey(1), h[:, :8]))["params"]
    return mixer, params, h


@pytest.mark.parametrize("precision", sorted(DTYPES))
def test_the_fused_mixer_matches_the_jax_numpy_mixer(precision, monkeypatch,
                                                     hvd):
    """One row tile of 2 heads x 128: convolutions, gate, scan and norm all
    kernels and nothing 4-D, against the same parameters with the two
    elementwise chains in ``jax.numpy`` round the same scan kernels."""
    dtype = DTYPES[precision][0]
    mixer, _, h = mixer_and_inputs(dtype)
    params = mixer.init(jax.random.PRNGKey(1), h[:, :8])["params"]
    # parameters a step away from their initial values: the norm's weight 1
    # and a zero-mean dt_bias would hide a wrong gradient's sign
    params = jax.tree_util.tree_map(
        lambda p: p + 0.05 * normal(7, p.shape), params)
    gauge = hvd.metrics.registry().gauge("horovod_kda_fused_mixers")

    def loss(p, x):
        return jnp.sum(jnp.sin(mixer.apply({"params": p}, x)
                               .astype(jnp.float32)))

    matmul = "highest" if dtype == jnp.float32 else None
    with jax.default_matmul_precision(matmul):
        got = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(params, h)
        assert gauge.value > 0
        monkeypatch.setattr(kda_fused, "takes_kernel", lambda *a: False)
        want = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(params, h)
        assert gauge.value == 0
    limit = 2e-5 if dtype == jnp.float32 else 4e-2
    assert abs(got[0] - want[0]) <= limit * abs(want[0])
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got[1]),
                            jax.tree_util.tree_leaves(want[1])):
        assert share(g, w) <= limit, jax.tree_util.keystr(path)


def counted(monkeypatch, module, name):
    """``module.<name>`` with its calls' arguments kept."""
    real, calls = getattr(module, name), []

    def patched(*args, **kwargs):
        calls.append((args, kwargs))
        return real(*args, **kwargs)

    monkeypatch.setattr(module, name, patched)
    return calls


OTHER_SHAPES = {    # (heads, head_dim, T, the scan's kernels answer as they do)
    "a_head_of_64": (2, 64, 512, True),
    "rows_that_are_no_whole_tile": (2, 128, 384, True),
    "a_scan_that_kept_jax_numpy": (2, 128, 512, False),
}


@pytest.mark.parametrize("case", sorted(OTHER_SHAPES))
def test_other_shapes_call_the_jax_numpy_functions_as_before(case, monkeypatch,
                                                             hvd):
    """Where the rule says no the mixer calls ``l2_norm`` twice (q, then k),
    ``kda`` with six operands on (B, T, H, d) and ``head_norm_then_gate(o,
    gate, scale, eps)``, module attributes of ``models/kda.py`` a test may
    replace, and none of the fused entries."""
    heads, d, t, scan_kernels = OTHER_SHAPES[case]
    if not scan_kernels:
        monkeypatch.setattr(kda_ops, "takes_kernel", lambda *a: False)
    mixer, params, h = mixer_and_inputs(jnp.bfloat16, heads, d, t)
    l2 = counted(monkeypatch, kda_model, "l2_norm")
    norm = counted(monkeypatch, kda_model, "head_norm_then_gate")
    scan = counted(monkeypatch, kda_model, "kda")
    fused = [counted(monkeypatch, kda_fused, "gate"),
             counted(monkeypatch, kda_fused, "out_norm"),
             counted(monkeypatch, kda_model, "kda_lanes")]
    gauge = hvd.metrics.registry().gauge("horovod_kda_fused_mixers")
    gauge.set(3)
    jax.eval_shape(lambda p, x: mixer.apply({"params": p}, x), params, h)
    assert gauge.value == 0
    assert [len(c) for c in fused] == [0, 0, 0]
    assert [a[0].shape for a, _ in l2] == [(1, t, heads, d)] * 2
    (args, kwargs), = scan
    assert len(args) == 6 and [x.shape for x in args[:4]] == [
        (1, t, heads, d)] * 4 and args[4].shape == (1, t, heads)
    (args, kwargs), = norm
    assert [x.shape for x in args[:3]] == [(1, t, heads, d),
                                           (1, t, heads * d), (d,)]
    assert args[3] == mixer.rms_norm_eps and not kwargs


def test_the_rule_asks_for_one_shape_and_dtype():
    x = jax.ShapeDtypeStruct((1, 512, HEADS * D), jnp.bfloat16)
    assert kda_fused.takes_kernel(x, x, x, x, x, HEADS, kda_ops.CHUNK)
    other = jax.ShapeDtypeStruct(x.shape, jnp.float32)
    for n in range(1, 5):
        arrays = [x] * 5
        arrays[n] = other
        assert not kda_fused.takes_kernel(*arrays, HEADS, kda_ops.CHUNK)
    assert not kda_fused.takes_kernel(x, x, x, x, x, HEADS, 32)
    f16 = jax.ShapeDtypeStruct(x.shape, jnp.float16)
    assert not kda_fused.takes_kernel(*[f16] * 5, HEADS, kda_ops.CHUNK)


def test_the_lanes_entry_refuses_what_the_kernels_do_not_tile():
    q = jnp.zeros((1, 64, HEADS * D), jnp.bfloat16)     # one chunk: no pair
    with pytest.raises(ValueError, match="lanes_take_kernel"):
        kda_ops.kda_lanes(q, q, q, q, jnp.zeros((1, 64, HEADS)))


def test_the_gauge_counts_the_fused_mixers_and_resets(hvd):
    gauge = hvd.metrics.registry().gauge("horovod_kda_fused_mixers")
    fused, params, h = mixer_and_inputs(jnp.bfloat16)
    plain, plain_params, short = mixer_and_inputs(jnp.bfloat16, t=128)

    def trace(mixer, p, x):
        jax.eval_shape(lambda p, x: mixer.apply({"params": p}, x), p, x)

    trace(plain, plain_params, short)
    assert gauge.value == 0
    trace(fused, params, h)
    trace(fused, params, h)
    assert gauge.value == 2
    trace(plain, plain_params, short)
    assert gauge.value == 0


# ------------------------------------------------ the lowered mixer's names

@pytest.fixture(scope="module")
def lowered_mixers():
    """A mixer's loss and gradients at 2 heads x 128 over one bf16 row tile,
    LOWERED FOR THE TPU (nothing compiles or runs), on the fused path and,
    with the rule answering no, on the ``jax.numpy`` one: (the ``op_name``s,
    the module's text)."""
    mixer, params, h = mixer_and_inputs(jnp.bfloat16)
    mixer = mixer.clone(interpret=False)

    def lower():
        lowered = jax.jit(jax.grad(lambda p, x: jnp.sum(
            mixer.apply({"params": p}, x).astype(jnp.float32)),
            argnums=(0, 1))).trace(params, h).lower(
                lowering_platforms=("tpu",))
        return (set(re.findall(r'loc\("([^"]*)"',
                               lowered.as_text(debug_info=True))),
                lowered.as_text())

    fused = lower()
    with pytest.MonkeyPatch.context() as m:
        m.setattr(kda_fused, "takes_kernel", lambda *a: False)
        plain = lower()
    return {"fused": fused, "plain": plain}


@pytest.mark.parametrize("scope,kernel,backward", [
    (names.KDA_GATE, "_fwd", False), (names.KDA_GATE, "_bwd", True),
    (names.KDA_OUT_NORM, "_fwd", False), (names.KDA_OUT_NORM, "_bwd", True)])
def test_the_kernels_are_read_under_the_mixers_scopes(scope, kernel, backward,
                                                      lowered_mixers):
    """The kernels' own names are NOT registered: ``device_profile.name_of``
    gives every instruction of theirs to the scope both rules of the
    ``custom_vjp`` enter, which ``benchmarks/kda_cost.py`` asks for by
    equality of a path component."""
    from horovod_tpu.metrics import device_profile

    found, _ = lowered_mixers["fused"]
    assert scope + kernel not in names.ALL
    # (a jitted call's body is lowered once: its ops' names are the site's
    # followed by the body's own)
    inside = f"{scope}{kernel}/pallas_call"
    assert inside in found
    call = "_out_norm" if scope == names.KDA_OUT_NORM else "_gate"
    site, = [n for n in found if n.endswith(f"/jit({call}{kernel}_call)")]
    assert ("transpose(jvp(" in site) == backward
    assert site.split("/")[-2] == scope
    assert device_profile.name_of(f"{site}/{inside}") == scope
    # and whatever else the rules trace (the parameters' rows, the sums over
    # a head's columns, the casts of the cotangents) is under the scope too
    for op_name in found:
        if f"/{scope}/" in op_name:
            assert device_profile.name_of(op_name) == scope, op_name


def test_nothing_is_laid_out_by_head_between_the_convolutions_and_o_proj(
        lowered_mixers):
    """On the fused path no instruction of the lowered mixer has an operand
    or a result (B, T, H, 128): no ``reshape`` / ``transpose`` / ``copy`` to
    the 4-D form is there for XLA to turn into a relayout. The ``jax.numpy``
    path of the same mixer has them (the pattern finds what it looks for)."""
    by_head = re.compile(rf"tensor<\d+x{row_tile(2)}x{HEADS}x{D}x")
    assert not by_head.search(lowered_mixers["fused"][1])
    assert by_head.search(lowered_mixers["plain"][1])
    for name in ("hvd_kda_gate_fwd", "hvd_kda_gate_bwd", "hvd_kda_scan_fwd",
                 "hvd_kda_scan_bwd", "hvd_kda_out_norm_fwd",
                 "hvd_kda_out_norm_bwd"):
        assert name in lowered_mixers["fused"][1], name
