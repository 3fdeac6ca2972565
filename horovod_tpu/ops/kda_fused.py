"""The KDA mixer's two elementwise chains as pallas TPU kernels, forward AND
backward, one pass over HBM each way, on the (B, T, H x 128) arrays the
convolutions write and the scan's kernels read (``models/kda.py`` has the
equations; ``ops/mamba_fused.py`` is the shape of kernel):

- :func:`gate`: ``q = L2norm(q) d^-0.5``, ``k = L2norm(k)`` a head (eps 1e-6)
  and ``g = -exp(A_log) softplus(decay + dt_bias)``, kernels
  ``hvd_kda_gate_fwd`` / ``hvd_kda_gate_bwd`` under the scope ``hvd_kda_gate``
  (``models.kda.l2_norm`` and the mixer's softplus expression are the
  definition);
- :func:`out_norm`: ``RMSNorm(o) scale sigmoid(gate)``, the norm a head with
  ONE weight of 128 shared by the heads, THEN the gate
  (``models.kda.head_norm_then_gate`` is the definition; Mamba-2's
  ``mamba_fused.gate_norm`` gates first, with silu, and has a weight a
  feature), kernels ``hvd_kda_out_norm_fwd`` / ``hvd_kda_out_norm_bwd`` under
  ``hvd_kda_out_norm``.

Each is a ``jax.custom_vjp`` whose backward recomputes the chain from its
inputs inside the kernel: the residuals are the inputs alone, no float32
intermediate and no (B, T, H, 128) array reaches HBM. The kernels' own names
are NOT names of ``common/device_names.py``: their time falls to the scope
both rules enter, which is what the readers ask for (as the scan's).

A grid step holds a block of rows (of one batch row) by all H x 128 lanes and
walks it a head at a time, the block's rows by 128 lanes, in ONE traced loop
over a lane offset. Parameter gradients are accumulated in float32 in VMEM
across the whole grid and written at its end.

Numerics are the definitions': float32 arithmetic inside, the sums of squares
and ``g`` float32, q, k and the normed output rounded once to the activations'
dtype; float32 parameters and parameter gradients. The sigmoids are
``jax.nn.sigmoid`` and softplus' own ``exp`` over ``1 + exp``, a division each,
NOT ``mamba_fused``'s ``tanh`` form: PR 51's build of these kernels read the
TPU's ``tanh`` leaving a sigmoid off by ~5e-6 absolute, which on the slopes of
1e-3 .. 1e-1 a freshly initialised ``dt_bias`` gives moved the float32
gradients of ``dt_bias`` and the decay's projections by 2e-3 .. 4e-3 of their
largest entry, past the 1.5e-3 the Kimi cell's check holds them to; as built
here they read the ``jax.numpy`` lines' 1.3e-4 .. 1.6e-4 (PERF.md §6, PR 52).

Which shapes take the kernels is a rule on what the caller can see
(:func:`takes_kernel`). ``interpret=True`` runs them in the Pallas
interpreter, asked for by the caller and never inferred from the platform.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common.device_names import KDA_GATE, KDA_OUT_NORM
from . import kda as kda_ops
from .mamba_fused import (_EDGE, _VMEM_LIMIT_BYTES, _f32, _fold, _grid_ends,
                          norm_takes_kernel, row_tile)

_LANES = 128            # a head
_ALL = slice(None)
L2_EPS = 1e-6           # ``models.kda.l2_norm``'s


def takes_kernel(q, k, v, decay, gate, heads: int, chunk: int) -> bool:
    """Whether the mixer's five (B, T, inner) arrays between its convolutions
    and ``o_proj`` are shapes both kernel pairs tile: one shape and dtype,
    bf16 or f32; heads of 128 lanes; T a multiple of ``mamba_fused.row_tile``;
    and the scan between them takes ITS kernels on the same arrays
    (``ops.kda.lanes_take_kernel``): either side in ``jax.numpy`` would put
    the (B, T, H, 128) form and its relayouts back."""
    return (all(x.shape == q.shape and x.dtype == q.dtype
                for x in (k, v, decay, gate))
            and norm_takes_kernel(q, gate, heads)
            and q.shape[2] == heads * _LANES
            and kda_ops.lanes_take_kernel(q, k, v, heads, chunk))


def _gate_rows(itemsize: int) -> int:
    """Rows of the gate kernels' block: half a row tile. The backward holds
    ten blocks of the activations' size (q, k, decay, dq, dk in, dg twice as
    wide, three out) and their doubles: 40 MiB at 4,096 lanes."""
    return row_tile(itemsize) // 2


def _rows_specs(x, rows):
    """The grid (batch, blocks of ``rows`` rows) over ``x (B, T, inner)`` and
    the ``BlockSpec``s of a block of rows by all lanes and of a parameter's
    one row of ``width`` lanes."""
    b, t, inner = x.shape
    return (b, t // rows), pl.BlockSpec(
        (None, rows, inner), lambda n, i: (n, i, 0)), lambda width: (
            pl.BlockSpec((1, width), lambda n, i: (0, 0)))


def _params(semantics):
    return pltpu.CompilerParams(dimension_semantics=(semantics, semantics),
                                vmem_limit_bytes=_VMEM_LIMIT_BYTES)


def _softplus_and_slope(z):
    """``softplus(z)`` in ``jax.nn.softplus``'s own form and its derivative
    ``sigmoid(z)`` from the same ``exp``, in f32: exact in both tails."""
    e = jnp.exp(-jnp.abs(z))
    return (jnp.maximum(z, 0.0) + jnp.log1p(e),
            jnp.where(z >= 0.0, 1.0, e) / (1.0 + e))


def _unit(x):
    """``x / sqrt(sum(x^2) + eps)`` a row of a head's 128 lanes, and the
    inverse length."""
    inv = lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)
    return x * inv, inv


def _for_each_head(ref, head):
    """``head(its lanes)`` for every head of a block: one traced loop over a
    lane offset (32 unrolled heads would lower 32 copies of the body). A
    head's tile is ALL the block's rows by 128 lanes: walked in pieces of 16 /
    32 / 64 / 128 rows the gate's forward read 3.6 / 1.9 / 1.5 / 1.48 ms on
    the chip, whole 1.48 (PR 51's sweep; 1.47 here, PERF.md §5)."""
    def body(h, carry):
        head(pl.ds(pl.multiple_of(h * _LANES, _LANES), _LANES))
        return carry

    lax.fori_loop(0, ref.shape[1] // _LANES, body, None)


# ------------------------------------------- L2 norms of q and k, log-decay

def _gate_fwd_kernel(q_ref, k_ref, z_ref, coef_ref, bias_ref, qo_ref, ko_ref,
                     g_ref, *, q_scale):
    def head(cols):
        qo_ref[:, cols] = (_unit(_f32(q_ref, _ALL, cols))[0]
                           * q_scale).astype(qo_ref.dtype)
        ko_ref[:, cols] = _unit(_f32(k_ref, _ALL, cols))[0].astype(
            ko_ref.dtype)
        g_ref[:, cols] = coef_ref[:, cols] * _softplus_and_slope(
            _f32(z_ref, _ALL, cols) + bias_ref[:, cols])[0]

    _for_each_head(q_ref, head)


def _unit_bwd(x_ref, dy_ref, cols, scale):
    """The cotangent of ``x`` under ``y = unit(x) scale``."""
    y, inv = _unit(_f32(x_ref, _ALL, cols))
    dn = _f32(dy_ref, _ALL, cols) * scale
    return inv * (dn - y * jnp.sum(dn * y, axis=-1, keepdims=True))


def _gate_bwd_kernel(q_ref, k_ref, z_ref, coef_ref, bias_ref, dqo_ref, dko_ref,
                     dg_ref, dq_ref, dk_ref, dz_ref, dcoef_ref, dbias_ref,
                     acc_ref, *, q_scale):
    first_step, last_step = _grid_ends()

    @pl.when(first_step)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def head(cols):
        dq_ref[:, cols] = _unit_bwd(q_ref, dqo_ref, cols, q_scale).astype(
            dq_ref.dtype)
        dk_ref[:, cols] = _unit_bwd(k_ref, dko_ref, cols, 1.0).astype(
            dk_ref.dtype)
        soft, slope = _softplus_and_slope(
            _f32(z_ref, _ALL, cols) + bias_ref[:, cols])
        dg = dg_ref[:, cols]
        dz = dg * coef_ref[:, cols] * slope
        dz_ref[:, cols] = dz.astype(dz_ref.dtype)
        acc_ref[0, :, cols] += _fold(dg * soft)
        acc_ref[1, :, cols] += _fold(dz)

    _for_each_head(q_ref, head)

    @pl.when(last_step)
    def _store():
        dcoef_ref[...] = jnp.sum(acc_ref[0], axis=0, keepdims=True)
        dbias_ref[...] = jnp.sum(acc_ref[1], axis=0, keepdims=True)


# The calls are jitted so that a model's layers and the recomputed forward
# share ONE traced and lowered copy of each kernel (ops/flash_attention.py).
@functools.partial(jax.jit, static_argnums=(5, 6))
def _gate_fwd_call(q, k, decay, coef, bias, q_scale, interpret):
    inner, size = q.shape[2], q.size
    grid, rows, row = _rows_specs(q, _gate_rows(q.dtype.itemsize))
    return pl.pallas_call(
        functools.partial(_gate_fwd_kernel, q_scale=q_scale),
        grid=grid,
        in_specs=[rows, rows, rows, row(inner), row(inner)],
        out_specs=[rows, rows, rows],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(q.shape, jnp.float32)],
        compiler_params=_params("parallel"),
        cost_estimate=pl.CostEstimate(
            flops=16 * size, transcendentals=2 * size,
            bytes_accessed=(5 * q.dtype.itemsize + 4) * size),
        interpret=interpret,
        name=KDA_GATE + "_fwd",
    )(q, k, decay, coef.reshape(1, -1), bias.reshape(1, -1))


@functools.partial(jax.jit, static_argnums=(8, 9))
def _gate_bwd_call(q, k, decay, coef, bias, dq, dk, dg, q_scale, interpret):
    inner, size = q.shape[2], q.size
    grid, rows, row = _rows_specs(q, _gate_rows(q.dtype.itemsize))
    like = jax.ShapeDtypeStruct(q.shape, q.dtype)
    sums = jax.ShapeDtypeStruct((1, inner), jnp.float32)
    dq, dk, dz, dcoef, dbias = pl.pallas_call(
        functools.partial(_gate_bwd_kernel, q_scale=q_scale),
        grid=grid,
        in_specs=[rows, rows, rows, row(inner), row(inner), rows, rows, rows],
        out_specs=[rows, rows, rows, row(inner), row(inner)],
        out_shape=[like, like, like, sums, sums],
        scratch_shapes=[pltpu.VMEM((2, _EDGE, inner), jnp.float32)],
        compiler_params=_params("arbitrary"),
        cost_estimate=pl.CostEstimate(
            flops=40 * size, transcendentals=3 * size,
            bytes_accessed=(8 * q.dtype.itemsize + 4) * size),
        interpret=interpret,
        name=KDA_GATE + "_bwd",
    )(q, k, decay, coef.reshape(1, -1), bias.reshape(1, -1), dq, dk, dg)
    return dq, dk, dz, dcoef.reshape(inner), dbias.reshape(inner)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _gate(q, k, decay, coef, bias, q_scale, interpret):
    with jax.named_scope(KDA_GATE):
        return tuple(_gate_fwd_call(q, k, decay, coef, bias, q_scale,
                                    interpret))


def _gate_forward(q, k, decay, coef, bias, q_scale, interpret):
    return (_gate(q, k, decay, coef, bias, q_scale, interpret),
            (q, k, decay, coef, bias))


def _gate_backward(q_scale, interpret, res, cotangents):
    q, k, decay, coef, bias = res
    dq, dk, dg = cotangents
    with jax.named_scope(KDA_GATE):
        dq, dk, dz, dcoef, dbias = _gate_bwd_call(
            q, k, decay, coef, bias, dq.astype(q.dtype), dk.astype(k.dtype),
            dg.astype(jnp.float32), q_scale, interpret)
    return dq, dk, dz, dcoef.astype(coef.dtype), dbias.astype(bias.dtype)


_gate.defvjp(_gate_forward, _gate_backward)


def gate(q, k, decay, a_log, dt_bias, interpret: bool = False):
    """``(L2norm(q) d^-0.5, L2norm(k), -exp(a_log) softplus(decay +
    dt_bias))`` for shapes :func:`takes_kernel` accepts: q, k, decay (B, T,
    H x 128), the norms a head; ``a_log`` (H,) and ``dt_bias`` (H x 128,)
    float32. q and k come back in their dtype, ``g`` in float32, all (B, T,
    H x 128). Gradients reach all five."""
    d = q.shape[2] // a_log.shape[0]
    with jax.named_scope(KDA_GATE):
        coef = jnp.repeat(-jnp.exp(a_log.astype(jnp.float32)), d)
    return _gate(q, k, decay, coef, dt_bias, d ** -0.5, interpret)


# --------------------------------------- head norm, then the sigmoid gate

def _normed_and_gate(o_ref, gate_ref, cols, eps):
    """(RMSNorm(o) without its weight, the inverse root mean square,
    sigmoid(gate)) of a head, f32."""
    o = _f32(o_ref, _ALL, cols)
    inv = lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    return o * inv, inv, jax.nn.sigmoid(_f32(gate_ref, _ALL, cols))


def _out_norm_fwd_kernel(o_ref, gate_ref, s_ref, y_ref, *, eps):
    def head(cols):
        normed, _, sig = _normed_and_gate(o_ref, gate_ref, cols, eps)
        y_ref[:, cols] = (normed * s_ref[...] * sig).astype(y_ref.dtype)

    _for_each_head(o_ref, head)


def _out_norm_bwd_kernel(o_ref, gate_ref, s_ref, dy_ref, do_ref, dgate_ref,
                         ds_ref, acc_ref, *, eps):
    first_step, last_step = _grid_ends()

    @pl.when(first_step)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def head(cols):
        normed, inv, sig = _normed_and_gate(o_ref, gate_ref, cols, eps)
        dy, scale = _f32(dy_ref, _ALL, cols), s_ref[...]
        dgate_ref[:, cols] = (dy * (normed * scale) * sig * (1.0 - sig)
                              ).astype(dgate_ref.dtype)
        passed = dy * sig                       # d (normed x scale)
        dn = passed * scale
        # d o of o * rsqrt(mean(o^2) + eps)
        do_ref[:, cols] = (inv * (dn - normed * jnp.mean(
            dn * normed, axis=-1, keepdims=True))).astype(do_ref.dtype)
        acc_ref[...] += _fold(passed * normed)

    _for_each_head(o_ref, head)

    @pl.when(last_step)
    def _store():
        ds_ref[...] = jnp.sum(acc_ref[...], axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _out_norm_fwd_call(o, gate, scale, eps, interpret):
    grid, rows, row = _rows_specs(o, row_tile(o.dtype.itemsize))
    return pl.pallas_call(
        functools.partial(_out_norm_fwd_kernel, eps=eps),
        grid=grid,
        in_specs=[rows, rows, row(_LANES)],
        out_specs=rows,
        out_shape=jax.ShapeDtypeStruct(o.shape, o.dtype),
        compiler_params=_params("parallel"),
        cost_estimate=pl.CostEstimate(
            flops=10 * o.size, transcendentals=o.size,
            bytes_accessed=3 * o.size * o.dtype.itemsize),
        interpret=interpret,
        name=KDA_OUT_NORM + "_fwd",
    )(o, gate, scale.reshape(1, -1))


@functools.partial(jax.jit, static_argnums=(4, 5))
def _out_norm_bwd_call(o, gate, scale, dy, eps, interpret):
    grid, rows, row = _rows_specs(o, row_tile(o.dtype.itemsize))
    like = jax.ShapeDtypeStruct(o.shape, o.dtype)
    do, dgate, ds = pl.pallas_call(
        functools.partial(_out_norm_bwd_kernel, eps=eps),
        grid=grid,
        in_specs=[rows, rows, row(_LANES), rows],
        out_specs=[rows, rows, row(_LANES)],
        out_shape=[like, like,
                   jax.ShapeDtypeStruct((1, _LANES), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((_EDGE, _LANES), jnp.float32)],
        compiler_params=_params("arbitrary"),
        cost_estimate=pl.CostEstimate(
            flops=30 * o.size, transcendentals=o.size,
            bytes_accessed=5 * o.size * o.dtype.itemsize),
        interpret=interpret,
        name=KDA_OUT_NORM + "_bwd",
    )(o, gate, scale.reshape(1, -1), dy)
    return do, dgate, ds.reshape(_LANES)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def out_norm(o, gate, scale, eps: float, interpret: bool = False):
    """``RMSNorm(o) scale sigmoid(gate)`` in o's dtype for shapes
    :func:`takes_kernel` accepts: o, gate (B, T, H x 128), the norm a head;
    ``scale`` (128,) float32, ONE weight shared by the heads."""
    with jax.named_scope(KDA_OUT_NORM):
        return _out_norm_fwd_call(o, gate, scale, eps, interpret)


def _out_norm_forward(o, gate, scale, eps, interpret):
    return out_norm(o, gate, scale, eps, interpret), (o, gate, scale)


def _out_norm_backward(eps, interpret, res, dy):
    o, gate, scale = res
    with jax.named_scope(KDA_OUT_NORM):
        do, dgate, ds = _out_norm_bwd_call(o, gate, scale, dy.astype(o.dtype),
                                           eps, interpret)
    return do, dgate, ds.astype(scale.dtype)


out_norm.defvjp(_out_norm_forward, _out_norm_backward)
