"""The Mamba-2 mixer's two elementwise chains, and LFM2's gated short
convolution, as pallas TPU kernels, forward AND backward, one pass over HBM
each way:

- :func:`conv_silu`: ``silu(causal_depthwise_conv(x, kernel, bias))``
  (``ops.ssd.causal_depthwise_conv`` is the definition), kernels
  ``hvd_mamba_conv_fwd`` / ``hvd_mamba_conv_bwd``;
- :func:`gate_norm`: ``RMSNorm(y * silu(z))`` in the activations' dtype
  (``models.mamba.gated_rms_norm`` is the definition), kernels
  ``hvd_mamba_gate_norm_fwd`` / ``hvd_mamba_gate_norm_bwd``.

- :func:`gated_conv`: ``C * conv(B * X)`` of ``[B | C | X]`` in one array,
  no activation and no bias (``models.short_conv.gated_conv`` is the
  definition; docs/short-conv.md), kernels ``hvd_sconv_conv_fwd`` /
  ``hvd_sconv_conv_bwd``: a sibling pair of the first, on its tiling helpers.

Three callers: ``models/mamba.py`` (Mamba-2: both chains),
``models/kda.py`` (Kimi Delta Attention: :func:`conv_silu` under its own
kernel names, three calls a layer) and ``models/short_conv.py`` (LFM2's
gated short convolution: :func:`gated_conv`).

Each is a ``jax.custom_vjp`` whose backward recomputes the chain from its
inputs inside the kernel: the residuals are the inputs alone, and no float32
intermediate ever reaches HBM (the ``jax.numpy`` forms write the padded row
in float32 and read it back, four misaligned slices of it; PERF.md §6, PR 31).

A grid step holds a block of ``row_tile`` rows (of one batch row) by all the
channels and walks it in pieces of ``_PIECE_ROWS`` rows x ``_lane_chunk``
lanes that stay in registers. The mixer's ``xBC`` and ``z`` are column runs of
one wide projection, and ``u`` and ``B | C`` column runs of the convolution's
result: the kernels read their input out of the wide array where it lies
(``wide=``, ``start=``) and write each run as an output of its own
(``splits=``), so that no slice is copied on either side.

The convolution needs the ``K - 1`` rows before a piece: inside a block they
are the rows above it, at a block's top they come through a second
``BlockSpec`` over the same array (the smallest block of rows that ends where
this one starts; zeros at the row's start). Its backward needs ``g = dy *
silu'(conv)`` of the ``K - 1`` rows AFTER a piece: it walks a block's pieces
in reverse and carries them, and computes them for the rows after the block
from two more such small blocks. The shifts along rows are sublane rotations
in registers (``pltpu.roll``) of a piece with its 8 neighbouring rows
attached. Parameter gradients are accumulated in float32 in VMEM across the
whole grid and written at its end.

Numerics are the definitions': float32 arithmetic inside; the convolution
rounded to x's dtype before silu, silu's result rounded again; the norm's
result rounded once; float32 parameters and parameter gradients.

Which shapes take the kernels is a rule on what the caller can see
(:func:`conv_takes_kernel`, :func:`norm_takes_kernel`): channels a multiple of
128 lanes, rows a multiple of :func:`row_tile`, bf16 or f32. ``interpret=True``
runs them in the Pallas interpreter, asked for by the caller and never
inferred from the platform, as for ``flash_attention``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common.device_names import (MAMBA_CONV_BWD, MAMBA_CONV_FWD,
                                   MAMBA_GATE_NORM_BWD, MAMBA_GATE_NORM_FWD,
                                   SCONV_CONV_BWD, SCONV_CONV_FWD)

# A block of rows at Granite's 4,352 channels is 4.25 MiB. The gated norm's
# backward holds five blocks (y, z, do, dy, dz) and their doubles: 50 MiB at
# the most a block may hold, above the 16 MiB a kernel gets by default on this
# libtpu, well inside the v5e's 128 MiB of VMEM.
_VMEM_LIMIT_BYTES = 64 * 1024 * 1024
_BLOCK_BYTES = 5 * 1024 * 1024      # the most one block of rows may hold
# ... and of the convolution's alone, whose backward holds three blocks (x,
# dy, dx) and their doubles: 34 MiB at the 5,760 channels of Olmo-Hybrid's
# [q | k | v] side by side (15 heads of 96 | 96 | 192), 5.6 MiB a block.
_CONV_BLOCK_BYTES = 6 * 1024 * 1024
# ... and of the gated convolution's [B | C | X]: 512 rows of LFM2's 3 x 2,048
# in bf16. Its backward holds that block, its gradient's and dy's, and their
# doubles: 28 MiB.
_GATED_BLOCK_BYTES = 6 * 1024 * 1024
_EDGE = 8               # rows of f32 that ride along at a piece's border
_PIECE_ROWS = 64        # rows of a piece: a multiple of a bf16 tile's 16
_MAX_TAPS = _EDGE + 1   # a convolution wider than this keeps jax.numpy


def row_tile(itemsize: int) -> int:
    """Rows of a block: 512 of bf16, 256 of f32."""
    return 1024 // itemsize


def _tiles(x, block_bytes=_BLOCK_BYTES) -> bool:
    """bf16 or f32, the rows a whole number of row tiles, a block of rows by
    all the features at most ``block_bytes``."""
    if x.ndim != 3 or x.dtype not in (jnp.bfloat16, jnp.float32):
        return False
    tile_bytes = row_tile(x.dtype.itemsize) * x.dtype.itemsize
    return (x.shape[1] % row_tile(x.dtype.itemsize) == 0
            and x.shape[2] * tile_bytes <= block_bytes)


def conv_takes_kernel(x, kernel, splits=None) -> bool:
    """Whether ``x (B, T, C)`` under ``kernel (K, C)``, its result cut into
    column runs of widths ``splits``, is a shape the convolution's kernels
    tile: bf16 or f32, C and every run a multiple of 128, T a multiple of the
    row tile, K of at most 9 taps."""
    return (_tiles(x, _CONV_BLOCK_BYTES) and 1 <= kernel.shape[0] <= _MAX_TAPS
            and all(w % 128 == 0 for w in splits or (x.shape[2],)))


def norm_takes_kernel(y, z, groups: int) -> bool:
    """Whether ``y``, ``z`` ``(B, T, inner)`` in ``groups`` groups are shapes
    the gated norm's kernels tile: one dtype, bf16 or f32, ``inner / groups``
    a multiple of 128, T a multiple of the row tile."""
    return (_tiles(y) and y.shape == z.shape and y.dtype == z.dtype
            and y.shape[2] % groups == 0
            and (y.shape[2] // groups) % 128 == 0)


def _lane_chunk(width: int) -> int:
    """Lanes of a piece: 256 where they divide ``width``, else 128."""
    return 128 if width % 256 else 256


def _halo_rows(itemsize: int) -> int:
    """The smallest block of rows a ``BlockSpec`` may cut: one tile's, 8 of
    f32 and 16 of bf16."""
    return 32 // itemsize


def _f32(ref, rows, cols):
    return ref[rows, cols].astype(jnp.float32)


def _fold(x):
    """``x (n * 8, w)`` summed over its groups of 8 rows: (8, w), whole
    registers added."""
    return functools.reduce(
        jnp.add, (x[r:r + _EDGE] for r in range(0, x.shape[0], _EDGE)))


def _grid_ends():
    """Whether this is the first, and the last, step of a two-axis grid: where
    an accumulator that lives across the whole grid is zeroed and stored."""
    at = [pl.program_id(a) for a in (0, 1)]
    last = [pl.num_programs(a) - 1 for a in (0, 1)]
    return (jnp.logical_and(at[0] == 0, at[1] == 0),
            jnp.logical_and(at[0] == last[0], at[1] == last[1]))


def _silu_and_slope(c):
    """``silu(c)`` and its derivative in f32. The sigmoid is taken through
    ``tanh``, one transcendental and no division: 0.11 of the forward
    kernel's 0.62 ms (PERF.md §6, PR 31)."""
    sig = 0.5 * jnp.tanh(0.5 * c) + 0.5
    return c * sig, sig * (1.0 + c * (1.0 - sig))


# ------------------------------------------------------- convolution + silu

def _taps_in(prev, cur, k):
    """``cur (rows, w)`` moved down by ``K - 1, ..., 1, 0`` rows, the last rows
    of ``prev (8, w)`` coming in at the top: ``x[t - (K - 1) + j]`` for each
    tap j."""
    joined = jnp.concatenate([prev, cur], axis=0)
    return [cur if s == 0 else pltpu.roll(joined, s, 0)[_EDGE:]
            for s in range(k - 1, -1, -1)]


def _conv(taps, bias, shifted, dtype):
    """The convolution of a piece in f32, rounded to ``dtype`` as the
    definition rounds it, back in f32."""
    out = bias
    for tap, x in zip(taps, shifted):
        out = out + tap * x
    return out.astype(dtype).astype(jnp.float32)


def _for_each_chunk(k_ref, b_ref, widths, body):
    """``body(its columns in x, which output, its columns there, the K taps
    (1, cw) each, the bias (1, cw); None without a ``b_ref``)`` for every
    chunk of lanes of a block,
    none across a border between two outputs. An output's chunks are one
    traced loop over a lane offset: 17 unrolled copies of the body cost the
    cell 2 s of tracing and lowering a compiled program (PERF.md §6, PR 31)."""
    first = 0
    for out, width in enumerate(widths):
        cw = _lane_chunk(width)

        def chunk(c, carry, first=first, out=out, cw=cw):
            here = pl.multiple_of(c * cw, cw)
            cols = pl.ds(first + here, cw)
            body(cols, out, pl.ds(here, cw),
                 [k_ref[j:j + 1, cols] for j in range(k_ref.shape[0])],
                 None if b_ref is None else b_ref[:, cols])
            return carry

        lax.fori_loop(0, width // cw, chunk, None)
        first += width


def _conv_fwd_kernel(x_ref, before_ref, k_ref, b_ref, *o_refs, piece):
    k, dtype = k_ref.shape[0], o_refs[0].dtype
    at_start = pl.program_id(1) == 0

    def chunk(cols, out, there, taps, bias):
        def rows_of(i, prev):
            rows = pl.ds(pl.multiple_of(i * piece, piece), piece)
            cur = _f32(x_ref.at[0], rows, cols)
            c = _conv(taps, bias, _taps_in(prev, cur, k), dtype)
            o_refs[out][rows, there] = _silu_and_slope(c)[0].astype(dtype)
            return cur[piece - _EDGE:]

        before = before_ref[0, :, cols].astype(jnp.float32)[-_EDGE:]
        lax.fori_loop(0, x_ref.shape[1] // piece, rows_of,
                      jnp.where(at_start, 0.0, before))

    _for_each_chunk(k_ref, b_ref, [o.shape[1] for o in o_refs], chunk)


def _conv_bwd_kernel(x_ref, before_ref, after_ref, *refs, piece):
    n = (len(refs) - 6) // 2        # outputs of the forward: dy and its halo
    dy_refs, dy_after_refs = refs[:n], refs[n:2 * n]
    k_ref, b_ref, dx_ref, dk_ref, db_ref, acc_ref = refs[2 * n:]
    k, dtype = k_ref.shape[0], dx_ref.dtype
    tr, halo = x_ref.shape[1], before_ref.shape[1]
    pieces = tr // piece
    row, last_row = pl.program_id(1), pl.num_programs(1) - 1
    first_step, last_step = _grid_ends()

    @pl.when(first_step)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def chunk(cols, out, there, taps, bias):
        dy_ref, dy_after_ref = dy_refs[out], dy_after_refs[out]

        def slope_of(prev, cur, dy):
            shifted = _taps_in(prev, cur, k)
            return shifted, dy * _silu_and_slope(
                _conv(taps, bias, shifted, dtype))[1]

        def rows_of(n, g_after):
            i = pieces - 1 - n
            rows = pl.ds(pl.multiple_of(i * piece, piece), piece)
            # the rows before the piece: the block's own, or at its top the
            # small block's (one traced body for both: the load above row 0
            # is clamped and not used)
            above = pl.ds(pl.multiple_of(jnp.maximum(i * piece - halo, 0),
                                         halo), halo)
            prev = jnp.where(i > 0, _f32(x_ref.at[0], above, cols)[-_EDGE:],
                             before)
            shifted, g = slope_of(prev, _f32(x_ref.at[0], rows, cols),
                                  _f32(dy_ref, rows, there))
            joined = jnp.concatenate([g, g_after], axis=0)
            dx = None
            for j, tap in enumerate(taps):
                ahead = k - 1 - j       # dx[t] += kernel[j] g[t + K - 1 - j]
                term = tap * (g if ahead == 0 else pltpu.roll(
                    joined, piece + _EDGE - ahead, 0)[:piece])
                dx = term if dx is None else dx + term
                acc_ref[j, :, cols] += _fold(g * shifted[j])
            acc_ref[k, :, cols] += _fold(g)
            dx_ref[rows, cols] = dx.astype(dtype)
            return g[:_EDGE]

        before = jnp.where(
            row == 0, 0.0, before_ref[0, :, cols].astype(jnp.float32)[-_EDGE:])
        # g of the rows after the block, from the block's last rows and the
        # small blocks that start where it ends; nothing after the row's end
        _, g_after = slope_of(
            _f32(x_ref.at[0], slice(tr - halo, tr), cols)[-_EDGE:],
            after_ref[0, :, cols].astype(jnp.float32)[:_EDGE],
            dy_after_ref[:, there].astype(jnp.float32)[:_EDGE])
        # upwards: a piece hands the g of its first rows to the one above it
        lax.fori_loop(0, pieces, rows_of,
                      jnp.where(row == last_row, 0.0, g_after))

    _for_each_chunk(k_ref, b_ref, [dy.shape[1] for dy in dy_refs], chunk)

    @pl.when(last_step)
    def _store():
        dk_ref[...] = jnp.sum(acc_ref[:k], axis=1)
        db_ref[...] = jnp.sum(acc_ref[k], axis=0, keepdims=True)


def _conv_specs(wide, start, c, k):
    """The grid (batch, row tiles) over ``wide (B, T, W)``, whose columns
    ``start : start + c`` are x, and the ``BlockSpec``s: of x's block of rows,
    of the small blocks of x that end where it starts and start where it
    ends (all three by element offsets: ``start`` is no multiple of ``c``),
    and, for an array as wide as its block, of a block of rows, of the small
    block after it, of the taps and the bias."""
    (b, t, _), itemsize = wide.shape, wide.dtype.itemsize
    tr, halo = row_tile(itemsize), _halo_rows(itemsize)

    def columns(rows, first_row):   # whole tiles: Mosaic wants that proven
        return pl.BlockSpec(
            (pl.Element(1), pl.Element(rows), pl.Element(c)),
            lambda n, i: (n, pl.multiple_of(first_row(i), halo), start))

    return (b, t // tr), dict(
        x=columns(tr, lambda i: i * tr),
        before=columns(halo, lambda i: jnp.maximum(i * tr - halo, 0)),
        after=columns(halo, lambda i: jnp.minimum((i + 1) * tr, t - halo)),
        rows=lambda width: pl.BlockSpec((None, tr, width),
                                        lambda n, i: (n, i, 0)),
        rows_after=lambda width: pl.BlockSpec(
            (None, halo, width),
            lambda n, i: (n, jnp.minimum((i + 1) * (tr // halo),
                                         t // halo - 1), 0)),
        taps=pl.BlockSpec((k, c), lambda n, i: (0, 0)),
        bias=pl.BlockSpec((1, c), lambda n, i: (0, 0)))


# The calls are jitted so that the layers of a model and the recomputed
# forward, which call them with one signature, share ONE traced and lowered
# copy of each kernel (ops/flash_attention.py says why).
@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _conv_fwd_call(wide, kernel, bias, start, splits, interpret,
                   name=MAMBA_CONV_FWD):
    (b, t, _), (k, c) = wide.shape, kernel.shape
    grid, spec = _conv_specs(wide, start, c, k)
    return pl.pallas_call(
        functools.partial(_conv_fwd_kernel, piece=_PIECE_ROWS),
        grid=grid,
        in_specs=[spec["x"], spec["before"], spec["taps"], spec["bias"]],
        out_specs=[spec["rows"](width) for width in splits],
        out_shape=[jax.ShapeDtypeStruct((b, t, width), wide.dtype)
                   for width in splits],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=(2 * k + 6) * b * t * c, transcendentals=b * t * c,
            bytes_accessed=2 * b * t * c * wide.dtype.itemsize),
        interpret=interpret,
        name=name,
    )(wide, wide, kernel, bias.reshape(1, -1))


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _conv_bwd_call(wide, kernel, bias, dys, start, interpret,
                   name=MAMBA_CONV_BWD):
    (b, t, _), (k, c) = wide.shape, kernel.shape
    grid, spec = _conv_specs(wide, start, c, k)
    widths = [dy.shape[2] for dy in dys]
    dx, dk, db = pl.pallas_call(
        functools.partial(_conv_bwd_kernel, piece=_PIECE_ROWS),
        grid=grid,
        in_specs=[spec["x"], spec["before"], spec["after"],
                  *map(spec["rows"], widths), *map(spec["rows_after"], widths),
                  spec["taps"], spec["bias"]],
        out_specs=[spec["rows"](c), spec["taps"], spec["bias"]],
        out_shape=[jax.ShapeDtypeStruct((b, t, c), wide.dtype),
                   jax.ShapeDtypeStruct((k, c), jnp.float32),
                   jax.ShapeDtypeStruct((1, c), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((k + 1, _EDGE, c), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=(6 * k + 12) * b * t * c, transcendentals=b * t * c,
            bytes_accessed=3 * b * t * c * wide.dtype.itemsize),
        interpret=interpret,
        name=name,
    )(wide, wide, wide, *dys, *dys, kernel, bias.reshape(1, -1))
    return dx, dk, db.reshape(c)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _conv_silu(wide, x, kernel, bias, start, splits, interpret, names):
    return tuple(_conv_fwd_call(wide, kernel, bias, start, splits, interpret,
                                names[0]))


def _conv_forward(wide, x, kernel, bias, start, splits, interpret, names):
    return (tuple(_conv_fwd_call(wide, kernel, bias, start, splits, interpret,
                                 names[0])),
            (wide, kernel, bias))


def _conv_backward(start, splits, interpret, names, res, dys):
    wide, kernel, bias = res
    dx, dk, db = _conv_bwd_call(
        wide, kernel, bias, tuple(dy.astype(wide.dtype) for dy in dys), start,
        interpret, names[1])
    # x's cotangent alone: the forward read x out of ``wide``, whose other
    # columns belong to other readers
    return None, dx, dk.astype(kernel.dtype), db.astype(bias.dtype)


_conv_silu.defvjp(_conv_forward, _conv_backward)


def conv_silu(x, kernel, bias, interpret: bool = False, *, splits=None,
              wide=None, start: int = 0,
              names=(MAMBA_CONV_FWD, MAMBA_CONV_BWD)):
    """``silu(causal_depthwise_conv(x, kernel, bias))`` in x's dtype for
    shapes :func:`conv_takes_kernel` accepts: x (B, T, C); kernel (K, C) and
    bias (C,) float32. With ``splits`` (widths that sum to C) the result is a
    tuple of its column runs, each written once by the kernel: no slice of it
    is copied afterwards. Where x is the columns ``start : start + C`` of a
    wider array, pass that as ``wide``: the kernels then read x out of it
    where it lies, the slice that made x is never computed, and x still
    receives the whole gradient (``wide`` none). ``names``: what the forward
    and the backward kernel are called in the program, for another layer
    that runs this convolution (``models/kda.py``)."""
    if start % 128:
        raise ValueError(f"x starts at column {start}, not at a whole lane tile")
    outs = _conv_silu(x if wide is None else wide, x, kernel, bias, start,
                      tuple(splits or (x.shape[2],)), interpret, tuple(names))
    return outs if splits else outs[0]


# ------------------------------------------- doubly gated short convolution

def gated_conv_takes_kernel(bcx, kernel) -> bool:
    """Whether ``bcx (B, T, 3 D)`` = ``[B | C | X]`` under ``kernel (K, D)`` is
    a shape the gated convolution's kernels tile: bf16 or f32, D a multiple of
    128, T a multiple of the row tile, a block of rows by all 3 D columns at
    most ``_GATED_BLOCK_BYTES``, K of at most 9 taps."""
    if bcx.ndim != 3 or bcx.dtype not in (jnp.bfloat16, jnp.float32):
        return False
    d, tr = kernel.shape[1], row_tile(bcx.dtype.itemsize)
    return (bcx.shape[2] == 3 * d and d % 128 == 0 and bcx.shape[1] % tr == 0
            and 1 <= kernel.shape[0] <= _MAX_TAPS
            and bcx.shape[2] * tr * bcx.dtype.itemsize <= _GATED_BLOCK_BYTES)


def _taps_of(taps, shifted):
    """``sum_j taps[j] * shifted[j]`` in f32: the convolution with no bias."""
    return functools.reduce(jnp.add, (k * x for k, x in zip(taps, shifted)))


def _gated_fwd_kernel(w_ref, before_ref, k_ref, o_ref, *, piece):
    k, d = k_ref.shape
    at_start = pl.program_id(1) == 0

    def chunk(cols, out, there, taps, bias):
        gate, x = (pl.ds(cols.start + n * d, cols.size) for n in (1, 2))

        def rows_of(i, prev):
            rows = pl.ds(pl.multiple_of(i * piece, piece), piece)
            u = _f32(w_ref.at[0], rows, cols) * _f32(w_ref.at[0], rows, x)
            conv = _taps_of(taps, _taps_in(prev, u, k))
            o_ref[rows, there] = (_f32(w_ref.at[0], rows, gate)
                                  * conv).astype(o_ref.dtype)
            return u[piece - _EDGE:]

        before = (before_ref[0, :, cols].astype(jnp.float32)
                  * before_ref[0, :, x].astype(jnp.float32))[-_EDGE:]
        lax.fori_loop(0, o_ref.shape[0] // piece, rows_of,
                      jnp.where(at_start, 0.0, before))

    _for_each_chunk(k_ref, None, [d], chunk)


def _gated_bwd_kernel(w_ref, before_ref, after_ref, dy_ref, dy_after_ref,
                      k_ref, dw_ref, dk_ref, acc_ref, *, piece):
    k, d = k_ref.shape
    tr, halo = dy_ref.shape[0], before_ref.shape[1]
    pieces = tr // piece
    row, last_row = pl.program_id(1), pl.num_programs(1) - 1
    first_step, last_step = _grid_ends()

    @pl.when(first_step)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def chunk(cols, out, there, taps, bias):
        gate, x = (pl.ds(cols.start + n * d, cols.size) for n in (1, 2))

        def u_of(ref, rows):
            return _f32(ref, rows, cols) * _f32(ref, rows, x)

        def rows_of(n, g_after):
            i = pieces - 1 - n
            rows = pl.ds(pl.multiple_of(i * piece, piece), piece)
            # u of the rows before the piece: the block's own, or at its top
            # the small block's (the load above row 0 is clamped, not used)
            above = pl.ds(pl.multiple_of(jnp.maximum(i * piece - halo, 0),
                                         halo), halo)
            prev = jnp.where(i > 0, u_of(w_ref.at[0], above)[-_EDGE:], before)
            b, xs = _f32(w_ref.at[0], rows, cols), _f32(w_ref.at[0], rows, x)
            shifted = _taps_in(prev, b * xs, k)
            dy = _f32(dy_ref, rows, there)
            g = dy * _f32(w_ref.at[0], rows, gate)      # d conv
            joined = jnp.concatenate([g, g_after], axis=0)
            du = None
            for j, tap in enumerate(taps):
                ahead = k - 1 - j       # du[t] += kernel[j] g[t + K - 1 - j]
                term = tap * (g if ahead == 0 else pltpu.roll(
                    joined, piece + _EDGE - ahead, 0)[:piece])
                du = term if du is None else du + term
                acc_ref[j, :, cols] += _fold(g * shifted[j])
            dw_ref[rows, cols] = (du * xs).astype(dw_ref.dtype)
            dw_ref[rows, gate] = (dy * _taps_of(taps, shifted)).astype(
                dw_ref.dtype)
            dw_ref[rows, x] = (du * b).astype(dw_ref.dtype)
            return g[:_EDGE]

        before = jnp.where(row == 0, 0.0, u_of(before_ref.at[0],
                                               slice(None))[-_EDGE:])
        # g of the rows after the block; nothing after the row's end
        g_after = (dy_after_ref[:, there].astype(jnp.float32)
                   * after_ref[0, :, gate].astype(jnp.float32))[:_EDGE]
        # upwards: a piece hands the g of its first rows to the one above it
        lax.fori_loop(0, pieces, rows_of,
                      jnp.where(row == last_row, 0.0, g_after))

    _for_each_chunk(k_ref, None, [d], chunk)

    @pl.when(last_step)
    def _store():
        dk_ref[...] = jnp.sum(acc_ref[...], axis=1)


def _gated_specs(bcx, k):
    """:func:`_conv_specs` over all of ``bcx``'s columns, the taps' block as
    wide as ONE of its three runs."""
    d = bcx.shape[2] // 3
    grid, spec = _conv_specs(bcx, 0, 3 * d, k)
    return grid, d, {**spec, "taps": pl.BlockSpec((k, d), lambda n, i: (0, 0))}


@functools.partial(jax.jit, static_argnums=(2,))
def _gated_fwd_call(bcx, kernel, interpret):
    (b, t, _), k = bcx.shape, kernel.shape[0]
    grid, d, spec = _gated_specs(bcx, k)
    return pl.pallas_call(
        functools.partial(_gated_fwd_kernel, piece=_PIECE_ROWS),
        grid=grid,
        in_specs=[spec["x"], spec["before"], spec["taps"]],
        out_specs=spec["rows"](d),
        out_shape=jax.ShapeDtypeStruct((b, t, d), bcx.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=(2 * k + 2) * b * t * d, transcendentals=0,
            bytes_accessed=4 * b * t * d * bcx.dtype.itemsize),
        interpret=interpret,
        name=SCONV_CONV_FWD,
    )(bcx, bcx, kernel)


@functools.partial(jax.jit, static_argnums=(3,))
def _gated_bwd_call(bcx, kernel, dy, interpret):
    (b, t, _), k = bcx.shape, kernel.shape[0]
    grid, d, spec = _gated_specs(bcx, k)
    return pl.pallas_call(
        functools.partial(_gated_bwd_kernel, piece=_PIECE_ROWS),
        grid=grid,
        in_specs=[spec["x"], spec["before"], spec["after"], spec["rows"](d),
                  spec["rows_after"](d), spec["taps"]],
        out_specs=[spec["rows"](3 * d), spec["taps"]],
        out_shape=[jax.ShapeDtypeStruct(bcx.shape, bcx.dtype),
                   jax.ShapeDtypeStruct((k, d), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((k, _EDGE, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=(6 * k + 6) * b * t * d, transcendentals=0,
            bytes_accessed=7 * b * t * d * bcx.dtype.itemsize),
        interpret=interpret,
        name=SCONV_CONV_BWD,
    )(bcx, bcx, bcx, dy, dy, kernel)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def gated_conv(bcx, kernel, interpret: bool = False):
    """``C * conv(B * X)`` in ``bcx``'s dtype for shapes
    :func:`gated_conv_takes_kernel` accepts: ``bcx`` (B, T, 3 D) = ``[B | C |
    X]`` as ONE projection wrote it, ``kernel`` (K, D) float32; a causal
    depthwise convolution with no activation and no bias
    (``models.short_conv.gated_conv`` is the definition). The forward reads
    ``bcx`` once and writes the result once; the backward reads ``bcx`` and
    the cotangent once, recomputes the convolution, and writes ONE gradient
    as wide as ``bcx`` and the taps' (float32)."""
    return _gated_fwd_call(bcx, kernel, interpret)


def _gated_forward(bcx, kernel, interpret):
    return _gated_fwd_call(bcx, kernel, interpret), (bcx, kernel)


def _gated_backward(interpret, res, dy):
    bcx, kernel = res
    dw, dk = _gated_bwd_call(bcx, kernel, dy.astype(bcx.dtype), interpret)
    return dw, dk.astype(kernel.dtype)


gated_conv.defvjp(_gated_forward, _gated_backward)


# ---------------------------------------------------------------- gated norm

def _gated(y_ref, z_ref, rows, cols):
    y, z = _f32(y_ref, rows, cols), _f32(z_ref, rows, cols)
    silu, slope = _silu_and_slope(z)
    return y, silu, slope, y * silu


def _norm_pieces(ref, piece, groups):
    """How many pieces of ``piece`` rows (one tile's) a block holds, and each
    group's columns: a piece is a few rows by one group's features."""
    width = ref.shape[1] // groups
    return ref.shape[0] // piece, [slice(g * width, (g + 1) * width)
                                   for g in range(groups)]


def _norm_fwd_kernel(y_ref, z_ref, s_ref, o_ref, *, piece, groups, eps):
    pieces, columns = _norm_pieces(y_ref, piece, groups)

    def rows_of(i, carry):
        rows = pl.ds(pl.multiple_of(i * piece, piece), piece)
        for cols in columns:
            gated = _gated(y_ref, z_ref, rows, cols)[3]
            inv = lax.rsqrt(jnp.mean(gated * gated, axis=-1, keepdims=True)
                            + eps)
            o_ref[rows, cols] = (gated * inv * s_ref[:, cols]).astype(
                o_ref.dtype)
        return carry

    lax.fori_loop(0, pieces, rows_of, None)


def _norm_bwd_kernel(y_ref, z_ref, s_ref, do_ref, dy_ref, dz_ref, ds_ref,
                     acc_ref, *, piece, groups, eps):
    pieces, columns = _norm_pieces(y_ref, piece, groups)
    first_step, last_step = _grid_ends()

    @pl.when(first_step)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def rows_of(i, carry):
        rows = pl.ds(pl.multiple_of(i * piece, piece), piece)
        for cols in columns:
            y, silu, slope, gated = _gated(y_ref, z_ref, rows, cols)
            do = _f32(do_ref, rows, cols)
            inv = lax.rsqrt(jnp.mean(gated * gated, axis=-1, keepdims=True)
                            + eps)
            normed = gated * inv
            acc_ref[:, cols] += _fold(do * normed)
            dn = do * s_ref[:, cols]
            # d gated of gated * rsqrt(mean(gated^2) + eps)
            dg = inv * (dn - normed * jnp.mean(dn * normed, axis=-1,
                                               keepdims=True))
            dy_ref[rows, cols] = (dg * silu).astype(dy_ref.dtype)
            dz_ref[rows, cols] = (dg * y * slope).astype(dz_ref.dtype)
        return carry

    lax.fori_loop(0, pieces, rows_of, None)

    @pl.when(last_step)
    def _store():
        ds_ref[...] = jnp.sum(acc_ref[...], axis=0, keepdims=True)


def _norm_specs(y):
    """The grid (batch, row tiles) and the ``BlockSpec``s of a block of rows
    (y's; z's too: the first ``inner`` columns of an array that may be wider)
    and of the scale."""
    (b, t, inner), tr = y.shape, row_tile(y.dtype.itemsize)
    return (b, t // tr), (
        pl.BlockSpec((None, tr, inner), lambda n, i: (n, i, 0)),
        pl.BlockSpec((1, inner), lambda n, i: (0, 0)))


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _norm_fwd_call(y, wide, scale, groups, eps, interpret):
    grid, (rows, row) = _norm_specs(y)
    return pl.pallas_call(
        functools.partial(_norm_fwd_kernel, groups=groups, eps=eps,
                          piece=_halo_rows(y.dtype.itemsize)),
        grid=grid,
        in_specs=[rows, rows, row],
        out_specs=rows,
        out_shape=jax.ShapeDtypeStruct(y.shape, y.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=10 * y.size, transcendentals=y.size,
            bytes_accessed=3 * y.size * y.dtype.itemsize),
        interpret=interpret,
        name=MAMBA_GATE_NORM_FWD,
    )(y, wide, scale.reshape(1, -1))


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _norm_bwd_call(y, wide, scale, do, groups, eps, interpret):
    grid, (rows, row) = _norm_specs(y)
    inner = y.shape[2]
    dy, dz, ds = pl.pallas_call(
        functools.partial(_norm_bwd_kernel, groups=groups, eps=eps,
                          piece=_halo_rows(y.dtype.itemsize)),
        grid=grid,
        in_specs=[rows, rows, row, rows],
        out_specs=[rows, rows, row],
        out_shape=[jax.ShapeDtypeStruct(y.shape, y.dtype),
                   jax.ShapeDtypeStruct(y.shape, y.dtype),
                   jax.ShapeDtypeStruct((1, inner), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((_EDGE, inner), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=30 * y.size, transcendentals=y.size,
            bytes_accessed=5 * y.size * y.dtype.itemsize),
        interpret=interpret,
        name=MAMBA_GATE_NORM_BWD,
    )(y, wide, scale.reshape(1, -1), do)
    return dy, dz, ds.reshape(inner)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _gate_norm(y, wide, z, scale, groups, eps, interpret):
    return _norm_fwd_call(y, wide, scale, groups, eps, interpret)


def _norm_forward(y, wide, z, scale, groups, eps, interpret):
    return (_norm_fwd_call(y, wide, scale, groups, eps, interpret),
            (y, wide, scale))


def _norm_backward(groups, eps, interpret, res, do):
    y, wide, scale = res
    dy, dz, ds = _norm_bwd_call(y, wide, scale, do.astype(y.dtype), groups,
                                eps, interpret)
    return dy, None, dz, ds.astype(scale.dtype)     # z's, none for ``wide``


_gate_norm.defvjp(_norm_forward, _norm_backward)


def gate_norm(y, z, scale, groups: int, eps: float, interpret: bool = False,
              *, wide=None):
    """``RMSNorm(y * silu(z)) * scale`` over each of ``groups`` runs of the
    features, in y's dtype, for shapes :func:`norm_takes_kernel` accepts:
    y, z (B, T, inner); scale (inner,) float32. Where z is the FIRST ``inner``
    columns of a wider array, pass that as ``wide``: the kernels read z out of
    it where it lies, as :func:`conv_silu` reads x."""
    return _gate_norm(y, z if wide is None else wide, z, scale, groups, eps,
                      interpret)
