"""Mamba-2's state-space layer in its chunked ("state-space dual") form, and
the causal depthwise convolution in front of it (Dao & Gu, arXiv:2405.21060
§6-7).

Per head ``h`` with state ``S`` (P x N), ``S_0 = 0``::

    a_t = exp(dt_t * A_h)
    S_t = a_t S_{t-1} + dt_t * u_t B_t^T
    y_t = S_t C_t + D_h u_t

``B`` and ``C`` belong to a group of heads (``G`` groups, Granite 4.0-H: 1).
:func:`ssd` computes the same ``y`` without a step per token. The row is cut
into chunks of ``chunk`` positions and, with ``cum`` the running sum of
``dt * A`` inside a chunk:

- a state per chunk: what the chunk's own inputs leave at its end,
  ``sum_s exp(cum_last - cum_s) dt_s u_s B_s^T``;
- the recurrence over chunks: ``S <- exp(cum_last) S + state``, carried in
  float32, which hands each chunk the state it starts from;
- the outputs, written once: inside a chunk ``sum_{s<=l} (C_l . B_s)
  exp(cum_l - cum_s) dt_s u_s`` — one (chunk x chunk) masked score matrix a
  head, times the chunk's inputs — plus the carried state's part
  ``exp(cum_l) S_start C_l``, plus ``D u``.

Where the shapes tile (:func:`takes_kernel`: one group, heads a whole number
of 128-lane blocks, state and chunk multiples of 128, the row a whole number
of chunks, bf16 or f32) the three steps run as two pallas TPU kernels,
``hvd_ssd_scan_fwd`` and ``hvd_ssd_scan_bwd`` (a ``jax.custom_vjp``), on the
arrays as the mixer holds them: u and y (b, T, H P), B and C (b, T, N), dt
(b, T, H). A grid step is one chunk of one row, the chunk axis sequential,
the carried state VMEM scratch; a head's scores, decays and masked scores are
(128 x 128) tiles in VMEM, the dead triangle's never computed. Nothing sized
heads x chunk x chunk and nothing tiled (chunk, head, P) reaches HBM; the
backward reads the state each chunk starts from, which the forward of a
differentiated call writes (chunks x N x H P float32: 128 MiB a layer at
Granite's widths, alive inside one layer's backward). PERF.md §6, PR 40.

Every other shape runs the ``jax.numpy`` form below, which is also the
DEFINITION the kernels are tested against: a row of many chunks as one
``lax.scan`` over blocks of ``CHUNK_BLOCK`` chunks (``hvd_ssd_scan``) whose
carry is the state: a block computes its chunks' states, steps the recurrence
through them and writes their outputs.

Numerics, of both: ``dt``, ``A``, every sum of ``dt * A``, every decay and the
carried state are float32 whatever the activations' dtype; the argument of a
decay is masked BEFORE ``exp`` (a factorised ``exp(cum_l) exp(-cum_s)``
overflows float32 inside one chunk). The three products with a ``chunk``-long
or ``N``-long contraction take their operands in ``u``'s dtype (bf16 as
trained: the masked scores and the carried state are rounded to it for the
product, as the published kernels do) and accumulate in float32; they follow
``jax.default_matmul_precision`` as a plain ``@`` does, inside the kernels
too. The gradients of ``A``, ``D`` and ``dt`` are float32 sums; the running
sums of ``dt * A`` are ``jax.numpy``'s on both paths, differentiated by JAX.

The ``jax.numpy`` scan is differentiable by JAX. The masked scores of all its
heads (heads x chunk x chunk a chunk) go through HBM; a block runs under
``jax.checkpoint`` so that no more than ``CHUNK_BLOCK`` chunks' scores are
alive at once, forward or backward (PERF.md §6, PR 30).
:func:`causal_depthwise_conv` is the convolution's DEFINITION, in
``jax.numpy`` too; shapes that tile run it fused with the silu that follows
it as a kernel pair (``ops/mamba_fused.py`` ``conv_silu``, PR 31).
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import device_names

# The jax.numpy scan (shapes the kernels do not tile): chunks whose (heads x
# chunk x chunk) masked scores are alive at once: 64 chunks x 64 heads x 256
# x 256 f32 are 1 GiB a layer, several times over in the backward; 8 chunks
# are 128 MiB and still 512 matrix products. Chunks,
# not heads: the chunk axis is the row's major axis, so a block of chunks is a
# slice of every operand as it lies in memory (a block of heads cost a
# transposing copy of u and of y, 35 ms a step at Granite's widths).
CHUNK_BLOCK = 8


def causal_depthwise_conv(x, kernel, bias, scope=device_names.MAMBA_CONV):
    """``out[t, c] = bias[c] + sum_j kernel[j, c] * x[t - (K - 1) + j, c]``
    with zeros before the row's start. x: (B, T, C); kernel: (K, C); bias:
    (C,). Computed as K shifted multiply-adds in float32; returns x's dtype.
    ``scope`` is the name it runs under (another layer's, where it calls).
    The padded row goes through HBM in float32 and its K slices are not
    aligned to a tile: 9x the bytes' time at Granite's widths, which is why
    ``Mamba2Mixer`` takes ``mamba_fused.conv_silu`` where the shape tiles."""
    k, t = kernel.shape[0], x.shape[1]
    with jax.named_scope(scope):
        padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0))).astype(jnp.float32)
        out = bias.astype(jnp.float32)
        for j in range(k):
            out = out + kernel[j].astype(jnp.float32) * padded[:, j:j + t]
        return out.astype(x.dtype)


def _dot(a, b, contract, batch):
    """``lax.dot_general`` accumulating in float32; the result's axes are the
    batch axes, then a's free axes, then b's. (Not ``jnp.einsum``: it puts its
    subscripts, commas and all, into every ``op_name``.)"""
    return lax.dot_general(a, b, (contract, batch),
                           preferred_element_type=jnp.float32)


def _decay(total):
    """``exp`` of a sum of ``dt * A`` (<= 0, or -inf above the diagonal): every
    decay of the layer, in float32."""
    return jnp.exp(total.astype(jnp.float32))


def _carry_over_chunks(keep, left, start):
    """The recurrence over chunks. keep: (c, ...) what a chunk keeps of the
    state it starts from; left: (c, ..., p, n) what its own inputs leave at
    its end; start: (..., p, n) the state before the first of them. Returns
    (the state after the last, the state each chunk STARTS from), float32."""
    def carry_on(state, chunk_in):
        keep_c, left_c = chunk_in
        return keep_c[..., None, None] * state + left_c, state

    return lax.scan(carry_on, start.astype(jnp.float32), (keep, left))


def _chunk_states(Bc, cum, dt, u):
    """The state each chunk's own inputs leave at its end. Bc: (b, c, l, g,
    n); cum, dt: (b, c, l, h) f32; u: (b, c, l, h, p). Returns (b, c, g,
    h / g, p, n) f32."""
    b, c, l, h = cum.shape
    g, p = Bc.shape[3], u.shape[-1]
    to_end = _decay(cum[:, :, -1:, :] - cum) * dt               # (b,c,l,h)
    weighted = (u.astype(jnp.float32) * to_end[..., None]).astype(u.dtype)
    return _dot(Bc, weighted.reshape(b, c, l, g, h // g, p), ((2,), (2,)),
                ((0, 1, 3), (0, 1, 3))).transpose(0, 1, 2, 4, 5, 3)


def _chunk_outputs(Cc, Bc, cum, dt, u, starts, D):
    """y of some chunks, given the state each starts from. Cc, Bc: (b, c, l,
    g, n); cum, dt: (b, c, l, h) f32; u: (b, c, l, h, p); starts: (b, c, g,
    h / g, p, n) f32; D: (h,). Returns (b, c, l, h, p) in u's dtype."""
    b, c, l, h = cum.shape
    g, p = Cc.shape[3], u.shape[-1]
    scores = _dot(Cc, Bc, ((4,), (4,)), ((0, 1, 3), (0, 1, 3)))  # (b,c,g,l,s)
    if 1 < g < h:
        scores = jnp.repeat(scores, h // g, axis=2)
    cum_h, dt_h = cum.swapaxes(2, 3), dt.swapaxes(2, 3)         # (b,c,h,l)
    # exp(cum_l - cum_s) on s <= l; the argument is <= 0 there, and masked
    # BEFORE exp so that the dead triangle's positive sums never overflow
    seg = cum_h[..., :, None] - cum_h[..., None, :]             # (b,c,h,l,s)
    decay = _decay(jnp.where(jnp.tril(jnp.ones((l, l), bool)), seg, -jnp.inf))
    masked = scores * decay * dt_h[..., None, :]
    within = _dot(masked.astype(u.dtype), u, ((4,), (2,)),
                  ((0, 1, 2), (0, 1, 3))).swapaxes(2, 3)        # (b,c,l,h,p)
    carried = _dot(Cc, starts.astype(u.dtype), ((4,), (5,)),
                   ((0, 1, 3), (0, 1, 2))).swapaxes(2, 3)       # (b,c,l,g,h/g,p)
    y = (within + carried.reshape(b, c, l, h, p) * _decay(cum)[..., None]
         + D.astype(jnp.float32)[:, None] * u.astype(jnp.float32))
    return y.astype(u.dtype)


def _some_chunks(state, chunks, D):
    """Some consecutive chunks of a row, given the state before the first:
    (the state after the last, their y). chunks = (Cc, Bc, cum, dt, u), the
    chunks on axis 1."""
    Cc, Bc, cum, dt, u = chunks
    b, c, l, h = cum.shape
    g = Bc.shape[3]
    keep = _decay(cum[:, :, -1, :]).reshape(b, c, g, h // g)
    state, starts = _carry_over_chunks(
        jnp.moveaxis(keep, 1, 0),
        jnp.moveaxis(_chunk_states(Bc, cum, dt, u), 1, 0), state)
    return state, _chunk_outputs(Cc, Bc, cum, dt, u,
                                 jnp.moveaxis(starts, 0, 1), D)


# ------------------------------------------------------------------ kernels
#
# Where the shapes tile (:func:`takes_kernel`) the scan runs as two pallas TPU
# kernels, ``hvd_ssd_scan_fwd`` and ``hvd_ssd_scan_bwd``, over the arrays as
# the mixer holds them: u, y (b, T, H P), B, C (b, T, N), dt and the running
# sums (b, T, H). A grid step is one chunk of one row, the chunk axis is
# sequential and the carried state lives in VMEM as (N, H P): the lanes are
# u's, so the carried state's part of y and the chunk states are products over
# whole blocks of 128 lanes. A block of 128 lanes holds ``128 / P`` heads. The
# blocks are ONE traced loop of ``_GROUP`` blocks a step; the blocks of a step
# and the heads of a block are unrolled. A head's scores, decay and masked
# scores are (128 x 128) tiles in VMEM, those wholly above the diagonal never
# made; nothing of that size, and nothing tiled (chunk, head, P), reaches HBM.
#
# dt and the sums come in twice, as they lie (a head's numbers down the
# sublanes of one lane: picked out by a masked lane sum) and transposed by XLA
# outside ((b, H, T), 1/32 of u's bytes: a head's row, broadcast down the
# sublanes); the backward returns its gradients in both forms for the same
# reason, added outside. The running sums themselves, and so A's and part of
# dt's gradient, stay ``jax.numpy``'s, differentiated by JAX.

_VMEM_LIMIT_BYTES = 100 * 1024 * 1024
_LANES = 128
_TILE = 128             # rows and columns of a tile of a head's scores
_GROUP = 2              # lane blocks a step of the kernels' one loop holds
_FOLD = 8               # a register's sublanes: rows D's gradient is summed
                        # into, the alignment of a load at a traced row

_NN = (((1,), (0,)), ((), ()))      # a @ b
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


def takes_kernel(u, dt, B, C, chunk: int) -> bool:
    """Whether ``ssd``'s operands at this chunk are shapes the kernels tile:
    u, B, C of one dtype, bf16 or f32; one group; a head a whole share of a
    block of 128 lanes and the heads a whole number of blocks; the state and
    the chunk multiples of 128; the row a whole number of chunks."""
    (b, t, h, p), (g, n) = u.shape, B.shape[2:]
    return (u.dtype in (jnp.bfloat16, jnp.float32)
            and B.dtype == u.dtype and C.dtype == u.dtype
            and g == 1 and _LANES % p == 0 and (h * p) % _LANES == 0
            and n % _LANES == 0 and chunk % _LANES == 0 and t % chunk == 0)


def _mm(a, b, form):
    return _dot(a, b, *form)


def _lane_sum(x):
    return jnp.sum(x, axis=1, keepdims=True)


def _row(ref, head, t):
    """Tile ``t`` of row ``head`` of a transposed array (h, l), (1, tile): out
    of the 8 rows that hold it (a load of one row at a traced offset must be
    of the whole row)."""
    rows = ref[pl.ds(pl.multiple_of(lax.div(head, _FOLD) * _FOLD, _FOLD),
                     _FOLD), _tile(t)]
    mine = (lax.broadcasted_iota(jnp.int32, rows.shape, 0)
            == lax.rem(head, _FOLD))
    return jnp.sum(jnp.where(mine, rows, 0.0), axis=0, keepdims=True)


def _is_head(head, rows, heads):
    """The lane of an (rows, heads) array that is ``head``'s. (Made at the
    size it is used at: Mosaic holds a mask that no row changes as one row,
    and cannot cut rows out of it.)"""
    return lax.broadcasted_iota(jnp.int32, (rows, heads), 1) == head


def _lanes_of(k, p, rows):
    """The lanes of a block of 128 that are its ``k``-th head's."""
    lane = lax.broadcasted_iota(jnp.int32, (rows, _LANES), 1)
    return jnp.logical_and(lane >= k * p, lane < (k + 1) * p)


class _Head(NamedTuple):
    """What one head reads of the per-head arrays in a chunk."""
    number: Any         # traced: which head of the layer
    cum_c: list         # its column of cum, a strip of rows each (tile, 1)
    dt_c: list          # its column of dt, likewise
    cum_r: list         # its row of cum, a tile of columns each (1, tile)
    dt_r: list          # its row of dt, likewise
    last: Any           # the chunk's last sum (1, 1)


def _heads_of_block(j, per_block, refs):
    """The heads of lane block ``j``. refs = (cum, dt (l, h), their transposes
    (h, l))."""
    cum_ref, dt_ref, cum_t_ref, dt_t_ref = refs
    l, h = cum_ref.shape
    tiles = range(l // _TILE)
    heads = []
    for k in range(per_block):
        head = j * per_block + k

        def column(ref, rows):
            x = ref[rows, :]
            return _lane_sum(jnp.where(_is_head(head, x.shape[0], h), x, 0.0))

        heads.append(_Head(
            head,
            [column(cum_ref, _tile(i)) for i in tiles],
            [column(dt_ref, _tile(i)) for i in tiles],
            [_row(cum_t_ref, head, t) for t in tiles],
            [_row(dt_t_ref, head, t) for t in tiles],
            column(cum_ref, slice(l - 1, l))))
    return heads


def _on_lanes(heads, p):
    """``cum_c`` (l, 128), ``dt_c`` (l, 128) and ``last`` (1, 128) of a lane
    block's heads, each head's on the lanes of u that are its."""
    def placed(pieces):
        out = None
        for k, x in enumerate(pieces):
            out = (jnp.broadcast_to(x, (x.shape[0], _LANES)) if out is None
                   else jnp.where(_lanes_of(k, p, x.shape[0]), x, out))
        return out

    def columns(strips_of_heads):
        return jnp.concatenate([placed(strip)
                                for strip in zip(*strips_of_heads)], axis=0)

    return (columns([head.cum_c for head in heads]),
            columns([head.dt_c for head in heads]),
            placed([head.last for head in heads]))


def _live_tiles(l):
    """The (row strip, column tile) pairs of the (l x l) scores' lower
    triangle, a row strip's tiles together: above them every decay is 0."""
    return [(i, list(range(i + 1))) for i in range(l // _TILE)]


def _tile(i):
    return slice(i * _TILE, (i + 1) * _TILE)


def _decay_tile(cum_c, cum_r, i, j):
    """``exp(cum_l - cum_s)`` of a tile: on the diagonal the argument is
    masked BEFORE exp (0 above it), below it every s <= l."""
    seg = cum_c[i] - cum_r[j]
    if i == j:
        below = (lax.broadcasted_iota(jnp.int32, seg.shape, 0)
                 >= lax.broadcasted_iota(jnp.int32, seg.shape, 1))
        seg = jnp.where(below, seg, -jnp.inf)
    return jnp.exp(seg)


def _for_lane_blocks(blocks, group):
    """``group(the lane offsets of _GROUP blocks, their first's number)`` for
    every such run of a chunk's lane blocks: ONE traced loop, its body the
    blocks of a group unrolled. The blocks of a group are independent chains
    of some hundred cycles each, which the scheduler interleaves; a loop over
    single blocks is bound by a chain's length, not by any unit's work. At
    Granite's shape (PERF.md §6, PR 40; ms a call, forward / backward): one
    block a step 1.14 / 2.28, two 0.92 / 2.06, four 0.82 / 2.00, and four cost
    the cell 11 s of tracing and lowering where two cost 5."""
    width = _GROUP if blocks % _GROUP == 0 else 1

    def body(g, carry):
        group([pl.ds(pl.multiple_of((g * width + w) * _LANES, _LANES), _LANES)
               for w in range(width)], g * width)
        return carry

    lax.fori_loop(0, blocks // width, body, None)


def _plus(a, b):
    return b if a is None else a + b


def _scan_fwd_kernel(u_ref, dt_ref, cum_ref, dt_t_ref, cum_t_ref, b_ref,
                     c_ref, d_ref, y_ref, *rest, p, save):
    starts_ref, state_ref, scores_ref = rest if save else (None,) + rest
    l, dtype, f32 = u_ref.shape[0], u_ref.dtype, jnp.float32
    per_block = _LANES // p

    @pl.when(pl.program_id(1) == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    if save:
        starts_ref[...] = state_ref[...]
    Bm, Cm = b_ref[...], c_ref[...]
    scores_ref[...] = _mm(Cm, Bm, _NT)                          # (l, s)
    refs = (cum_ref, dt_ref, cum_t_ref, dt_t_ref)

    def block(j, cols, start):
        """Writes y of lane block ``j``; returns the state after the chunk."""
        ub = u_ref[:, cols]
        uf = ub.astype(f32)
        heads = _heads_of_block(j, per_block, refs)
        cum, dt, last = _on_lanes(heads, p)
        carried = _mm(Cm, start.astype(dtype), _NN) * jnp.exp(cum)
        for i, tiles in _live_tiles(l):
            within = None
            for k, head in enumerate(heads):
                # all lanes of the product; the head's own are kept
                out = None
                for t in tiles:
                    out = _plus(out, _mm(
                        (scores_ref[_tile(i), _tile(t)]
                         * _decay_tile(head.cum_c, head.cum_r, i, t)
                         * head.dt_r[t]).astype(dtype), ub[_tile(t)], _NN))
                within = (out if within is None else
                          jnp.where(_lanes_of(k, p, _TILE), out, within))
            y_ref[_tile(i), cols] = (
                within + carried[_tile(i)]
                + d_ref[:, cols] * uf[_tile(i)]).astype(dtype)
        to_end = jnp.exp(last - cum) * dt
        return jnp.exp(last) * start + _mm(Bm, (uf * to_end).astype(dtype),
                                           _TN)

    def group(lanes, first):
        # every load of the state before any store to it: the offsets are
        # traced, and a load after a store would wait for it
        starts = [state_ref[:, cols] for cols in lanes]
        ends = [block(first + w, cols, start)
                for w, (cols, start) in enumerate(zip(lanes, starts))]
        for cols, end in zip(lanes, ends):
            state_ref[:, cols] = end

    _for_lane_blocks(u_ref.shape[1] // _LANES, group)


def _scan_bwd_kernel(u_ref, dt_ref, cum_ref, dt_t_ref, cum_t_ref, b_ref,
                     c_ref, d_ref, starts_ref, dy_ref,
                     du_ref, db_ref, dc_ref, ddt_ref, dcum_ref, ddt_t_ref,
                     dcum_t_ref, dd_ref, dstate_ref, scores_ref, dscores_ref,
                     dbc_ref, dkeep_ref, *, p):
    (l, h), dtype, f32 = dt_ref.shape, u_ref.dtype, jnp.float32
    per_block, strips = _LANES // p, range(l // _TILE)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        dstate_ref[...] = jnp.zeros_like(dstate_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    Bm, Cm = b_ref[...], c_ref[...]
    scores_ref[...] = _mm(Cm, Bm, _NT)
    for ref in (dscores_ref, dbc_ref, ddt_ref, dcum_ref, dkeep_ref):
        ref[...] = jnp.zeros_like(ref)
    refs = (cum_ref, dt_ref, cum_t_ref, dt_t_ref)

    def block(j, cols, dend, sums):
        """Writes du of lane block ``j`` and its heads' rows of ddt_t; returns
        d of the state before the chunk, D's gradient by lane, and ``sums``
        with the block's part of what a chunk sums over its heads."""
        dscores, dbc, dw, dsum, dkeep = sums
        ub, dyb = u_ref[:, cols], dy_ref[:, cols]
        uf, dyf = ub.astype(f32), dyb.astype(f32)
        start = starts_ref[:, cols]
        start_c, dend_c = start.astype(dtype), dend.astype(dtype)
        heads = _heads_of_block(j, per_block, refs)
        cum, dt, last = _on_lanes(heads, p)
        from_start = jnp.exp(cum)
        to_end = jnp.exp(last - cum) * dt
        dweighted = _mm(Bm, dend_c, _NN)            # d (u to_end dt)
        # by lane, a head's lanes to be summed: d to_end; d cum_l through the
        # carried state's decay; d exp(cum_last) of the state's own decay
        dw_lanes = dweighted * uf
        dfrom_lanes = dyf * _mm(Cm, start_c, _NN) * from_start
        dkeep_lanes = jnp.sum(dend * start, axis=0, keepdims=True)
        du = [None for _ in strips]
        for k, head in enumerate(heads):
            cum_c, cum_r, dt_r = head.cum_c, head.cum_r, head.dt_r
            dy_k = jnp.where(_lanes_of(k, p, l), dyb, jnp.zeros_like(dyb))
            down = [None for _ in strips]
            for i, tiles in _live_tiles(l):
                across = None
                for t in tiles:
                    decay = _decay_tile(cum_c, cum_r, i, t)
                    kept = scores_ref[_tile(i), _tile(t)] * decay
                    dmasked = _mm(dy_k[_tile(i)], ub[_tile(t)], _NT)
                    du[t] = _plus(du[t], _mm((kept * dt_r[t]).astype(dtype),
                                             dy_k[_tile(i)], _TN))
                    dscores[i, t] = _plus(dscores.get((i, t)),
                                          dmasked * decay * dt_r[t])
                    dseg = dmasked * kept           # d (cum_l - cum_s) / dt_s
                    down[t] = _plus(down[t],
                                    jnp.sum(dseg, axis=0, keepdims=True))
                    across = _plus(across, dseg * dt_r[t])
                mine = _is_head(head.number, _TILE, h)
                lanes = _lanes_of(k, p, _TILE)
                dsum[i] = _plus(dsum[i], jnp.where(
                    mine, _lane_sum(across) + _lane_sum(jnp.where(
                        lanes, dfrom_lanes[_tile(i)], 0.0)), 0.0))
                dw[i] = _plus(dw[i], jnp.where(mine, _lane_sum(jnp.where(
                    lanes, dw_lanes[_tile(i)], 0.0)), 0.0))
            ddt_t_ref[pl.ds(head.number, 1), :] = jnp.concatenate(down,
                                                                  axis=1)
            dkeep = _plus(dkeep, jnp.where(
                _is_head(head.number, 1, h), _lane_sum(jnp.where(
                    _lanes_of(k, p, 1), dkeep_lanes, 0.0)), 0.0))
        du_ref[:, cols] = (jnp.concatenate(du, axis=0) + d_ref[:, cols] * dyf
                           + dweighted * to_end).astype(dtype)
        dyu = dyf * uf
        dcarried = (dyf * from_start).astype(dtype)
        dbc = (_plus(dbc[0], _mm((uf * to_end).astype(dtype), dend_c, _NT)),
               _plus(dbc[1], _mm(dcarried, start_c, _NT)))
        return (jnp.exp(last) * dend + _mm(Cm, dcarried, _TN),
                functools.reduce(jnp.add, (dyu[r:r + _FOLD]
                                           for r in range(0, l, _FOLD))),
                (dscores, dbc, dw, dsum, dkeep))

    def group(lanes, first):
        # loads of what the group also stores first, the stores last (the
        # forward says why); what a chunk sums over its heads is summed over
        # the group's in values and added to the chunk's once a group
        dends = [dstate_ref[:, cols] for cols in lanes]
        sums = ({}, (None, None), [None for _ in strips],
                [None for _ in strips], None)
        ends = []
        for w, (cols, dend) in enumerate(zip(lanes, dends)):
            *end, sums = block(first + w, cols, dend, sums)
            ends.append(end)
        for cols, (dstart, dd) in zip(lanes, ends):
            dstate_ref[:, cols] = dstart
            dd_ref[:, cols] += dd
        dscores, dbc, dw, dsum, dkeep = sums
        for (i, t), tile in dscores.items():
            dscores_ref[_tile(i), _tile(t)] += tile
        dbc_ref[0] += dbc[0]
        dbc_ref[1] += dbc[1]
        for i in strips:
            ddt_ref[_tile(i), :] += dw[i]
            dcum_ref[_tile(i), :] += dsum[i]
        dkeep_ref[...] += dkeep

    _for_lane_blocks(u_ref.shape[1] // _LANES, group)
    # what the heads left by lane, in all heads' arithmetic at once: ddt holds
    # d to_end, dcum the sums over s and p, ddt_t the sums over l
    cum, dt, last = cum_ref[...], dt_ref[...], cum_ref[l - 1:, :]
    to_end = jnp.exp(last - cum)
    ddt = ddt_ref[...] * to_end
    dlast = (jnp.sum(ddt * dt, axis=0, keepdims=True)
             + dkeep_ref[...] * jnp.exp(last))
    at_last = lax.broadcasted_iota(jnp.int32, cum.shape, 0) == l - 1
    ddt_ref[...] = ddt
    dcum_ref[...] = (dcum_ref[...] - ddt * dt
                     + jnp.where(at_last, dlast, 0.0))
    dcum_t_ref[...] = -ddt_t_ref[...] * dt_t_ref[...]
    dscores = dscores_ref[...].astype(dtype)
    db_ref[...] = (dbc_ref[0] + _mm(dscores, Cm, _TN)).astype(dtype)
    dc_ref[...] = (dbc_ref[1] + _mm(dscores, Bm, _NN)).astype(dtype)


def _scan_specs(u, dt, chunk, backwards):
    """The grid (rows, chunks) and the ``BlockSpec``s of a chunk of: an array
    with T on its second axis, one with T on its last, the chunk's own block
    of an array with a chunk axis, and one block for the whole grid. The
    backward walks a row's chunks from its last."""
    (b, t, _), c = u.shape, u.shape[1] // chunk

    def at(i):
        return c - 1 - i if backwards else i

    return (b, c), dict(
        rows=lambda width: pl.BlockSpec((None, chunk, width),
                                        lambda n, i: (n, at(i), 0)),
        lanes=pl.BlockSpec((None, -(-dt.shape[2] // _FOLD) * _FOLD, chunk),
                           lambda n, i: (n, 0, at(i))),
        chunk=lambda *shape: pl.BlockSpec((None, None) + shape,
                                          lambda n, i: (n, at(i), 0, 0)),
        whole=lambda *shape: pl.BlockSpec(shape, lambda n, i: (0, 0)),
        row=lambda *shape: pl.BlockSpec((None,) + shape,
                                        lambda n, i: (n, 0, 0)))


def _scan_cost(u, n, chunk, passes):
    (b, t, hp), size = u.shape, u.dtype.itemsize
    products = 2 * b * t * hp * (chunk + 2 * n) + 2 * b * t * chunk * n
    return pl.CostEstimate(
        flops=passes * products, transcendentals=passes * b * t * chunk
        * (hp // _LANES), bytes_accessed=passes * 2 * b * t * hp * size)


# The calls are jitted so that a model's layers and the recomputed forward
# share ONE traced and lowered copy of each kernel (ops/flash_attention.py).
@functools.partial(jax.jit, static_argnums=(8, 9, 10))
def _scan_fwd_call(u, dt, cum, dt_t, cum_t, B, C, d_lanes, chunk, save,
                   interpret):
    (b, t, hp), h, n, f32 = u.shape, dt.shape[2], B.shape[2], jnp.float32
    grid, spec = _scan_specs(u, dt, chunk, False)
    rows, lanes = spec["rows"], spec["lanes"]
    out = pl.pallas_call(
        functools.partial(_scan_fwd_kernel, p=hp // h, save=save),
        grid=grid,
        in_specs=[rows(hp), rows(h), rows(h), lanes, lanes, rows(n), rows(n),
                  spec["whole"](1, hp)],
        out_specs=[rows(hp)] + [spec["chunk"](n, hp)] * save,
        out_shape=[jax.ShapeDtypeStruct(u.shape, u.dtype)]
        + [jax.ShapeDtypeStruct((b, t // chunk, n, hp), f32)] * save,
        scratch_shapes=[pltpu.VMEM((n, hp), f32),
                        pltpu.VMEM((chunk, chunk), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        cost_estimate=_scan_cost(u, n, chunk, 1),
        interpret=interpret,
        name=device_names.SSD_SCAN + "_fwd",
    )(u, dt, cum, dt_t, cum_t, B, C, d_lanes)
    return tuple(out)


@functools.partial(jax.jit, static_argnums=(10, 11))
def _scan_bwd_call(u, dt, cum, dt_t, cum_t, B, C, d_lanes, starts, dy, chunk,
                   interpret):
    (b, t, hp), h, n, f32 = u.shape, dt.shape[2], B.shape[2], jnp.float32
    grid, spec = _scan_specs(u, dt, chunk, True)
    rows, lanes = spec["rows"], spec["lanes"]

    def like(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype)

    return pl.pallas_call(
        functools.partial(_scan_bwd_kernel, p=hp // h),
        grid=grid,
        in_specs=[rows(hp), rows(h), rows(h), lanes, lanes, rows(n), rows(n),
                  spec["whole"](1, hp), spec["chunk"](n, hp), rows(hp)],
        out_specs=[rows(hp), rows(n), rows(n), rows(h), rows(h), lanes, lanes,
                   spec["row"](_FOLD, hp)],
        out_shape=[like(u), like(B), like(C), like(dt), like(cum),
                   like(dt_t), like(cum_t),
                   jax.ShapeDtypeStruct((b, _FOLD, hp), f32)],
        scratch_shapes=[pltpu.VMEM((n, hp), f32),
                        pltpu.VMEM((chunk, chunk), f32),
                        pltpu.VMEM((chunk, chunk), f32),
                        pltpu.VMEM((2, chunk, n), f32),
                        pltpu.VMEM((1, h), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        cost_estimate=_scan_cost(u, n, chunk, 2),
        interpret=interpret,
        name=device_names.SSD_SCAN + "_bwd",
    )(u, dt, cum, dt_t, cum_t, B, C, d_lanes, starts, dy)


def _scan_operands(u, dt, cum, B, C, D):
    """The kernels' operands: beside u, dt, the sums, B and C as they lie, dt
    and the sums transposed (the heads a whole number of 8 sublanes) and D on
    its heads' lanes."""
    h = dt.shape[2]

    def transposed(x):
        return jnp.pad(x.swapaxes(1, 2), ((0, 0), (0, -h % _FOLD), (0, 0)))

    return (u, dt, cum, transposed(dt), transposed(cum), B, C,
            jnp.repeat(D.astype(jnp.float32), u.shape[2] // h)[None])


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _scan(u, dt, cum, B, C, D, chunk, interpret):
    return _scan_fwd_call(*_scan_operands(u, dt, cum, B, C, D), chunk, False,
                          interpret)[0]


def _scan_forward(u, dt, cum, B, C, D, chunk, interpret):
    with jax.named_scope(device_names.SSD_SCAN):
        operands = _scan_operands(u, dt, cum, B, C, D)
        y, starts = _scan_fwd_call(*operands, chunk, True, interpret)
    return y, (operands, starts, D)


def _scan_backward(chunk, interpret, res, dy):
    operands, starts, D = res
    (hp, h), dtype = (x.shape[2] for x in operands[:2]), operands[0].dtype
    with jax.named_scope(device_names.SSD_SCAN):
        du, dB, dC, ddt, dcum, ddt_t, dcum_t, dd = _scan_bwd_call(
            *operands, starts, dy.astype(dtype), chunk, interpret)
        return (du, ddt + ddt_t[:, :h].swapaxes(1, 2),
                dcum + dcum_t[:, :h].swapaxes(1, 2), dB, dC,
                dd.reshape(-1, h, hp // h).sum((0, 2)).astype(D.dtype))


_scan.defvjp(_scan_forward, _scan_backward)


def ssd(u, dt, A, B, C, D, chunk: int, interpret: bool = False):
    """The state-space layer above on whole rows.

    u: (b, T, H, P) inputs; dt: (b, T, H) step sizes, already positive
    (softplus applied); A: (H,) negative; B, C: (b, T, G, N) with ``H % G ==
    0``; D: (H,). Returns y: (b, T, H, P) in u's dtype. A row that is not a
    whole number of chunks is padded at its end with ``dt = 0`` positions,
    which leave the state as it is and are cut off again; a row shorter than
    ``chunk`` is one chunk of its own length.

    Shapes :func:`takes_kernel` accepts run the kernels (``interpret=True``:
    in the Pallas interpreter, asked for by the caller and never inferred
    from the platform; a machine without a TPU raises at lowering without
    it); every other shape runs the ``jax.numpy`` scan."""
    from ..metrics import record_ssd_plan

    b, t, h, p = u.shape
    g, n = B.shape[2], B.shape[3]
    if h % g:
        raise ValueError(f"{h} heads do not divide into {g} groups")
    if chunk <= 0:
        raise ValueError(f"ssd chunk must be positive, got {chunk}")
    chunk = min(chunk, t)
    kernel = takes_kernel(u, dt, B, C, chunk)
    record_ssd_plan(chunk, kernel)
    f32 = jnp.float32
    if kernel:
        dt = dt.astype(f32)
        cum = jnp.cumsum((dt * A.astype(f32)).reshape(b, t // chunk, chunk, h),
                         axis=2).reshape(b, t, h)
        # u, B and C as the mixer holds them: the views it made are undone
        with jax.named_scope(device_names.SSD_SCAN):
            y = _scan(u.reshape(b, t, h * p), dt, cum, B.reshape(b, t, n),
                      C.reshape(b, t, n), D, chunk, interpret)
        return y.reshape(b, t, h, p)
    pad = -t % chunk
    if pad:
        u, dt, B, C = (jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
                       for x in (u, dt, B, C))
    c = (t + pad) // chunk
    dt = dt.astype(f32).reshape(b, c, chunk, h)
    uc = u.reshape(b, c, chunk, h, p)
    Bc, Cc = (x.reshape(b, c, chunk, g, n) for x in (B, C))
    cum = jnp.cumsum(dt * A.astype(f32), axis=2)            # (b,c,l,h), <= 0

    # CHUNK_BLOCK chunks at a time, the state carried from block to block:
    # ONE device op (a ``while``) that carries the scope. The ops of its body
    # are events of the device trace too, and their names end in
    # ``while/body/...`` with the scope further up, where a label that keeps
    # an ``op_name``'s last segments does not see it: a reader that sums the
    # labels holding ``hvd_ssd`` counts the scan once.
    chunks = (Cc, Bc, cum, dt, uc)
    start = jnp.zeros((b, g, h // g, p, n), f32)
    with jax.named_scope(device_names.SSD_SCAN):
        if c <= CHUNK_BLOCK or c % CHUNK_BLOCK:
            _, y = _some_chunks(start, chunks, D)
        else:
            def blocked(x):         # (b, c, ..) -> (c / CB, b, CB, ..)
                return jnp.moveaxis(x.reshape(
                    (b, c // CHUNK_BLOCK, CHUNK_BLOCK) + x.shape[2:]), 1, 0)

            _, y = lax.scan(
                jax.checkpoint(lambda state, xs: _some_chunks(state, xs, D)),
                start, tuple(map(blocked, chunks)))
            y = jnp.moveaxis(y, 0, 1).reshape(b, c, chunk, h, p)
    return y.reshape(b, c * chunk, h, p)[:, :t]
