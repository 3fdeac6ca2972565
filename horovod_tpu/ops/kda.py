"""Kimi Delta Attention's recurrence in its chunked form: a gated delta rule
whose decay is one number a CHANNEL (Kimi Linear, arXiv:2510.26692, the
section that defines KDA and its chunkwise form; docs/linear-attention.md).

Per head with state ``S`` (key x value, ``S_0 = 0``), ``alpha_t = exp(g_t)``
a vector of the key's width, ``beta_t`` one number::

    S'_t = Diag(alpha_t) S_{t-1}
    S_t  = S'_t + beta_t k_t (v_t - S'_t^T k_t)^T
    o_t  = S_t^T q_t

:func:`kda_recurrence` is that, a step a token: the DEFINITION, and what the
tests hold everything else against. :func:`kda` computes the same ``o``
without a step per token. The row is cut into chunks of ``chunk`` positions;
with ``G_r`` the running sum of ``g`` inside a chunk (inclusive), ``K+ = K
exp(G)``, ``K- = K exp(-G)``, ``Q+ = Q exp(G)`` row by row and channel by
channel, and ``S`` the state the chunk starts from::

    A = (I + strict_lower(diag(beta) K+ K-^T))^-1 diag(beta)
    W = A K+;  U = A V;  D = U - W S
    O = Q+ S + tril(Q+ K-^T) D
    S_next = Diag(exp(G_C)) S + (K exp(G_C - G))^T D

**How the exponents are bounded.** ``exp(-G)`` overflows float32 inside one
chunk (``g`` reaches -16 x softplus a token), and the pairwise decay of two
positions is a vector, so it cannot be a mask on a score tile as
``ops/ssd.py``'s is. ``K-`` is therefore never formed. It occurs only in the
two (chunk x chunk) matrices ``K+ K-^T`` and ``Q+ K-^T``, whose entry (r, s)
is ``sum_d a_rd k_sd exp(G_rd - G_sd)`` with r >= s. A chunk is cut into
sub-blocks of ``SUB`` positions. A tile ON the diagonal is computed entry by
entry, the exponent ``G_r - G_s`` masked to the causal half BEFORE ``exp``
(a fused multiply-reduce over the channels: nothing sized sub x sub x
channels is kept). A tile BELOW the diagonal takes the start of its row
block as the reference point: ``a_r exp(G_r - G_ref)`` times ``k_s exp(G_ref -
G_s)``, both exponents sums of ``g`` over positions that lie between, so <= 0.
Every other ``exp`` of the layer (``G``, ``G_C - G``, ``G_C``) has an
argument <= 0 by construction: a decay underflows to 0, which is its value,
and nothing overflows whatever ``g`` is.

The unit-triangular system is solved as a product: with ``N`` strictly lower
triangular, ``(I + N)^-1 = (I - N)(I + N^2)(I + N^4)...``, ``log2(chunk)``
squarings, all matrix products on float32 operands (six bf16 passes a
product where the activations are float32, three where they are bf16).

A row of many chunks is one ``lax.scan`` over blocks of ``CHUNK_BLOCK``
chunks whose carry is the state (float32): a block computes everything that
does not need the state for all its chunks at once, then steps through them.
Forward and backward are one ``jax.custom_vjp``: the forward of a
differentiated call saves the state each BLOCK starts from (row / (chunk x
CHUNK_BLOCK) states of heads x key x value float32: 128 MiB a layer at 16,384
tokens, 32 heads of 128; the gauge ``horovod_kda_saved_state_bytes_per_layer``),
and the backward walks the blocks in reverse, recomputing a block from its
saved state and handing the state's cotangent on: chunked like the forward,
never a step per token. Gradients reach q, k, v, g and beta.

Numerics: ``g``, its running sums, every decay, the carried state, the
solve's result and the diagonal tiles are float32 whatever the activations'
dtype. The products with a chunk-long or key-long contraction take their
operands in ``q``'s dtype (bf16 as trained: ``A``, the scores and the carried
state are rounded to it for the product, as the published kernels do) and
accumulate in float32; they follow ``jax.default_matmul_precision`` as a plain
``@`` does.

Where the shapes tile (:func:`takes_kernel`: q, k, v of one dtype, bf16 or
float32; one block of 128 lanes a head for keys and for values; chunks of
``CHUNK`` in sub-blocks of ``SUB``; blocks of 2 or 4 chunks) all of the above
runs as two pallas TPU kernels, ``hvd_kda_scan_fwd`` and ``hvd_kda_scan_bwd``
(a ``jax.custom_vjp``), under the same scope ``hvd_kda_scan`` and with the
same numerics: the sums of ``g``, the tiles, the solve, ``W``, ``U`` and the
recurrence of a block never leave VMEM, the carried state is VMEM scratch
along a sequential block axis, the forward of a differentiated call writes
the same block-start states and the backward, derived by hand
(``_block_backward``), recomputes a block from its saved state. The section
"kernels" below says how they tile. Every other shape runs the ``jax.numpy``
scan, which is also the DEFINITION the kernels are tested against
(tests/test_kda_kernels.py) and the CPU path. PERF.md §6, PR 49.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import device_names
from .ssd import _dot

CHUNK = 64          # the released training kernel's chunk
SUB = 16            # positions of a sub-block: diagonal tiles entry by entry
CHUNK_BLOCK = 4     # chunks a step of the scan over the row holds (PERF.md §6,
#                     PR 48: 16 / 8 / 4 / 2 / 1 read 101 / 88 / 69 / 64 / 64 ms a
#                     forward + backward of the cell's row; 1 keeps 512 MiB)
# The solve's products, float32 operands either way: three bf16 passes where
# the activations are bf16 (16 bits of mantissa: more than the operands it is
# applied to carry), six where they are float32. [is float32]
SOLVE_PRECISION = (lax.Precision.HIGH, lax.Precision.HIGHEST)


def kda_recurrence(q, k, v, g, beta):
    """The definition, a step a token, in float32. q, k, g: (B, T, H, K); v:
    (B, T, H, V); beta: (B, T, H). Returns o (B, T, H, V) float32."""
    f32 = jnp.float32
    q, k, v, g, beta = (x.astype(f32) for x in (q, k, v, g, beta))
    b, _, h, dk = k.shape

    def step(state, x):
        qt, kt, vt, gt, bt = x                      # (b, h, .)
        state = jnp.exp(gt)[..., None] * state
        seen = jnp.einsum("bhkv,bhk->bhv", state, kt)
        state = state + (bt[..., None] * kt)[..., None] * (vt - seen)[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, qt)

    with jax.default_matmul_precision("highest"):
        _, o = lax.scan(step, jnp.zeros((b, h, dk, v.shape[-1]), f32),
                        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def _unit_lower_inverse(n, precision=lax.Precision.HIGHEST):
    """``(I + n)^-1`` for strictly lower triangular ``n`` (..., C, C), float32:
    ``(I - n)(I + n^2)(I + n^4)...``, exact once the power passes C."""
    size = n.shape[-1]
    mm = functools.partial(jnp.matmul, precision=precision)
    power = -n
    result = jnp.eye(size, dtype=n.dtype) + power
    for _ in range(max(0, int(np.ceil(np.log2(size))) - 1)):
        power = mm(power, power)
        result = result + mm(result, power)
    return result


def _unit_lower_inverse_by_halves(n, precision=lax.Precision.HIGHEST):
    """``(I + n)^-1`` for strictly lower triangular ``n`` (..., C, C), float32,
    block by block: the inverse of ``[[A, 0], [C, B]]`` is ``[[A^-1, 0],
    [-B^-1 C A^-1, B^-1]]``, from blocks of two rows up to the whole, two
    products a doubling. Every factor is the inverse of a block of ``I + n``
    itself, so where that inverse is bounded nothing on the way to it is
    larger: :func:`_unit_lower_inverse`'s powers of ``n`` grow as
    ``|n|^p binom(C, p)`` where the keys of a chunk are close to each other,
    and its sum of them then cancels to nothing (docs/linear-attention.md,
    "beta in (0, 2)")."""
    size = n.shape[-1]
    mm = functools.partial(jnp.matmul, precision=precision)
    r, s = np.arange(size)[:, None], np.arange(size)
    result = jnp.eye(size, dtype=n.dtype) - jnp.where(r // 2 == s // 2, n, 0.0)
    width = 2
    while width < size:
        across = (r // (2 * width) == s // (2 * width)) & (r // width != s // width)
        result = result - mm(mm(result, jnp.where(across, n, 0.0)), result)
        width *= 2
    return result


@jax.checkpoint
def _diagonal_tiles(qf, kf, gl):
    """The tiles on the diagonal, entry by entry. qf, kf, gl: (b, N, n, c, h,
    d) float32, ``gl`` the running sum of g inside the sub-block. Returns
    (k k^T, q k^T) with the pairwise decay, each (b, N, n, h, c, c), zero
    above the diagonal. Recomputed in a backward pass, never kept."""
    c = gl.shape[3]
    seen = np.tril(np.ones((c, c), bool))[:, :, None, None]      # (r, s, 1, 1)
    gap = gl[:, :, :, :, None] - gl[:, :, :, None, :]            # (.., r, s, h, d)
    decay = jnp.where(seen, jnp.exp(jnp.where(seen, gap, 0.0)), 0.0)
    cols = kf[:, :, :, None, :] * decay

    def against(rows):
        return jnp.moveaxis(jnp.sum(rows[:, :, :, :, None] * cols, axis=-1),
                            -1, 3)

    return against(kf), against(qf)


def _state_free_parts(q, k, v, g, beta, chunk, sub, by_halves=False):
    """What a block of N chunks needs that does not depend on the carried
    state (``by_halves``: which of the two solves). q, k, g: (b, N chunk, h, dk); v: (.., dv); beta: (b, N chunk, h).
    Returns W (b, N, h, C, dk) and U (b, N, h, C, dv) float32, the causal
    scores M (b, N, h, C, C) float32, Q+ and K exp(G_C - G) (b, N, C, h, dk)
    in q's dtype, and the chunk's whole decay (b, N, h, dk) float32."""
    f32, act = jnp.float32, q.dtype
    b, length, h, dk = k.shape
    count, n = length // chunk, chunk // sub
    cut = (b, count, n, sub, h, dk)
    qf, kf = q.astype(f32).reshape(cut), k.astype(f32).reshape(cut)
    gl = jnp.cumsum(g.astype(f32).reshape(cut), axis=3)
    block_sum = gl[:, :, :, -1]                         # (b, N, n, h, d)
    start = jnp.cumsum(block_sum, axis=2) - block_sum   # before each sub-block
    total = start[:, :, -1] + block_sum[:, :, -1]       # (b, N, h, d)
    in_chunk = start[:, :, :, None] + gl                # G, (b, N, n, c, h, d)

    kk_diag, qk_diag = _diagonal_tiles(qf, kf, gl)
    since_start = jnp.exp(gl)
    # k's and q's rows of a sub-block one operand: (b, N, n, 2 c, h, d)
    rows = jnp.concatenate([(kf * since_start).astype(act),
                            (qf * since_start).astype(act)], axis=3)
    # Below the diagonal the reference point is the start of the ROW block:
    # every column before it decays up to there, every row from there on.
    # cols[i] holds, for row block i, the chunk's columns s < i c as k_s
    # exp(start_i - G_s), zeros from the diagonal tile on: ONE product a
    # chunk and head gives every tile below the diagonal of both matrices.
    before = (np.arange(n)[:, None] > np.arange(n))[:, :, None, None, None]
    gap = start[:, :, :, None, None] - in_chunk[:, :, None]  # (b,N,i,j,c,h,d)
    cols = jnp.where(before, kf[:, :, None] * jnp.exp(
        jnp.where(before, gap, 0.0)), 0.0).astype(act).reshape(
            b, count, n, chunk, h, dk)
    below = _dot(rows, cols, ((5,), (5,)), ((0, 1, 2, 4), (0, 1, 2, 4)))
    # the diagonal tiles into their places: (b, N, i, h, c, j, c') with i = j
    eye = np.eye(n, dtype=np.float32)[:, None, None, :, None]

    def placed(diag, rows_of):
        tiles = (diag[:, :, :, :, :, None, :] * eye).reshape(
            b, count, n, h, sub, chunk) + rows_of
        return jnp.moveaxis(tiles, 3, 2).reshape(b, count, h, chunk, chunk)

    kk = placed(kk_diag, below[..., :sub, :])           # (b, N, h, C, C)
    scores = placed(qk_diag, below[..., sub:, :])       # causal, diagonal in

    beta = jnp.moveaxis(beta.astype(f32).reshape(b, count, chunk, h), 2, 3)
    strict = np.tril(np.ones((chunk, chunk), bool), -1)
    solve = _unit_lower_inverse_by_halves if by_halves else _unit_lower_inverse
    solved = solve(jnp.where(strict, kk * beta[..., :, None], 0.0),
                   SOLVE_PRECISION[act == f32]) * beta[..., None, :]
    whole = (b, count, chunk, h, dk)
    k_plus = (kf * jnp.exp(in_chunk)).reshape(whole).astype(act)
    a = solved.astype(act)
    w = _dot(a, k_plus, ((4,), (2,)), ((0, 1, 2), (0, 1, 3)))
    u = _dot(a, v.reshape(b, count, chunk, h, v.shape[-1]), ((4,), (2,)),
             ((0, 1, 2), (0, 1, 3)))
    q_plus = (qf * jnp.exp(in_chunk)).reshape(whole).astype(act)
    k_end = (kf * jnp.exp(total[:, :, None, None] - in_chunk)
             ).reshape(whole).astype(act)
    return w, u, scores, q_plus, k_end, jnp.exp(total)


def _block(q, k, v, g, beta, state, chunk, sub, by_halves=False):
    """A block of chunks from the state it starts from (b, h, dk, dv)
    float32: (o (b, N chunk, h, dv) float32, the state after it)."""
    act = q.dtype
    b, length, h, _ = k.shape

    def step(s, x):
        w, u, scores, q_plus, k_end, decay = x
        sa = s.astype(act)
        d = u - _dot(w.astype(act), sa, ((3,), (2,)), ((0, 1), (0, 1)))
        da = d.astype(act)
        o = (_dot(q_plus, sa, ((3,), (2,)), ((0, 2), (0, 1)))
             + _dot(scores.astype(act), da, ((3,), (2,)), ((0, 1), (0, 1))))
        s = decay[..., None] * s + _dot(k_end, da, ((1,), (2,)),
                                        ((0, 2), (0, 1)))
        return s, o

    parts = _state_free_parts(q, k, v, g, beta, chunk, sub, by_halves)
    state, o = lax.scan(step, state,
                        tuple(jnp.moveaxis(x, 1, 0) for x in parts))
    # (N, b, h, C, dv) -> (b, N C, h, dv)
    return o.transpose(1, 0, 3, 2, 4).reshape(b, length, h, -1), state


def _blocks(x, block_len):
    """(b, T, ...) -> (T / block_len, b, block_len, ...)."""
    b, t = x.shape[:2]
    return jnp.moveaxis(x.reshape(b, t // block_len, block_len, *x.shape[2:]),
                        1, 0)


def _row(x):
    """The inverse of :func:`_blocks`."""
    x = jnp.moveaxis(x, 0, 1)
    return x.reshape(x.shape[0], x.shape[1] * x.shape[2], *x.shape[3:])


def _scan_blocks(q, k, v, g, beta, chunk, sub, block_len, by_halves):
    """(o in v's dtype, the state each block starts from)."""
    b, _, h, dk = k.shape

    def step(state, x):
        o, after = _block(*x, state, chunk, sub, by_halves)
        return after, (o.astype(v.dtype), state)

    with jax.named_scope(device_names.KDA_SCAN):
        _, (o, starts) = lax.scan(
            step, jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32),
            tuple(_blocks(x, block_len) for x in (q, k, v, g, beta)))
    return _row(o), starts


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _kda(q, k, v, g, beta, chunk, sub, block_len, by_halves):
    return _scan_blocks(q, k, v, g, beta, chunk, sub, block_len, by_halves)[0]


def _kda_forward(q, k, v, g, beta, chunk, sub, block_len, by_halves):
    o, starts = _scan_blocks(q, k, v, g, beta, chunk, sub, block_len,
                             by_halves)
    return o, (q, k, v, g, beta, starts)


def _kda_backward(chunk, sub, block_len, by_halves, res, do):
    *operands, starts = res

    def step(d_state, x):
        *block, state, d_o = x
        _, vjp = jax.vjp(
            functools.partial(_block, chunk=chunk, sub=sub,
                              by_halves=by_halves), *block, state)
        *grads, d_state = vjp((d_o.astype(jnp.float32), d_state))
        return d_state, tuple(grads)

    with jax.named_scope(device_names.KDA_SCAN):
        _, grads = lax.scan(
            step, jnp.zeros(starts.shape[1:], jnp.float32),
            (*(_blocks(x, block_len) for x in operands), starts,
             _blocks(do, block_len)), reverse=True)
    return tuple(_row(dx).astype(x.dtype) for dx, x in zip(grads, operands))


_kda.defvjp(_kda_forward, _kda_backward)


# ------------------------------------------------------------------ kernels
#
# Where the shapes tile (:func:`takes_kernel`) the scan runs as two pallas TPU
# kernels, ``hvd_kda_scan_fwd`` and ``hvd_kda_scan_bwd``, on q, k, v, g viewed
# (B, T, H x 128) as the mixer holds them and beta (B, T, H). A grid step is
# one BLOCK of chunks (``plan``'s: 4, or 2) of one row and ``_HEADS`` heads;
# the block axis is sequential and the carried state of every head lives in
# VMEM, transposed (value x key) so that a chunk's decay scales its lanes. The
# heads of a step are one traced loop of ``_GROUP`` heads a step, whose
# blocks' rows stand below each other in every array: the sums of g are
# products with a matrix of 0s and 1s, the diagonal tiles of ALL sub-blocks
# are computed at once (sub-blocks x 8 x 128, column by column), and TWO
# chunks stand side by side in every (row x column) matrix, which is then a
# whole (128 x 128) tile with zeros where a row and a column are of different
# chunks: the solve, the products with ``A`` and with the scores are on the
# pair. The long chains (the solve's six products, the recurrence's two a
# chunk) are emitted in step over the group's pairs and heads: emitted a
# chain at a time they ran a chain at a time (PERF.md §6, PR 49).

_VMEM_LIMIT_BYTES = 100 * 1024 * 1024
_LANES = 128
_PAIR = 2 * CHUNK       # rows of the matrices of two chunks side by side
_HEADS = 8              # heads a grid step holds, at the most
_GROUP = 2              # heads a step of a kernel's one loop holds (4 read
#                         the forward 0.4 ms of 10.3 faster and the backward
#                         no faster, and lowered for twice as long)
_FOLD = 8               # a register's sublanes

_NN = (((1,), (0,)), ((), ()))      # a @ b
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b
_F32 = jnp.float32


def takes_kernel(q, k, v, chunk: int, block_len: int) -> bool:
    """Whether ``kda``'s operands under this plan are shapes the kernels
    tile: q, k, v of one dtype, bf16 or f32; keys and values of one block of
    128 lanes a head; chunks of ``CHUNK`` in sub-blocks of ``SUB``; a block
    of chunks a whole number of pairs of them."""
    return (q.dtype in (jnp.bfloat16, jnp.float32)
            and k.dtype == q.dtype and v.dtype == q.dtype
            and q.shape == k.shape and k.shape[-1] == _LANES
            and v.shape[-1] == _LANES and chunk == CHUNK
            and block_len % _PAIR == 0)


def _pass(a, b, form):
    """One pass over bf16 operands, whatever ``jax.default_matmul_precision``
    says (Mosaic refuses bf16 operands under ``highest``)."""
    return lax.dot_general(a, b, form, precision=lax.Precision.DEFAULT,
                           preferred_element_type=_F32)


def _mm(a, b, form):
    """A product in the operands' dtype, float32 accumulation: float32
    operands follow ``jax.default_matmul_precision`` as a plain ``@`` does."""
    if a.dtype == jnp.bfloat16:
        return _pass(a, b, form)
    return _dot(a, b, *form)


def _iota(shape, axis):
    return lax.broadcasted_iota(jnp.int32, shape, axis)


def _sums_mm(ones, x, form):
    """A product of a bf16 matrix of 0s and 1s with float32 ``x``, exact in
    each term: ``x`` as three bf16 pieces (8 bits of mantissa each: all 24), a
    pass a piece, float32 accumulation."""
    bf16 = jnp.bfloat16
    hi = x.astype(bf16)
    rest = x - hi.astype(_F32)
    mid = rest.astype(bf16)
    low = (rest - mid.astype(_F32)).astype(bf16)
    return (_pass(ones, hi, form) + _pass(ones, mid, form)
            + _pass(ones, low, form))


def _solve_mm(a, b, form, exact):
    """A product of the solve, float32 operands: six bf16 passes where the
    activations are float32, three (high x high, low x high, high x low:
    ``lax.Precision.HIGH``, which Mosaic does not take by name) where they
    are bf16."""
    if exact:
        return lax.dot_general(a, b, form, precision=lax.Precision.HIGHEST,
                               preferred_element_type=_F32)
    bf16 = jnp.bfloat16
    a_hi, b_hi = a.astype(bf16), b.astype(bf16)
    a_lo = (a - a_hi.astype(_F32)).astype(bf16)
    b_lo = (b - b_hi.astype(_F32)).astype(bf16)
    return (_pass(a_hi, b_hi, form) + _pass(a_lo, b_hi, form)
            + _pass(a_hi, b_lo, form))


def _pair_masks():
    """For a pair of chunks, rows r and columns s of (128 x 128): the three
    sums of g as one matrix of 0s and 1s stacked (inside the sub-block up to
    r; the chunk's sub-blocks before r's; the whole chunk), the causal and
    the strictly causal half of a chunk's own square, the identity."""
    shape = (_PAIR, _PAIR)
    r, s = _iota(shape, 0), _iota(shape, 1)
    chunk_of = CHUNK.bit_length() - 1
    sub_of = SUB.bit_length() - 1
    same_chunk = (r >> chunk_of) == (s >> chunk_of)
    sub_r, sub_s = r >> sub_of, s >> sub_of

    def ones(mask):
        return jnp.where(mask, 1.0, 0.0).astype(_F32)

    sums = jnp.concatenate([
        ones(jnp.logical_and(sub_r == sub_s, r >= s)),
        ones(jnp.logical_and(same_chunk, sub_s < sub_r)),
        ones(same_chunk)], axis=0).astype(jnp.bfloat16)
    return dict(sums=sums, causal=jnp.logical_and(same_chunk, r >= s),
                strict=jnp.logical_and(same_chunk, r > s), eye=ones(r == s))


def _tiles_3d(x):
    """(rows, 128) -> (sub-blocks, SUB, 128)."""
    return x.reshape(x.shape[0] // SUB, SUB, x.shape[1])


def _tile_lanes(shape):
    """Of (sub-blocks, SUB, 128): the row inside the sub-block, and the lane
    less the lane of the sub-block's first column in its pair."""
    per_pair = _PAIR // SUB
    first = (_iota(shape, 0) & (per_pair - 1)) * SUB
    return _iota(shape, 1), _iota(shape, 2) - first


def _halves(x):
    """(rows, 128) as (sub-blocks, 8, 128) a register's rows of a sub-block:
    the upper and the lower half."""
    x = _tiles_3d(x)
    return [x[:, n:n + _FOLD] for n in range(0, SUB, _FOLD)]


def _whole(halves, shape):
    return jnp.concatenate(halves, axis=1).reshape(shape)


def _diagonal_tiles_fwd(kf, qf, gl):
    """The tiles on the diagonal, entry by entry, of all sub-blocks at once.
    kf, qf, gl: (rows, 128) float32, ``gl`` the running sum of g inside the
    sub-block. Returns (k k^T, q k^T) with the pairwise decay as (rows, 128):
    a row's entries on the lanes of its sub-block's columns in its pair, zero
    everywhere else and above the diagonal. Column j meets the rows of j's
    register and of the one below it: the rows above see nothing of it."""
    ks, qs, gs = _halves(kf), _halves(qf), _halves(gl)
    row, lane = _tile_lanes(ks[0].shape)
    kk, qk = [jnp.zeros_like(x) for x in ks], [jnp.zeros_like(x) for x in ks]
    for j in range(SUB):
        own, at = j // _FOLD, lane == j
        kj, gj = (x[own][:, j % _FOLD:j % _FOLD + 1] for x in (ks, gs))
        for half in range(own, SUB // _FOLD):
            # the exponent masked to the causal half BEFORE exp (rows above
            # j read exp(0) and are masked after)
            cols = kj * jnp.exp(jnp.minimum(gs[half] - gj, 0.0))
            kk[half] = jnp.where(at, jnp.sum(ks[half] * cols, axis=2,
                                             keepdims=True), kk[half])
            qk[half] = jnp.where(at, jnp.sum(qs[half] * cols, axis=2,
                                             keepdims=True), qk[half])
    # (lanes of other tiles were never written)
    seen = [row + half * _FOLD >= lane for half in range(SUB // _FOLD)]
    return tuple(_whole([jnp.where(at, x, 0.0) for at, x in zip(seen, tiles)],
                        kf.shape) for tiles in (kk, qk))


def _diagonal_tiles_bwd(kf, qf, gl, dkk, dqk):
    """The cotangents the diagonal tiles hand to k, q and ``gl``, given those
    of the two matrices (rows, 128) (read on the tiles' own lanes only, the
    strict half of ``dkk``: the caller masks). Every exponent is the
    forward's, and a column meets the rows the forward gave it."""
    ks, qs, gs = _halves(kf), _halves(qf), _halves(gl)
    das, dbs = _halves(dkk), _halves(dqk)
    row, lane = _tile_lanes(ks[0].shape)
    zeros = [jnp.zeros_like(x) for x in ks]
    dk, dq, dg, dk_col, dg_col = (list(zeros) for _ in range(5))
    for j in range(SUB):
        own, at = j // _FOLD, lane == j
        kj, gj = (x[own][:, j % _FOLD:j % _FOLD + 1] for x in (ks, gs))
        to_k = to_g = None
        for half in range(own, SUB // _FOLD):
            decay = jnp.exp(jnp.minimum(gs[half] - gj, 0.0))
            cols = kj * decay
            a = jnp.sum(jnp.where(at, das[half], 0.0), axis=2, keepdims=True)
            b = jnp.sum(jnp.where(at, dbs[half], 0.0), axis=2, keepdims=True)
            dk[half], dq[half] = dk[half] + a * cols, dq[half] + b * cols
            dcols = a * ks[half] + b * qs[half]
            moved = dcols * cols                # d (gl_r - gl_j)
            dg[half] = dg[half] + moved
            to_k = _plus(to_k, jnp.sum(dcols * decay, axis=1, keepdims=True))
            to_g = _plus(to_g, jnp.sum(moved, axis=1, keepdims=True))
        mine = row == j % _FOLD
        dk_col[own] = jnp.where(mine, to_k, dk_col[own])
        dg_col[own] = jnp.where(mine, to_g, dg_col[own])
    return (_whole([x + y for x, y in zip(dk, dk_col)], kf.shape),
            _whole(dq, kf.shape),
            _whole([x - y for x, y in zip(dg, dg_col)], kf.shape))


def _row_blocks_below(pair_rows):
    """(row block, first row of its chunk, its own first row) of the row
    blocks of a pair that have tiles below the diagonal."""
    return [(i, i * SUB // CHUNK * CHUNK, i * SUB)
            for i in range(pair_rows // SUB) if i * SUB % CHUNK]


def _decay_below(in_chunk, start, c0, lo):
    """For the row block that starts at ``lo`` of a pair (its chunk at
    ``c0``): exp(start - G_s) of the chunk's columns before it. The
    reference point is the START of the row block: every exponent a sum of g
    over positions between."""
    ref = jnp.concatenate([start[lo:lo + SUB]] * ((lo - c0) // SUB), axis=0)
    return jnp.exp(ref - in_chunk[c0:lo])


def _columns_below(cols, c0, lo, act):
    """The chunk's columns before a row block (rows c0 to lo of the pair) as
    the pair's: zero rows for every other column. (128, 128) in ``act``."""
    pieces = ([jnp.zeros((c0, _LANES), _F32)] if c0 else []) + [cols] + [
        jnp.zeros((_PAIR - lo, _LANES), _F32)]
    return jnp.concatenate(pieces, axis=0).astype(act)


def _unit_lower_inverses(powers, eye, exact):
    """``(I - power)^-1`` for each ``power`` (128, 128) strictly lower
    triangular inside each chunk's square and zero outside:
    ``_unit_lower_inverse``'s sum of the powers below ``CHUNK``, the next
    power and the next sum ONE product ((the sum of the powers below 2^n; the
    2^n-th power) times the latter), its operands stacked. A solve is six
    products each waiting for the one before: the matrices advance in step,
    so that one's product runs while another's is on its way."""
    results = [eye + power for power in powers]
    powers = [_solve_mm(power, power, _NN, exact) for power in powers]
    doublings = CHUNK.bit_length() - 2          # 1 -> 2 -> ... -> CHUNK powers
    for _ in range(doublings - 1):
        both = [_solve_mm(jnp.concatenate([result, power], axis=0), power, _NN,
                          exact) for result, power in zip(results, powers)]
        results = [result + x[:_PAIR] for result, x in zip(results, both)]
        powers = [x[_PAIR:] for x in both]
    return [result + _solve_mm(result, power, _NN, exact)
            for result, power in zip(results, powers)]


def _unit_lower_inverses_by_halves(powers, eye, exact):
    """``(I - power)^-1`` as :func:`_unit_lower_inverses` gives it, block by
    block (:func:`_unit_lower_inverse_by_halves`): from blocks of two rows
    up to a chunk's square, each doubling two products of whole (128 x 128)
    tiles, the rows and columns of other blocks masked to zero; ten products
    each waiting for the one before, the matrices in step."""
    r, s = _iota((_PAIR, _PAIR), 0), _iota((_PAIR, _PAIR), 1)
    results = [eye + jnp.where((r >> 1) == (s >> 1), power, 0.0)
               for power in powers]
    for level in range(1, CHUNK.bit_length() - 1):      # blocks of 2, .., 32
        across = jnp.logical_and((r >> (level + 1)) == (s >> (level + 1)),
                                 (r >> level) != (s >> level))
        inner = [_solve_mm(jnp.where(across, power, 0.0), result, _NN, exact)
                 for power, result in zip(powers, results)]
        results = [result + _solve_mm(result, x, _NN, exact)
                   for result, x in zip(results, inner)]
    return results


class _Parts(NamedTuple):
    """What a block of one head holds that does not need the carried state,
    each (rows, 128) float32 unless said; a (row x column) matrix's columns
    are those of the row's PAIR of chunks."""
    kf: Any
    qf: Any
    vf: Any
    gl: Any             # the running sum of g inside the sub-block
    start: Any          # the sum over the chunk's sub-blocks before
    in_chunk: Any       # G = start + gl
    since: Any          # exp(gl)
    from_start: Any     # exp(G)
    to_end: Any         # exp(G_C - G)
    whole: Any          # exp(G_C), every row of a chunk the same
    kk: Any             # K+ K-^T, the causal half
    scores: Any         # Q+ K-^T, the causal half
    solved: Any         # (I + strict(diag(beta) kk))^-1
    w: Any
    u: Any


def _state_free(q, k, v, g, beta, masks, exact, by_halves=False):
    """``_Parts`` of a block of some heads, a head's rows below another's.
    q, k, v: (rows, 128); g: float32; beta: (rows, 1)."""
    act, rows = q.dtype, q.shape[0]
    qf, kf, vf = q.astype(_F32), k.astype(_F32), v.astype(_F32)
    pairs = _pairs_of(rows)
    sums = [_sums_mm(masks["sums"], g[p], _NN) for p in pairs]
    gl, start, total = (jnp.concatenate(
        [s[n * _PAIR:(n + 1) * _PAIR] for s in sums], axis=0) for n in range(3))
    in_chunk = start + gl
    since, from_start = jnp.exp(gl), jnp.exp(in_chunk)
    to_end, whole = jnp.exp(total - in_chunk), jnp.exp(total)
    kk_diag, qk_diag = _diagonal_tiles_fwd(kf, qf, gl)
    k_since, q_since = kf * since, qf * since
    k_plus_b = (kf * from_start * beta).astype(act)
    v_b = (vf * beta).astype(act)
    kk, scores = [], []
    for p in pairs:
        below = {}
        for i, c0, lo in _row_blocks_below(_PAIR):
            mine = slice(p.start + lo, p.start + lo + SUB)
            both = jnp.concatenate([k_since[mine], q_since[mine]],
                                   axis=0).astype(act)
            cols = kf[p][c0:lo] * _decay_below(in_chunk[p], start[p], c0, lo)
            below[i] = _mm(both, _columns_below(cols, c0, lo, act), _NT)
        for out, diag, half in ((kk, kk_diag, 0), (scores, qk_diag, SUB)):
            out.append(jnp.concatenate([
                diag[p.start + i * SUB:p.start + (i + 1) * SUB]
                + (below[i][half:half + SUB] if i in below else 0.0)
                for i in range(_PAIR // SUB)], axis=0))
    solve = _unit_lower_inverses_by_halves if by_halves else _unit_lower_inverses
    solved = solve([jnp.where(masks["strict"], -x * beta[p], 0.0)
                    for x, p in zip(kk, pairs)], masks["eye"], exact)
    w = [_mm(x.astype(act), k_plus_b[p], _NN) for x, p in zip(solved, pairs)]
    u = [_mm(x.astype(act), v_b[p], _NN) for x, p in zip(solved, pairs)]

    def rows_of(pieces):
        return jnp.concatenate(pieces, axis=0)

    return _Parts(kf, qf, vf, gl, start, in_chunk, since, from_start,
                  to_end, whole, rows_of(kk), rows_of(scores), rows_of(solved),
                  rows_of(w), rows_of(u))


def _twice(x):
    """A chunk's rows (all the same: a decay of the whole chunk) as the
    state's 128."""
    return jnp.concatenate([x, x], axis=0)


def _chunks_of(rows):
    return [slice(c, c + CHUNK) for c in range(0, rows, CHUNK)]


def _pairs_of(rows):
    return [slice(p, p + _PAIR) for p in range(0, rows, _PAIR)]


def _recurrence(parts, states, masks, act, want_o):
    """Steps each head's carried state (value x key, float32) through its
    block's chunks, the heads in step (a chunk is two products that wait for
    each other). Returns (o (rows, 128) float32 or None, the states after the
    block, the state each chunk starts from, D (rows, 128))."""
    rows = parts.kf.shape[0]
    q_plus = (parts.qf * parts.from_start).astype(act)
    k_end = (parts.kf * parts.to_end).astype(act)
    w = parts.w.astype(act)
    chunks = _chunks_of(rows)
    per_head = len(chunks) // len(states)
    states = list(states)
    starts, d, carried = ([None] * len(chunks) for _ in range(3))
    for n in range(per_head):
        for head in range(len(states)):
            at = head * per_head + n
            c, state = chunks[at], states[head]
            starts[at] = state
            sa = state.astype(act)
            d[at] = parts.u[c] - _mm(w[c], sa, _NT)
            if want_o:
                carried[at] = _mm(q_plus[c], sa, _NT)
            states[head] = _twice(parts.whole[c]) * state + _mm(
                d[at].astype(act), k_end[c], _TN)
    d = jnp.concatenate(d, axis=0)
    o = None
    if want_o:
        da = d.astype(act)
        o = jnp.concatenate([
            jnp.concatenate(carried[p.start // CHUNK:p.stop // CHUNK], axis=0)
            + _mm(jnp.where(masks["causal"], parts.scores[p], 0.0
                            ).astype(act), da[p], _NN)
            for p in _pairs_of(rows)], axis=0)
    return o, states, starts, d


def _plus(a, b):
    return b if a is None else a + b


def _block_backward(q, k, v, g, beta, states, do, dstates, masks, exact,
                    by_halves=False):
    """The cotangents of a block of some heads (a head's rows below
    another's), by hand (the autodiff of ``_block`` is the definition it is
    tested against). states: what each head's block starts from; do: (rows,
    128); dstates: the cotangents of the states after it (all states value x
    key, float32). Returns (dq, dk, dv, dg, dbeta (rows, 1), the cotangents
    of ``states``), float32."""
    act, rows = q.dtype, q.shape[0]
    parts = _state_free(q, k, v, g, beta, masks, exact, by_halves)
    _, _, starts, d = _recurrence(parts, states, masks, act, False)
    kf, qf, vf = parts.kf, parts.qf, parts.vf
    k_plus, q_plus = kf * parts.from_start, qf * parts.from_start
    k_end, k_since, q_since = kf * parts.to_end, kf * parts.since, qf * parts.since
    k_plus_b, v_b = (k_plus * beta).astype(act), (vf * beta).astype(act)
    q_plus_a, k_end_a = q_plus.astype(act), k_end.astype(act)
    wa, da, doa = parts.w.astype(act), d.astype(act), do.astype(act)
    pairs = _pairs_of(rows)

    # what the scores hand on, the state apart: tril(Q+ K-^T)^T dO to D,
    # tril(dO D^T) to the scores
    dd_within = jnp.concatenate([_mm(jnp.where(
        masks["causal"], parts.scores[p], 0.0).astype(act), doa[p], _TN)
        for p in pairs], axis=0)
    dscores = jnp.concatenate([jnp.where(
        masks["causal"], _mm(doa[p], da[p], _NT), 0.0) for p in pairs], axis=0)

    # the recurrence, from a block's last chunk to its first, the heads in step
    chunks = _chunks_of(rows)
    per_head = len(chunks) // len(states)
    dstates = list(dstates)
    dd, dq_plus, dk_end, dw, dtotal = ([None] * len(chunks) for _ in range(5))
    first_row = _iota((CHUNK, _LANES), 0) == 0
    for step in reversed(range(per_head)):
        for head in range(len(states)):
            n = head * per_head + step
            c, dstate = chunks[n], dstates[head]
            sa, dsa = starts[n].astype(act), dstate.astype(act)
            dd[n] = dd_within[c] + _mm(k_end_a[c], dsa, _NT)
            dda = dd[n].astype(act)
            dq_plus[n] = _mm(doa[c], sa, _NN)
            dk_end[n] = _mm(da[c], dsa, _NN)
            dw[n] = -_mm(dda, sa, _NN)
            # d exp(G_C), by key; on the chunk's first row (any one would do)
            dwhole = jnp.sum(starts[n] * dstate, axis=0, keepdims=True)
            dtotal[n] = jnp.where(first_row, parts.whole[c] * dwhole, 0.0)
            dstates[head] = (_mm(doa[c], q_plus_a[c], _TN)
                             + _twice(parts.whole[c]) * dstate
                             - _mm(dda, wa[c], _TN))
    dd, dq_plus, dk_end, dw, dtotal = (
        jnp.concatenate(x, axis=0) for x in (dd, dq_plus, dk_end, dw, dtotal))

    # W = A (beta K+), U = A (beta V), A the solve's result
    solved = [parts.solved[p] for p in pairs]
    dwa, dua = dw.astype(act), dd.astype(act)
    dk_plus_b = [_mm(x.astype(act), dwa[p], _TN) for x, p in zip(solved, pairs)]
    dv_b = [_mm(x.astype(act), dua[p], _TN) for x, p in zip(solved, pairs)]
    dsolved = [_mm(dwa[p], k_plus_b[p], _NT) + _mm(dua[p], v_b[p], _NT)
               for p in pairs]
    # d (I + N)^-1 = -T^T dT T^T, on the strict half of a chunk's square
    inner = [_solve_mm(dx, x, _NT, exact) for dx, x in zip(dsolved, solved)]
    dkk = [jnp.where(masks["strict"], -_solve_mm(x, y, _TN, exact), 0.0)
           for x, y in zip(solved, inner)]
    dk_plus_b, dv_b, dkk = (jnp.concatenate(x, axis=0)
                            for x in (dk_plus_b, dv_b, dkk))
    dbeta = jnp.sum(dk_plus_b * k_plus + dv_b * vf + dkk * parts.kk, axis=1,
                    keepdims=True)
    dkk = dkk * beta
    dk_plus = dk_plus_b * beta

    # the tiles below the diagonal: a row block's rows since its start, the
    # chunk's columns before it up to there
    subs = rows // SUB
    dk_rows, dq_rows, dk_cols, din_chunk, dstart = (
        [None] * subs for _ in range(5))
    for p in pairs:
        base = p.start // SUB
        for i, c0, lo in _row_blocks_below(_PAIR):
            mine = slice(p.start + lo, p.start + lo + SUB)
            live = slice(p.start + c0, p.start + lo)
            both = jnp.concatenate([k_since[mine], q_since[mine]],
                                   axis=0).astype(act)
            dboth = jnp.concatenate([dkk[mine], dscores[mine]],
                                    axis=0).astype(act)
            decay = _decay_below(parts.in_chunk[p], parts.start[p], c0, lo)
            cols = kf[live] * decay
            drows = _mm(dboth, _columns_below(cols, c0, lo, act), _NN)
            dk_rows[base + i], dq_rows[base + i] = drows[:SUB], drows[SUB:]
            dcols = _mm(dboth, both, _TN)[c0:lo]
            moved = dcols * cols                    # d (start - G_s)
            for n in range((lo - c0) // SUB):
                piece = slice(n * SUB, (n + 1) * SUB)
                at = base + c0 // SUB + n
                dk_cols[at] = _plus(dk_cols[at], dcols[piece] * decay[piece])
                din_chunk[at] = _plus(din_chunk[at], -moved[piece])
                dstart[base + i] = _plus(dstart[base + i], moved[piece])

    def whole(pieces):
        zero = jnp.zeros((SUB, _LANES), _F32)
        return jnp.concatenate([zero if x is None else x for x in pieces],
                               axis=0)

    dk_rows, dq_rows, dk_cols, din_chunk, dstart = map(
        whole, (dk_rows, dq_rows, dk_cols, din_chunk, dstart))
    dk_diag, dq_diag, dgl = _diagonal_tiles_bwd(kf, qf, parts.gl, dkk, dscores)

    # every decay's cotangent is free of further exps: x exp(.) is at hand
    moved_end = dk_end * k_end
    din_chunk = din_chunk + dq_plus * q_plus + dk_plus * k_plus - moved_end
    dgl = dgl + dk_rows * k_since + dq_rows * q_since + din_chunk
    dstart = dstart + din_chunk
    dtotal = dtotal + moved_end
    dg = jnp.concatenate([_sums_mm(masks["sums"], jnp.concatenate(
        [dgl[p], dstart[p], dtotal[p]], axis=0), _TN) for p in pairs], axis=0)
    dq = dq_diag + dq_rows * parts.since + dq_plus * parts.from_start
    dk = (dk_diag + dk_cols + dk_rows * parts.since
          + dk_plus * parts.from_start + dk_end * parts.to_end)
    return dq, dk, dv_b * beta, dg, dbeta, dstates


class _Head(NamedTuple):
    lanes: Any          # its lanes in the grid step's blocks
    of_all: Any         # its lanes in an array of all heads
    number: Any
    beta: Any           # (rows, 1)


def _for_heads(beta_ref, heads, load, work, store):
    """The heads of a grid step, ``_GROUP`` at a time, as ONE traced loop:
    ``store(head, what work(the heads, what load(head) gave) gives it)``. A
    head's block is a few long chains of small products (the solve: six in a
    row; the recurrence: two a chunk), each waiting for the one before: the
    heads of a group are as many independent sets of them, and ``work`` makes
    them advance in step. Every load of a group before its work and every
    store after it: the offsets are traced, and a load after a store would
    wait for it."""
    width = max(n for n in range(1, _GROUP + 1) if heads % n == 0)
    first = pl.program_id(2) * heads
    every = _iota(beta_ref.shape, 1)

    def body(n, carry):
        group = []
        for w in range(width):
            at = n * width + w
            beta = jnp.sum(jnp.where(every == first + at, beta_ref[...], 0.0),
                           axis=1, keepdims=True)
            group.append(_Head(
                pl.ds(pl.multiple_of(at * _LANES, _LANES), _LANES),
                pl.ds(pl.multiple_of((first + at) * _LANES, _LANES), _LANES),
                first + at, beta))
        for head, done in zip(group, work(group,
                                          [load(head) for head in group])):
            store(head, done)
        return carry

    lax.fori_loop(0, heads // width, body, None)


def _below_each_other(loaded):
    """What ``load`` gave each head, the heads' rows below each other."""
    return [jnp.concatenate(x, axis=0) for x in zip(*loaded)]


def _by_head(x, heads):
    rows = x.shape[0] // heads
    return [x[n * rows:(n + 1) * rows] for n in range(heads)]


def _scan_fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, *rest,
                     heads, save, exact, by_halves):
    starts_ref, state_ref = rest if save else (None,) + rest
    masks, first = _pair_masks(), pl.program_id(1) == 0

    def load(head):
        return (q_ref[:, head.lanes], k_ref[:, head.lanes],
                v_ref[:, head.lanes], g_ref[:, head.lanes], head.beta,
                jnp.where(first, 0.0, state_ref[:, head.of_all]))

    def work(group, loaded):
        states = [x[-1] for x in loaded]
        parts = _state_free(*_below_each_other([x[:-1] for x in loaded]),
                            masks, exact, by_halves)
        o, after, _, _ = _recurrence(parts, states, masks, q_ref.dtype, True)
        return zip(_by_head(o, len(group)), states, after)

    def store(head, done):
        o, state, after = done
        o_ref[:, head.lanes] = o.astype(o_ref.dtype)
        state_ref[:, head.of_all] = after
        if save:
            starts_ref[:, head.lanes] = state

    _for_heads(beta_ref, heads, load, work, store)


def _scan_bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, starts_ref, do_ref,
                     dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dstate_ref, *,
                     heads, exact, by_halves):
    masks, first = _pair_masks(), pl.program_id(1) == 0
    every = _iota(beta_ref.shape, 1)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        dbeta_ref[...] = jnp.zeros_like(dbeta_ref)

    def load(head):
        return (q_ref[:, head.lanes], k_ref[:, head.lanes],
                v_ref[:, head.lanes], g_ref[:, head.lanes], head.beta,
                do_ref[:, head.lanes], starts_ref[:, head.lanes],
                jnp.where(first, 0.0, dstate_ref[:, head.of_all]))

    def work(group, loaded):
        q, k, v, g, beta, do = _below_each_other([x[:-2] for x in loaded])
        *grads, dstates = _block_backward(
            q, k, v, g, beta, [x[-2] for x in loaded], do,
            [x[-1] for x in loaded], masks, exact, by_halves)
        return zip(*(_by_head(x, len(group)) for x in grads), dstates)

    def store(head, done):
        dq, dk, dv, dg, dbeta, dstate = done
        dq_ref[:, head.lanes] = dq.astype(dq_ref.dtype)
        dk_ref[:, head.lanes] = dk.astype(dk_ref.dtype)
        dv_ref[:, head.lanes] = dv.astype(dv_ref.dtype)
        dg_ref[:, head.lanes] = dg
        dbeta_ref[...] += jnp.where(every == head.number, dbeta, 0.0)
        dstate_ref[:, head.of_all] = dstate

    _for_heads(beta_ref, heads, load, work, store)


def _scan_specs(q, beta, block_len, backwards):
    """The grid (rows, blocks of chunks, groups of heads), the heads a step
    holds, and the ``BlockSpec``s of a step's block of: an array of all heads'
    lanes, beta, the saved states. The backward walks a row's blocks from its
    last."""
    (b, t, lanes), h = q.shape, beta.shape[2]
    heads = max(n for n in range(1, _HEADS + 1) if h % n == 0)
    blocks = t // block_len

    def at(i):
        return blocks - 1 - i if backwards else i

    width = heads * _LANES
    return (b, blocks, h // heads), heads, dict(
        rows=pl.BlockSpec((None, block_len, width),
                          lambda n, i, j: (n, at(i), j)),
        beta=pl.BlockSpec((None, block_len, h), lambda n, i, j: (n, at(i), 0)),
        states=pl.BlockSpec((None, None, _LANES, width),
                            lambda n, i, j: (n, at(i), 0, j)))


def _scan_cost(q, passes):
    """A pass as ``benchmarks/kda_cost.py`` counts the model's need of it."""
    b, t, lanes = q.shape
    half = CHUNK * CHUNK
    per_chunk_head = 5 * half * _LANES + 6 * CHUNK * _LANES * _LANES
    return pl.CostEstimate(
        flops=passes * b * t // CHUNK * (lanes // _LANES) * per_chunk_head,
        transcendentals=passes * b * t * lanes * (SUB + 6),
        bytes_accessed=passes * b * t * lanes * (4 * q.dtype.itemsize + 4))


def _compiler_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT_BYTES)


# The calls are jitted so that a model's layers and the recomputed forward
# share ONE traced and lowered copy of each kernel (ops/flash_attention.py).
@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8))
def _scan_fwd_call(q, k, v, g, beta, block_len, save, interpret,
                   by_halves=False):
    (b, t, lanes), f32 = q.shape, jnp.float32
    grid, heads, spec = _scan_specs(q, beta, block_len, False)
    rows = spec["rows"]
    out = pl.pallas_call(
        functools.partial(_scan_fwd_kernel, heads=heads, save=save,
                          exact=q.dtype == f32, by_halves=by_halves),
        grid=grid,
        in_specs=[rows, rows, rows, rows, spec["beta"]],
        out_specs=[rows] + [spec["states"]] * save,
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype)]
        + [jax.ShapeDtypeStruct((b, t // block_len, _LANES, lanes), f32)] * save,
        scratch_shapes=[pltpu.VMEM((_LANES, lanes), f32)],
        compiler_params=_compiler_params(),
        cost_estimate=_scan_cost(q, 1),
        interpret=interpret,
        name=device_names.KDA_SCAN + "_fwd",
    )(q, k, v, g, beta)
    return tuple(out)


@functools.partial(jax.jit, static_argnums=(7, 8, 9))
def _scan_bwd_call(q, k, v, g, beta, starts, do, block_len, interpret,
                   by_halves=False):
    grid, heads, spec = _scan_specs(q, beta, block_len, True)
    rows = spec["rows"]

    def like(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype)

    return pl.pallas_call(
        functools.partial(_scan_bwd_kernel, heads=heads,
                          exact=q.dtype == jnp.float32, by_halves=by_halves),
        grid=grid,
        in_specs=[rows, rows, rows, rows, spec["beta"], spec["states"], rows],
        out_specs=[rows, rows, rows, rows, spec["beta"]],
        out_shape=[like(q), like(k), like(v), like(g), like(beta)],
        scratch_shapes=[pltpu.VMEM((_LANES, q.shape[2]), jnp.float32)],
        compiler_params=_compiler_params(),
        cost_estimate=_scan_cost(q, 2),
        interpret=interpret,
        name=device_names.KDA_SCAN + "_bwd",
    )(q, k, v, g, beta, starts, do)


def _lanes(x):
    """(B, T, H, 128) as the kernels read it: (B, T, H x 128)."""
    return x.reshape(x.shape[0], x.shape[1], -1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _kda_kernels(q, k, v, g, beta, block_len, interpret, by_halves):
    """The kernels on q, k, v, g (B, T, H x 128) and beta (B, T, H)."""
    with jax.named_scope(device_names.KDA_SCAN):
        o, = _scan_fwd_call(q, k, v, g, beta, block_len, False, interpret,
                            by_halves)
    return o


def _kda_kernels_forward(q, k, v, g, beta, block_len, interpret, by_halves):
    with jax.named_scope(device_names.KDA_SCAN):
        o, starts = _scan_fwd_call(q, k, v, g, beta, block_len, True,
                                   interpret, by_halves)
    return o, (q, k, v, g, beta, starts)


def _kda_kernels_backward(block_len, interpret, by_halves, res, do):
    q, k, v, g, beta, starts = res
    with jax.named_scope(device_names.KDA_SCAN):
        return tuple(_scan_bwd_call(q, k, v, g, beta, starts,
                                    do.astype(v.dtype), block_len, interpret,
                                    by_halves))


_kda_kernels.defvjp(_kda_kernels_forward, _kda_kernels_backward)


def plan(t: int, chunk: int = CHUNK):
    """(chunk, sub-block, block length in positions) a row of ``t`` positions
    is cut into: the configured chunk, or the row itself where it is shorter;
    sub-blocks of ``SUB`` where they divide the chunk; as many chunks a block
    as divide the row, ``CHUNK_BLOCK`` at the most."""
    if chunk <= 0:
        raise ValueError(f"chunk must be positive, got {chunk}")
    chunk = min(chunk, t)
    if t % chunk:
        raise ValueError(f"a row of {t} positions is no whole number of "
                         f"chunks of {chunk}")
    count = t // chunk
    per_block = max(n for n in range(1, CHUNK_BLOCK + 1) if count % n == 0)
    return chunk, SUB if chunk % SUB == 0 else chunk, per_block * chunk


def saved_state_bytes(b, t, h, dk, dv, chunk: int = CHUNK) -> int:
    """Bytes the backward keeps of the carried states of one call."""
    return t // plan(t, chunk)[2] * b * h * dk * dv * 4


def _record_plan(b, t, h, dk, dv, chunk, kernel):
    from ..metrics import record_kda_plan

    record_kda_plan(chunk, saved_state_bytes(b, t, h, dk, dv, chunk), kernel)


def kda(q, k, v, g, beta, chunk: int = CHUNK, *, interpret: bool = False,
        neg_eigval: bool = False):
    """The chunked gated delta rule. q, k: (B, T, H, K) (k of unit length a
    head where the layer is KDA's; q scaled by the caller); v: (B, T, H, V);
    g: (B, T, H, K) float32, the log of each channel's decay, <= 0; beta:
    (B, T, H) in (0, 1), or with ``neg_eigval`` in (0, 2): the transition
    ``I - beta k k^T`` then has an eigenvalue in (-1, 1), and a chunk's
    triangular system is solved block by block
    (:func:`_unit_lower_inverse_by_halves`), which stays exact where keys of
    a chunk are close to each other and the other solve's powers are not.
    ``T`` a whole number of chunks (or shorter than one). Returns o (B, T, H,
    V) in v's dtype.

    Shapes :func:`takes_kernel` accepts run the kernels (``interpret=True``:
    in the Pallas interpreter, asked for by the caller and never inferred
    from the platform; a machine without a TPU raises at lowering without
    it); every other shape runs the ``jax.numpy`` scan."""
    b, t, h, dk = k.shape
    chunk, sub, block_len = plan(t, chunk)
    kernel = takes_kernel(q, k, v, chunk, block_len)
    _record_plan(b, t, h, dk, v.shape[-1], chunk, kernel)
    g, beta = g.astype(jnp.float32), beta.astype(jnp.float32)
    if kernel:
        return _kda_kernels(_lanes(q), _lanes(k), _lanes(v), _lanes(g), beta,
                            block_len, interpret, neg_eigval).reshape(v.shape)
    return _kda(q, k, v, g, beta, chunk, sub, block_len, neg_eigval)


def _lanes_block_len(q, k, v, heads, chunk):
    """The block length of the row's plan where q, k, v (B, T, H x 128) are
    operands the kernels take (their (B, T, H, 128) views pass
    :func:`takes_kernel`), else None."""
    if any(x.ndim != 3 or x.shape[2] % heads for x in (q, k, v)):
        return None
    b, t, _ = q.shape
    if chunk <= 0 or t % min(chunk, t):
        return None
    chunk, _, block_len = plan(t, chunk)

    def view(x):
        return jax.ShapeDtypeStruct(
            (b, t, heads, x.shape[2] // heads), x.dtype)

    taken = takes_kernel(view(q), view(k), view(v), chunk, block_len)
    return block_len if taken else None


def lanes_take_kernel(q, k, v, heads: int, chunk: int) -> bool:
    """Whether q, k, v (B, T, H x 128) are operands :func:`kda_lanes` runs."""
    return _lanes_block_len(q, k, v, heads, chunk) is not None


def kda_lanes(q, k, v, g, beta, chunk: int = CHUNK, *,
              interpret: bool = False, neg_eigval: bool = False):
    """:func:`kda` on the arrays as the kernels read them, for a caller that
    holds them so (``models/kda.py`` between its fused passes): q, k, v, g
    (B, T, H x 128), beta (B, T, H); returns o (B, T, H x 128) in v's dtype,
    and the gradients come back in that form too: no (B, T, H, 128) array is
    made on either side. Only for operands :func:`lanes_take_kernel`
    accepts: there is no ``jax.numpy`` scan on this form."""
    (b, t, _), heads = q.shape, beta.shape[2]
    block_len = _lanes_block_len(q, k, v, heads, chunk)
    if block_len is None:
        raise ValueError(
            f"kda_lanes: q {q.shape} {q.dtype}, k {k.shape} {k.dtype}, v "
            f"{v.shape} {v.dtype} under chunks of {chunk} are no operands of "
            "the scan's kernels (lanes_take_kernel); call kda on the "
            "(B, T, H, K) form")
    _record_plan(b, t, heads, _LANES, _LANES, chunk, True)
    return _kda_kernels(q, k, v, g.astype(jnp.float32),
                        beta.astype(jnp.float32), block_len, interpret,
                        neg_eigval)
