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

This is ``jax.numpy`` throughout, under the name ``hvd_kda_scan``: no Pallas
kernel is built yet (PERF.md §7 says what one would take).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

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


def _state_free_parts(q, k, v, g, beta, chunk, sub):
    """What a block of N chunks needs that does not depend on the carried
    state. q, k, g: (b, N chunk, h, dk); v: (.., dv); beta: (b, N chunk, h).
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
    solved = _unit_lower_inverse(
        jnp.where(strict, kk * beta[..., :, None], 0.0),
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


def _block(q, k, v, g, beta, state, chunk, sub):
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

    parts = _state_free_parts(q, k, v, g, beta, chunk, sub)
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


def _scan_blocks(q, k, v, g, beta, chunk, sub, block_len):
    """(o in v's dtype, the state each block starts from)."""
    b, _, h, dk = k.shape

    def step(state, x):
        o, after = _block(*x, state, chunk, sub)
        return after, (o.astype(v.dtype), state)

    with jax.named_scope(device_names.KDA_SCAN):
        _, (o, starts) = lax.scan(
            step, jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32),
            tuple(_blocks(x, block_len) for x in (q, k, v, g, beta)))
    return _row(o), starts


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _kda(q, k, v, g, beta, chunk, sub, block_len):
    return _scan_blocks(q, k, v, g, beta, chunk, sub, block_len)[0]


def _kda_forward(q, k, v, g, beta, chunk, sub, block_len):
    o, starts = _scan_blocks(q, k, v, g, beta, chunk, sub, block_len)
    return o, (q, k, v, g, beta, starts)


def _kda_backward(chunk, sub, block_len, res, do):
    *operands, starts = res

    def step(d_state, x):
        *block, state, d_o = x
        _, vjp = jax.vjp(
            functools.partial(_block, chunk=chunk, sub=sub), *block, state)
        *grads, d_state = vjp((d_o.astype(jnp.float32), d_state))
        return d_state, tuple(grads)

    with jax.named_scope(device_names.KDA_SCAN):
        _, grads = lax.scan(
            step, jnp.zeros(starts.shape[1:], jnp.float32),
            (*(_blocks(x, block_len) for x in operands), starts,
             _blocks(do, block_len)), reverse=True)
    return tuple(_row(dx).astype(x.dtype) for dx, x in zip(grads, operands))


_kda.defvjp(_kda_forward, _kda_backward)


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


def kda(q, k, v, g, beta, chunk: int = CHUNK):
    """The chunked gated delta rule. q, k: (B, T, H, K) (k of unit length a
    head where the layer is KDA's; q scaled by the caller); v: (B, T, H, V);
    g: (B, T, H, K) float32, the log of each channel's decay, <= 0; beta:
    (B, T, H) in (0, 1). ``T`` a whole number of chunks (or shorter than
    one). Returns o (B, T, H, V) in v's dtype."""
    from ..metrics import record_kda_plan

    b, t, h, dk = k.shape
    chunk, sub, block_len = plan(t, chunk)
    record_kda_plan(chunk, saved_state_bytes(b, t, h, dk, v.shape[-1], chunk))
    return _kda(q, k, v, g.astype(jnp.float32), beta.astype(jnp.float32),
                chunk, sub, block_len)
