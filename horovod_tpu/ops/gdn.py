"""The gated delta rule with ONE decay a head (Gated DeltaNet,
arXiv:2412.06464; with beta in (0, 2), arXiv:2411.12537) in its chunked
form, for keys and values of different widths (Olmo-Hybrid's heads: keys of
96 under values of 192; docs/linear-attention.md, "one decay a head").

Per head with state ``S`` (key x value, ``S_0 = 0``), ``alpha_t = exp(g_t)``
ONE number, ``beta_t`` one number::

    S'_t = alpha_t S_{t-1}
    S_t  = S'_t + beta_t k_t (v_t - S'_t^T k_t)^T
    o_t  = S_t^T q_t

:func:`gdn_recurrence` is that, a step a token: the definition. :func:`gdn`
computes the same ``o`` chunk by chunk. With ``G_r`` the running sum of ``g``
inside a chunk (inclusive), ``Gamma_rs = exp(G_r - G_s)`` for r >= s (a
chunk x chunk MATRIX: the decay has left the lanes), ``S`` the state the
chunk starts from::

    A = (I + strict_lower(diag(beta) (K K^T * Gamma)))^-1 diag(beta)
    W = A (K exp(G));  U = A V;  D = U - W S
    O = (Q exp(G)) S + tril(Q K^T * Gamma) D
    S_next = exp(G_C) S + (K exp(G_C - G))^T D

``K K^T`` and ``Q K^T`` are plain products and the decay multiplies them
AFTER they are formed: none of ``ops/kda.py``'s entry-by-entry diagonal
tiles, no reference points. Every exponent is a sum of ``g`` over positions
that lie between (masked to the causal half BEFORE ``exp``), so <= 0: a
decay underflows to 0, which is its value, and nothing overflows.

The triangular system is ``ops/kda.py``'s (block by block where beta may
reach 2), and so are the numerics: ``g``, its sums, every decay, the carried
state and the solve in float32; the products with a chunk-long or key-long
contraction on operands in ``q``'s dtype, accumulated in float32.

Where the shapes tile (:func:`takes_kernel`) the rule runs as two pallas TPU
kernels, ``hvd_gdn_scan_fwd`` and ``hvd_gdn_scan_bwd`` (one
``jax.custom_vjp``; the backward derived by hand, ``_block_backward``). A
head's keys take ONE block of 128 lanes and its values whole blocks of 128:
q and k are laid out (B, T, H x 128) and v (B, T, H x 256) with ZERO lanes
after a head's 96 | 192, which is exact (a zero key lane adds nothing to a
score or to the state, a zero value lane is a state column that stays 0);
the MXU's passes are 128 wide either way. A grid step is one head and one
block of up to 8 chunks; the block axis is sequential, the head's state
(128 x 256 float32) is VMEM scratch. TWO chunks stand side by side in every
(row x column) matrix, a whole (128 x 128) tile with zeros where row and
column are of different chunks, and the block's pairs advance through the
solve in step. ``G`` is summed outside (``jnp.cumsum``, whose transpose
JAX derives) and read with beta as ROWS (B, H, 1, T): a column is taken
from a row, and a row from a column, through the identity's mask. The
forward of a differentiated call saves the state each block starts from; the
backward walks the blocks in reverse and recomputes a block from it.

Every other shape, and the CPU, runs ``ops/kda.py``'s ``jax.numpy`` scan on
the decay broadcast to the key's channels, which is the same rule.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import device_names
from . import kda as _kda
from .kda import (_F32, _NN, _NT, _PAIR, _TN, CHUNK, _chunks_of, _iota, _mm,
                  _pairs_of, _solve_mm)

_LANES = 128
_VMEM_LIMIT_BYTES = 100 * 1024 * 1024
BLOCK_LENS = (512, 256, 128)    # positions of a grid step's block, the first
#                                 that divides the row
_MAX_VALUE_LANES = 512


def gdn_recurrence(q, k, v, g, beta):
    """The definition, a step a token, in float32. q, k: (B, T, H, K); v:
    (B, T, H, V); g, beta: (B, T, H). Returns o (B, T, H, V) float32."""
    return _kda.kda_recurrence(q, k, v, g[..., None], beta)


def padded(width: int) -> int:
    """Lanes a head's ``width`` channels take in the kernels."""
    return -(-width // _LANES) * _LANES


def block_len(t: int):
    """Positions of a grid step's block for a row of ``t``, or None."""
    return next((n for n in BLOCK_LENS if t % n == 0), None)


def takes_kernel(q, k, v, chunk: int) -> bool:
    """Whether the operands are shapes the kernels tile: q, k, v of one
    dtype, bf16 or float32; keys of at most 128 a head; values of at most
    512; chunks of ``CHUNK``; a row of whole pairs of chunks."""
    return (q.dtype in (jnp.bfloat16, jnp.float32)
            and k.dtype == q.dtype and v.dtype == q.dtype
            and q.shape == k.shape and k.shape[-1] <= _LANES
            and v.shape[-1] <= _MAX_VALUE_LANES and chunk == CHUNK
            and block_len(q.shape[1]) is not None)


def saved_state_bytes(b, t, h, dk, dv, chunk: int = CHUNK,
                      kernel: bool = False) -> int:
    """Bytes the backward keeps of the carried states of one call."""
    if kernel:
        return t // block_len(t) * b * h * padded(dk) * padded(dv) * 4
    return _kda.saved_state_bytes(b, t, h, dk, dv, chunk)


# ------------------------------------------------------------------ kernels

def _masks():
    """For a pair of chunks, rows r and columns s of (128 x 128): the causal
    and the strictly causal half of a chunk's own square, the identity."""
    shape = (_PAIR, _PAIR)
    r, s = _iota(shape, 0), _iota(shape, 1)
    chunk_of = CHUNK.bit_length() - 1
    same = (r >> chunk_of) == (s >> chunk_of)
    return dict(causal=jnp.logical_and(same, r >= s),
                strict=jnp.logical_and(same, r > s), same=r == s,
                eye=jnp.where(r == s, 1.0, 0.0).astype(_F32),
                lane=_iota((1, _PAIR), 1), row=_iota((_PAIR, 1), 0))


def _col(row, masks):
    """(1, 128) -> (128, 1)."""
    return jnp.sum(jnp.where(masks["same"], row, 0.0), axis=1, keepdims=True)


def _row(col, masks):
    """(128, 1) -> (1, 128)."""
    return jnp.sum(jnp.where(masks["same"], col, 0.0), axis=0, keepdims=True)


def _lane_sum(x):
    return jnp.sum(x, axis=1, keepdims=True)


class _Pair(NamedTuple):
    """What a pair of chunks holds that does not need the carried state;
    float32 unless said. Columns are (128, 1), matrices (128, 128)."""
    kf: Any
    qf: Any
    vf: Any
    beta: Any           # column
    from_start: Any     # exp(G), column
    to_end: Any         # exp(G_C - G), column
    whole: Any          # exp(G_C), column: a chunk's rows all the same
    decay: Any          # Gamma, the causal half
    kkd: Any            # K K^T * Gamma, the causal half
    scores: Any         # Q K^T * Gamma, the causal half
    solved: Any         # (I + strict(diag(beta) kkd))^-1
    w: Any
    u: Any


def _rows_of(ref):
    """A (1, rows) ref as its pairs' (1, 128), each loaded where it lies."""
    return [ref[:, p] for p in _pairs_of(ref.shape[1])]


def _state_free(q, k, v, g_rows, beta_rows, masks, exact, by_halves):
    """``_Pair`` of each pair of a block. q, k: (rows, 128); v: (rows, V);
    g_rows (the running sum inside the chunk), beta_rows: a (1, 128) float32
    a pair."""
    act = q.dtype
    first_chunk = masks["row"] < CHUNK
    held = []
    for p, gr, br in zip(_pairs_of(q.shape[0]), g_rows, beta_rows):
        gc, bc = _col(gr, masks), _col(br, masks)
        decay = jnp.where(masks["causal"],
                          jnp.exp(jnp.minimum(gc - gr, 0.0)), 0.0)
        ends = [_lane_sum(jnp.where(masks["lane"] == last, gr, 0.0))
                for last in (CHUNK - 1, _PAIR - 1)]
        total = jnp.where(first_chunk, ends[0], ends[1])        # column
        kf, qf, vf = (x[p].astype(_F32) for x in (k, q, v))
        held.append(dict(
            kf=kf, qf=qf, vf=vf, beta=bc, from_start=jnp.exp(gc),
            to_end=jnp.exp(total - gc), whole=jnp.exp(total), decay=decay,
            kkd=_mm(k[p], k[p], _NT) * decay,
            scores=_mm(q[p], k[p], _NT) * decay))
    solve = (_kda._unit_lower_inverses_by_halves if by_halves
             else _kda._unit_lower_inverses)
    solved = solve([jnp.where(masks["strict"], -x["kkd"] * x["beta"], 0.0)
                    for x in held], masks["eye"], exact)
    out = []
    for x, t in zip(held, solved):
        ta = t.astype(act)
        k_plus_b = (x["kf"] * (x["from_start"] * x["beta"])).astype(act)
        v_b = (x["vf"] * x["beta"]).astype(act)
        out.append(_Pair(solved=t, w=_mm(ta, k_plus_b, _NN),
                         u=_mm(ta, v_b, _NN), **x))
    return out


def _twice(x):
    """A chunk's column (its rows all the same) as the state's 128 rows."""
    return jnp.concatenate([x, x], axis=0)


def _recurrence(parts, state, act, want_o):
    """Steps the carried state (key x value, float32) through a block's
    chunks. Returns (o (rows, V) float32 or None, the state after the block,
    the state each chunk starts from, D of each pair (128, V))."""
    starts, ds, os = [], [], []
    for x in parts:
        q_plus = (x.qf * x.from_start).astype(act)
        k_end = (x.kf * x.to_end).astype(act)
        wa = x.w.astype(act)
        d, carried = [], []
        for n, c in enumerate(_chunks_of(_PAIR)):
            starts.append(state)
            sa = state.astype(act)
            d.append(x.u[c] - _mm(wa[c], sa, _NN))
            if want_o:
                carried.append(_mm(q_plus[c], sa, _NN))
            state = _twice(x.whole[c]) * state + _mm(
                k_end[c], d[n].astype(act), _TN)
        d = jnp.concatenate(d, axis=0)
        ds.append(d)
        if want_o:
            os.append(jnp.concatenate(carried, axis=0) + _mm(
                x.scores.astype(act), d.astype(act), _NN))
    return (jnp.concatenate(os, axis=0) if want_o else None, state, starts, ds)


def _block_backward(q, k, v, g_rows, beta_rows, state, do, dstate, masks,
                    exact, by_halves):
    """The cotangents of a block, by hand (the autodiff of ``ops/kda.py``'s
    ``_block`` on the broadcast decay is what it is tested against). state:
    what the block starts from; do: (rows, V); dstate: the cotangent of the
    state after it. Returns (dq, dk, dv float32, dG and dbeta as rows (1,
    128) a pair, the cotangent of ``state``)."""
    act = q.dtype
    parts = _state_free(q, k, v, g_rows, beta_rows, masks, exact, by_halves)
    _, _, starts, ds = _recurrence(parts, state, act, False)
    pairs = _pairs_of(q.shape[0])
    grads = [None] * len(pairs)
    for at in reversed(range(len(pairs))):
        x, d, doa = parts[at], ds[at], do[pairs[at]].astype(act)
        da = d.astype(act)
        q_plus, k_plus = x.qf * x.from_start, x.kf * x.from_start
        k_end = x.kf * x.to_end
        q_plus_a, k_end_a, wa = (y.astype(act) for y in (q_plus, k_end, x.w))
        scores_a = x.scores.astype(act)
        # what the scores hand on, the state apart
        dd_within = _mm(scores_a, doa, _TN)
        dscores = jnp.where(masks["causal"], _mm(doa, da, _NT), 0.0)
        # the recurrence, from the pair's last chunk to its first
        dd, dq_plus, dk_end, dw, dtotal = ([None, None] for _ in range(5))
        for n, c in reversed(list(enumerate(_chunks_of(_PAIR)))):
            start = starts[2 * at + n]
            sa, dsa = start.astype(act), dstate.astype(act)
            dd[n] = dd_within[c] + _mm(k_end_a[c], dsa, _NN)
            dda = dd[n].astype(act)
            dq_plus[n] = _mm(doa[c], sa, _NT)
            dk_end[n] = _mm(da[c], dsa, _NT)
            dw[n] = -_mm(dda, sa, _NT)
            # d exp(G_C): one number a chunk
            dtotal[n] = jnp.sum(_lane_sum(start * dstate), axis=0,
                                keepdims=True) * x.whole[c][:1]
            dstate = (_mm(q_plus_a[c], doa[c], _TN)
                      + _twice(x.whole[c]) * dstate - _mm(wa[c], dda, _TN))
        dd, dq_plus, dk_end, dw = (jnp.concatenate(y, axis=0)
                                   for y in (dd, dq_plus, dk_end, dw))
        # W = A (beta K+), U = A (beta V), A the solve's result
        ta, dwa, dua = x.solved.astype(act), dw.astype(act), dd.astype(act)
        k_plus_b = (k_plus * x.beta).astype(act)
        v_b = (x.vf * x.beta).astype(act)
        dk_plus_b, dv_b = _mm(ta, dwa, _TN), _mm(ta, dua, _TN)
        dsolved = _mm(dwa, k_plus_b, _NT) + _mm(dua, v_b, _NT)
        # d (I + N)^-1 = -T^T dT T^T, on the strict half of a chunk's square
        inner = _solve_mm(dsolved, x.solved, _NT, exact)
        dn = jnp.where(masks["strict"],
                       -_solve_mm(x.solved, inner, _TN, exact), 0.0)
        dbeta = _lane_sum(dn * x.kkd) + _lane_sum(dk_plus_b * k_plus) + (
            _lane_sum(dv_b * x.vf))
        dkkd = dn * x.beta
        # Gamma = exp(G_r - G_s) multiplies both matrices: its exponent's
        # cotangent goes to the row's G and, negated, to the column's
        dgap = dkkd * x.kkd + dscores * x.scores
        dkk_a = (dkkd * x.decay).astype(act)
        dqk_a = (dscores * x.decay).astype(act)
        ka, qa = k[pairs[at]], q[pairs[at]]
        dk_plus = dk_plus_b * x.beta
        moved_end = _lane_sum(dk_end * k_end)
        dg_col = (_lane_sum(dgap) + _lane_sum(dq_plus * q_plus)
                  + _lane_sum(dk_plus * k_plus) - moved_end)
        ends = [dtotal[n] + jnp.sum(moved_end[c], axis=0, keepdims=True)
                for n, c in enumerate(_chunks_of(_PAIR))]
        dg_row = (_row(dg_col, masks) - jnp.sum(dgap, axis=0, keepdims=True)
                  + jnp.where(masks["lane"] == CHUNK - 1, ends[0], 0.0)
                  + jnp.where(masks["lane"] == _PAIR - 1, ends[1], 0.0))
        grads[at] = (
            _mm(dqk_a, ka, _NN) + dq_plus * x.from_start,
            _mm(dkk_a, ka, _NN) + _mm(dkk_a, ka, _TN) + _mm(dqk_a, qa, _TN)
            + dk_plus * x.from_start + dk_end * x.to_end,
            dv_b * x.beta, dg_row, _row(dbeta, masks))
    dq, dk, dv, dg, dbeta = zip(*grads)
    return (jnp.concatenate(dq, axis=0), jnp.concatenate(dk, axis=0),
            jnp.concatenate(dv, axis=0), dg, dbeta, dstate)


def _scan_fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, *rest, save,
                     exact, by_halves):
    starts_ref, state_ref = rest if save else (None,) + rest
    masks = _masks()
    state = jnp.where(pl.program_id(2) == 0, 0.0, state_ref[...])
    parts = _state_free(q_ref[...], k_ref[...], v_ref[...], _rows_of(g_ref),
                        _rows_of(beta_ref), masks, exact, by_halves)
    o, after, _, _ = _recurrence(parts, state, q_ref.dtype, True)
    o_ref[...] = o.astype(o_ref.dtype)
    state_ref[...] = after
    if save:
        starts_ref[...] = state


def _scan_bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, starts_ref, do_ref,
                     dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dstate_ref, *,
                     exact, by_halves):
    dstate = jnp.where(pl.program_id(2) == 0, 0.0, dstate_ref[...])
    dq, dk, dv, dg, dbeta, dstate = _block_backward(
        q_ref[...], k_ref[...], v_ref[...], _rows_of(g_ref),
        _rows_of(beta_ref), starts_ref[...], do_ref[...], dstate, _masks(),
        exact, by_halves)
    dq_ref[...] = dq.astype(dq_ref.dtype)
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)
    for p, dg_row, dbeta_row in zip(_pairs_of(dg_ref.shape[1]), dg, dbeta):
        dg_ref[:, p] = dg_row
        dbeta_ref[:, p] = dbeta_row
    dstate_ref[...] = dstate


def _scan_specs(q, v, g, backwards):
    """The grid (rows, heads, blocks of chunks) and the ``BlockSpec``s of a
    step's block of: a head's key lanes, its value lanes, a row of one number
    a position, the saved states. The backward walks a row's blocks from its
    last."""
    (b, t, _), h = q.shape, g.shape[1]
    length, values = block_len(t), v.shape[2] // h
    blocks = t // length

    def at(i):
        return blocks - 1 - i if backwards else i

    return (b, h, blocks), dict(
        keys=pl.BlockSpec((None, length, _LANES),
                          lambda n, j, i: (n, at(i), j)),
        values=pl.BlockSpec((None, length, values),
                            lambda n, j, i: (n, at(i), j)),
        row=pl.BlockSpec((None, None, 1, length),
                         lambda n, j, i: (n, j, 0, at(i))),
        states=pl.BlockSpec((None, None, None, _LANES, values),
                            lambda n, j, i: (n, at(i), j, 0, 0)))


def _scan_cost(q, v, passes):
    """A pass as ``benchmarks/gdn_cost.py`` counts the model's need of it,
    on the lanes the kernels hold."""
    b, t, key_lanes = q.shape
    heads = key_lanes // _LANES
    values = v.shape[2] // heads
    per_chunk = 2 * CHUNK * (
        2 * CHUNK * _LANES + CHUNK * CHUNK * 6
        + CHUNK * (_LANES + values) + 3 * _LANES * values + CHUNK * values)
    return pl.CostEstimate(
        flops=passes * b * heads * t // CHUNK * per_chunk,
        transcendentals=passes * b * heads * t * (CHUNK + 3),
        bytes_accessed=passes * b * t * (
            (2 * key_lanes + 2 * v.shape[2]) * q.dtype.itemsize + 8 * heads))


def _compiler_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT_BYTES)


def _states_shape(q, v, g):
    b, t, _ = q.shape
    h = g.shape[1]
    return jax.ShapeDtypeStruct(
        (b, t // block_len(t), h, _LANES, v.shape[2] // h), jnp.float32)


# The calls are jitted so that a model's layers and the recomputed forward
# share ONE traced and lowered copy of each kernel (ops/flash_attention.py).
@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _scan_fwd_call(q, k, v, g, beta, save, interpret, by_halves):
    grid, spec = _scan_specs(q, v, g, False)
    out = pl.pallas_call(
        functools.partial(_scan_fwd_kernel, save=save,
                          exact=q.dtype == jnp.float32, by_halves=by_halves),
        grid=grid,
        in_specs=[spec["keys"], spec["keys"], spec["values"], spec["row"],
                  spec["row"]],
        out_specs=[spec["values"]] + [spec["states"]] * save,
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype)]
        + [_states_shape(q, v, g)] * save,
        scratch_shapes=[pltpu.VMEM(_states_shape(q, v, g).shape[3:],
                                   jnp.float32)],
        compiler_params=_compiler_params(),
        cost_estimate=_scan_cost(q, v, 1),
        interpret=interpret,
        name=device_names.GDN_SCAN_FWD,
    )(q, k, v, g, beta)
    return tuple(out)


@functools.partial(jax.jit, static_argnums=(7, 8))
def _scan_bwd_call(q, k, v, g, beta, starts, do, interpret, by_halves):
    grid, spec = _scan_specs(q, v, g, True)

    def like(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype)

    return pl.pallas_call(
        functools.partial(_scan_bwd_kernel, exact=q.dtype == jnp.float32,
                          by_halves=by_halves),
        grid=grid,
        in_specs=[spec["keys"], spec["keys"], spec["values"], spec["row"],
                  spec["row"], spec["states"], spec["values"]],
        out_specs=[spec["keys"], spec["keys"], spec["values"], spec["row"],
                   spec["row"]],
        out_shape=[like(q), like(k), like(v), like(g), like(beta)],
        scratch_shapes=[pltpu.VMEM(starts.shape[3:], jnp.float32)],
        compiler_params=_compiler_params(),
        cost_estimate=_scan_cost(q, v, 2),
        interpret=interpret,
        name=device_names.GDN_SCAN_BWD,
    )(q, k, v, g, beta, starts, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _gdn_kernels(q, k, v, g, beta, interpret, by_halves):
    """The kernels on q, k (B, T, H x 128), v (B, T, H x V), and the running
    sum of g inside each chunk and beta as rows (B, H, 1, T)."""
    with jax.named_scope(device_names.GDN_SCAN):
        o, = _scan_fwd_call(q, k, v, g, beta, False, interpret, by_halves)
    return o


def _gdn_kernels_forward(q, k, v, g, beta, interpret, by_halves):
    with jax.named_scope(device_names.GDN_SCAN):
        o, starts = _scan_fwd_call(q, k, v, g, beta, True, interpret,
                                   by_halves)
    return o, (q, k, v, g, beta, starts)


def _gdn_kernels_backward(interpret, by_halves, res, do):
    q, k, v, g, beta, starts = res
    with jax.named_scope(device_names.GDN_SCAN):
        return tuple(_scan_bwd_call(q, k, v, g, beta, starts,
                                    do.astype(v.dtype), interpret, by_halves))


_gdn_kernels.defvjp(_gdn_kernels_forward, _gdn_kernels_backward)


def _in_lanes(x, lanes):
    """(B, T, H, d) -> (B, T, H x lanes), zero lanes after a head's d."""
    b, t, h, d = x.shape
    if d != lanes:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, lanes - d)))
    return x.reshape(b, t, h * lanes)


def _as_rows(x):
    """(B, T, H) -> (B, H, 1, T)."""
    return jnp.moveaxis(x, 1, 2)[:, :, None, :]


def gdn(q, k, v, g, beta, chunk: int = CHUNK, *, interpret: bool = False,
        neg_eigval: bool = False):
    """The chunked gated delta rule with one decay a head. q, k: (B, T, H,
    K) (k of unit length a head, q scaled by the caller); v: (B, T, H, V); g:
    (B, T, H) float32, the log of the head's decay, <= 0; beta: (B, T, H) in
    (0, 1), or with ``neg_eigval`` in (0, 2) (``ops.kda.kda``'s rule for the
    solve). ``T`` a whole number of chunks (or shorter than one). Returns o
    (B, T, H, V) in v's dtype.

    Shapes :func:`takes_kernel` accepts run the kernels (``interpret=True``:
    in the Pallas interpreter, asked for by the caller and never inferred
    from the platform); every other shape runs ``ops/kda.py``'s ``jax.numpy``
    scan on the decay broadcast to the key's channels."""
    from ..metrics import record_gdn_plan

    b, t, h, dk = k.shape
    dv = v.shape[-1]
    g, beta = g.astype(jnp.float32), beta.astype(jnp.float32)
    chunk, sub, length = _kda.plan(t, chunk)
    kernel = takes_kernel(q, k, v, chunk)
    record_gdn_plan(chunk, saved_state_bytes(b, t, h, dk, dv, chunk, kernel),
                    padded(dk) if kernel else dk)
    if not kernel:
        return _kda._kda(q, k, v, jnp.broadcast_to(g[..., None], k.shape),
                         beta, chunk, sub, length, neg_eigval)
    with jax.named_scope(device_names.GDN_SCAN):
        sums = jnp.cumsum(g.reshape(b, t // chunk, chunk, h),
                          axis=2).reshape(b, t, h)
        operands = (_in_lanes(q, _LANES), _in_lanes(k, _LANES),
                    _in_lanes(v, padded(dv)), _as_rows(sums), _as_rows(beta))
    o = _gdn_kernels(*operands, interpret, neg_eigval)
    with jax.named_scope(device_names.GDN_SCAN):
        return o.reshape(b, t, h, padded(dv))[..., :dv]
