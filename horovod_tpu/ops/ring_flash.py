"""Ring attention with the local block product fused as pallas kernels.

This closes the fusion gap left by ring_attention.py: there the per-step
local product runs as XLA einsums that materialize the (T_local, T_local)
logits block in HBM; here each ring step calls position-aware variants of
the flash-attention kernels (ops/flash_attention.py), so HBM traffic per
step stays O(T_local·D) and the (m, l, acc) online-softmax state carries
ACROSS ring steps as device arrays.

Design (the kernels are the flash-attention ones generalized two ways):

- **Carries in/out.** The forward kernel takes the running (acc, m, l) as
  inputs, accumulates the incoming K/V block into them in VMEM scratch,
  and writes them back out — one rank's attention state threads through
  all n ring steps without ever normalizing until the end.
- **Global positions, not block indices.** Causal masking uses explicit
  per-row global position arrays (sublane-replicated int32), so the same
  kernel is correct for contiguous ring layouts AND the zigzag layout
  (ring_attention.zigzag_shard) whose per-rank positions are
  non-contiguous. Fully-masked (q-block, k-block) pairs are skipped
  inside the kernel with ``pl.when``; fully-masked whole ring steps are
  skipped outside with ``lax.cond`` before the kernel is even launched.

Backward is the standard ring-flash schedule: recompute p = exp(s − lse)
blockwise; dQ accumulates locally on the query's rank, while (dK, dV)
travel around the ring WITH their (K, V) block — after n rotations each
block's gradient lands back on the rank that owns it. No (T, T) matrix is
ever materialized in either pass, on any rank.

The reference has no sequence parallelism at all (SURVEY.md §5.7 — only
allreduce/allgather/broadcast are exposed, /root/reference/horovod/common/
operations.h:108-126); this module is part of the TPU build's long-context
first-class mandate.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common.device_names import (RING_FLASH_BWD_DKV, RING_FLASH_BWD_DQ,
                                   RING_FLASH_FWD)
from ..compat import axis_size

from .flash_attention import (
    DEFAULT_BLOCK_K,
    DEFAULT_BLOCK_Q,
    NEG_INF,
    _check_blocks,
    _gqa_group,
    _kv_row,
    _q_row,
    _rows,
    _unrows,
)
from .ring_attention import zigzag_positions


# ----------------------------------------------------------------- kernels

def _rf_fwd_kernel(q_ref, k_ref, v_ref, o_in_ref, m_in_ref, l_in_ref,
                   qpos_ref, kpos_ref, o_out_ref, m_out_ref, l_out_ref,
                   acc_ref, m_ref, l_ref, *, nk, sm_scale):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = o_in_ref[...]
        m_ref[...] = m_in_ref[...]
        l_ref[...] = l_in_ref[...]

    qp = qpos_ref[0, :]
    kp = kpos_ref[:, 0]

    @pl.when(jnp.max(qp) >= jnp.min(kp))
    def _update():
        q = q_ref[0].astype(jnp.float32) * sm_scale
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = q @ k.T
        s = jnp.where(qp[:, None] >= kp[None, :], s, NEG_INF)
        m_prev = m_ref[0, 0, :]
        l_prev = l_ref[0, 0, :]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        # Rows with no live key yet carry the NEG_INF sentinel; pivot those
        # to 0 so exp() underflows to 0 instead of producing inf/nan.
        m_safe = jnp.where(m_new <= NEG_INF * 0.5, 0.0, m_new)
        alpha = jnp.exp(m_prev - m_safe)
        p = jnp.exp(s - m_safe[:, None])
        l_ref[...] = jnp.broadcast_to(
            (l_prev * alpha + p.sum(axis=-1))[None, None, :], l_ref.shape)
        acc_ref[0] = acc_ref[0] * alpha[:, None] + p @ v
        m_ref[...] = jnp.broadcast_to(m_new[None, None, :], m_ref.shape)

    @pl.when(ki == nk - 1)
    def _finalize():
        o_out_ref[...] = acc_ref[...]
        m_out_ref[...] = m_ref[...]
        l_out_ref[...] = l_ref[...]


def _rf_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                  qpos_ref, kpos_ref, dq_in_ref, dq_out_ref, dq_acc_ref, *,
                  nk, sm_scale):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc_ref[...] = dq_in_ref[...]

    qp = qpos_ref[0, :]
    kp = kpos_ref[:, 0]

    @pl.when(jnp.max(qp) >= jnp.min(kp))
    def _update():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = (q @ k.T) * sm_scale
        s = jnp.where(qp[:, None] >= kp[None, :], s, NEG_INF)
        p = jnp.exp(s - lse[:, None])
        ds = p * (do @ v.T - delta[:, None])
        dq_acc_ref[0] = dq_acc_ref[0] + (ds @ k) * sm_scale

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_out_ref[...] = dq_acc_ref[...]


def _rf_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   qpos_ref, kpos_ref, dk_in_ref, dv_in_ref,
                   dk_out_ref, dv_out_ref, dk_acc_ref, dv_acc_ref, *,
                   nq, group, sm_scale):
    # Innermost grid dim sweeps (g, qi): for GQA a shared kv head
    # accumulates every group q-head's contribution before writing out
    # (grid dim 0 is a KV row); group == 1 is the plain qi walk.
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        dk_acc_ref[...] = dk_in_ref[...]
        dv_acc_ref[...] = dv_in_ref[...]

    qp = qpos_ref[0, :]
    kp = kpos_ref[:, 0]

    @pl.when(jnp.max(qp) >= jnp.min(kp))
    def _update():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = (q @ k.T) * sm_scale
        s = jnp.where(qp[:, None] >= kp[None, :], s, NEG_INF)
        p = jnp.exp(s - lse[:, None])                    # (block_q, block_k)
        dv_acc_ref[0] = dv_acc_ref[0] + p.T @ do
        ds = p * (do @ v.T - delta[:, None])
        dk_acc_ref[0] = dk_acc_ref[0] + (ds.T @ q) * sm_scale

    @pl.when(j == nq * group - 1)
    def _finalize():
        dk_out_ref[...] = dk_acc_ref[...]
        dv_out_ref[...] = dv_acc_ref[...]


# ---------------------------------------------------------- pallas wrappers
# All operate in rows layout: (R, t, d) with R = batch*heads. Query
# positions are (8, t) int32 (sublane-replicated, same trick as the lse
# layout in flash_attention.py — legal because block_q is 128-quantized).
# Key positions are (t, 128) int32 (lane-replicated): block_k is only
# 8-quantized, so it must land in the SUBLANE dimension — a (8, block_k)
# lane block would fail Mosaic's 128-divisibility rule for e.g.
# t_local=2560 → block_k=320.

def _qd_spec(bq, d):
    return pl.BlockSpec((1, bq, d), lambda r, qi, ki: (r, qi, 0))


def _kd_spec(bk, d):
    return pl.BlockSpec((1, bk, d), lambda r, qi, ki: (r, ki, 0))


def _row_spec(bq):
    return pl.BlockSpec((1, 8, bq), lambda r, qi, ki: (r, 0, qi))


def _qpos_spec(bq):
    return pl.BlockSpec((8, bq), lambda r, qi, ki: (0, qi))


def _kpos_spec(bk):
    return pl.BlockSpec((bk, 128), lambda r, qi, ki: (ki, 0))


def _fwd_block_call(qr, k_blk, v_blk, o, m, l, qpos, kpos, bq, bk,
                    h, hkv, group, interpret):
    R, t, d = qr.shape
    nq, nk = t // bq, t // bk
    kernel = functools.partial(_rf_fwd_kernel, nk=nk, sm_scale=d ** -0.5)
    kv = pl.BlockSpec(
        (1, bk, d), lambda r, qi, ki: (_kv_row(r, h, hkv, group), ki, 0))
    return pl.pallas_call(
        kernel,
        grid=(R, nq, nk),
        in_specs=[_qd_spec(bq, d), kv, kv,
                  _qd_spec(bq, d), _row_spec(bq), _row_spec(bq),
                  _qpos_spec(bq), _kpos_spec(bk)],
        out_specs=[_qd_spec(bq, d), _row_spec(bq), _row_spec(bq)],
        out_shape=[jax.ShapeDtypeStruct((R, t, d), jnp.float32),
                   jax.ShapeDtypeStruct((R, 8, t), jnp.float32),
                   jax.ShapeDtypeStruct((R, 8, t), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((1, bq, d), jnp.float32),
                        pltpu.VMEM((1, 8, bq), jnp.float32),
                        pltpu.VMEM((1, 8, bq), jnp.float32)],
        # The (o, m, l) carries update IN PLACE across ring steps: without
        # the aliases every step round-trips fresh HBM output buffers for
        # state that is dead on entry (~2x carry HBM traffic per step).
        input_output_aliases={3: 0, 4: 1, 5: 2},
        interpret=interpret,
        name=RING_FLASH_FWD,
    )(qr, k_blk, v_blk, o, m, l, qpos, kpos)


def _dq_block_call(qr, k_blk, v_blk, dor, lse, delta, qpos, kpos, dq,
                   bq, bk, h, hkv, group, interpret):
    R, t, d = qr.shape
    nq, nk = t // bq, t // bk
    kernel = functools.partial(_rf_dq_kernel, nk=nk, sm_scale=d ** -0.5)
    kv = pl.BlockSpec(
        (1, bk, d), lambda r, qi, ki: (_kv_row(r, h, hkv, group), ki, 0))
    return pl.pallas_call(
        kernel,
        grid=(R, nq, nk),
        in_specs=[_qd_spec(bq, d), kv, kv,
                  _qd_spec(bq, d), _row_spec(bq), _row_spec(bq),
                  _qpos_spec(bq), _kpos_spec(bk), _qd_spec(bq, d)],
        out_specs=_qd_spec(bq, d),
        out_shape=jax.ShapeDtypeStruct((R, t, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, bq, d), jnp.float32)],
        input_output_aliases={8: 0},  # dq accumulator updates in place
        interpret=interpret,
        name=RING_FLASH_BWD_DQ,
    )(qr, k_blk, v_blk, dor, lse, delta, qpos, kpos, dq)


def _dkv_block_call(qr, k_blk, v_blk, dor, lse, delta, qpos, kpos, dk, dv,
                    bq, bk, h, hkv, group, interpret):
    R, t, d = qr.shape
    Rkv = k_blk.shape[0]
    nq, nk = t // bq, t // bk
    kernel = functools.partial(_rf_dkv_kernel, nq=nq, group=group,
                               sm_scale=d ** -0.5)

    # One grid row per KV row; innermost dim sweeps (g, qi) so a shared kv
    # head accumulates its whole group before the write-out.
    def q_row(r, j):
        return _q_row(r, j, nq, h, hkv, group)

    qd = pl.BlockSpec((1, bq, d), lambda r, ki, j: (q_row(r, j), j % nq, 0))
    kd = pl.BlockSpec((1, bk, d), lambda r, ki, j: (r, ki, 0))
    row = pl.BlockSpec((1, 8, bq), lambda r, ki, j: (q_row(r, j), 0, j % nq))
    qpos_s = pl.BlockSpec((8, bq), lambda r, ki, j: (0, j % nq))
    kpos_s = pl.BlockSpec((bk, 128), lambda r, ki, j: (ki, 0))
    return pl.pallas_call(
        kernel,
        grid=(Rkv, nk, nq * group),
        in_specs=[qd, kd, kd, qd, row, row, qpos_s, kpos_s, kd, kd],
        out_specs=[kd, kd],
        out_shape=[jax.ShapeDtypeStruct((Rkv, t, d), jnp.float32),
                   jax.ShapeDtypeStruct((Rkv, t, d), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((1, bk, d), jnp.float32),
                        pltpu.VMEM((1, bk, d), jnp.float32)],
        input_output_aliases={8: 0, 9: 1},  # dk/dv ride the ring in place
        interpret=interpret,
        name=RING_FLASH_BWD_DKV,
    )(qr, k_blk, v_blk, dor, lse, delta, qpos, kpos, dk, dv)


# ------------------------------------------------------------ ring schedule

def _positions(rank_idx, t: int, n: int, zigzag: bool):
    if zigzag:
        return zigzag_positions(rank_idx, t, n)
    return rank_idx * t + jnp.arange(t)


def _qpos_arr(pos, t):
    return jnp.broadcast_to(pos[None, :].astype(jnp.int32), (8, t))


def _kpos_arr(pos, t):
    return jnp.broadcast_to(pos[:, None].astype(jnp.int32), (t, 128))


def ring_flash_attention(q, k, v, axis_name: str, zigzag: bool = False,
                         block_q: int = DEFAULT_BLOCK_Q,
                         block_k: int = DEFAULT_BLOCK_K,
                         interpret: bool = False, window=None):
    """Causal ring attention over ``axis_name`` with pallas-fused local
    blocks, trainable. q: ``(B, T_local, H, D)``; k, v: same or
    ``(B, T_local, Hkv, D)`` with ``H % Hkv == 0`` (grouped-query
    attention — and the ring only ever rotates the SMALLER kv blocks and
    their gradients, so GQA cuts ICI traffic by the group factor too).
    Sequence already sharded on ``axis_name``. Same semantics as
    :func:`ring_attention.ring_attention` (including ``zigzag``), same
    block-size and ``interpret`` contract as
    :func:`flash_attention.flash_attention`. ``window`` must stay None: the
    ring's kernels mask by position against the diagonal alone, and a window
    raises rather than be ignored."""
    if window is not None:
        raise NotImplementedError(
            f"ring_flash_attention has no window (got window={window}): the "
            f"flash kernels on one chip do (ops.flash_attention)")
    return _ring_flash_attention(q, k, v, axis_name, zigzag, block_q, block_k,
                                 interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _ring_flash_attention(q, k, v, axis_name, zigzag, block_q, block_k,
                          interpret):
    out, _ = _rf_fwd(q, k, v, axis_name, zigzag, block_q, block_k, interpret)
    return out


def _rf_fwd(q, k, v, axis_name, zigzag, block_q, block_k, interpret):
    n = axis_size(axis_name)
    my = lax.axis_index(axis_name)
    b, t, h, d = q.shape
    h, hkv, group = _gqa_group(q, k, v)
    bq, bk = _check_blocks(t, block_q, block_k, interpret)
    qr = _rows(q, b, t, h, d)
    kr, vr = (_rows(x, b, t, hkv, d) for x in (k, v))
    R = b * h

    o = jnp.zeros((R, t, d), jnp.float32)
    m = jnp.full((R, 8, t), NEG_INF, jnp.float32)
    l = jnp.zeros((R, 8, t), jnp.float32)
    q_pos = _positions(my, t, n, zigzag)
    qpos = _qpos_arr(q_pos, t)
    perm = [(i, (i + 1) % n) for i in range(n)]

    k_blk, v_blk = kr, vr
    for step in range(n):
        src = (my - step) % n
        k_pos = _positions(src, t, n, zigzag)
        kpos = _kpos_arr(k_pos, t)
        fully_masked = jnp.max(q_pos) < jnp.min(k_pos)
        o, m, l = lax.cond(
            fully_masked,
            lambda o, m, l, *_: (o, m, l),
            lambda o, m, l, kb, vb, kp: _fwd_block_call(
                qr, kb, vb, o, m, l, qpos, kp, bq, bk, h, hkv, group,
                interpret),
            o, m, l, k_blk, v_blk, kpos,
        )
        if step + 1 < n:
            k_blk = lax.ppermute(k_blk, axis_name, perm)
            v_blk = lax.ppermute(v_blk, axis_name, perm)

    l_row = l[:, 0, :]                                   # (R, t)
    out_r = o / jnp.where(l_row == 0.0, 1.0, l_row)[:, :, None]
    lse = m + jnp.log(jnp.where(l == 0.0, 1.0, l))       # (R, 8, t)
    out = _unrows(out_r.astype(q.dtype), b, t, h, d)
    return out, (q, k, v, out_r.astype(q.dtype), lse)


def _rf_bwd(axis_name, zigzag, block_q, block_k, interpret, res, dout):
    q, k, v, out_r, lse = res
    n = axis_size(axis_name)
    my = lax.axis_index(axis_name)
    b, t, h, d = q.shape
    h, hkv, group = _gqa_group(q, k, v)
    bq, bk = _check_blocks(t, block_q, block_k, interpret)
    qr, dor = (_rows(x, b, t, h, d) for x in (q, dout))
    kr, vr = (_rows(x, b, t, hkv, d) for x in (k, v))
    R = b * h

    delta = jnp.sum(dor.astype(jnp.float32) * out_r.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[:, None, :], (R, 8, t))

    q_pos = _positions(my, t, n, zigzag)
    qpos = _qpos_arr(q_pos, t)
    perm = [(i, (i + 1) % n) for i in range(n)]

    dq = jnp.zeros((R, t, d), jnp.float32)
    dk_blk = jnp.zeros((b * hkv, t, d), jnp.float32)
    dv_blk = jnp.zeros((b * hkv, t, d), jnp.float32)
    k_blk, v_blk = kr, vr
    for step in range(n):
        src = (my - step) % n
        k_pos = _positions(src, t, n, zigzag)
        kpos = _kpos_arr(k_pos, t)
        fully_masked = jnp.max(q_pos) < jnp.min(k_pos)
        dq = lax.cond(
            fully_masked,
            lambda dq, *_: dq,
            lambda dq, kb, vb, kp: _dq_block_call(
                qr, kb, vb, dor, lse, delta, qpos, kp, dq, bq, bk,
                h, hkv, group, interpret),
            dq, k_blk, v_blk, kpos,
        )
        dk_blk, dv_blk = lax.cond(
            fully_masked,
            lambda dk, dv, *_: (dk, dv),
            lambda dk, dv, kb, vb, kp: _dkv_block_call(
                qr, kb, vb, dor, lse, delta, qpos, kp, dk, dv, bq, bk,
                h, hkv, group, interpret),
            dk_blk, dv_blk, k_blk, v_blk, kpos,
        )
        # (dK, dV) travel WITH their (K, V) block; after the n-th rotation
        # each block's gradient is back on the rank that owns the block.
        dk_blk = lax.ppermute(dk_blk, axis_name, perm)
        dv_blk = lax.ppermute(dv_blk, axis_name, perm)
        if step + 1 < n:
            k_blk = lax.ppermute(k_blk, axis_name, perm)
            v_blk = lax.ppermute(v_blk, axis_name, perm)

    return (_unrows(dq.astype(q.dtype), b, t, h, d),
            _unrows(dk_blk.astype(k.dtype), b, t, hkv, d),
            _unrows(dv_blk.astype(v.dtype), b, t, hkv, d))


_ring_flash_attention.defvjp(_rf_fwd, _rf_bwd)
