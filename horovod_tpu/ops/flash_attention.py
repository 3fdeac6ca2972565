"""Fused causal attention as pallas TPU kernels (flash-attention schedule),
forward AND backward — fully trainable, with K/V streamed block-by-block.

The transformer's attention is the one hot op XLA does not fuse into a
single kernel: the naive schedule materializes the (T, T) logits in HBM
(memory traffic O(T²) — the HBM-bandwidth wall at long sequence). These
kernels compute attention block-by-block in VMEM with the online-softmax
recurrence, so HBM traffic stays O(T·D) — the playbook case for pallas
(/opt/skills/guides/pallas_guide.md; the algorithm is the published
flash-attention recurrence).

Blocks STREAM through the innermost grid dimension (TPU grids execute
sequentially, so VMEM scratch carries the running (max, sum, acc) across
block iterations): per-program VMEM is O(block·D), independent of sequence
length — no full K/V row staging, no VMEM ceiling at long context.

Three kernels behind one ``jax.custom_vjp``:
- forward: grid (batch·head, q-block, k-block); scratch-carried online
  (m, l, acc); emits the per-row logsumexp residual L in a
  sublane-replicated layout that satisfies TPU block tiling.
- backward dQ: same grid; recomputes p = exp(s − L) blockwise and
  accumulates dQ = scale · Σ_k [p ∘ (dO·Vᵀ − D)] · K in scratch.
- backward dK/dV: grid (batch·head, k-block, q-block); accumulates
  dV = Σ pᵀ·dO and dK = scale · Σ [p ∘ (dO·Vᵀ − D)]ᵀ·Q in scratch.
(D = rowsum(dO ∘ O) is an elementwise reduction computed outside.)

Causal programs skip the dead triangle with ``pl.when`` — no compute for
fully-masked blocks.

Pairs with the sequence-parallel schedules in ring_attention.py (which move
K/V between chips); `causal_reference` is the oracle both are tested
against. The kernels compile for the TPU (Mosaic); ``interpret=True`` runs
them in the Pallas interpreter instead — something the caller asks for
(the CPU tests do), never inferred from the platform: with the default
``interpret=False`` a machine without a TPU raises at lowering.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common.device_names import FLASH_BWD_DKV, FLASH_BWD_DQ, FLASH_FWD

NEG_INF = -1e30


# ------------------------------------------------------------------- forward

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref, *,
                block_q, block_k, nk, causal, sm_scale):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    live = (ki < (qi + 1) * (block_q // block_k)) if causal else (ki >= 0)

    @pl.when(live)
    def _update():
        q = q_ref[0].astype(jnp.float32) * sm_scale      # (block_q, d)
        k = k_ref[0].astype(jnp.float32)                 # (block_k, d)
        v = v_ref[0].astype(jnp.float32)
        s = q @ k.T                                      # (block_q, block_k)
        if causal:
            q_pos = qi * block_q + jax.lax.iota(jnp.int32, block_q)
            k_pos = ki * block_k + jax.lax.iota(jnp.int32, block_k)
            s = jnp.where(q_pos[:, None] >= k_pos[None, :], s, NEG_INF)
        m_prev = m_ref[0, 0, :]
        l_prev = l_ref[0, 0, :]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_ref[...] = jnp.broadcast_to(
            (l_prev * alpha + p.sum(axis=-1))[None, None, :], l_ref.shape)
        acc_ref[0] = acc_ref[0] * alpha[:, None] + p @ v
        m_ref[...] = jnp.broadcast_to(m_new[None, None, :], m_ref.shape)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[0, 0, :]
        o_ref[0] = (acc_ref[0] / l[:, None]).astype(o_ref.dtype)
        lse_ref[0] = jnp.broadcast_to(
            (m_ref[0, 0, :] + jnp.log(l))[None, :], lse_ref.shape[1:])


# ---------------------------------------------------------------- backward dQ

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_acc_ref, *, block_q, block_k, nk, causal, sm_scale):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)

    live = (ki < (qi + 1) * (block_q // block_k)) if causal else (ki >= 0)

    @pl.when(live)
    def _update():
        q = q_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0]                              # (block_q,)
        delta = delta_ref[0, 0]
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = (q @ k.T) * sm_scale
        if causal:
            q_pos = qi * block_q + jax.lax.iota(jnp.int32, block_q)
            k_pos = ki * block_k + jax.lax.iota(jnp.int32, block_k)
            s = jnp.where(q_pos[:, None] >= k_pos[None, :], s, NEG_INF)
        p = jnp.exp(s - lse[:, None])
        ds = p * (do @ v.T - delta[:, None])
        dq_acc_ref[0] = dq_acc_ref[0] + (ds @ k) * sm_scale

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = dq_acc_ref[0].astype(dq_ref.dtype)


# ------------------------------------------------------------- backward dK/dV

def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                dv_ref, dk_acc_ref, dv_acc_ref, *, block_q, block_k, nq,
                group, causal, sm_scale):
    ki = pl.program_id(1)
    # Innermost grid dim walks (g, qi): for GQA (group > 1) the same
    # k/v-head block accumulates gradient contributions from every q head
    # in its group — the grid dim 0 row is a KV row, and j sweeps the
    # group's q blocks. group == 1 reduces to the plain j == qi walk.
    j = pl.program_id(2)
    qi = j % nq

    @pl.when(j == 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    # first q-block whose rows can see this k-block
    live = (qi >= (ki * block_k) // block_q) if causal else (qi >= 0)

    @pl.when(live)
    def _update():
        k = k_ref[0].astype(jnp.float32)                 # (block_k, d)
        v = v_ref[0].astype(jnp.float32)
        q = q_ref[0].astype(jnp.float32)                 # (block_q, d)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0]                              # (block_q,)
        delta = delta_ref[0, 0]
        s = (q @ k.T) * sm_scale
        if causal:
            q_pos = qi * block_q + jax.lax.iota(jnp.int32, block_q)
            k_pos = ki * block_k + jax.lax.iota(jnp.int32, block_k)
            s = jnp.where(q_pos[:, None] >= k_pos[None, :], s, NEG_INF)
        p = jnp.exp(s - lse[:, None])                    # (block_q, block_k)
        dv_acc_ref[0] = dv_acc_ref[0] + p.T @ do
        ds = p * (do @ v.T - delta[:, None])
        dk_acc_ref[0] = dk_acc_ref[0] + (ds.T @ q) * sm_scale

    @pl.when(j == nq * group - 1)
    def _finalize():
        dk_ref[0] = dk_acc_ref[0].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[0].astype(dv_ref.dtype)


# ----------------------------------------------------------------- public API

def _fit_block(t, want, quantum):
    """Largest block <= want that divides t and is a multiple of quantum
    (TPU tiling), or t itself when t <= want. A ceiling below the quantum
    rounds up to the quantum (a sub-quantum block can never lower on TPU).
    None when nothing fits."""
    if t <= want:
        return t
    want = max(want, quantum)
    b = (want // quantum) * quantum
    while b >= quantum:
        if t % b == 0:
            return b
        b -= quantum
    # No conforming divisor at all (e.g. t = 8*prime): the whole axis is
    # always a legal block ("equal to the respective dimension"), so fall
    # back to it — correct, though VMEM-heavy for very long non-tileable
    # sequences, where padding to a friendlier length is the better call.
    return t


# Default kernel tiles — the single source of truth (Block/TransformerLM
# and the benchmark read these). Measured by the r3 sweep
# (examples/transformer_benchmark.py --sweep-blocks, table in
# docs/benchmarks.md): 1024/1024 wins at every feasible sequence length on
# v5e at D=64 (+12% over the old 1024/512 at seq 4k, +27% at 16k);
# block_q=2048 exceeds the backward kernel's scoped VMEM (19.3M > 16M).
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024


def _check_blocks(t, block_q, block_k, interpret):
    # TPU lowering wants the lse/delta blocks (1, 8, block_q) 128-divisible
    # in the last dim and the K/V blocks (1, block_k, d) 8-divisible in the
    # second-minor — so blocks shrink to the largest conforming divisor of
    # the sequence length (requested sizes are ceilings, not contracts).
    q_quantum = 1 if interpret else 128
    k_quantum = 1 if interpret else 8
    bq = _fit_block(t, min(block_q, t), q_quantum)
    bk = _fit_block(bq, min(block_k, bq), k_quantum)
    return bq, bk


def _rows(x, b, t, h, d):
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _unrows(x, b, t, h, d):
    return x.reshape(b, h, t, d).transpose(0, 2, 1, 3)


def _gqa_group(q, k, v):
    """(h, hkv, group) for grouped-query attention: q has h heads, k/v may
    have fewer (hkv), each shared by a contiguous group of h//hkv q heads
    (the standard GQA layout). h == hkv is plain multi-head."""
    h, hkv = q.shape[2], k.shape[2]
    if v.shape[2] != hkv:
        raise ValueError(f"k has {hkv} heads but v has {v.shape[2]}")
    if h % hkv:
        raise ValueError(f"q heads {h} not divisible by kv heads {hkv}")
    return h, hkv, h // hkv


def _kv_row(r, h, hkv, group):
    """Map a q-row index (b*h + head) to its kv-row (b*hkv + head//group)."""
    return (r // h) * hkv + (r % h) // group


def _q_row(r, j, nq, h, hkv, group):
    """Inverse walk for the dK/dV grids: kv-row ``r`` with innermost grid
    index ``j`` sweeping (g, qi) maps to q-row b*h + kv_head*group + g.
    The single definition keeps the group layout in one place with
    :func:`_kv_row` — the two must stay inverses."""
    return (r // hkv) * h + (r % hkv) * group + j // nq


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal: bool = True,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool = False):
    """Fused attention, trainable. q: ``(B, T, H, D)``, k/v: ``(B, T, H, D)``
    or ``(B, T, Hkv, D)`` with ``H % Hkv == 0`` for grouped-query attention
    (each kv head serves a contiguous group of q heads — no head
    replication ever materializes; the kernels alias the shared kv block
    via the grid index map). Sequence length must be a multiple of
    ``block_q`` and ``block_q`` of ``block_k`` (both clamp down to the
    sequence length for short inputs; the defaults measured fastest on v5e
    at d=64 — bigger blocks amortize scratch round-trips and feed the MXU
    wider). ``interpret=True`` runs the kernels in the Pallas interpreter
    (CPU tests); the default compiles them for the TPU and raises on a
    machine that has none."""
    out, _ = _fwd(q, k, v, causal, block_q, block_k, interpret)
    return out


def _fwd(q, k, v, causal, block_q, block_k, interpret):
    b, t, h, d = q.shape
    h, hkv, group = _gqa_group(q, k, v)
    block_q, block_k = _check_blocks(t, block_q, block_k, interpret)
    qr = _rows(q, b, t, h, d)
    kr, vr = (_rows(x, b, t, hkv, d) for x in (k, v))
    nk = t // block_k
    kernel = functools.partial(
        _fwd_kernel, block_q=block_q, block_k=block_k, nk=nk, causal=causal,
        sm_scale=d ** -0.5)
    kv_spec = pl.BlockSpec(
        (1, block_k, d), lambda r, qi, ki: (_kv_row(r, h, hkv, group), ki, 0))
    out, lse = pl.pallas_call(
        kernel,
        grid=(b * h, t // block_q, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda r, qi, ki: (r, qi, 0)),
            kv_spec,
            kv_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda r, qi, ki: (r, qi, 0)),
            pl.BlockSpec((1, 8, block_q), lambda r, qi, ki: (r, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, t, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, 8, t), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, block_q, d), jnp.float32),   # acc
            pltpu.VMEM((1, 8, block_q), jnp.float32),   # m
            pltpu.VMEM((1, 8, block_q), jnp.float32),   # l
        ],
        interpret=interpret,
        name=FLASH_FWD,
    )(qr, kr, vr)
    return _unrows(out, b, t, h, d), (q, k, v, out, lse)


def _bwd_rule(causal, block_q, block_k, interpret, res, dout):
    q, k, v, out, lse = res
    b, t, h, d = q.shape
    h, hkv, group = _gqa_group(q, k, v)
    block_q, block_k = _check_blocks(t, block_q, block_k, interpret)
    qr, dor = (_rows(x, b, t, h, d) for x in (q, dout))
    kr, vr = (_rows(x, b, t, hkv, d) for x in (k, v))
    outr = out  # saved in rows layout by _fwd
    # D_i = rowsum(dO ∘ O): cheap elementwise reduction, done outside;
    # broadcast to the same (rows, 8, t) sublane layout as lse
    delta = jnp.sum(dor.astype(jnp.float32) * outr.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[:, None, :], (b * h, 8, t))

    nq, nk = t // block_q, t // block_k
    common = dict(block_q=block_q, block_k=block_k, causal=causal,
                  sm_scale=d ** -0.5)
    kv_spec = pl.BlockSpec(
        (1, block_k, d), lambda r, qi, ki: (_kv_row(r, h, hkv, group), ki, 0))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, nk=nk, **common),
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda r, qi, ki: (r, qi, 0)),
            kv_spec,
            kv_spec,
            pl.BlockSpec((1, block_q, d), lambda r, qi, ki: (r, qi, 0)),
            pl.BlockSpec((1, 8, block_q), lambda r, qi, ki: (r, 0, qi)),
            pl.BlockSpec((1, 8, block_q), lambda r, qi, ki: (r, 0, qi)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda r, qi, ki: (r, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, t, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((1, block_q, d), jnp.float32)],
        interpret=interpret,
        name=FLASH_BWD_DQ,
    )(qr, kr, vr, dor, lse, delta)

    # dK/dV: one grid row per KV row; the innermost dim sweeps (g, qi) so a
    # shared kv head accumulates all of its group's q-head contributions in
    # scratch before writing out (grid dim 0 = b*hkv, not b*h).
    def q_row(r, j):
        return _q_row(r, j, nq, h, hkv, group)

    qd = pl.BlockSpec((1, block_q, d), lambda r, ki, j: (q_row(r, j), j % nq, 0))
    row = pl.BlockSpec((1, 8, block_q), lambda r, ki, j: (q_row(r, j), 0, j % nq))
    kd = pl.BlockSpec((1, block_k, d), lambda r, ki, j: (r, ki, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, nq=nq, group=group, **common),
        grid=(b * hkv, nk, nq * group),
        in_specs=[qd, kd, kd, qd, row, row],
        out_specs=[kd, kd],
        out_shape=[
            jax.ShapeDtypeStruct((b * hkv, t, d), k.dtype),
            jax.ShapeDtypeStruct((b * hkv, t, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, block_k, d), jnp.float32),   # dk acc
            pltpu.VMEM((1, block_k, d), jnp.float32),   # dv acc
        ],
        interpret=interpret,
        name=FLASH_BWD_DKV,
    )(qr, kr, vr, dor, lse, delta)

    return (_unrows(dq, b, t, h, d), _unrows(dk, b, t, hkv, d),
            _unrows(dv, b, t, hkv, d))


flash_attention.defvjp(_fwd, _bwd_rule)
