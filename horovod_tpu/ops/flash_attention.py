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
  (m, l, acc). The row statistics m and l are ``(block_q, 128)`` f32, one
  row per sublane row and replicated along the lanes — the orientation of
  the score tile's own rows, so no vector moves between lanes and sublanes
  inside a block step. The loaded tile is walked in 256 x 512 sub-tiles.
  Matmul operands stay in the caller's dtype and accumulate in f32; the
  scale multiplies the f32 product, as in both backward kernels. Emits
  the per-row logsumexp residual L in a sublane-replicated ``(8, t)``
  layout that satisfies TPU block tiling (one transpose per q block).
- backward dQ: same grid; recomputes p = exp(s − L) blockwise and
  accumulates dQ = scale · Σ_k [p ∘ (dO·Vᵀ − D)] · K in scratch.
- backward dK/dV: grid (batch·head, k-block, q-block); accumulates
  dV = Σ pᵀ·dO and dK = scale · Σ [p ∘ (dO·Vᵀ − D)]ᵀ·Q in scratch.
(D = rowsum(dO ∘ O) is an elementwise reduction computed outside.)

Causal programs skip the dead triangle with ``pl.when`` — no compute for
fully-masked blocks — and build the mask only on the blocks the diagonal
crosses (forward and dK/dV: two bodies; 16 of 136 live blocks at 16k /
1024 / 1024); there the forward also skips the sub-tiles above the
diagonal. ``horovod_flash_unmasked_block_share`` says how often the
unmasked body engages (:func:`block_census`).

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


# ------------------------------------------------- causal block geometry

def _live(qi, ki, block_q, block_k):
    """Some row of q block ``qi`` sees a column of k block ``ki``: the block
    lies on or below the causal diagonal (``block_q % block_k == 0``)."""
    return ki < (qi + 1) * (block_q // block_k)


def _crossed(qi, ki, block_q, block_k):
    """A live block the diagonal runs through: only these need the mask;
    live blocks before them lie wholly below the diagonal."""
    return ki >= qi * (block_q // block_k)


def block_census(t, block_q, block_k, causal):
    """(live, masked): the k-block steps one head's forward executes at
    these (fitted) blocks, and how many of them the causal diagonal crosses
    — the closed form of :func:`_live` / :func:`_crossed` over the grid."""
    nq, nk = t // block_q, t // block_k
    if not causal:
        return nq * nk, 0
    ratio = block_q // block_k
    return ratio * nq * (nq + 1) // 2, ratio * nq


# ------------------------------------------------------------------- forward

# Row statistics (running max m, running sum l) live as (block_q, 128) f32,
# one row per sublane row and the value replicated along the lanes — the
# orientation of the score tile's rows, so max, sum, exp(s - m) and the
# rescaling of acc never move a vector between lanes and sublanes.
STAT_LANES = 128

_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_NN = (((1,), (0,)), ((), ()))      # a @ b


def _lanes(x, n):
    """Widen (or narrow) a lane-replicated ``(rows, STAT_LANES)`` statistic
    to ``n`` lanes: whole-register copies when ``n`` is a multiple of the
    lane count, a lane slice below it."""
    w = x.shape[1]
    if n == w:
        return x
    if n % w == 0:
        return jnp.tile(x, (1, n // w))
    if n < w:
        return x[:, :n]
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _sub_tile(block, want):
    """Width of the sub-tiles a block is walked in: ``want`` where it
    divides a larger block, else the whole block."""
    return want if block > want and block % want == 0 else block


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref, *,
                block_q, block_k, nk, causal, sm_scale):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    d = v_ref.shape[-1]     # the accumulator's width: v's, not q's
    ratio = block_q // block_k
    # The loaded tile is walked in (sub_q, sub_k) score sub-tiles: row groups
    # are independent (the scheduler overlaps one's softmax with the next
    # one's q @ k.T), and narrower column strips keep a stage's working set
    # small. Measured on v5e at 1024/1024, D=128: 256 x 512 (PERF.md §6).
    sub_q = _sub_tile(block_q, 256)
    sub_k = _sub_tile(block_k, 512)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def sub_tile(r0, c0, off):
        """Online-softmax update of rows [r0, r0 + sub_q) with columns
        [c0, c0 + sub_k) of the loaded tile. ``off`` is None where no mask
        is needed, else the sub-tile's first column less its first row."""
        rows, cols = pl.ds(r0, sub_q), pl.ds(c0, sub_k)
        v = v_ref[0, cols, :]
        s = jax.lax.dot_general(
            q_ref[0, rows, :], k_ref[0, cols, :], _NT,
            preferred_element_type=jnp.float32) * sm_scale
        if off is not None:
            row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(row - col >= off, s, NEG_INF)
        m_prev = m_ref[rows, :]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - _lanes(m_new, sub_k))
        l_ref[rows, :] = alpha * l_ref[rows, :] + p.sum(axis=1, keepdims=True)
        m_ref[rows, :] = m_new
        acc_ref[0, rows, :] = (
            acc_ref[0, rows, :] * _lanes(alpha, d)
            + jax.lax.dot_general(p.astype(v.dtype), v, _NN,
                                  preferred_element_type=jnp.float32))

    def below():
        """A block wholly below the diagonal (or non-causal): no mask. The
        row groups are one traced body, unrolled when lowered."""
        def row_group(i, carry):
            for c0 in range(0, block_k, sub_k):
                sub_tile(pl.multiple_of(i * sub_q, sub_q), c0, None)
            return carry
        jax.lax.fori_loop(0, block_q // sub_q, row_group, 0, unroll=True)

    def crossed(first_col):
        """A block the diagonal crosses; ``first_col`` is its first column
        less the q block's first row (static). Sub-tiles wholly above the
        diagonal are skipped, those it crosses are masked, those below it
        are not."""
        for r0 in range(0, block_q, sub_q):
            for c0 in range(0, block_k, sub_k):
                off = first_col + c0 - r0
                if off <= sub_q - 1:
                    sub_tile(r0, c0, off if off + sub_k - 1 > 0 else None)

    if causal:
        # Two bodies: blocks wholly below the diagonal never build a mask;
        # the ``ratio`` blocks the diagonal crosses each know where.
        pl.when(jnp.logical_not(_crossed(qi, ki, block_q, block_k)))(below)
        for j in range(ratio):
            pl.when(ki == qi * ratio + j)(
                functools.partial(crossed, j * block_k))
    else:
        below()

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[...]
        o_ref[0] = (acc_ref[0] / _lanes(l, d)).astype(o_ref.dtype)
        # the one move per q block: rows down the sublanes -> along the lanes
        lse_ref[0] = (m_ref[...] + jnp.log(l)).T[:lse_ref.shape[1]]


# ---------------------------------------------------------------- backward dQ

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_acc_ref, *, block_q, block_k, nk, causal, sm_scale):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)

    live = _live(qi, ki, block_q, block_k) if causal else (ki >= 0)

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

    def update(masked):
        k = k_ref[0].astype(jnp.float32)                 # (block_k, d)
        v = v_ref[0].astype(jnp.float32)
        q = q_ref[0].astype(jnp.float32)                 # (block_q, d)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0]                              # (block_q,)
        delta = delta_ref[0, 0]
        s = (q @ k.T) * sm_scale
        if masked:
            q_pos = qi * block_q + jax.lax.iota(jnp.int32, block_q)
            k_pos = ki * block_k + jax.lax.iota(jnp.int32, block_k)
            s = jnp.where(q_pos[:, None] >= k_pos[None, :], s, NEG_INF)
        p = jnp.exp(s - lse[:, None])                    # (block_q, block_k)
        dv_acc_ref[0] = dv_acc_ref[0] + p.T @ do
        ds = p * (do @ v.T - delta[:, None])
        dk_acc_ref[0] = dk_acc_ref[0] + (ds.T @ q) * sm_scale

    if causal:
        # The mask on the blocks the diagonal crosses only, as in the
        # forward (this kernel's transposes leave the vector units less
        # slack than dq's: it gains 2.5% at 16384 / 1024 / 1024, dq nothing).
        live = _live(qi, ki, block_q, block_k)
        crossed = _crossed(qi, ki, block_q, block_k)
        pl.when(live & crossed)(functools.partial(update, True))
        pl.when(jnp.logical_not(crossed))(functools.partial(update, False))
    else:
        update(False)

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
# and the benchmark read these). Chosen by the r3 sweep
# (examples/transformer_benchmark.py --sweep-blocks, table in
# docs/benchmarks.md) at D=64, with the forward the kernels had then:
# 1024/1024 won at every feasible sequence length on v5e (+12% over the old
# 1024/512 at seq 4k, +27% at 16k); block_q=2048 exceeds the backward
# kernel's scoped VMEM (19.3M > 16M). Not re-swept at D=128 since the
# forward's cost per block step changed (PERF.md §7).
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
    if q.shape[3] != k.shape[3]:
        raise ValueError(f"q has head size {q.shape[3]} but k has {k.shape[3]}")
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal: bool = True,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool = False,
                    sm_scale: float | None = None):
    """Fused attention, trainable. q: ``(B, T, H, D)``, k/v: ``(B, T, H, D)``
    or ``(B, T, Hkv, D)`` with ``H % Hkv == 0`` for grouped-query attention
    (each kv head serves a contiguous group of q heads — no head
    replication ever materializes; the kernels alias the shared kv block
    via the grid index map). v's head size may differ from q's and k's
    (latent attention: 192 | 128): the output, dO, dV and their accumulators
    follow v, dq and dk follow q; the default scale is q's ``D ** -0.5``.
    Sequence length must be a multiple of
    ``block_q`` and ``block_q`` of ``block_k`` (both clamp down to the
    sequence length for short inputs; the defaults measured fastest on v5e
    at d=64 — bigger blocks amortize scratch round-trips and feed the MXU
    wider). q, k and v go to the MXU in the dtype they arrive in; the
    softmax statistics, the accumulators and ``exp`` are f32 throughout.
    ``sm_scale`` multiplies the f32 scores inside the kernels, forward and
    backward (None: ``D ** -0.5``; Granite's ``attention_multiplier`` is not).
    ``interpret=True`` runs the kernels in the Pallas interpreter (CPU
    tests); the default compiles them for the TPU and raises on a machine
    that has none."""
    out, _ = _fwd(q, k, v, causal, block_q, block_k, interpret, sm_scale)
    return out


def _fwd(q, k, v, causal, block_q, block_k, interpret, sm_scale):
    from ..metrics import record_flash_plan
    t = q.shape[1]
    record_flash_plan(*block_census(
        t, *_check_blocks(t, block_q, block_k, interpret), causal))
    return _fwd_call(q, k, v, causal, block_q, block_k, interpret, sm_scale)


# The calls are jitted so that the layers of a model, which call them with
# one signature, share ONE traced and lowered copy of each kernel: the
# kernels' bodies are unrolled and cost seconds to trace and lower, and a
# step is traced and lowered on every start, warm or cold.
@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7))
def _fwd_call(q, k, v, causal, block_q, block_k, interpret, sm_scale):
    b, t, h, d = q.shape
    h, hkv, group = _gqa_group(q, k, v)
    dv = v.shape[3]
    block_q, block_k = _check_blocks(t, block_q, block_k, interpret)
    qr = _rows(q, b, t, h, d)
    kr, vr = _rows(k, b, t, hkv, d), _rows(v, b, t, hkv, dv)
    nk = t // block_k
    kernel = functools.partial(
        _fwd_kernel, block_q=block_q, block_k=block_k, nk=nk, causal=causal,
        sm_scale=d ** -0.5 if sm_scale is None else sm_scale)
    def kv_spec(width):
        return pl.BlockSpec((1, block_k, width), lambda r, qi, ki: (
            _kv_row(r, h, hkv, group), ki, 0))

    out, lse = pl.pallas_call(
        kernel,
        grid=(b * h, t // block_q, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda r, qi, ki: (r, qi, 0)),
            kv_spec(d),
            kv_spec(dv),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda r, qi, ki: (r, qi, 0)),
            pl.BlockSpec((1, 8, block_q), lambda r, qi, ki: (r, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, t, dv), q.dtype),
            jax.ShapeDtypeStruct((b * h, 8, t), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, block_q, dv), jnp.float32),       # acc
            pltpu.VMEM((block_q, STAT_LANES), jnp.float32),  # m
            pltpu.VMEM((block_q, STAT_LANES), jnp.float32),  # l
        ],
        interpret=interpret,
        name=FLASH_FWD,
    )(qr, kr, vr)
    return _unrows(out, b, t, h, dv), (q, k, v, out, lse)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _bwd_rule(causal, block_q, block_k, interpret, sm_scale, res, dout):
    q, k, v, out, lse = res
    b, t, h, d = q.shape
    h, hkv, group = _gqa_group(q, k, v)
    dv = v.shape[3]
    block_q, block_k = _check_blocks(t, block_q, block_k, interpret)
    qr, dor = _rows(q, b, t, h, d), _rows(dout, b, t, h, dv)
    kr, vr = _rows(k, b, t, hkv, d), _rows(v, b, t, hkv, dv)
    outr = out  # saved in rows layout by _fwd
    # D_i = rowsum(dO ∘ O): cheap elementwise reduction, done outside;
    # broadcast to the same (rows, 8, t) sublane layout as lse
    delta = jnp.sum(dor.astype(jnp.float32) * outr.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[:, None, :], (b * h, 8, t))

    nq, nk = t // block_q, t // block_k
    common = dict(block_q=block_q, block_k=block_k, causal=causal,
                  sm_scale=d ** -0.5 if sm_scale is None else sm_scale)

    def kv_spec(width):
        return pl.BlockSpec((1, block_k, width), lambda r, qi, ki: (
            _kv_row(r, h, hkv, group), ki, 0))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, nk=nk, **common),
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda r, qi, ki: (r, qi, 0)),
            kv_spec(d),
            kv_spec(dv),
            pl.BlockSpec((1, block_q, dv), lambda r, qi, ki: (r, qi, 0)),
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

    def qd(width):
        return pl.BlockSpec((1, block_q, width),
                            lambda r, ki, j: (q_row(r, j), j % nq, 0))

    def kd(width):
        return pl.BlockSpec((1, block_k, width), lambda r, ki, j: (r, ki, 0))

    row = pl.BlockSpec((1, 8, block_q), lambda r, ki, j: (q_row(r, j), 0, j % nq))
    dk, dv_rows = pl.pallas_call(
        functools.partial(_dkv_kernel, nq=nq, group=group, **common),
        grid=(b * hkv, nk, nq * group),
        in_specs=[qd(d), kd(d), kd(dv), qd(dv), row, row],
        out_specs=[kd(d), kd(dv)],
        out_shape=[
            jax.ShapeDtypeStruct((b * hkv, t, d), k.dtype),
            jax.ShapeDtypeStruct((b * hkv, t, dv), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, block_k, d), jnp.float32),   # dk acc
            pltpu.VMEM((1, block_k, dv), jnp.float32),  # dv acc
        ],
        interpret=interpret,
        name=FLASH_BWD_DKV,
    )(qr, kr, vr, dor, lse, delta)

    return (_unrows(dq, b, t, h, d), _unrows(dk, b, t, hkv, d),
            _unrows(dv_rows, b, t, hkv, dv))


flash_attention.defvjp(_fwd, _bwd_rule)
