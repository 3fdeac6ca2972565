"""Sequence-parallel attention: ring attention + Ulysses all-to-all.

The reference has no sequence parallelism (SURVEY.md §5.7); the TPU build
makes long-context first-class. Two schedules over a sequence-sharded mesh
axis:

- :func:`ring_attention` — blockwise causal attention with online softmax;
  K/V blocks rotate around the ring via ``ppermute`` so each hop rides a
  single ICI link while the current block's matmuls run on the MXU
  (communication hides behind compute for T_local*D large enough). The
  per-step local block product runs as XLA einsums — simple and fine for
  moderate local blocks; ring_flash.py is the fused variant that routes
  the block product through position-aware pallas flash kernels with the
  (acc, m, l) state carried across ring steps (use it when T_local is
  large enough that the (T_local, T_local) logits block stresses HBM).
- :func:`ulysses_attention` — all-to-all re-shard: trade the sequence shard
  for a head shard, run dense local attention, trade back. Cheaper at modest
  sequence lengths when heads % devices == 0.

Both take q, k, v of shape [B, T_local, H, D] (sequence already sharded on
``axis_name``) and return [B, T_local, H, D].
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..compat import axis_size


def _block_update(q, k, v, o, m, l, q_pos, k_pos, scale):
    """One flash-attention accumulation step with global causal masking.

    o: [B,T,H,D] f32 accumulator; m, l: [B,H,T] f32 running max / normalizer.
    q_pos/k_pos: global sequence positions of the local rows (explicit so
    non-contiguous layouts — zigzag — mask correctly).
    """
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    mask = (q_pos[:, None] >= k_pos[None, :])[None, None]  # [1,1,Tq,Tk]
    logits = jnp.where(mask, logits, -jnp.inf)

    block_max = jnp.max(logits, axis=-1)                       # [B,H,Tq]
    m_new = jnp.maximum(m, block_max)
    m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    p = jnp.where(mask, jnp.exp(logits - m_safe[..., None]), 0.0)
    corr = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - m_safe))
    l_new = l * corr + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), v).astype(jnp.float32)
    o_new = o * corr.transpose(0, 2, 1)[..., None] + pv
    return o_new, m_new, l_new


def zigzag_positions(rank_idx, t_local: int, n: int):
    """Global positions of rank ``rank_idx``'s tokens under zigzag sharding:
    the sequence is cut into 2n stripes and rank r holds stripes r and
    2n-1-r, so every rank sees the same causal workload (contiguous
    sharding leaves rank 0 with almost no unmasked keys and rank n-1 with
    all of them). ``rank_idx`` may be a traced ``lax.axis_index``."""
    if t_local % 2:
        raise ValueError(
            f"zigzag needs an even per-rank sequence (two stripes); got "
            f"t_local={t_local}")
    half = t_local // 2
    i = jnp.arange(t_local)
    low = rank_idx * half + i
    high = (2 * n - 1 - rank_idx) * half + (i - half)
    return jnp.where(i < half, low, high)


def _zigzag_order(t: int, n: int):
    """The permutation both shard and unshard derive from: stripe r then
    stripe 2n-1-r for each rank r."""
    if t % (2 * n):
        raise ValueError(f"sequence {t} must divide into 2*{n} stripes")
    half = t // (2 * n)
    order = []
    for r in range(n):
        order.extend(range(r * half, (r + 1) * half))
        order.extend(range((2 * n - 1 - r) * half, (2 * n - r) * half))
    return order


def zigzag_shard(x, n: int, axis: int = 1):
    """Host-side layout change: reorder the FULL sequence so that a plain
    contiguous split over ``n`` ranks hands each rank its two zigzag
    stripes. Apply to tokens before sharding (and to targets/positions the
    same way); invert with :func:`zigzag_unshard`."""
    return jnp.take(x, jnp.asarray(_zigzag_order(x.shape[axis], n)), axis=axis)


def zigzag_unshard(x, n: int, axis: int = 1):
    """Inverse permutation of :func:`zigzag_shard`."""
    order = _zigzag_order(x.shape[axis], n)
    inv = [0] * len(order)
    for i, o in enumerate(order):
        inv[o] = i
    return jnp.take(x, jnp.asarray(inv), axis=axis)


def ring_attention(q, k, v, axis_name: str, zigzag: bool = False,
                   window=None):
    """Causal ring attention over ``axis_name`` (sequence-sharded).
    ``window`` must stay None: no ring schedule masks a band's lower edge or
    skips the ring steps wholly below it, and one raises rather than attend
    to every earlier key.

    With contiguous sharding (default), blocks from src > rank are fully
    masked — ~half the ring steps do dead work and the last rank is the
    critical path. ``zigzag=True`` assumes the zigzag layout
    (:func:`zigzag_shard` at the caller: rank r holds stripes r and
    2n-1-r), which balances the causal workload across ranks; the masking
    uses explicit global positions so correctness is independent of the
    layout (oracle-tested both ways).

    GQA: k/v may carry fewer heads than q (grouped-query attention). The
    ring rotates the SMALL k/v blocks — the ICI bandwidth saving is
    heads/kv_heads — and each step's local block product replicates heads
    on the fly (the flash variant in ring_flash.py aliases the shared head
    in-kernel instead).
    """
    if window is not None:
        raise NotImplementedError(
            f"ring_attention has no window (got window={window}): the flash "
            f"kernels on one chip do (ops.flash_attention)")
    n = axis_size(axis_name)
    my = lax.axis_index(axis_name)
    b, t, h, d = q.shape
    kvh = k.shape[2]
    if h % kvh != 0 or v.shape[2] != kvh:
        raise ValueError(
            f"q heads {h} must be a multiple of kv heads {kvh} "
            f"(v has {v.shape[2]})")
    rep = h // kvh
    scale = d**-0.5

    o = jnp.zeros((b, t, h, d), jnp.float32)
    m = jnp.full((b, h, t), -jnp.inf, jnp.float32)
    l = jnp.zeros((b, h, t), jnp.float32)

    def positions(rank_idx):
        if zigzag:
            return zigzag_positions(rank_idx, t, n)
        return rank_idx * t + jnp.arange(t)

    q_pos = positions(my)
    perm = [(i, (i + 1) % n) for i in range(n)]

    k_blk, v_blk = k, v
    for step in range(n):
        src = (my - step) % n
        k_pos = positions(src)
        # Skip fully-masked blocks (every key in the future of every query):
        # with contiguous sharding that is every block from src > rank —
        # rank 0 skips n-1 of n steps, rank n-1 none, which is exactly the
        # imbalance zigzag exists to fix (each rank then holds one early and
        # one late stripe, so skipped work evens out across ranks).
        fully_masked = jnp.max(q_pos) < jnp.min(k_pos)
        o, m, l = lax.cond(
            fully_masked,
            lambda o, m, l, *_: (o, m, l),
            lambda o, m, l, kb, vb, kp: _block_update(
                q,
                kb if rep == 1 else jnp.repeat(kb, rep, axis=2),
                vb if rep == 1 else jnp.repeat(vb, rep, axis=2),
                o, m, l, q_pos, kp, scale),
            o, m, l, k_blk, v_blk, k_pos,
        )
        if step + 1 < n:
            k_blk = lax.ppermute(k_blk, axis_name, perm)
            v_blk = lax.ppermute(v_blk, axis_name, perm)

    l = jnp.where(l == 0.0, 1.0, l)
    out = o / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def causal_reference(q, k, v, window=None):
    """Single-device dense causal attention — the oracle the sequence-parallel
    schedules are tested against. q,k,v: [B, T, H, D]. ``window``: the query
    at position p sees the keys ``p - window < j <= p`` (the flash kernels'
    ``window``; no ring schedule has one)."""
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    t = q.shape[1]
    mask = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    if window is not None:
        mask &= jnp.arange(t)[None, :] > jnp.arange(t)[:, None] - window
    logits = jnp.where(mask[None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def ulysses_attention(q, k, v, axis_name: str, impl: str = "dense",
                      interpret: bool = False):
    """All-to-all sequence parallelism (DeepSpeed-Ulysses schedule): re-shard
    [B, T/n, H, D] -> [B, T, H/n, D], causal attention on the full sequence
    with a head shard, re-shard back.

    ``impl="flash"`` runs the local attention through the pallas flash
    kernel (flash_attention.py) instead of dense einsums — after the
    all-to-all each shard holds the FULL sequence, which is exactly the
    regime the fused kernel exists for (the dense schedule materializes
    the (T, T) logits and stops compiling around seq 8k); ``interpret``
    is passed through to it (flash_attention's contract)."""
    n = axis_size(axis_name)
    h = q.shape[2]
    kvh = k.shape[2]
    if h % n != 0:
        raise ValueError(f"heads {h} not divisible by axis size {n}")
    if v.shape[2] != kvh:
        raise ValueError(f"k has {kvh} heads but v has {v.shape[2]}")
    if kvh != h and (kvh % n != 0 or h % kvh != 0):
        # GQA shards cleanly iff every device gets whole kv heads AND the
        # q→kv grouping stays contiguous after the split (h % kvh == 0
        # makes per-device rep = (h/n)/(kvh/n) integral).
        raise ValueError(
            f"GQA kv heads {kvh} must be a multiple of the axis size {n} "
            f"(and q heads {h} a multiple of {kvh}) so the all-to-all can "
            f"hand every device whole kv heads; use "
            f"ring_attention/ring_flash_attention otherwise")
    if impl not in ("dense", "flash"):
        raise ValueError(f"unknown impl={impl!r}; use 'dense' or 'flash'")

    def to_heads(x):  # [B,Tl,H,D] -> [B,T,H/n,D]
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    def to_seq(x):  # [B,T,H/n,D] -> [B,Tl,H,D]
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    qh, kh, vh = to_heads(q), to_heads(k), to_heads(v)
    if kvh != h and impl == "dense":
        # The all-to-all moved the SMALL kv head set (the ICI saving);
        # replicate locally for the plain multi-head einsum. The flash
        # kernel aliases the shared head in its index map instead — the
        # post-split local grouping (q head j → kv head j//rep) matches
        # the global GQA grouping because h % kvh == 0.
        rep = h // kvh
        kh = jnp.repeat(kh, rep, axis=2)
        vh = jnp.repeat(vh, rep, axis=2)
    if impl == "flash":
        from .flash_attention import flash_attention

        out = flash_attention(qh, kh, vh, interpret=interpret)
    else:
        scale = q.shape[-1] ** -0.5
        logits = jnp.einsum("bqhd,bkhd->bhqk", qh, kh).astype(jnp.float32) * scale
        t = qh.shape[1]
        mask = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
        logits = jnp.where(mask[None, None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs, vh)
    return to_seq(out)
