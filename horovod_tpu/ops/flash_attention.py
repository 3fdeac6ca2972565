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

Three kernels behind one ``jax.custom_vjp``, one plan: matmul operands go
to the MXU in the dtype they arrive in and accumulate in f32 (``p`` and
``ds`` are cast to that dtype just before their products); the scale
multiplies the f32 scores; ``exp``, the statistics and the mask are f32; the
loaded tile is walked in unrolled sub-tiles; no vector moves between lanes
and sublanes inside a block step, and no score-sized tile is transposed.
- forward: grid (batch·head, q-block, k-block), causal-dense calls the
  folded triangle of it (below); scratch-carried online (m, l, acc). The
  row statistics m and l are ``(block_q, 128)`` f32, one row per sublane
  row and replicated along the lanes — the orientation of the score tile's
  own rows. Sub-tiles of 256 x 512. Emits the per-row
  logsumexp residual L in a sublane-replicated ``(8, t)`` layout that
  satisfies TPU block tiling (one transpose per q block).
- backward dQ: same grid, query-major like the forward; L and D are turned
  ONCE per q block into the same lane-replicated ``(block_q, 128)`` scratch;
  recomputes p = exp(s − L) per sub-tile and accumulates
  dQ = scale · Σ_k [p ∘ (dO·Vᵀ − D)] · K in scratch.
- backward dK/dV: grid (batch·kv-head, k-block, group · q-block), folded
  likewise over k blocks, KEY-major:
  the score sub-tile is sᵀ = K·Qᵀ (keys down the sublanes, queries along
  the lanes), so L and D are used as they are stored (along the lanes) and
  dV = Σ pᵀ·dO, dK = scale · Σ [pᵀ ∘ (V·dOᵀ − D)]·Q are plain a @ b
  products of the tile as it lies.
(D = rowsum(dO ∘ O) is an elementwise reduction computed outside.)

Causal programs hold, fetch and run nothing above the diagonal. The GRIDS of
a causal-dense call hold the live block steps alone (a rectangle's other
half, 120 of 256 steps a head at 16k / 1024 / 1024, were grid steps that
fetched a K and a V block, or a Q and a dO block, to compute nothing): the
triangle is folded, a grid row walks q block ``p`` over its k blocks and
then q block ``nq - 1 - p`` over its own, ``nq // 2`` rows of ``ratio *
(nq + 1)`` steps; dK/dV folds k blocks the same way, the group's sweep
inside each half (:func:`_q_major_grid`, :func:`_k_major_grid`; the kernels
decode a step with the index maps' functions). Per q block the k blocks
still come in ascending order, per k block the (head, q block) sweep
likewise: every accumulator adds the terms it added on the rectangle, in the
same order. An odd ``nq``'s middle block has a row of its own whose second
half is dead, its maps held at the last live block; one block a row is the
plain grid; non-causal calls keep the rectangle (all of it is live) and
windowed calls the band's grids. ``horovod_flash_dead_step_share`` counts the
steps of the traced grid that run nothing. Blocks wholly below the diagonal
build no mask; in the blocks it crosses (16 of 136 live blocks at 16k / 1024
/ 1024, every block at 1k) sub-tiles wholly above it are skipped, those it
crosses are masked and those below it are not — in all three kernels, the
backward's in sub-tiles of 256 x 256 there.
``horovod_flash_unmasked_block_share`` says how often the unmasked body
engages and ``horovod_flash_bwd_skipped_subtile_share`` how much of the live
blocks the backward never computes (:func:`block_census`).

Three kinds of call run these kernels, and ONE place builds their three
``pallas_call``s (:func:`_calls`: grids, block specs, scratch, output shapes,
device names). A dense call hands it its operands alone; a windowed one
(``window=``) the band, whose blocks alone the grids then hold
(``hvd_flash_win_*``); :func:`selected_attention` a selection that is DATA
(learned sparse attention: ops/sparse_attention.py), each query's kept keys
as bits: two scalar-prefetched tables, the words as one more operand and the
kernels' ``sel=``, on the causal-dense grids (``hvd_flash_sel_*``).

Pairs with the sequence-parallel schedules in ring_attention.py (which move
K/V between chips); `causal_reference` is the oracle both are tested
against. The kernels compile for the TPU (Mosaic); ``interpret=True`` runs
them in the Pallas interpreter instead — something the caller asks for
(the CPU tests do), never inferred from the platform: with the default
``interpret=False`` a machine without a TPU raises at lowering.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common.device_names import (FLASH_BWD_DKV, FLASH_BWD_DQ, FLASH_FWD,
                                   FLASH_SEL_BWD_DKV, FLASH_SEL_BWD_DQ,
                                   FLASH_SEL_FWD, FLASH_WIN_BWD_DKV,
                                   FLASH_WIN_BWD_DQ, FLASH_WIN_FWD)

NEG_INF = -1e30


# ------------------------------------------------- causal block geometry

def _crossed(qi, ki, block_q, block_k):
    """Not wholly below the causal diagonal (``block_q % block_k == 0``):
    of these, the ``block_q // block_k`` blocks from ``ki == qi * ratio`` on
    are the ones the diagonal runs through, which alone need the mask; the
    blocks after them lie wholly above it and are never computed."""
    return ki >= qi * (block_q // block_k)


def block_census(t, block_q, block_k, causal, window=None):
    """(live, masked, bwd_sub_tiles, bwd_skipped) of one head at these
    (fitted) blocks. ``live``: the k-block steps the forward executes, and
    ``masked``: how many of them the causal diagonal crosses — the closed
    form of the kernels' predicates (:func:`_crossed`) over the grid. Each
    backward kernel runs the same block steps and walks a crossed block in
    sub-tiles (``_BWD_SUB_CROSSED``): ``bwd_skipped`` of them lie wholly
    above the diagonal and are never computed (:func:`_mask_offset`, as the
    kernels ask it), of the ``bwd_sub_tiles`` of that size that the live
    blocks hold: the skipped share of the live blocks' area. With a
    ``window`` (causal: query p sees keys ``p - window < j <= p``) ``live``
    are the block steps the band touches, ``masked`` those either of its
    edges crosses, ``bwd_skipped`` the sub-tiles of those that lie wholly
    outside it (:func:`_band_offsets`: an offset is met once a q block, less
    the q blocks whose k block would lie before the sequence)."""
    nq, nk = t // block_q, t // block_k
    sub_q = _sub_tile(block_q, _BWD_SUB_CROSSED[0])
    sub_k = _sub_tile(block_k, _BWD_SUB_CROSSED[1])
    per_block = (block_q // sub_q) * (block_k // sub_k)
    if not causal:
        return nq * nk, 0, nq * nk * per_block, 0
    if window is not None and window < t:
        crossed, inside = _band_offsets(t, block_q, block_k, window)

        def met(first):     # q blocks whose block at this offset is in range
            return nq - max(0, -(first // block_q))

        live = sum(map(met, crossed + inside))
        skipped = sum(
            met(first) * (_mask_offset(first + k0 - q0, sub_q, sub_k,
                                       window) is False)
            for first in crossed for q0 in range(0, block_q, sub_q)
            for k0 in range(0, block_k, sub_k))
        return live, sum(map(met, crossed)), live * per_block, skipped
    ratio = block_q // block_k
    live = ratio * nq * (nq + 1) // 2
    skipped = sum(
        _mask_offset(j * block_k + k0 - q0, sub_q, sub_k) is False
        for j in range(ratio) for q0 in range(0, block_q, sub_q)
        for k0 in range(0, block_k, sub_k))
    return live, ratio * nq, live * per_block, nq * skipped


def _band_steps(block_q, block_k, window):
    """(k blocks a q block's band can touch, q blocks a k block's can): the
    inner extents of a windowed call's grids. A block step is placed by
    ``first``, its first key position less its first query position, a
    multiple of ``block_k``: live from ``2 - block_k - window`` (its last key
    is the first query's oldest) to ``block_q - block_k`` (the diagonal's
    last)."""
    return (block_q // block_k + (window + block_k - 2) // block_k,
            1 + (window + block_q - 2) // block_q)


def _band_k_block(qi, step, ratio, steps):
    """The k block that step ``step`` of q block ``qi``'s band is, the
    diagonal's last block at the last of the ``steps``; negative before the
    sequence's first block (the kernels run nothing there, the index maps
    clamp to block 0 so that nothing is fetched)."""
    return step + (qi + 1) * ratio - steps


def _band_offsets(t, block_q, block_k, window):
    """(crossed, inside): the ``first`` of every block step a windowed call
    of ``t`` positions meets, those an edge of the band crosses (the diagonal,
    the window's lower edge, or both: each walked with its own static
    offsets) and those wholly inside it (no mask; a contiguous run)."""
    steps, _ = _band_steps(block_q, block_k, window)
    top = block_q - block_k
    offsets = [first for first in range(top - (steps - 1) * block_k,
                                        top + 1, block_k)
               if first >= block_q - t]       # the grid's farthest: ki = 0
    kinds = [_mask_offset(first, block_q, block_k, window) for first in offsets]
    return ([f for f, kind in zip(offsets, kinds) if kind not in (None, False)],
            [f for f, kind in zip(offsets, kinds) if kind is None])


# ------------------------------------------------------- the grids' steps

# A causal-dense call's live block steps are a triangle: q block ``i`` sees
# ``ratio * (i + 1)`` k blocks, k block ``j`` is seen by ``nq - j // ratio``
# q blocks. Block ``p`` and block ``n - 1 - p`` together hold the same number
# of steps for every ``p``: a grid row walks one, then the other (the module's
# docstring). Nothing but integer arithmetic on the grid's indices; the index
# maps and the kernels decode a step with the same functions.

def _div(a, b):
    """``a // b`` of non-negative integers as ONE operation (``//`` on a
    traced value is floor division's dozen, and an index map is traced and
    lowered for every operand of every program: set-up time), none where
    ``b`` is 1."""
    return a if isinstance(b, int) and b == 1 else jax.lax.div(a, b)


def _fold(p, s, n, head):
    """Step ``s`` of row ``p`` of a triangle over ``n > 1`` blocks folded in
    half: the row's first ``head`` steps are block ``p``'s, the others block
    ``n - 1 - p``'s. -> (block, i, live): the step is the block's ``i``-th.
    An odd ``n``'s middle block is its own partner: the second half of its
    row is dead (``live`` False; True, the Python value, for an even ``n``)
    and ``i`` stays at the block's last step there, so that an index map
    fetches nothing."""
    second = s >= head
    block = jnp.where(second, n - 1 - p, p)
    i = jnp.where(second, s - head, s)
    if n % 2 == 0:
        return block, i, True
    live = jnp.logical_or(jnp.logical_not(second), block != p)
    return block, jnp.where(live, i, head - 1), live


def _q_fold(p, s, nq, ratio):
    """Step ``s`` of row ``p`` of the forward's and dQ's folded grids ->
    (qi, ki, live): a q block's k blocks in ascending order."""
    return _fold(p, s, nq, ratio * (p + 1))


def _k_fold(p, s, nq, ratio, group):
    """Step ``s`` of row ``p`` of dK/dV's folded grid -> (ki, g, qi, i,
    count, live): the ``i``-th of the ``count`` steps of k block ``ki``'s
    sweep over the group's heads ``g`` and, inside a head, up the q blocks
    ``qi`` from the one that holds its own positions on."""
    ki, i, live = _fold(p, s, nq * ratio, group * (nq - _div(p, ratio)))
    seen = nq - _div(ki, ratio)
    g = _div(i, seen)
    return ki, g, nq - seen + i - g * seen, i, group * seen, live


def _folded(nq, causal, window):
    """Whether a call's three grids are the folded triangle: causal-dense,
    and more than one q block a row (one is its own partner)."""
    return causal and window is None and nq > 1


def _q_major_grid(t, block_q, block_k, causal, window):
    """(rows, steps, q_block, k_block) of the forward's and dQ's grids ``(b *
    h, rows, steps)``; ``q_block(row, step)`` and ``k_block(row, step)`` are
    the blocks the index maps name there. Causal-dense: the folded triangle,
    ``nq // 2`` rows of ``ratio * (nq + 1)`` steps, all live (an odd ``nq``
    has one more row, half of it dead). Under a window: a q block a row and
    the k blocks its band can touch (:func:`_band_k_block`; those before the
    sequence are block 0 again). Else the rectangle, all of it live."""
    nq, ratio = t // block_q, block_q // block_k
    if window is not None:
        steps = _band_steps(block_q, block_k, window)[0]
        return (nq, steps, lambda qi, step: qi, lambda qi, step: jnp.maximum(
            _band_k_block(qi, step, ratio, steps), 0))
    if _folded(nq, causal, window):
        return ((nq + 1) // 2, ratio * (nq + 1),
                lambda p, s: _q_fold(p, s, nq, ratio)[0],
                lambda p, s: _q_fold(p, s, nq, ratio)[1])
    return nq, t // block_k, lambda qi, ki: qi, lambda qi, ki: ki


def _k_major_grid(t, block_q, block_k, group, causal, window):
    """(rows, steps, k_block, head, q_block) of dK/dV's grid ``(b * hkv,
    rows, steps)``: the k block, the head of its group and the q block the
    index maps name at ``(row, step)``. Causal-dense: the folded triangle,
    ``nk // 2`` rows of ``group * (nq + 1)`` steps. Under a window: a k block
    a row and, a head, the q blocks its band can touch from its own on (those
    past the sequence are the last one again). Else the rectangle."""
    nq, nk, ratio = t // block_q, t // block_k, block_q // block_k
    if window is not None:
        q_steps = _band_steps(block_q, block_k, window)[1]
        return (nk, group * q_steps, lambda ki, j: ki,
                lambda ki, j: j // q_steps, lambda ki, j: jnp.minimum(
                    j % q_steps + ki // ratio, nq - 1))
    if _folded(nq, causal, window):
        def at(p, s):
            return _k_fold(p, s, nq, ratio, group)

        return ((nk + 1) // 2, group * (nq + 1), lambda p, s: at(p, s)[0],
                lambda p, s: at(p, s)[1], lambda p, s: at(p, s)[2])
    return (nk, group * nq, lambda ki, j: ki, lambda ki, j: j // nq,
            lambda ki, j: j % nq)


def _q_major_step(row, step, block_q, block_k, nk, causal, window):
    """Where the forward's and dQ's kernels stand at ``(row, step)`` of their
    grid: (qi, ki, i, count): the step is q block ``qi``'s ``i``-th of
    ``count``, the first ``_init``'s and the last ``_finalize``'s. ``nk``:
    the k blocks of a row. Under a window (``nk``: those the band can touch)
    the step is the band's, and the kernels place it."""
    ratio = block_q // block_k
    if not _folded(nk // ratio, causal, window):
        return row, step, step, nk
    qi, ki, live = _q_fold(row, step, nk // ratio, ratio)
    if live is not True:    # the middle row's dead half: above the diagonal
        ki = jnp.where(live, ki, nk)
    return qi, ki, ki, ratio * (qi + 1)


def _k_major_step(row, j, block_q, block_k, nq, group, causal, window):
    """Where dK/dV's kernel stands at ``(row, j)`` of its grid: (qi, ki, i,
    count): the step is k block ``ki``'s ``i``-th of ``count``. ``nq``: the q
    blocks of a column, under a window those its band can touch, from the one
    that holds the k block's own positions on."""
    ratio = block_q // block_k
    if not _folded(nq, causal, window):
        qi = j % nq if window is None else j % nq + row // ratio
        return qi, row, j, nq * group
    ki, _, qi, i, count, live = _k_fold(row, j, nq, ratio, group)
    if live is not True:    # the middle row's dead half: above the diagonal
        ki, i = jnp.where(live, ki, nq * ratio), jnp.where(live, i, -1)
    return qi, ki, i, count


def _q_major_specs(t, block_q, block_k, heads, causal, window, chunk=None):
    """((rows, steps), q_spec, kv_spec, stat_spec, words_spec) of the
    forward's and dQ's grids (:func:`_q_major_grid`): ``q_spec(width)`` for
    q, o, dO and dq, ``kv_spec(width)`` for k and v at the q row's key/value
    head (``heads = (h, hkv, group)``; ``kv_spec(width, (h, 1, h))`` for a key
    part that ONE head holds for all; ``lanes``: the block of that width
    along the lanes, where k and v lie side by side in one array),
    ``stat_spec`` for lse and delta. The
    index maps take a call's scalar-prefetched tables after the grid's
    indices: none, or a selected call's two (``chunk`` given). Then
    ``kv_spec`` and ``words_spec`` (of the q block's packed selection; ``()``
    without one) name the k block the second table holds: at a step whose
    block has no selected pair, the one already resident."""
    h, hkv, group = heads
    nq, nk = t // block_q, t // block_k
    rows, steps, q_block, k_block = _q_major_grid(t, block_q, block_k, causal,
                                                  window)

    def held_k(r, p, s, tables):
        if not tables:
            return k_block(p, s)
        return tables[1][((r // h) * nq + q_block(p, s)) * nk + k_block(p, s)]

    def q_spec(width):
        return pl.BlockSpec((1, block_q, width), lambda r, p, s, *tables: (
            r, q_block(p, s), 0))

    def kv_spec(width, kv_heads=(h, hkv, group), lanes=0):
        return pl.BlockSpec((1, block_k, width), lambda r, p, s, *tables: (
            _kv_row(r, *kv_heads), held_k(r, p, s, tables), lanes))

    stat_spec = pl.BlockSpec((1, 8, block_q), lambda r, p, s, *tables: (
        r, 0, q_block(p, s)))
    words_spec = () if chunk is None else (pl.BlockSpec(
        (1, block_q, chunk), lambda r, p, s, *tables: (
            r // h, q_block(p, s),
            _word_group(held_k(r, p, s, tables), block_k, chunk))),)
    return (rows, steps), q_spec, kv_spec, stat_spec, words_spec


# ------------------------------------------------- a selection that is data

class _Sel(NamedTuple):
    """What a selected call's kernels hold beside their operands
    (:func:`selected_attention`): ``live_ref``, the scalar-prefetched table
    (B, nq, nk) of the block steps that hold a selected pair; ``words_ref``,
    the q block's packed selection (``ops.sparse_attention.pack``; the
    transposed words in dK/dV's kernel, whose score tiles are); ``per_batch``:
    the rows of the grid's first axis a batch row takes."""
    live_ref: Any
    words_ref: Any
    chunk: int
    nq: int
    nk: int
    per_batch: int
    transposed: bool = False

    def live(self, qi, ki):
        b = pl.program_id(0) // self.per_batch
        return self.live_ref[(b * self.nq + jnp.minimum(qi, self.nq - 1))
                             * self.nk + jnp.minimum(ki, self.nk - 1)] != 0

    def keep(self, s, queries, base, c0, width, block_k):
        """The score sub-tile ``s`` with its pairs outside the selection at
        NEG_INF: ``queries`` the q block's slice, the keys ``[base + c0, base
        + c0 + width)`` of the k block that starts at ``base``."""
        from .sparse_attention import tile_bits

        return jnp.where(tile_bits(self.words_ref, (0,), queries, (base, c0),
                                   width, self.chunk, block_k,
                                   self.transposed), s, NEG_INF)


def _selection_tables(words, block_q, block_k, chunk):
    """The scalar-prefetched tables of a selected call, flat int32: (live (B,
    nq, nk); the k block to hold at each step of a q block's sweep; the q
    block to hold at each step of a k block's) - a dead step's index maps
    name the block already resident, so nothing is fetched for it."""
    from .sparse_attention import block_liveness, fetch_table

    live = block_liveness(words, block_q, block_k, chunk)
    return (live.astype(jnp.int32).reshape(-1), fetch_table(live).reshape(-1),
            fetch_table(jnp.swapaxes(live, 1, 2)).reshape(-1))


def _word_group(k_block, block_k, chunk):
    """The group of word columns (32 chunks of keys each) that holds a k
    block's chunks."""
    return _div(k_block * block_k, 32 * chunk)


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


def _causal_bodies(qi, ki, block_q, block_k, below, crossed, sel):
    """Block step ``(qi, ki)`` of a causal-dense grid, in two bodies: blocks
    wholly below the diagonal never build a mask (``below()``); the ``ratio``
    blocks it crosses each know where (``crossed(first)``). Blocks above it
    match neither: nothing runs there, nor (``sel``, a selected call's) where
    no pair of the block step is selected."""
    live = None if sel is None else sel.live(qi, ki)

    def when(cond):
        return pl.when(cond if live is None else cond & live)

    when(jnp.logical_not(_crossed(qi, ki, block_q, block_k)))(below)
    for j in range(block_q // block_k):
        when(ki == qi * (block_q // block_k) + j)(
            functools.partial(crossed, j * block_k))


def _keys(k_ref, shared, at):
    """Rows ``at`` of the loaded k block. With a shared part (``shared[0]``:
    the ONE head's block at the same positions, :func:`flash_attention`'s
    ``k_shared``) a head's own lanes and the shared ones side by side: joined
    in registers at the own part's last lane (a multiple of 128 on the TPU:
    whole registers), so every product that follows is an assembled k's."""
    k = k_ref[0, at, :]
    if shared is None:
        return k
    return jnp.concatenate([k, shared[0][0, at, :]], axis=1)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref, *,
                block_q, block_k, nk, causal, sm_scale, window=None, t=None,
                sel=None, shared=None):
    qi, ki, step, steps = _q_major_step(
        pl.program_id(1), pl.program_id(2), block_q, block_k, nk, causal,
        window)
    d = v_ref.shape[-1]     # the accumulator's width: v's, not q's
    ratio = block_q // block_k
    # The loaded tile is walked in (sub_q, sub_k) score sub-tiles: row groups
    # are independent (the scheduler overlaps one's softmax with the next
    # one's q @ k.T), and narrower column strips keep a stage's working set
    # small. Measured on v5e at 1024/1024, D=128: 256 x 512 (PERF.md §6).
    sub_q = _sub_tile(block_q, 256)
    sub_k = _sub_tile(block_k, 512)

    @pl.when(step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def sub_tile(r0, c0, off, sub_k=sub_k):
        """Online-softmax update of rows [r0, r0 + sub_q) with columns
        [c0, c0 + sub_k) of the loaded tile. ``off`` is None where no mask
        is needed, else :func:`_mask_offset`'s answer."""
        rows, cols = pl.ds(r0, sub_q), pl.ds(c0, sub_k)
        v = v_ref[0, cols, :]
        s = _masked(jax.lax.dot_general(
            q_ref[0, rows, :], _keys(k_ref, shared, cols), _NT,
            preferred_element_type=jnp.float32) * sm_scale, off, 0)
        if sel is not None:
            s = sel.keep(s, rows, ki * block_k, c0, sub_k, block_k)
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

    def crossed(first_col, sub_k=sub_k):
        """A block an edge of the band crosses; ``first_col`` is its first
        column less the q block's first row (static). Sub-tiles wholly
        outside the band (above the diagonal; under a window, below its
        lower edge too) are skipped, those an edge crosses are masked, the
        others are not."""
        for r0 in range(0, block_q, sub_q):
            for c0 in range(0, block_k, sub_k):
                off = _mask_offset(first_col + c0 - r0, sub_q, sub_k, window)
                if off is not False:
                    sub_tile(r0, c0, off, sub_k)

    if window is not None:
        # The grid's last axis holds only the k blocks a q block's band can
        # touch, the diagonal's last one last: a block step's place against
        # the band is static, and only its being in the sequence is not.
        # Crossed blocks are walked in the backward's finer columns: at a
        # window of a block or so most of their area lies outside the band.
        ki = _band_k_block(qi, step, ratio, nk)
        crossed_at, inside = _band_offsets(t, block_q, block_k, window)
        first = (step + ratio - nk) * block_k
        fine = _sub_tile(block_k, _BWD_SUB_CROSSED[1])
        if inside:
            pl.when((ki >= 0) & (first >= inside[0]) & (first <= inside[-1]))(
                below)
        for at in crossed_at:
            pl.when((ki >= 0) & (first == at))(
                functools.partial(crossed, at, fine))
    elif causal:
        _causal_bodies(qi, ki, block_q, block_k, below, crossed, sel)
    else:
        below()

    @pl.when(step == steps - 1)
    def _finalize():
        l = l_ref[...]
        o_ref[0] = (acc_ref[0] / _lanes(l, d)).astype(o_ref.dtype)
        # the one move per q block: rows down the sublanes -> along the lanes
        lse_ref[0] = (m_ref[...] + jnp.log(l)).T[:lse_ref.shape[1]]


# ------------------------------------------------------------------ backward

# Both backward kernels walk the loaded tile in score sub-tiles of (query
# positions, key positions), as the forward does: the f32 score-sized
# temporaries stay at a sub-tile's size. Below the diagonal the forward's
# 256 x 512: the size moves neither kernel by 0.5% from there to the whole
# tile with bf16 operands, but f32 operands traced under "highest" at 1024
# blocks overflow dkv's 16 MiB of scoped VMEM from 512 x 512 on (16 heads x
# 4096 x 128). A block the diagonal crosses is walked finer: there only the
# sub-tiles on or below the diagonal run (one strip per row group), and
# 256 x 256 skips 6 of a 1024 x 1024 block's 16 where 256 x 512 skips 2 of
# 8 (PERF.md §6, PR 35: the sweeps on the chip).
_BWD_SUB = (256, 512)
_BWD_SUB_CROSSED = (256, 256)


def _mask_offset(first, sub_q, sub_k, window=None):
    """How a score sub-tile of ``sub_q`` query by ``sub_k`` key positions
    meets the causal diagonal; ``first`` is its first key position less its
    first query position. False: wholly above the diagonal (skipped); None:
    wholly on or below it (no mask); else ``first``: the entry at query a,
    key b of the sub-tile is live where ``a - b >= first``. With a
    ``window`` the band has a lower edge too, ``a - b < first + window``:
    False also where the sub-tile lies wholly below it, None where neither
    edge crosses it, else ``(lo, hi)`` with the entry live where ``lo <=
    a - b < hi``, an edge that does not cross it None."""
    if first > sub_q - 1:
        return False
    lo = first if first + sub_k - 1 > 0 else None
    if window is None:
        return lo
    hi = first + window
    if hi <= -(sub_k - 1):
        return False
    hi = hi if hi <= sub_q - 1 else None
    return None if lo is None and hi is None else (lo, hi)


def _masked(s, off, q_axis):
    """A score sub-tile with its entries outside the band at NEG_INF;
    ``off`` is :func:`_mask_offset`'s answer (None: as it is), ``q_axis``
    the axis its queries lie along."""
    if off is None:
        return s
    a = jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
    b = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
    if not isinstance(off, tuple):
        return jnp.where(a - b >= off, s, NEG_INF)
    lo, hi = off
    live = (a - b < hi if lo is None else a - b >= lo if hi is None
            else (a - b >= lo) & (a - b < hi))
    return jnp.where(live, s, NEG_INF)


def _walk(causal, q_major, qi, ki, block_q, block_k, tile, add, window=None,
          t=None, sel=None):
    """The walk both backward kernels share over block step ``(qi, ki)``.
    The accumulators' rows lie along the queries (``q_major``: dq) or along
    the keys (dk/dv); the other axis is summed over. ``tile(rows, cols,
    off)`` returns one sub-tile's contributions to the accumulators' rows
    (``rows`` and ``cols`` are slices of the loaded block, ``off`` is None
    or :func:`_mask_offset`'s offset); ``add(rows, parts)`` adds a row
    group's sum once. With a ``window`` (of a sequence of ``t``) a block step
    outside the sequence, which a windowed grid's first or last rows hold,
    runs nothing. ``sel`` (a selected call's): nothing runs at a block step
    none of whose pairs is selected."""
    def extents(sub):
        """(rows, a row group's, columns, a strip's) of the loaded tile."""
        sub_q, sub_k = _sub_tile(block_q, sub[0]), _sub_tile(block_k, sub[1])
        return ((block_q, sub_q, block_k, sub_k) if q_major
                else (block_k, sub_k, block_q, sub_q))

    def below():
        """Wholly below the diagonal (or non-causal): no mask. Row groups
        are one traced body, unrolled when lowered."""
        n_rows, sub_rows, n_cols, sub_cols = extents(_BWD_SUB)

        def row_group(i, carry):
            rows = pl.ds(pl.multiple_of(i * sub_rows, sub_rows), sub_rows)
            strips = [tile(rows, pl.ds(c0, sub_cols), None)
                      for c0 in range(0, n_cols, sub_cols)]
            add(rows, [sum(part) for part in zip(*strips)])
            return carry
        jax.lax.fori_loop(0, n_rows // sub_rows, row_group, 0, unroll=True)

    def crossed(first):
        """A block an edge of the band crosses, its first key less its first
        query ``first`` (all static; ``j * block_k`` for the ``j``-th block
        the diagonal crosses): of a row group's sub-tiles, those wholly
        outside the band are skipped and the rest, a contiguous run, are one
        masked strip."""
        n_rows, sub_rows, n_cols, sub_cols = extents(_BWD_SUB_CROSSED)

        def offset(r0, c0, width):
            (q0, k0), (ext_q, ext_k) = (
                ((r0, c0), (sub_rows, width)) if q_major
                else ((c0, r0), (width, sub_rows)))
            return _mask_offset(first + k0 - q0, ext_q, ext_k, window)

        for r0 in range(0, n_rows, sub_rows):
            rows = pl.ds(r0, sub_rows)
            live = [c0 for c0 in range(0, n_cols, sub_cols)
                    if offset(r0, c0, sub_cols) is not False]
            if live:
                width = live[-1] + sub_cols - live[0]
                add(rows, tile(rows, pl.ds(live[0], width),
                               offset(r0, live[0], width)))

    if not causal:
        return below()
    if window is not None:
        crossed_at, inside = _band_offsets(t, block_q, block_k, window)
        first = ki * block_k - qi * block_q
        here = (ki >= 0) & (qi < t // block_q)
        if inside:
            pl.when(here & (first >= inside[0]) & (first <= inside[-1]))(below)
        for at in crossed_at:
            pl.when(here & (first == at))(functools.partial(crossed, at))
        return
    _causal_bodies(qi, ki, block_q, block_k, below, crossed, sel)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_acc_ref, lse_rows_ref, delta_rows_ref, *, block_q, block_k,
               nk, causal, sm_scale, window=None, t=None, sel=None,
               shared=None):
    """Query-major: a score sub-tile has its queries down the sublanes, and
    lse and delta are read from lane-replicated scratch, as the forward
    keeps m and l."""
    qi, ki, step, steps = _q_major_step(
        pl.program_id(1), pl.program_id(2), block_q, block_k, nk, causal,
        window)
    if window is not None:      # the forward's grid: the band's k blocks
        ki = _band_k_block(qi, step, block_q // block_k, nk)

    @pl.when(step == 0)
    def _init():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)
        # the one move per q block: along the lanes (as stored) -> rows down
        # the sublanes, replicated along the lanes
        for ref, rows_ref in ((lse_ref, lse_rows_ref),
                              (delta_ref, delta_rows_ref)):
            rows_ref[...] = jnp.broadcast_to(
                ref[0, :1, :], (STAT_LANES, block_q)).T

    def tile(rows, cols, off):
        k = _keys(k_ref, shared, cols)
        s = _masked(jax.lax.dot_general(
            q_ref[0, rows, :], k, _NT,
            preferred_element_type=jnp.float32) * sm_scale, off, 0)
        if sel is not None:
            s = sel.keep(s, rows, ki * block_k, cols.start, cols.size, block_k)
        p = jnp.exp(s - _lanes(lse_rows_ref[rows, :], s.shape[1]))
        dp = jax.lax.dot_general(
            do_ref[0, rows, :], v_ref[0, cols, :], _NT,
            preferred_element_type=jnp.float32)
        ds = p * (dp - _lanes(delta_rows_ref[rows, :], s.shape[1]))
        return (jax.lax.dot_general(ds.astype(k.dtype), k, _NN,
                                    preferred_element_type=jnp.float32),)

    def add(rows, parts):
        dq_acc_ref[0, rows, :] = dq_acc_ref[0, rows, :] + parts[0]

    _walk(causal, True, qi, ki, block_q, block_k, tile, add, window, t, sel)

    @pl.when(step == steps - 1)
    def _finalize():
        dq_ref[0] = (dq_acc_ref[0] * sm_scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                dv_ref, dk_acc_ref, dv_acc_ref, *, block_q, block_k, nq,
                group, causal, sm_scale, window=None, t=None, sel=None,
                shared=None):
    """Key-major: a score sub-tile is TRANSPOSED, keys down the sublanes and
    queries along the lanes, so the four products are a @ b.T (k . q^T,
    v . dO^T) and a @ b (pT @ dO, dsT @ q), none with a transposed left
    operand, and lse and delta are used as they are stored: along the lanes,
    broadcast down the sublanes. With a shared key part (``shared``: its
    block, and the block of its gradient) dk is accumulated whole and written
    in its two parts: the head's own lanes to ``dk_ref``, the shared lanes'
    to this head's row of the shared part's gradient, which the call sums
    over the heads."""
    # The innermost grid dim walks (g, qi): for GQA (group > 1) the same
    # k/v-head block accumulates gradient contributions from every q head
    # in its group — the grid dim 0 row is a KV row, and the sweep runs over
    # the group's q blocks. group == 1 reduces to the plain walk over qi.
    qi, ki, step, steps = _k_major_step(
        pl.program_id(1), pl.program_id(2), block_q, block_k, nq, group,
        causal, window)

    @pl.when(step == 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    def tile(rows, cols, off):
        q, do = q_ref[0, cols, :], do_ref[0, cols, :]
        st = _masked(jax.lax.dot_general(
            _keys(k_ref, shared, rows), q, _NT,
            preferred_element_type=jnp.float32) * sm_scale, off, 1)
        if sel is not None:     # the transposed words: keys down the sublanes
            st = sel.keep(st, cols, ki * block_k, rows.start, rows.size,
                          block_k)
        pt = jnp.exp(st - lse_ref[0, :1, cols])
        dpt = jax.lax.dot_general(
            v_ref[0, rows, :], do, _NT, preferred_element_type=jnp.float32)
        dst = pt * (dpt - delta_ref[0, :1, cols])
        return (jax.lax.dot_general(dst.astype(q.dtype), q, _NN,
                                    preferred_element_type=jnp.float32),
                jax.lax.dot_general(pt.astype(do.dtype), do, _NN,
                                    preferred_element_type=jnp.float32))

    def add(rows, parts):
        dk_acc_ref[0, rows, :] = dk_acc_ref[0, rows, :] + parts[0]
        dv_acc_ref[0, rows, :] = dv_acc_ref[0, rows, :] + parts[1]

    _walk(causal, False, qi, ki, block_q, block_k, tile, add, window, t, sel)

    @pl.when(step == steps - 1)
    def _finalize():
        dk = (dk_acc_ref[0] * sm_scale).astype(dk_ref.dtype)
        if shared is None:
            dk_ref[0] = dk
        else:
            own = dk_ref.shape[-1]
            dk_ref[0], shared[1][0] = dk[:, :own], dk[:, own:]
        dv_ref[0] = dv_acc_ref[0].astype(dv_ref.dtype)


# ----------------------------------------------------------------- public API

def _fit_block(t, want, quantum):
    """Largest block <= want that divides t and is a multiple of quantum
    (TPU tiling), or t itself when t <= want. A ceiling below the quantum
    rounds up to the quantum (a sub-quantum block can never lower on TPU)."""
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
# (examples/transformer_benchmark.py --sweep-blocks, on an earlier
# installation) at D=64, with the forward the kernels had then:
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


# Operands cross the kernels' boundary as head-major copies ``(B * H, T,
# D)``, whatever their width. The free view ``(B, T, H * D)`` with the head
# chosen by the index maps (possible where ``D % 128 == 0``) was built and
# measured on the v5e (PERF.md §6, PR 39) and is slower: a block becomes
# ``block / 8`` runs of one 2 KiB tile a stride of ``H`` tiles apart (the
# kernels alone +1..+8%), and a 4-D ``(B, T, H, D)`` array is tiled over ``(H,
# D)``, so the reshape is never a bitcast, while XLA folds most of these
# transposes into the layout the neighbouring fusions write and read.

def _rows(x, b, t, h, d):
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _unrows(x, b, t, h, d):
    return x.reshape(b, h, t, d).transpose(0, 2, 1, 3)


def _gqa_group(q, k, v, shared=None):
    """(h, hkv, group) for grouped-query attention: q has h heads, k/v may
    have fewer (hkv), each shared by a contiguous group of h//hkv q heads
    (the standard GQA layout). h == hkv is plain multi-head. ``shared``: the
    key part ONE head holds for all, q's trailing lanes' partner. ``v``
    None: k's array holds ``[k | v]`` a head (:func:`_kv_widths`)."""
    h, hkv = q.shape[2], k.shape[2]
    if v is not None and v.shape[2] != hkv:
        raise ValueError(f"k has {hkv} heads but v has {v.shape[2]}")
    if h % hkv:
        raise ValueError(f"q heads {h} not divisible by kv heads {hkv}")
    if shared is not None and shared.shape[:3] != (*k.shape[:2], 1):
        raise ValueError(f"the shared key part {shared.shape} is not one "
                         f"head at k's {k.shape[:2]} positions")
    _kv_widths(q, k, v, shared)
    return h, hkv, h // hkv


def _kv_widths(q, k, v, shared=None):
    """(dn, dv): the lanes of a key that a head owns (q's, less a shared
    part's) and v's. With ``v`` None k's array is ``[k | v]`` side by side a
    head, as ONE projection wrote them, and v is what follows k's ``dn``
    lanes: the index maps then read the two out of the one array by lane
    block, so ``dn`` must be a whole number of v's widths."""
    dn = q.shape[3] - (0 if shared is None else shared.shape[3])
    if v is not None:
        if k.shape[3] != dn:
            raise ValueError(f"q has head size {q.shape[3]} but k has "
                             f"{k.shape[3] + q.shape[3] - dn}")
        return dn, v.shape[3]
    dv = k.shape[3] - dn
    if dv < 1 or dn % dv:
        raise ValueError(f"[k | v] of {k.shape[3]} lanes a head is no k of "
                         f"{dn} and a v that divides it")
    return dn, dv


def _kv_row(r, h, hkv, group):
    """Map a q-row index (b*h + head) to its kv-row (b*hkv + head//group)."""
    return (r // h) * hkv + (r % h) // group


def _group_q_row(r, h, hkv, group):
    """Inverse of :func:`_kv_row` for the dK/dV grids: the first head of
    kv-row ``r``'s group is q-row b*h + kv_head*group, its head ``g`` the
    ``g``-th after it. The single definition keeps the group layout in one
    place — the two must stay inverses."""
    return (r // hkv) * h + (r % hkv) * group


def _q_row(r, j, nq, h, hkv, group):
    """The q-row at a rectangular grid's innermost index ``j``, which sweeps
    (g, qi) over ``nq`` q blocks a head."""
    return _group_q_row(r, h, hkv, group) + j // nq


def _plan(t, block_q, block_k, interpret, window):
    """(block_q, block_k, window) a call runs at. A window that reaches the
    sequence's first position from its last is no window: the call is the
    causal-dense one, bit for bit. Blocks the caller did not give (None) are
    the defaults, under a window too: at 16,384 x 64 over 8 x 128 and a
    window of 512 the 1024 x 1024 blocks (31 block steps a head, the
    sub-tiles outside the band skipped) ran the three kernels in 13.2 ms a
    call, blocks of the window's own size (63 steps) in 15.8, 256 x 256 in
    24.0 (PERF.md §6, PR 36: a block step's fixed cost outweighs the area)."""
    if window is not None and window >= t:
        window = None
    block_q, block_k = _check_blocks(
        t, DEFAULT_BLOCK_Q if block_q is None else block_q,
        DEFAULT_BLOCK_K if block_k is None else block_k, interpret)
    return block_q, block_k, window


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention(q, k, v, causal: bool = True,
                    block_q: int | None = None,
                    block_k: int | None = None,
                    interpret: bool = False,
                    sm_scale: float | None = None,
                    window: int | None = None,
                    k_shared=None):
    """Fused attention, trainable. q: ``(B, T, H, D)``, k/v: ``(B, T, H, D)``
    or ``(B, T, Hkv, D)`` with ``H % Hkv == 0`` for grouped-query attention
    (each kv head serves a contiguous group of q heads — no head
    replication ever materializes; the kernels alias the shared kv block
    via the grid index map). v's head size may differ from q's and k's
    (latent attention: 192 | 128): the output, dO, dV and their accumulators
    follow v, dq and dk follow q; the default scale is q's ``D ** -0.5``.
    No width crosses into the kernels without a copy: every operand and
    result is relaid head-major round the call (:func:`_rows`).
    ``k_shared`` ``(B, T, 1, Dr)`` (latent attention's rotary key): the part
    of every head's key that ONE head holds for all. ``k`` is then the heads'
    own ``D - Dr`` leading lanes, the score of a pair is ``q[:D - Dr] . k +
    q[D - Dr:] . k_shared``, and no k of ``D`` lanes a head exists outside the
    kernels: their index maps read the one head's block for every row, as the
    grouped-query path reads a shared head, and dK/dV writes that part's
    gradient a head, summed over the heads here. It is one more
    differentiable operand; not with a ``window``. On the TPU ``D - Dr`` is a
    multiple of 128.
    ``v=None``: ``k`` holds ``[k | v]`` side by side a head, ``(B, T, Hkv, Dk
    + Dv)`` as ONE projection wrote them (latent attention's ``kv_b_proj``),
    ``Dk`` a whole number of ``Dv`` (on the TPU both multiples of 128). The
    index maps read k and v out of the one head-major copy by lane block,
    dK/dV writes ``[dk | dv]`` the same way, and ``k``'s gradient is that
    array: no split before the call and no concatenation after it.
    Sequence length must be a multiple of
    ``block_q`` and ``block_q`` of ``block_k`` (both clamp down to the
    sequence length for short inputs; None: ``DEFAULT_BLOCK_Q`` /
    ``DEFAULT_BLOCK_K``, which measured fastest on v5e
    at d=64 — bigger blocks amortize scratch round-trips and feed the MXU
    wider). q, k and v go to the MXU in the dtype they arrive in; the
    softmax statistics, the accumulators and ``exp`` are f32 throughout.
    ``sm_scale`` multiplies the f32 scores inside the kernels, forward and
    backward (None: ``D ** -0.5``; Granite's ``attention_multiplier`` is not).
    ``window`` (causal only): the query at position p sees the keys ``p -
    window < j <= p``, itself included (Hugging Face's sliding-window mask).
    All three kernels then run grids that hold only the k blocks (q blocks)
    a block's band can touch, fetch and compute nothing else, skip the
    sub-tiles wholly outside the band and mask those an edge crosses; they
    carry names of their own (``hvd_flash_win_*``).
    ``interpret=True`` runs the kernels in the Pallas interpreter (CPU
    tests); the default compiles them for the TPU and raises on a machine
    that has none."""
    out, _ = _fwd(q, k, v, causal, block_q, block_k, interpret, sm_scale,
                  window, k_shared)
    return out


def _fwd(q, k, v, causal, block_q, block_k, interpret, sm_scale, window,
         k_shared=None):
    from ..metrics import record_flash_plan, record_flash_window_plan
    if window is not None and (not causal or window < 1):
        raise ValueError(f"window={window} needs causal=True and window >= 1")
    if window is not None and k_shared is not None:
        raise ValueError("a shared key part under a window is not built")
    t = q.shape[1]
    block_q, block_k, window = _plan(t, block_q, block_k, interpret, window)
    census = block_census(t, block_q, block_k, causal, window)
    rows, steps = _q_major_grid(t, block_q, block_k, causal, window)[:2]
    record_flash_plan(*census, grid_steps=(
        rows * steps if causal and window is None else None),
        shared_key_lanes=0 if k_shared is None else k_shared.shape[3])
    if window is not None:
        record_flash_window_plan(
            census[0], block_census(t, block_q, block_k, causal)[0])
    return _fwd_call(q, k, v, causal, block_q, block_k, interpret, sm_scale,
                     window, k_shared)


def _calls(q, k, v, causal, block_q, block_k, interpret, sm_scale, window,
           words=None, chunk=None, shared=None):
    """(forward, backward), the builders of a call's three ``pallas_call``s
    at fitted blocks (:func:`_plan`): the grid, block specs, scratch, output
    shapes and device name of each are stated here and nowhere else. A dense
    call hands in its operands alone; a ``window`` makes the grids the band's
    and goes to the kernels; a selection (``words``, ``chunk``) brings each
    call two scalar-prefetched tables, the words as one more operand and the
    kernels' ``sel=``; a ``shared`` key part (a dense call's alone) is one
    more operand of each call behind k's own index map, the kernels'
    ``shared=``, and one more output of dK/dV's; ``v`` None makes k's array
    both the k and the v operand, each a lane block of it, and dK/dV's two
    results one. ``forward()`` -> (out, residuals: the operands, out
    head-major, lse (B * H, 8, T)); ``backward(out, lse, dout)`` -> grads,
    None for a v that k's array holds, the shared part's last."""
    b, t, h, d = q.shape
    h, hkv, group = _gqa_group(q, k, v, shared)
    dn, dv = _kv_widths(q, k, v, shared)    # dn: the lanes a head's key owns
    fused = v is None       # [k | v] in one array: v the lane block after k's
    dkv, v_lanes = k.shape[3], dn // dv if fused else 0
    static = dict(block_q=block_q, block_k=block_k, causal=causal,
                  sm_scale=d ** -0.5 if sm_scale is None else sm_scale)
    # a row's k blocks and a column's q blocks, as the kernels count them
    k_steps, q_steps = t // block_k, t // block_q
    names = FLASH_FWD, FLASH_BWD_DQ, FLASH_BWD_DKV      # on the device
    tables, extra, sel = (), (), None
    if window is not None:      # those a band can touch; never with a selection
        names = FLASH_WIN_FWD, FLASH_WIN_BWD_DQ, FLASH_WIN_BWD_DKV
        static.update(window=window, t=t)
        k_steps, q_steps = _band_steps(block_q, block_k, window)
    elif words is not None:
        names = FLASH_SEL_FWD, FLASH_SEL_BWD_DQ, FLASH_SEL_BWD_DKV
        tables = _selection_tables(words, block_q, block_k, chunk)
        extra, sel = (words,), (chunk, q_steps, k_steps)
    (rows, steps), q_spec, kv_spec, stat_spec, extra_spec = _q_major_specs(
        t, block_q, block_k, (h, hkv, group), causal, window, chunk)
    if shared is not None:      # one head: head-major as it lies
        extra = (shared.reshape(b, t, d - dn),)
        extra_spec = (kv_spec(d - dn, (h, 1, h)),)

    def kernel(body, n_in, *per_batch, grad_at=None, **extent):
        """``body`` as a ``pallas_call`` runs it: a selected call's is given
        the two prefetched tables first and the words after the ``n_in``
        operands, the body's ``sel=`` (``per_batch``: :class:`_Sel`'s own);
        a call with a shared key part hands that operand's ref, which follows
        the ``n_in`` too, to the body's ``shared=``, and with it the ref of
        its gradient (dK/dV's: the body's ``grad_at``-th without it)."""
        if shared is not None:
            def with_shared(*refs):
                refs = list(refs)
                part = (refs.pop(n_in),)
                if grad_at is not None:
                    part += (refs.pop(grad_at),)
                body(*refs, **extent, **static, shared=part)
            return with_shared
        if sel is None:
            return functools.partial(body, **extent, **static)

        def selected(live_ref, at_ref, *refs):
            body(*refs[:n_in], *refs[n_in + 1:], **extent, **static,
                 sel=_Sel(live_ref, refs[n_in], *sel, *per_batch))
        return selected

    def kv_rows():
        kr = _rows(k, b, t, hkv, dkv)
        return kr, kr if fused else _rows(v, b, t, hkv, dv)

    def forward():
        out, lse = pl.pallas_call(
            kernel(_fwd_kernel, 3, h, nk=k_steps),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(tables[:2]), grid=(b * h, rows, steps),
                in_specs=[q_spec(d), kv_spec(dn), kv_spec(dv, lanes=v_lanes),
                          *extra_spec],
                out_specs=[q_spec(dv), stat_spec],
                scratch_shapes=[
                    pltpu.VMEM((1, block_q, dv), jnp.float32),       # acc
                    pltpu.VMEM((block_q, STAT_LANES), jnp.float32),  # m
                    pltpu.VMEM((block_q, STAT_LANES), jnp.float32),  # l
                ]),
            out_shape=[
                jax.ShapeDtypeStruct((b * h, t, dv), q.dtype),
                jax.ShapeDtypeStruct((b * h, 8, t), jnp.float32),
            ],
            interpret=interpret,
            name=names[0],
        )(*tables[:2], _rows(q, b, t, h, d), *kv_rows(), *extra)
        return _unrows(out, b, t, h, dv), (
            q, k, v, *(extra if shared is None else (shared,)), out, lse)

    def backward(out, lse, dout):
        qr, dor = _rows(q, b, t, h, d), _rows(dout, b, t, h, dv)
        kr, vr = kv_rows()
        # D_i = rowsum(dO ∘ O): cheap elementwise reduction, done outside;
        # broadcast to the same (rows, 8, t) sublane layout as lse
        delta = jnp.sum(dor.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1)
        delta = jnp.broadcast_to(delta[:, None, :], (b * h, 8, t))
        dq = pl.pallas_call(
            kernel(_dq_kernel, 6, h, nk=k_steps),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(tables[:2]), grid=(b * h, rows, steps),
                in_specs=[q_spec(d), kv_spec(dn), kv_spec(dv, lanes=v_lanes),
                          q_spec(dv), stat_spec, stat_spec, *extra_spec],
                out_specs=q_spec(d),
                scratch_shapes=[
                    pltpu.VMEM((1, block_q, d), jnp.float32),   # dq acc
                    pltpu.VMEM((block_q, STAT_LANES), jnp.float32),  # lse
                    pltpu.VMEM((block_q, STAT_LANES), jnp.float32),  # delta
                ]),                             # the two by rows
            out_shape=jax.ShapeDtypeStruct((b * h, t, d), q.dtype),
            interpret=interpret,
            name=names[1],
        )(*tables[:2], qr, kr, vr, dor, lse, delta, *extra)

        # dK/dV: one grid row per KV row; the innermost dim sweeps (g, qi) so
        # a shared kv head accumulates all of its group's q-head contributions
        # in scratch before writing out (grid dim 0 = b*hkv, not b*h). Of a
        # selection's tables it takes the first and the third: the q block to
        # name, at a step with no selected pair the one already resident.
        k_rows, sweep, k_block, head, q_block = _k_major_grid(
            t, block_q, block_k, group, causal, window)

        def held_q(r, p, s, tables):
            if not tables:
                return q_block(p, s)
            return tables[1][((r // hkv) * k_steps + k_block(p, s)) * q_steps
                             + q_block(p, s)]

        def q_row(r, p, s):
            return _group_q_row(r, h, hkv, group) + head(p, s)

        def qd(width):
            return pl.BlockSpec((1, block_q, width), lambda r, p, s, *tables: (
                q_row(r, p, s), held_q(r, p, s, tables), 0))

        def kd(width, lanes=0):
            return pl.BlockSpec((1, block_k, width), lambda r, p, s, *tables: (
                r, k_block(p, s), lanes))

        row = pl.BlockSpec((1, 8, block_q), lambda r, p, s, *tables: (
            q_row(r, p, s), 0, held_q(r, p, s, tables)))
        # dK/dV's score tiles have their keys down the sublanes: the words too
        extra_t, spec_t, grad_spec, grad_shape = (), (), (), ()
        if sel is not None:
            extra_t = (jnp.swapaxes(words, 1, 2),)
            spec_t = (pl.BlockSpec(
                (1, chunk, block_q), lambda r, p, s, *tables: (
                    r // hkv, _word_group(k_block(p, s), block_k, chunk),
                    held_q(r, p, s, tables))),)
        if shared is not None:
            # the one head's block in, a head's share of its gradient out
            extra_t = extra
            spec_t = (pl.BlockSpec(
                (1, block_k, d - dn), lambda r, p, s, *tables: (
                    r // hkv, k_block(p, s), 0)),)
            grad_spec = (kd(d - dn),)
            grad_shape = (jax.ShapeDtypeStruct((b * hkv, t, d - dn), k.dtype),)
        body, widths = _dkv_kernel, (dn, dv)
        if fused:
            # ONE result a kv row, [dk | dv] as the operand lies: the body
            # writes each through a view of its lanes
            widths = (dkv,)

            def body(*refs, **kw):
                _dkv_kernel(*refs[:6], refs[6].at[:, :, :dn],
                            refs[6].at[:, :, dn:], *refs[7:], **kw)
        results = pl.pallas_call(
            kernel(body, 6, hkv, True, grad_at=6 + len(widths), nq=q_steps,
                   group=group),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(tables[::2]),
                grid=(b * hkv, k_rows, sweep),
                in_specs=[qd(d), kd(dn), kd(dv, v_lanes), qd(dv), row, row,
                          *spec_t],
                out_specs=[*map(kd, widths), *grad_spec],
                scratch_shapes=[
                    pltpu.VMEM((1, block_k, d), jnp.float32),   # dk acc
                    pltpu.VMEM((1, block_k, dv), jnp.float32),  # dv acc
                ]),
            out_shape=[
                *(jax.ShapeDtypeStruct((b * hkv, t, width), x.dtype)
                  for width, x in zip(widths, (k, v))),
                *grad_shape,
            ],
            interpret=interpret,
            name=names[2],
        )(*tables[::2], qr, kr, vr, dor, lse, delta, *extra_t)
        by_head = results[len(widths):]
        return (_unrows(dq, b, t, h, d),
                *(_unrows(x, b, t, hkv, width)
                  for x, width in zip(results, widths)),
                *((None,) if fused else ()),
                *(jnp.sum(x.reshape(b, hkv, t, 1, d - dn), axis=1,
                          dtype=jnp.float32).astype(shared.dtype)
                  for x in by_head))

    return forward, backward


# The calls are jitted so that the layers of a model, which call them with
# one signature, share ONE traced and lowered copy of each kernel: the
# kernels' bodies are unrolled and cost seconds to trace and lower, and a
# step is traced and lowered on every start, warm or cold.
@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8))
def _fwd_call(q, k, v, causal, block_q, block_k, interpret, sm_scale,
              window=None, shared=None):
    forward, _ = _calls(q, k, v, causal, block_q, block_k, interpret,
                        sm_scale, window, shared=shared)
    return forward()


def _bwd(causal, block_q, block_k, interpret, sm_scale, window, res, dout):
    block_q, block_k, window = _plan(res[0].shape[1], block_q, block_k,
                                     interpret, window)
    grads = _bwd_rule(causal, block_q, block_k, interpret, sm_scale, window,
                      res, dout)
    return (*grads, None)[:4]       # a call without a shared part: None


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5))
def _bwd_rule(causal, block_q, block_k, interpret, sm_scale, window, res,
              dout):
    q, k, v, *shared, out, lse = res
    _, backward = _calls(q, k, v, causal, block_q, block_k, interpret,
                         sm_scale, window,
                         shared=shared[0] if shared else None)
    return backward(out, lse, dout)


flash_attention.defvjp(_fwd, _bwd)


# ------------------------------------------- attention over a selection

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def selected_attention(q, k, v, words, block_q: int | None = None,
                       block_k: int | None = None, interpret: bool = False,
                       sm_scale: float | None = None, chunk: int = 512):
    """Causal attention of each query over ITS selection of the earlier keys:
    ``(out (B, T, H, Dv), lse (B, H, T) float32)``. ``words`` (B, T, columns)
    int32 is the selection as bits, one a pair, every head's the same
    (``ops.sparse_attention.select`` / ``pack``: key ``s`` of query ``t`` in
    bit ``(s // chunk) % 32`` of column ``(s // chunk // 32) * chunk + s %
    chunk``); it carries no gradient, and only pairs ``s <= t`` may be set,
    at least one a query. The kernels are :func:`flash_attention`'s own -
    tile loop, online softmax, folded causal grids, grouped-query index maps -
    with the mask one more operand, under names of their own
    (``hvd_flash_sel_*``): a sub-tile's mask is a shift of a lane-aligned
    slice of the words (dK/dV's kernel, whose score tiles are transposed,
    reads the transposed words, a transient of the backward); a block step
    none of whose pairs is selected runs nothing (the liveness table is
    scalar-prefetched) and fetches nothing (its index maps name the block
    already resident). ``lse`` is the per-head logsumexp over the selection,
    for ``ops.sparse_attention.align_loss``; its cotangent is not used.
    ``block_k`` must hold whole chunks on the TPU."""
    return _sel_fwd(q, k, v, words, block_q, block_k, interpret, sm_scale,
                    chunk)[0]


def _sel_fwd(q, k, v, words, block_q, block_k, interpret, sm_scale, chunk):
    from ..metrics import record_flash_plan
    b, t, h, _ = q.shape
    block_q, block_k, _ = _plan(t, block_q, block_k, interpret, None)
    if (block_k % chunk and not interpret) or block_k > 32 * chunk:
        raise ValueError(f"block_k {block_k} must hold whole chunks of "
                         f"{chunk} keys, 32 at most")
    rows, steps = _q_major_grid(t, block_q, block_k, True, None)[:2]
    record_flash_plan(*block_census(t, block_q, block_k, True),
                      grid_steps=rows * steps)
    out, res = _sel_fwd_call(q, k, v, words, block_q, block_k, interpret,
                             sm_scale, chunk)
    return (out, res[-1][:, 0, :].reshape(b, h, t)), res


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8))
def _sel_fwd_call(q, k, v, words, block_q, block_k, interpret, sm_scale,
                  chunk):
    forward, _ = _calls(q, k, v, True, block_q, block_k, interpret, sm_scale,
                        None, words, chunk)
    return forward()


def _sel_bwd(block_q, block_k, interpret, sm_scale, chunk, res, cotangents):
    block_q, block_k, _ = _plan(res[0].shape[1], block_q, block_k, interpret,
                                None)
    return (*_sel_bwd_rule(block_q, block_k, interpret, sm_scale, chunk, res,
                           cotangents[0]), None)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _sel_bwd_rule(block_q, block_k, interpret, sm_scale, chunk, res, dout):
    _, backward = _calls(*res[:3], True, block_q, block_k, interpret,
                         sm_scale, None, res[3], chunk)
    return backward(*res[4:], dout)


selected_attention.defvjp(_sel_fwd, _sel_bwd)
