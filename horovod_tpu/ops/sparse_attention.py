"""Learned sparse attention: a lightning indexer scores every earlier token,
each query keeps its ``topk`` best, and attention runs over that selection
alone (DeepSeek Sparse Attention: DeepSeek-V3.2-Exp's technical report and
arXiv:2512.02556 §2.1; docs/sparse-attention.md has the equations).

Four pieces, none of which holds a (T x T) array of floats in HBM:

* :func:`select` - the indexer's score ``I[t, s] = sum_j w[t, j] ReLU(qI[t,
  j] . kI[s])`` (``indexer_heads`` query heads against ONE shared key head,
  float32) in tiles by a kernel (``hvd_dsa_indexer_scores``), ``q_chunk``
  query rows at a time; of each row the EXACT ``topk`` largest among its
  causal keys (all of them while ``t < topk``; ties to the lower position, as
  ``lax.top_k``) by a radix select on the scores' bit patterns in ONE kernel
  (``hvd_dsa_select``) that holds a tile of 128 queries' order keys in VMEM -
  32 counting passes over the key chunks at or before the tile's last query
  and over no other, no sort - and writes the selection as BITS, one a pair
  (:func:`pack`'s layout): ``words[b, t, c]`` holds, in bit ``n % 32``, whether
  query ``t`` keeps key ``n * chunk + c % chunk`` for the 32 key chunks ``n``
  of word group ``c // chunk``. A score sub-tile's mask is then ``(words >>
  n) & 1`` on a lane-aligned slice of the query block's words: no lane is
  moved.
* ``ops.flash_attention.selected_attention`` - the three flash kernels with
  that mask as one more operand; a block step none of whose pairs is selected
  fetches and runs nothing (:func:`block_liveness`, scalar-prefetched).
* :func:`align_loss` - the sparse training stage's loss ``mean_t KL(p_t ||
  r_t)`` over the selected pairs, ``p`` the main attention's probabilities
  averaged over the heads (recomputed tile by tile from the forward's per-head
  logsumexp), ``r`` the softmax of ``I`` over the selection; and its gradient
  ``r - p`` into ``I`` carried on through the weighted sum and the ReLU into
  ``qI``, ``kI`` and ``w`` in the same tile loop (``hvd_dsa_align_tiles``),
  which keeps a tile's ``ReLU(z)`` in VMEM between the two.
* the names and the census (:func:`census`): selected pairs and live block
  steps of a step, from the data.

The selection carries no gradient and is named (``SELECTED``) so that a
caller that recomputes a layer in its backward pass saves it: a recomputed
selection that breaks a float32 tie the other way would have the backward
differentiate another set than the forward ran (ops/moe.py ``CHOSEN_EXPERTS``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common.device_names import (DSA_ALIGN, DSA_ALIGN_TILES, DSA_INDEXER,
                                   DSA_INDEXER_SCORES, DSA_SELECT)
from .flash_attention import _fit_block

SELECTED = "dsa_selected"       # the checkpoint name of words and lse_i
# ... and of the alignment loss's residuals, which ARE its gradients: a caller
# that recomputes a layer saves them too (bf16 (T, heads x index_dim) and two
# small ones a layer) and the recomputation runs no second alignment pass.
ALIGN_GRADS = "dsa_align_grads"
WORD_BITS = 32
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_NN = (((1,), (0,)), ((), ()))      # a @ b
# The align kernel's scoped VMEM, of 128 MiB on a v5e. At the cell's shape (32
# heads over 4 of 128, an indexer of 16 x 64, tiles of 256 x 512, bf16) its
# blocks, each twice for the pipeline: q 2 x 2 MiB, k 2 x 0.5, qI 2 x 1 (64
# lanes padded to 128), qI^T 2 x 0.5, words 2 x 0.5, dqI 2 x 2, dkI^T (64 x T,
# whole) 2 x 4, the six narrow ones (lse, kI, w, lse_i, loss, dw) 2 x 0.125
# each; the scratch of ReLU(z), 16 x 256 x 512 float32, 8; a tile's
# temporaries (p, the index score, r - p, the running sum, the bits) ~3:
# ~35 MiB (float32 operands, as the check's legs: ~41).
_VMEM_LIMIT = 64 * 2 ** 20


def _relu(z):
    """The indexer's activation, in both kernels."""
    return jnp.maximum(z, 0.0)


def word_columns(t, chunk):
    """Columns of the packed selection of ``t`` keys: ``chunk`` for every 32
    key chunks."""
    if t % chunk:
        raise ValueError(f"{t} keys are no multiple of the chunk {chunk}")
    return chunk * -(-(t // chunk) // WORD_BITS)


def pack(mask, chunk):
    """(..., T) booleans -> (..., word_columns) int32: key ``s`` in bit ``(s
    // chunk) % 32`` of column ``(s // chunk // 32) * chunk + s % chunk``."""
    t = mask.shape[-1]
    groups = word_columns(t, chunk) // chunk
    padded = jnp.pad(mask, [(0, 0)] * (mask.ndim - 1)
                     + [(0, groups * WORD_BITS * chunk - t)])
    bits = padded.reshape(*mask.shape[:-1], groups, WORD_BITS, chunk)
    shifts = jnp.arange(WORD_BITS, dtype=jnp.uint32)[:, None]
    words = jnp.sum(bits.astype(jnp.uint32) << shifts, axis=-2,
                    dtype=jnp.uint32)
    return lax.bitcast_convert_type(
        words.reshape(*mask.shape[:-1], groups * chunk), jnp.int32)


def unpack(words, t, chunk):
    """:func:`pack`'s inverse: (..., word_columns) -> (..., T) booleans."""
    groups = words.shape[-1] // chunk
    w = lax.bitcast_convert_type(words, jnp.uint32).reshape(
        *words.shape[:-1], groups, 1, chunk)
    shifts = jnp.arange(WORD_BITS, dtype=jnp.uint32)[:, None]
    bits = ((w >> shifts) & 1).astype(bool)
    return bits.reshape(*words.shape[:-1], groups * WORD_BITS * chunk)[..., :t]


def tile_bits(ref, lead, rows, key0, width, chunk, block_k, transposed=False):
    """Inside a kernel: the selection of a score sub-tile as booleans, from
    the words block ``ref`` a q block holds. ``rows``: a ``pl.ds`` of the
    block's queries; the keys are ``[key0, key0 + width)`` with ``key0 = base
    + c0`` given as the pair ``(base, c0)``, ``base`` the k block's first key
    (a multiple of ``block_k``). Query-major ``(queries, keys)`` from a block
    ``(..., block_q, chunk)``; ``transposed``: ``(keys, queries)`` from the
    transposed words' block ``(..., chunk, block_q)``. A sub-tile that spans
    chunks is their pieces side by side."""
    base, c0 = key0
    whole = block_k % chunk == 0    # a k block is whole chunks: static columns
    if isinstance(c0, int) and whole:
        # cut at the chunks' borders, wherever the sub-tile starts
        cuts, at = [], 0
        while at < width:
            cuts.append((at, min(chunk - (c0 + at) % chunk, width - at)))
            at += cuts[-1][1]
    else:       # a traced start: one aligned piece, or whole chunks
        cuts = [(at, min(width, chunk)) for at in range(0, width,
                                                        min(width, chunk))]
    pieces = []
    for off, size in cuts:
        first = base + c0 + off
        n = (first // chunk) % WORD_BITS
        col = (c0 + off) % chunk if whole else first % chunk
        if not isinstance(col, int) and size % 8 == 0:
            col = pl.multiple_of(col, 8)
        span = pl.ds(col, size)
        w = (ref[(*lead, span, rows)] if transposed
             else ref[(*lead, rows, span)])
        pieces.append((lax.shift_right_logical(
            w, jnp.full(w.shape, n, jnp.int32)) & 1) != 0)
    return pieces[0] if len(pieces) == 1 else jnp.concatenate(
        pieces, axis=0 if transposed else 1)


# ----------------------------------------------------------- indexer scores

def _scores_kernel(row0_ref, qi_ref, ki_ref, w_ref, out_ref, *, heads, tq, tk):
    qt, kt = pl.program_id(0), pl.program_id(1)
    first_row = row0_ref[0] + qt * tq
    reached = kt * tk <= first_row + tq - 1     # not wholly above the diagonal

    @pl.when(reached)
    def _():
        k = ki_ref[...]
        acc = jnp.zeros((tq, tk), jnp.float32)
        for j in range(heads):
            z = lax.dot_general(qi_ref[j], k, _NT,
                                preferred_element_type=jnp.float32)
            acc = acc + w_ref[:, j:j + 1] * _relu(z)
        row = first_row + lax.broadcasted_iota(jnp.int32, acc.shape, 0)
        col = kt * tk + lax.broadcasted_iota(jnp.int32, acc.shape, 1)
        out_ref[...] = jnp.where(col <= row, acc, -jnp.inf)

    @pl.when(jnp.logical_not(reached))
    def _():
        out_ref[...] = jnp.full(out_ref.shape, -jnp.inf, jnp.float32)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _scores(qi, ki, w, row0, tile_k, interpret):
    """(rows, T) float32 scores of the query rows ``[row0, row0 + rows)``:
    ``qi`` (heads, rows, d), ``ki`` (T, d), ``w`` (rows, heads) float32; -inf
    at the keys after the query. Tiles wholly above the causal diagonal are
    not computed."""
    heads, rows, d = qi.shape
    t = ki.shape[0]
    tq, tk = _fit_block(rows, 256, 8), _fit_block(t, tile_k, 128)
    return pl.pallas_call(
        functools.partial(_scores_kernel, heads=heads, tq=tq, tk=tk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(rows // tq, t // tk),
            in_specs=[pl.BlockSpec((heads, tq, d), lambda i, j, r: (0, i, 0)),
                      pl.BlockSpec((tk, d), lambda i, j, r: (j, 0)),
                      pl.BlockSpec((tq, heads), lambda i, j, r: (i, 0))],
            out_specs=pl.BlockSpec((tq, tk), lambda i, j, r: (i, j))),
        out_shape=jax.ShapeDtypeStruct((rows, t), jnp.float32),
        interpret=interpret, name=DSA_INDEXER_SCORES,
    )(jnp.asarray(row0, jnp.int32).reshape(1), qi, ki, w)


# ---------------------------------------------------------------- selection

_DEAD = -2 ** 31        # the order key of a key no query may keep: below all
_SELECT_ROWS = 128      # a row tile: its order keys, (T x 128) int32, in VMEM
_SELECT_SLAB = 2048     # columns of scores a grid step fetches
_SELECT_SUMS = 64       # rows of a counting pass's partial sums: 8 registers


def _order_keys(scores):
    """float32 -> int32 whose signed order is the floats' (-0.0 as 0.0);
    ``_DEAD`` where the score is -inf (a key after the query) or NaN."""
    scores = jnp.where(scores == 0.0, 0.0, scores)
    bits = lax.bitcast_convert_type(scores, jnp.int32)
    keys = jnp.where(bits < 0, bits ^ jnp.int32(2 ** 31 - 1), bits)
    return jnp.where(scores > -jnp.inf, keys, jnp.int32(_DEAD))


def _scores_of(keys):
    """:func:`_order_keys`' inverse on the keys of finite scores."""
    return lax.bitcast_convert_type(
        jnp.where(keys < 0, keys ^ jnp.int32(2 ** 31 - 1), keys), jnp.float32)


def _reach(first, tq, width):
    """Blocks of ``width`` keys that the ``tq`` queries from ``first`` on can
    see: those at or before the last one's own."""
    return (first + tq - 1) // width + 1


def _select_kernel(row0_ref, s_ref, words_ref, lse_ref, keys_ref, top_ref,
                   upto_ref, packed_ref, *, tq, tk, t, topk, chunk, sums):
    """One row tile of ``tq`` queries, held TRANSPOSED: a query a lane, the
    keys down the sublanes, so a count over a row's keys is a sum of whole
    registers and nothing crosses lanes (``sums``: the rows of a pass's
    partial sums). Grid steps below the tile's reach: the slab's order keys
    into ``keys_ref`` (T, tq) and their running maximum into ``top_ref``. At
    the last grid step everything else, from ``keys_ref`` and
    over the key chunks at or before the tile's last query alone: the k-th
    largest key of every row by 32 counting passes; if a row has more keys
    equal to it than it may keep, the position of the last one it keeps by
    ``bit_length(T - 1)`` more over the positions of the equal ones
    (``upto_ref``); then one pass that packs the kept pairs and sums their
    exponentials."""
    i, j = pl.program_id(0), pl.program_id(1)
    first = row0_ref[0] + i * tq            # the tile's first query

    def folded(x, op):      # (n x sums, tq) or (n, sums, tq) -> (sums, tq)
        return op(x.reshape(-1, sums, tq), axis=0)

    def down(x, op):        # over the sublanes, the result in every one
        return jnp.broadcast_to(op(x, axis=0, keepdims=True), x.shape)

    @pl.when(j == 0)
    def _first():
        top_ref[...] = jnp.full_like(top_ref, _DEAD)

    @pl.when(j < _reach(first, tq, tk))
    def _fill():
        keys = _order_keys(s_ref[...]).T
        keys_ref[pl.ds(pl.multiple_of(j * tk, tk), tk), :] = keys
        top_ref[...] = jnp.maximum(top_ref[...], folded(keys, jnp.max))

    # at the tile's last grid step, so that the next tile's first slab is
    # fetched under the passes
    @pl.when(j == pl.num_programs(1) - 1)
    def _select():
        live = _reach(first, tq, chunk)     # key chunks to visit
        row = first + lax.broadcasted_iota(jnp.int32, (sums, tq), 1)
        k_row = jnp.minimum(row + 1, topk)
        within = (lax.broadcasted_iota(jnp.int32, (chunk // sums, sums, tq), 0)
                  * sums + lax.broadcasted_iota(
                      jnp.int32, (chunk // sums, sums, tq), 1))

        def piece(n):       # key chunk n: (keys, positions), (.., sums, tq)
            at = pl.multiple_of(n * chunk, chunk)
            return (keys_ref[pl.ds(at, chunk), :].reshape(within.shape),
                    at + within)

        def count(hit):     # per row, over the live chunks, in every sublane
            def one_chunk(n, partial):
                return partial + folded(
                    hit(*piece(n)).astype(jnp.int32), jnp.sum)
            return down(lax.fori_loop(
                0, live, one_chunk, jnp.zeros((sums, tq), jnp.int32)), jnp.sum)

        def narrow(b, carry):   # bit 31 - b of the k-th largest: sign first
            kth, reached = carry
            cand = kth ^ lax.shift_left(jnp.int32(1), 31 - b)
            n = count(lambda keys, _: keys >= cand)
            return (jnp.where(n >= k_row, cand, kth),
                    jnp.where(n >= k_row, n, reached))

        kth, reached = lax.fori_loop(
            0, 32, narrow, (jnp.full((sums, tq), _DEAD, jnp.int32),
                            jnp.zeros((sums, tq), jnp.int32)))
        # reached: the keys >= kth. More than the row may keep: ties at the
        # threshold beyond the wanted, the lower positions then (rare)
        upto_ref[...] = jnp.full((sums, tq), t, jnp.int32)

        @pl.when(jnp.max(reached - k_row) > 0)
        def _ties():
            wanted = k_row - count(lambda keys, _: keys > kth)      # >= 1

            def later(b, upto):     # the most positions holding < wanted
                cand = upto | lax.shift_left(
                    jnp.int32(1), (t - 1).bit_length() - 1 - b)
                n = count(lambda keys, at: (keys == kth) & (at < cand))
                return jnp.where(n < wanted, cand, upto)

            upto_ref[...] = lax.fori_loop(
                0, (t - 1).bit_length(), later,
                jnp.zeros((sums, tq), jnp.int32))

        upto = upto_ref[...]
        top = _scores_of(down(top_ref[...], jnp.max))
        packed_ref[...] = jnp.zeros_like(packed_ref)

        def keep(n, total):
            keys, at = piece(n)
            kept = (keys > kth) | ((keys == kth) & (at <= upto))
            cols = pl.ds(pl.multiple_of(n // WORD_BITS * chunk, chunk), chunk)
            packed_ref[cols, :] = packed_ref[cols, :] | jnp.where(
                kept, lax.shift_left(jnp.int32(1), n % WORD_BITS), 0
            ).reshape(chunk, tq)
            return total + folded(
                jnp.where(kept, jnp.exp(_scores_of(keys) - top), 0.0), jnp.sum)

        total = lax.fori_loop(0, live, keep,
                              jnp.zeros((sums, tq), jnp.float32))
        lse_ref[0] = (top + jnp.log(down(total, jnp.sum)))[:1]
        words_ref[...] = packed_ref[...].T


def select_share(t, rows, chunk):
    """Key columns the selection's passes visit over ``t x t``: every row
    tile of a chunk of ``rows`` queries stops at the key chunk of its last
    query."""
    tq = _fit_block(rows, _SELECT_ROWS, 8)
    return sum(_reach(first, tq, chunk) * chunk * tq
               for first in range(0, t, tq)) / (t * t)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _select_call(scores, row0, topk, chunk, interpret):
    """Of each row of ``scores`` (rows, T) - the query at ``row0 + i``, -inf
    at the keys after it - the ``min(topk, row0 + i + 1)`` largest among the
    keys ``s <= row0 + i``, ties to the lower position: (words (rows,
    word_columns) as :func:`pack` gives them, logsumexp of the kept scores
    (rows,)). A radix select in one kernel; slabs after the tile's last query
    are neither fetched nor visited."""
    rows, t = scores.shape
    tq = _fit_block(rows, _SELECT_ROWS, 8)
    tk = _fit_block(t, _SELECT_SLAB, chunk)      # whole chunks a slab
    cols = word_columns(t, chunk)
    sums = math.gcd(chunk, _SELECT_SUMS)
    words, lse = pl.pallas_call(
        functools.partial(_select_kernel, tq=tq, tk=tk, t=t, topk=topk,
                          chunk=chunk, sums=sums),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(rows // tq, t // tk),
            in_specs=[pl.BlockSpec((tq, tk), lambda i, j, r: (
                i, jnp.minimum(j, _reach(r[0] + i * tq, tq, tk) - 1)))],
            out_specs=[pl.BlockSpec((tq, cols), lambda i, j, r: (i, 0)),
                       pl.BlockSpec((1, 1, tq), lambda i, j, r: (i, 0, 0))],
            scratch_shapes=[pltpu.VMEM((t, tq), jnp.int32),
                            pltpu.VMEM((sums, tq), jnp.int32),
                            pltpu.VMEM((sums, tq), jnp.int32),
                            pltpu.VMEM((cols, tq), jnp.int32)]),
        out_shape=[jax.ShapeDtypeStruct((rows, cols), jnp.int32),
                   jax.ShapeDtypeStruct((rows // tq, 1, tq), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=DSA_SELECT,
    )(jnp.asarray(row0, jnp.int32).reshape(1), scores)
    return words, lse.reshape(rows)


def select(qi, ki, w, topk, chunk=512, q_chunk=512, interpret=False):
    """The selection of every query of ``qi`` (B, T, heads, d) against the
    shared key head ``ki`` (B, T, d) under the per-token head weights ``w``
    (B, T, heads), float32: ``(words (B, T, word_columns) int32, lse_i (B, T)
    float32)`` - the kept pairs as bits (:func:`pack`) and the logsumexp of
    each query's kept scores (what :func:`align_loss` normalises ``r`` by).
    No gradient flows into or out of either; both carry the name
    ``SELECTED``. Scores exist ``q_chunk`` query rows at a time."""
    from ..metrics import record_dsa_select_plan

    b, t, heads, d = qi.shape
    rows = _fit_block(t, q_chunk, 8)
    record_dsa_select_plan(select_share(t, rows, chunk))
    qi, ki, w = (lax.stop_gradient(x) for x in (qi, ki, w))
    qh = jnp.moveaxis(qi, 2, 1)                               # (B, heads, T, d)
    w = w.astype(jnp.float32)

    def one_row(args):
        qh, ki, w = args

        def one_chunk(i):
            row0 = i * rows
            with jax.named_scope(DSA_INDEXER):
                scores = _scores(
                    lax.dynamic_slice_in_dim(qh, row0, rows, axis=1), ki,
                    lax.dynamic_slice_in_dim(w, row0, rows, axis=0),
                    row0, chunk, interpret)
            with jax.named_scope(DSA_SELECT):
                return _select_call(scores, row0, topk, chunk, interpret)

        words, lse = lax.map(one_chunk, jnp.arange(t // rows))
        return words.reshape(t, -1), lse.reshape(t)

    words, lse_i = lax.map(one_row, (qh, ki, w))
    return checkpoint_name((words, lse_i), SELECTED)


def block_liveness(words, block_q, block_k, chunk):
    """(B, T / block_q, T / block_k) booleans: whether any pair of a block
    step is selected, from the packed selection (one OR over a q block's rows,
    then over a k block's chunks)."""
    b, t, cols = words.shape
    any_row = lax.reduce(
        words.reshape(b, t // block_q, block_q, cols), jnp.int32(0),
        lax.bitwise_or, (2,))                                 # (B, nq, cols)
    keys = unpack(any_row, cols // chunk * WORD_BITS * chunk, chunk)
    width = min(block_k, chunk)     # a k block narrower than a chunk: its part
    live = jnp.any(keys.reshape(b, t // block_q, -1, width), axis=-1)
    per = max(block_k // chunk, 1)
    live = live[..., :t // width].reshape(b, t // block_q, t // block_k, per)
    return jnp.any(live, axis=-1)


def fetch_table(live):
    """For the index maps: at each step of the last axis the block to have
    resident - itself where live, else the nearest live one before it (then
    nothing is fetched), else the first live one after it, else itself."""
    n = live.shape[-1]
    at = jnp.arange(n, dtype=jnp.int32)
    before = lax.cummax(jnp.where(live, at, -1), axis=live.ndim - 1)
    after = lax.cummin(jnp.where(live, at, n), axis=live.ndim - 1, reverse=True)
    return jnp.where(before >= 0, before, jnp.where(after < n, after, at))


def census(words, block_q, block_k, chunk):
    """(selected pairs, live block steps) of one layer's selection, int32
    scalars over the batch: what the step hands to
    ``metrics.record_dsa_census``."""
    pairs = jnp.sum(lax.population_count(
        lax.bitcast_convert_type(words, jnp.uint32)).astype(jnp.int32))
    live = jnp.sum(block_liveness(words, block_q, block_k, chunk),
                   dtype=jnp.int32)
    return pairs, live


# ------------------------------------------------------- the alignment loss

def _align_kernel(live_ref, q_ref, k_ref, lse_ref, qi_ref, qit_ref, ki_ref,
                  w_ref, lsei_ref, words_ref, loss_ref, *grad_refs, tq, tk,
                  nk, heads, group, index_heads, sm_scale, chunk, grads):
    """One tile of ``tq`` queries x ``tk`` keys. With ``grads`` the last of
    ``grad_refs`` is a VMEM scratch (index_heads, tq, tk) float32: the loss
    loop leaves every indexer head's ``ReLU(z_j)`` there and the gradient
    loop reads it back (``z > 0`` is ``ReLU(z) > 0``), so a score tile is one
    product, not two. Both loops stand inside the same live tile: no tile
    reads what another wrote. float32: a rounded copy would round dw's sum."""
    qt, kt = pl.program_id(0), pl.program_id(1)

    if grads:
        dqi_ref, dw_ref, dkit_ref, relu_ref = grad_refs

        @pl.when((qt == 0) & (kt == 0))
        def _first():
            dkit_ref[...] = jnp.zeros_like(dkit_ref)            # dkI^T, whole

    @pl.when(kt == 0)
    def _init():
        loss_ref[...] = jnp.zeros_like(loss_ref)
        if grads:
            dqi_ref[...] = jnp.zeros_like(dqi_ref)
            dw_ref[...] = jnp.zeros_like(dw_ref)

    @pl.when(live_ref[qt * nk + kt] != 0)
    def _tile():
        rows = pl.ds(0, tq)
        sel = tile_bits(words_ref, (), rows, (kt * tk, 0), tk, chunk, tk)
        lse = lse_ref[...]                                      # (tq, heads)
        # the heads one after another in ONE block of code, not a loop: a
        # loop's body is a product and then the vector work on it, and the
        # next head's product does not start under it
        total = jnp.zeros((tq, tk), jnp.float32)
        for a in range(heads):
            s = lax.dot_general(q_ref[a], k_ref[a // group], _NT,
                                preferred_element_type=jnp.float32) * sm_scale
            total = total + jnp.exp(s - lse[:, a:a + 1])
        p = jnp.where(sel, total, 0.0) / heads
        kI = ki_ref[...]
        w = w_ref[...]
        index = jnp.zeros((tq, tk), jnp.float32)
        for j in range(index_heads):
            relu_z = _relu(lax.dot_general(
                qi_ref[j], kI, _NT, preferred_element_type=jnp.float32))
            if grads:
                relu_ref[j] = relu_z
            index = index + w[:, j:j + 1] * relu_z
        log_r = index - lsei_ref[...]
        loss_ref[...] += jnp.sum(
            jnp.where(sel & (p > 0), p * (jnp.log(jnp.where(p > 0, p, 1.0))
                                          - log_r), 0.0),
            axis=1, keepdims=True)
        if not grads:
            return
        d_index = jnp.where(sel, jnp.exp(log_r) - p, 0.0)
        w_lane = lax.broadcasted_iota(jnp.int32, w.shape, 1)
        dw = jnp.zeros(w.shape, jnp.float32)
        cols = pl.ds(pl.multiple_of(kt * tk, tk), tk)
        for j in range(index_heads):
            relu_z = relu_ref[j]        # z > 0 is ReLU(z) > 0
            dw = dw + jnp.where(w_lane == j, jnp.sum(
                d_index * relu_z, axis=1, keepdims=True), 0.0)
            g = jnp.where(relu_z > 0, d_index * w[:, j:j + 1],
                          0.0).astype(kI.dtype)
            dqi_ref[j] += lax.dot_general(g, kI, _NN,
                                          preferred_element_type=jnp.float32)
            dkit_ref[:, cols] += lax.dot_general(
                qit_ref[j], g, _NN, preferred_element_type=jnp.float32)
        dw_ref[...] += dw


@functools.partial(jax.jit, static_argnums=(8, 9, 10, 11))
def _align_call(q, k, lse, qi, ki, w, lse_i, words, sm_scale, chunk, interpret,
                grads):
    """One batch row: ``q`` (H, T, D), ``k`` (Hkv, T, D), ``lse`` (T, H), ``qi``
    (Hi, T, Di), ``ki`` (T, Di), ``w`` (T, Hi) f32, ``lse_i`` (T, 1), ``words``
    (T, cols). -> the rows' loss terms (T, 1) and, with ``grads``, the
    gradients of their SUM: dqI (Hi, T, Di), dw (T, Hi), dkI^T (Di, T), f32.
    A live tile of 256 queries x ``chunk`` keys holds in VMEM what it makes:
    the heads' running sum, the index score and, with ``grads``, every
    indexer head's ReLU(z) (a scratch, ``_VMEM_LIMIT`` has the budget), so
    each score tile is computed once; without ``grads`` there is no second
    loop and no scratch."""
    heads, t, d = q.shape
    kv_heads = k.shape[0]
    index_heads, _, di = qi.shape
    tk = chunk      # ``words`` holds whole chunks of keys
    tq = _fit_block(t, 256, 8)
    nq, nk = t // tq, t // tk
    live = block_liveness(words[None], tq, tk, chunk)[0]
    groups_of = WORD_BITS * chunk // tk     # k tiles a word group holds

    def last(i):        # the last k tile a q tile's causal keys reach
        return (i * tq + tq - 1) // tk

    def at_q(shape, where):
        return pl.BlockSpec(shape, lambda i, j, live: where(i))

    def at_k(shape, where):
        return pl.BlockSpec(shape, lambda i, j, live: where(
            jnp.minimum(j, last(i))))

    in_specs = [
        at_q((heads, tq, d), lambda i: (0, i, 0)),
        at_k((kv_heads, tk, d), lambda j: (0, j, 0)),
        at_q((tq, heads), lambda i: (i, 0)),
        at_q((index_heads, tq, di), lambda i: (0, i, 0)),
        at_q((index_heads, di, tq), lambda i: (0, 0, i)),
        at_k((tk, di), lambda j: (j, 0)),
        at_q((tq, index_heads), lambda i: (i, 0)),
        at_q((tq, 1), lambda i: (i, 0)),
        pl.BlockSpec((tq, chunk), lambda i, j, live: (
            i, jnp.minimum(j, last(i)) // groups_of)),
    ]
    out_specs = [at_q((tq, 1), lambda i: (i, 0))]
    out_shape = [jax.ShapeDtypeStruct((t, 1), jnp.float32)]
    scratch = []
    if grads:
        scratch = [pltpu.VMEM((index_heads, tq, tk), jnp.float32)]
        out_specs += [at_q((index_heads, tq, di), lambda i: (0, i, 0)),
                      at_q((tq, index_heads), lambda i: (i, 0)),
                      pl.BlockSpec((di, t), lambda i, j, live: (0, 0))]
        out_shape += [jax.ShapeDtypeStruct((index_heads, t, di), jnp.float32),
                      jax.ShapeDtypeStruct((t, index_heads), jnp.float32),
                      jax.ShapeDtypeStruct((di, t), jnp.float32)]
    kernel = functools.partial(
        _align_kernel, tq=tq, tk=tk, nk=nk, heads=heads,
        group=heads // kv_heads, index_heads=index_heads, sm_scale=sm_scale,
        chunk=chunk, grads=grads)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(nq, nk), in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=DSA_ALIGN_TILES,
    )(live.reshape(-1).astype(jnp.int32), q, k, lse, qi,
      jnp.swapaxes(qi, 1, 2), ki, w, lse_i, words)


def align_terms(q, k, lse, qi, ki, w, words, lse_i, sm_scale=None, chunk=512,
                interpret=False, grads=True):
    """Over the batch, one kernel call a row: each query's term ``sum_{s in
    S_t} p (ln p - ln r)`` (B, T) and, with ``grads``, the gradients of the
    terms' SUM in the callers' layouts, float32: (dqI (B, T, Hi, Di), dkI (B,
    T, Di), dw (B, T, Hi)); None without. :func:`align_loss` is their mean."""
    sm_scale = q.shape[-1] ** -0.5 if sm_scale is None else sm_scale

    def one_row(args):
        q, k, lse, qi, ki, w, words, lse_i = args
        return _align_call(
            jnp.moveaxis(q, 1, 0), jnp.moveaxis(k, 1, 0), lse.T,
            jnp.moveaxis(qi, 1, 0), ki, w.astype(jnp.float32),
            lse_i[:, None], words, sm_scale, chunk, interpret, grads)

    with jax.named_scope(DSA_ALIGN):
        out = lax.map(one_row, (q, k, lse, qi, ki, w, words, lse_i))
        terms = out[0][..., 0]
        if not grads:
            return terms, None
        dqi, dw, dkit = out[1:]
        return terms, (jnp.moveaxis(dqi, 1, 2), jnp.swapaxes(dkit, 1, 2), dw)


def _align(q, k, lse, qi, ki, w, words, lse_i, sm_scale, chunk, interpret,
           grads):
    """The loss ``mean_t`` and, with ``grads``, its gradients in the
    operands' dtypes."""
    terms, gradients = align_terms(q, k, lse, qi, ki, w, words, lse_i,
                                   sm_scale, chunk, interpret, grads)
    with jax.named_scope(DSA_ALIGN):
        if not grads:
            return jnp.mean(terms), None
        return jnp.mean(terms), tuple(
            (g / terms.size).astype(x.dtype)
            for g, x in zip(gradients, (qi, ki, w)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10))
def align_loss(q, k, lse, qi, ki, w, words, lse_i, sm_scale=None, chunk=512,
               interpret=False):
    """``mean_t sum_{s in S_t} p[t, s] (ln p[t, s] - ln r[t, s])``: ``p`` the
    main attention's probabilities of the selected pairs averaged over the
    heads, from ``q`` (B, T, H, D), ``k`` (B, T, Hkv, D) and the selected
    forward's per-head logsumexp ``lse`` (B, H, T), all three constants; ``r``
    the softmax over the selection ``words`` of the indexer's score of ``qi``
    (B, T, Hi, Di), ``ki`` (B, T, Di) and ``w`` (B, T, Hi), normalised by
    ``lse_i`` (:func:`select`'s). Differentiable in ``qi``, ``ki`` and ``w``
    alone; the gradient comes out of the same tile loop as the value (``r -
    p`` on the selected pairs, through the weighted sum and the ReLU, whose
    ``ReLU(z)`` the tile keeps in VMEM from the value's loop: no score is
    computed twice), so a differentiated call runs the kernel once."""
    return _align(q, k, lse, qi, ki, w, words, lse_i, sm_scale, chunk,
                  interpret, False)[0]


def _align_fwd(q, k, lse, qi, ki, w, words, lse_i, sm_scale, chunk, interpret):
    loss, gradients = _align(q, k, lse, qi, ki, w, words, lse_i, sm_scale,
                             chunk, interpret, True)
    return loss, checkpoint_name(gradients, ALIGN_GRADS)


def _align_bwd(sm_scale, chunk, interpret, gradients, g):
    dqi, dki, dw = (g.astype(x.dtype) * x for x in gradients)
    return None, None, None, dqi, dki, dw, None, None


align_loss.defvjp(_align_fwd, _align_bwd)
