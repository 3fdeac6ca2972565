"""Grouped matrix products over ragged row groups as pallas TPU kernels,
forward AND backward: the experts' products of ``ops.moe.dropless_experts``.

``Y[rows of g] = X[rows of g] @ W[g]`` for rows sorted by group, the groups'
sizes known only on the device. Two kernels behind one ``jax.custom_vjp``
(:func:`grouped_matmul`):

- rows x weights (``hvd_moe_experts_gmm``): the forward, and with the
  weight block read transposed the input gradient ``dX = dY W[g]^T``. The
  grid walks row tiles group by group. A group's whole weight block (or a
  half of its columns) is resident in one of two VMEM slots for all the
  group's visits, fetched once per group, and the rows stream through once.
  The weights stay where they lie in HBM and the kernel copies the blocks
  itself, a GROUP ahead: a group's first visit starts its successor's copy
  into the other slot and waits for its own, started at its predecessor's
  first visit (a pipelined ``BlockSpec`` would ask for the block one grid
  STEP ahead, at the predecessor's last visit: a border visit of a few
  microseconds, shorter than an 8 MiB fetch; PERF.md §6, PR 64).
  What bounds the kernel: a group's products against ITS SUCCESSOR's
  fetch, whichever is longer (the MXU where an expert's rows take longer
  to multiply than a block's bytes to arrive, HBM where they do not), plus
  the first fetch of every column tile, which nothing hides. A
  tile wholly inside a group is one product of ``row_tile`` rows (the
  larger, the closer to the MXU's peak). A tile that a group border crosses
  is visited once per group it holds rows of, and each visit multiplies
  only the blocks of ``BORDER_ROWS`` rows that hold rows of its group and
  stores only those rows: a border costs one such block, not a tile.
- rows^T x rows (``hvd_moe_experts_tgmm``): the weight gradient
  ``dW[g] = X[rows of g]^T dY[rows of g]``, the ragged rows contracted
  inside the kernel (no transposed copy of X in HBM) into an f32 accumulator
  the size of the expert's block, stored when the group changes; border
  tiles block by block as above, the other groups' rows zeroed. A group
  with no rows is visited once, to store exact zeros.

Which tile visits which group is a few small integer arrays
(:func:`grouped_plan`) made on the device from the group sizes and handed to
the kernels by scalar prefetch; one plan serves every product of a layer.
The tiles are a rule on shapes and the ROWS' itemsize (:func:`row_tile`,
``BORDER_ROWS``, :func:`_column_tile`, :func:`_weight_grad_tiles`), chosen
by the sweep in PERF.md §6 (PR 29). Rows and weights are both bf16 or both
f32, or bf16 rows with FLOAT32 weights (a layer's master parameters as they
are, PR 63): the rows x weights kernel then rounds the group's resident block
to bf16 in VMEM where it multiplies it - the numbers a cast of the weights
beforehand would have given it, with no bf16 copy of the stack written to
HBM and read back; the weight gradient comes back in the weights' dtype.
Every product accumulates in f32 and is rounded once to the rows' dtype;
float32 operands traced under ``jax.default_matmul_precision("highest")``
are multiplied at that precision. ``interpret=True`` runs the kernels in the
Pallas interpreter, something the caller asks for and never inferred from
the platform, as for ``flash_attention``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common.device_names import MOE_EXPERTS_GMM, MOE_EXPERTS_TGMM

# The rows x weights kernel's two weight slots are 8 MiB (f32 weights under
# bf16 rows: 16, and the block rounded to bf16 as Mosaic keeps it), no more
# than a pipelined block's double buffer would take.
# The weight gradient's accumulator is 8 MiB and its output block's two
# buffers up to 16, the row tiles and the f32 product a few more: above the
# 16 MiB a kernel gets by default on this libtpu, well inside the v5e's
# 128 MiB of VMEM.
_VMEM_LIMIT_BYTES = 64 * 1024 * 1024
_WEIGHT_BLOCK_BYTES = 4 * 1024 * 1024   # one expert's block as the MXU reads it
_ACCUMULATOR_BYTES = 8 * 1024 * 1024    # the weight gradient's, in f32
BORDER_ROWS = 128       # rows a border tile is multiplied in blocks of


def row_tile(itemsize: int) -> int:
    """Rows a grid step multiplies: 512 of bf16, 256 of f32."""
    return 1024 // itemsize


def takes_kernel(x, w) -> bool:
    """Whether ``x (M, K) @ w (E, K, N)`` is a shape the kernels tile: bf16
    or f32 operands of one dtype, or bf16 rows with f32 weights (rounded to
    bf16 in VMEM), K and N multiples of 128, M a multiple of the row tile."""
    if (x.dtype, w.dtype) not in ((jnp.bfloat16, jnp.bfloat16),
                                  (jnp.float32, jnp.float32),
                                  (jnp.bfloat16, jnp.float32)):
        return False
    (m, k), n = x.shape, w.shape[2]
    return (k % 128 == 0 and n % 128 == 0 and w.shape[1] == k
            and m % row_tile(x.dtype.itemsize) == 0)


def border_overhead(rows: int, groups: int) -> float:
    """Worst-case row blocks multiplied over row blocks of work: each of the
    ``E - 1`` borders between groups puts one block of ``BORDER_ROWS`` rows
    through the MXU twice, ``(B + E - 1) / B`` with ``B`` the blocks."""
    blocks = rows // BORDER_ROWS
    return (blocks + groups - 1) / blocks


def lookahead_share(groups: int) -> float:
    """Share of a rows x weights call's weight-block fetches that are started
    a whole group ahead: all but the first of a column tile,
    ``(E - 1) / E``."""
    return (groups - 1) / groups


def _column_tile(k: int, n: int, itemsize: int) -> int:
    """The widest multiple-of-128 divisor of ``n`` whose ``k x tile`` weight
    block is at most ``_WEIGHT_BLOCK_BYTES`` at the ROWS' ``itemsize``, which
    is the MXU's operand: f32 weights under bf16 rows arrive at twice that."""
    tile = n
    while k * tile * itemsize > _WEIGHT_BLOCK_BYTES and tile % 256 == 0:
        tile //= 2
    return tile


def _weight_grad_tiles(k: int, n: int) -> tuple[int, int]:
    """The weight gradient's ``(k, n)`` accumulator tile: the expert's whole
    block (both operands are then read once), the wider side halved while
    the f32 tile is over ``_ACCUMULATOR_BYTES``."""
    while k * n * 4 > _ACCUMULATOR_BYTES and max(k, n) % 256 == 0:
        k, n = (k // 2, n) if k >= n else (k, n // 2)
    return k, n


def grouped_plan(group_sizes, rows: int, tm: int):
    """Which row tile and which group each grid step visits, for ``rows``
    rows (a multiple of ``tm``) in groups of ``group_sizes`` (E,) int32 that
    sum to ``rows``. Returns int32 arrays ``(offsets (E + 1,), groups (S,),
    tiles (S,), steps (1,))`` with ``S = rows / tm + E - 1`` the most steps
    any sizes need: group g holds rows ``offsets[g]:offsets[g + 1]``; step s
    visits tile ``tiles[s]`` for group ``groups[s]``; the first ``steps[0]``
    steps are real and the rest repeat the last one (the kernels skip them).
    A group visits every tile it has rows in, an empty group one tile (the
    weight gradient stores its zeros there), in row order: a tile's visits
    are consecutive, and so are a group's."""
    n_groups, n_tiles = group_sizes.shape[0], rows // tm
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes, dtype=jnp.int32)
    starts = ends - sizes
    visits = jnp.where(sizes > 0, (ends + tm - 1) // tm - starts // tm, 1)
    upto = jnp.cumsum(visits, dtype=jnp.int32)
    steps = jnp.minimum(jnp.arange(n_tiles + n_groups - 1, dtype=jnp.int32),
                        upto[-1] - 1)
    groups = jnp.sum(steps[:, None] >= upto[None, :], axis=1, dtype=jnp.int32)
    first_tile = jnp.minimum(starts // tm, n_tiles - 1)
    tiles = first_tile[groups] + steps - (upto - visits)[groups]
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    return offsets, groups, tiles, upto[-1:]


def _visit(plan_refs, step, tm):
    """Of grid step ``step``: (it is a real step of a group with rows, the
    group's rows are the tile's ``tm``, the group's first row and the one
    past its last, both counted from the tile's first)."""
    offsets, groups, tiles, steps = plan_refs
    group = groups[step]
    row0 = tiles[step] * tm
    start, end = offsets[group] - row0, offsets[group + 1] - row0
    return (jnp.logical_and(step < steps[0], end > start),
            jnp.logical_and(start <= 0, end >= tm), start, end)


def _border_blocks(start, end, tm, body):
    """``body(the block's rows as a slice, (BORDER_ROWS, 1) mask of the
    group's rows)`` for each block of ``BORDER_ROWS`` rows of a tile that
    holds rows ``start:end`` of the group."""
    sub = BORDER_ROWS

    def block(i, carry):
        row = pl.multiple_of(i * sub, sub)
        rows = row + lax.broadcasted_iota(jnp.int32, (sub, 1), 0)
        body(pl.ds(row, sub), jnp.logical_and(rows >= start, rows < end))
        return carry

    # lax.div, not //: the operands are not negative, and floor division's
    # sign handling is a fifth of what a kernel costs to lower
    lax.fori_loop(lax.div(jnp.maximum(start, 0), jnp.int32(sub)),
                  lax.div(jnp.minimum(end, tm) + (sub - 1), jnp.int32(sub)),
                  block, None)


# ------------------------------------------------------------ rows x weights

def _gmm_kernel(offsets, groups, tiles, steps, x_ref, w_hbm, o_ref, w_ref,
                arrived, *, tm, transpose_w):
    column, step = pl.program_id(0), pl.program_id(1)
    live, whole, start, end = _visit((offsets, groups, tiles, steps), step, tm)
    group, tn = groups[step], o_ref.shape[1]

    def fetch(g):
        """The copy of group ``g``'s block of this column tile into slot
        ``g % 2``."""
        slot = lax.rem(g, 2)
        columns = pl.ds(pl.multiple_of(column * tn, tn), tn)
        block = (w_hbm.at[g, columns, :] if transpose_w
                 else w_hbm.at[g, :, columns])
        return pltpu.make_async_copy(block, w_ref.at[slot], arrived.at[slot])

    # A group's FIRST visit asks for its successor's block and waits for its
    # own, asked for at its predecessor's first visit: a group's products
    # hide the next group's fetch. Every group has a visit and a group's
    # visits are consecutive (grouped_plan), so the successor is group + 1
    # and the slot it fills was last read by group - 1, whose visits are
    # over. The steps past the plan's end repeat its last one and open no
    # group: every copy started is waited for inside its column tile.
    @pl.when(jnp.logical_or(step == 0,
                            groups[jnp.maximum(step - 1, 0)] != group))
    def _arrive():
        @pl.when(step == 0)
        def _first_of_the_column_tile():
            fetch(group).start()

        @pl.when(group + 1 < w_hbm.shape[0])
        def _ahead():
            fetch(group + 1).start()

        fetch(group).wait()

    def product(x):
        # f32 weights under bf16 rows: the MXU reads the resident block
        # rounded to the rows' dtype, here and not once a group into a
        # scratch of its own: that read 0.13-0.28 ms a step SLOWER (PERF.md
        # §6, PR 63)
        contract = (((1,), (1 if transpose_w else 0,)), ((), ()))
        return lax.dot_general(
            x, w_ref[lax.rem(group, 2)].astype(x.dtype), contract,
            preferred_element_type=jnp.float32).astype(o_ref.dtype)

    @pl.when(jnp.logical_and(live, whole))
    def _inside():
        o_ref[...] = product(x_ref[...])

    # A tile that a group border crosses: only the blocks of rows that hold
    # rows of this group, and of those only this group's rows.
    @pl.when(jnp.logical_and(live, jnp.logical_not(whole)))
    def _border():
        def block(rows, mine):
            o_ref[rows, :] = jnp.where(mine, product(x_ref[rows, :]),
                                       o_ref[rows, :])

        _border_blocks(start, end, tm, block)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _gmm_call(x, w, plan, tm, tn, transpose_w, interpret):
    """``x (M, K)`` times ``w (E, K, N)``, or ``w (E, N, K)`` read
    transposed, over the plan's groups: (M, N) in x's dtype. ``w`` stays
    where it lies and the kernel copies a group's block into one of two VMEM
    slots a group ahead; ``w`` in another dtype than x's (f32 under bf16
    rows) is rounded to x's in VMEM, the resident block where it is
    multiplied."""
    (m, k), n = x.shape, w.shape[1 if transpose_w else 2]
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, transpose_w=transpose_w),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // tn, plan[1].shape[0]),
            in_specs=[
                pl.BlockSpec((tm, k), lambda j, s, o, g, t, c: (t[s], 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda j, s, o, g, t, c: (t[s], j)),
            scratch_shapes=[
                pltpu.VMEM((2, tn, k) if transpose_w else (2, k, tn), w.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(m * k * (n // tn) + m * n) * x.dtype.itemsize
            + w.size * w.dtype.itemsize),
        interpret=interpret,
        name=MOE_EXPERTS_GMM,
    )(*plan, x, w)


# -------------------------------------------------------------- rows^T x rows

def _tgmm_kernel(offsets, groups, tiles, steps, x_ref, dy_ref, o_ref, acc_ref,
                 *, tm):
    step, last = pl.program_id(2), pl.num_programs(2) - 1
    live, whole, start, end = _visit((offsets, groups, tiles, steps), step, tm)
    group = groups[step]

    @pl.when(jnp.logical_or(step == 0,
                            groups[jnp.maximum(step - 1, 0)] != group))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def accumulate(x, dy):
        acc_ref[...] += lax.dot_general(x, dy, (((0,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(live, whole))
    def _inside():
        accumulate(x_ref[...], dy_ref[...])

    @pl.when(jnp.logical_and(live, jnp.logical_not(whole)))
    def _border():
        def block(rows, mine):
            x, dy = x_ref[rows, :], dy_ref[rows, :]
            accumulate(jnp.where(mine, x, jnp.zeros_like(x)),
                       jnp.where(mine, dy, jnp.zeros_like(dy)))

        _border_blocks(start, end, tm, block)

    # The steps past the plan's end repeat its last one: the group changes
    # for the last time at the plan's last real step or at the grid's end.
    @pl.when(jnp.logical_and(
        step < steps[0],
        jnp.logical_or(step == steps[0] - 1,
                       groups[jnp.minimum(step + 1, last)] != group)))
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7))
def _tgmm_call(x, dy, plan, n_groups, tm, tk, tn, interpret):
    """``x[rows of g]^T @ dy[rows of g]`` for every group of the plan:
    (E, K, N) in x's dtype from ``x (M, K)`` and ``dy (M, N)``."""
    (m, k), n = x.shape, dy.shape[1]
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(k // tk, n // tn, plan[1].shape[0]),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda i, j, s, o, g, t, c: (t[s], i)),
                pl.BlockSpec((tm, tn), lambda i, j, s, o, g, t, c: (t[s], j)),
            ],
            out_specs=pl.BlockSpec((None, tk, tn),
                                   lambda i, j, s, o, g, t, c: (g[s], i, j)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((n_groups, k, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(m * k * (n // tn) + m * n * (k // tk)
                            + n_groups * k * n) * x.dtype.itemsize),
        interpret=interpret,
        name=MOE_EXPERTS_TGMM,
    )(*plan, x, dy)


# ----------------------------------------------------------------- public API

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def grouped_matmul(x, w, plan, interpret: bool = False):
    """``x[rows of g] @ w[g]`` for the groups of ``plan``
    (:func:`grouped_plan` at ``row_tile(x.dtype.itemsize)``): ``x (M, K)``,
    ``w (E, K, N)``, both bf16, both f32, or bf16 rows with f32 weights,
    which the kernel rounds to bf16 in VMEM (:func:`takes_kernel`). Returns
    (M, N) in x's dtype, accumulated in f32 and rounded once; the gradients
    come back in x's dtype and in w's."""
    return _forward(x, w, plan, interpret)[0]


def _forward(x, w, plan, interpret):
    itemsize = x.dtype.itemsize
    tn = _column_tile(w.shape[1], w.shape[2], itemsize)
    return (_gmm_call(x, w, plan, row_tile(itemsize), tn, False, interpret),
            (x, w, plan))


def _backward(interpret, res, dy):
    x, w, plan = res
    itemsize, (n_groups, k, n) = x.dtype.itemsize, w.shape
    tm = row_tile(itemsize)
    dx = _gmm_call(dy, w, plan, tm, _column_tile(n, k, itemsize), True,
                   interpret)
    dw = _tgmm_call(x, dy, plan, n_groups, tm, *_weight_grad_tiles(k, n),
                    interpret)
    return dx, dw.astype(w.dtype), None


grouped_matmul.defvjp(_forward, _backward)
