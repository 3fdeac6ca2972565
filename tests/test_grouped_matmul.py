"""The grouped-product kernels (``ops/grouped_matmul.py``) in the Pallas
interpreter against a per-group loop in float64: the forward, the input
gradient and the weight gradient, in bf16 and f32, over group layouts that
put borders inside row tiles and inside their blocks of 128 rows, a group
smaller than one block, an empty group (its weight gradient is exact zeros)
and every row in one group.

Tolerances, as shares of max|reference|: f32 1e-5 (the same arithmetic in
another order of sums); bf16 4e-3 (f32 accumulation rounded once, 2^-9)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import grouped_matmul as gm

K, N = 128, 256

# group sizes in units of 1/16 of the dtype's row tile (512 rows of bf16, 256
# of f32: 32 and 16 rows, so borders fall inside the border blocks of 128)
LAYOUTS = {
    "borders_inside_tiles": [21, 19, 11, 13],
    "groups_smaller_than_a_block": [16, 3, 1, 1, 2, 9],
    "empty_groups": [5, 0, 18, 0, 9, 0],
    "all_rows_in_one_group": [0, 0, 32, 0],
    "borders_on_tile_edges": [16, 0, 32, 16],
    # the weights' copies (PR 64): no successor to ask for; and a successor
    # asked for at every step, each group one visit of the same tile
    "one_group_only": [32],
    "one_visit_a_group_in_one_tile": [1] * 16,
}


@pytest.fixture(scope="module")
def computed():
    cache = {}

    def get(layout, dtype, weights=None, **shape):
        key = (layout, dtype, weights, *sorted(shape.items()))
        if key not in cache:
            cache[key] = _compute(LAYOUTS[layout], dtype, weights, **shape)
        return cache[key]

    return get


def _compute(sixteenths, dtype, weights=None, k=K, n=N, column_tiles=1):
    itemsize = jnp.dtype(dtype).itemsize
    tm = gm.row_tile(itemsize)
    sizes = np.asarray(sixteenths, np.int32) * (tm // 16)
    m, e = int(sizes.sum()), len(sizes)
    kx, kw, kd = jax.random.split(jax.random.PRNGKey(len(sixteenths)), 3)
    x = jax.random.normal(kx, (m, k), dtype)
    w = jax.random.normal(kw, (e, k, n), weights or dtype)
    dy = jax.random.normal(kd, (m, n), dtype)
    assert gm.takes_kernel(x, w)
    plan = gm.grouped_plan(jnp.asarray(sizes), m, tm)
    with pytest.MonkeyPatch.context() as patch, \
            jax.default_matmul_precision("highest"):
        if column_tiles == 2:
            # a resident block of at most half an expert's at the rows'
            # itemsize: two column tiles, forward (k x n read as it is) and,
            # where k == n, dX (n x k read transposed)
            patch.setattr(gm, "_WEIGHT_BLOCK_BYTES", k * n * itemsize // 2)
        assert n // gm._column_tile(k, n, itemsize) == column_tiles
        y, vjp = jax.vjp(lambda x, w: gm.grouped_matmul(x, w, plan, True), x, w)
        dx, dw = vjp(dy)
    x64, w64, dy64 = (np.asarray(a, np.float64) for a in (x, w, dy))
    if w.dtype != x.dtype:      # the block the MXU reads, rounded in VMEM
        w64 = np.asarray(w.astype(x.dtype), np.float64)
    want = {"forward": np.zeros((m, n)), "dx": np.zeros((m, k)),
            "dw": np.zeros((e, k, n))}
    start = 0
    for g, size in enumerate(sizes):
        rows = slice(start, start + size)
        want["forward"][rows] = x64[rows] @ w64[g]
        want["dx"][rows] = dy64[rows] @ w64[g].T
        want["dw"][g] = x64[rows].T @ dy64[rows]
        start += size
    return {"forward": y, "dx": dx, "dw": dw}, want, sizes


def _close(got, want, dtype):
    got = np.asarray(got, np.float64)
    assert got.shape == want.shape and np.isfinite(got).all()
    tol = 4e-3 if dtype == jnp.bfloat16 else 1e-5
    assert np.max(np.abs(got - want)) <= tol * np.max(np.abs(want))
    return got


@pytest.mark.parametrize("product", ["forward", "dx", "dw"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_kernels_match_a_per_group_loop(computed, layout, dtype, product):
    got, want, sizes = computed(layout, dtype)
    got = _close(got[product], want[product], dtype)
    if product == "dw":
        for g in np.flatnonzero(sizes == 0):
            assert not got[g].any()         # exact zeros, not small numbers


@pytest.mark.parametrize("product", ["forward", "dx"])
@pytest.mark.parametrize("rows, weights", [
    (jnp.bfloat16, jnp.bfloat16), (jnp.float32, jnp.float32),
    (jnp.bfloat16, jnp.float32)], ids=["bf16_bf16", "f32_f32", "bf16_f32"])
@pytest.mark.parametrize("layout", ["borders_inside_tiles", "empty_groups",
                                    "one_group_only",
                                    "one_visit_a_group_in_one_tile"])
def test_the_weights_copies_start_again_at_every_column_tile(
        computed, layout, rows, weights, product):
    """The rows x weights kernel copies a group's block itself, a group
    ahead, and the chain of copies lives inside ONE column tile: with two of
    them, forward and read transposed, the second starts with its own first
    fetch and every block multiplied is its group's and its columns'."""
    got, want, _ = computed(layout, rows, weights, k=256, n=256,
                            column_tiles=2)
    _close(got[product], want[product], rows)


@pytest.mark.parametrize("tm", [128, 512])
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_plan_gives_what_the_weights_copies_rely_on(layout, tm):
    """``_gmm_kernel`` starts group g + 1's copy at group g's first visit and
    waits for it at g + 1's: every group has a visit, a group's visits are
    consecutive, the groups come in order, and the steps past the plan's end
    open no group (no copy is started that nobody waits for)."""
    sizes = np.asarray(LAYOUTS[layout], np.int32) * (tm // 16)
    e, rows = len(sizes), int(sizes.sum())
    _, groups, _, steps = (np.asarray(a) for a in gm.grouped_plan(
        jnp.asarray(sizes), rows, tm))
    steps, groups = int(steps[0]), groups.tolist()
    first = [s for s in range(len(groups))
             if s == 0 or groups[s - 1] != groups[s]]
    # the groups opened are 0 .. E - 1, each once and in order: a group's
    # visits are consecutive and group g + 1's first follows group g's last
    assert [groups[s] for s in first] == list(range(e))
    assert np.all(np.diff(groups) >= 0)
    assert first[-1] < steps <= len(groups)
    assert set(groups[steps:]) <= {e - 1}


@pytest.fixture(scope="module")
def master_weights():
    """(gradients and forward of bf16 rows against FLOAT32 weights, the same
    against the weights cast to bf16 beforehand) per (layout, column tiles)."""
    cache = {}

    def get(layout, column_tiles):
        if (layout, column_tiles) not in cache:
            cache[layout, column_tiles] = (
                _both_weights(*SHORT_OF_THE_ROWS[layout][:1], column_tiles,
                              SHORT_OF_THE_ROWS[layout][1])
                if layout in SHORT_OF_THE_ROWS
                else _both_weights(LAYOUTS[layout], column_tiles))
        return cache[layout, column_tiles]

    return get


# One group that ends inside the second of two tiles, as a rank's held share
# leaves a buffer: the last border visit of a column tile and the first of the
# next are the SAME group's, with another column's block (a kernel that kept
# a group's rounded block from one visit to the next would multiply a stale
# one).
SHORT_OF_THE_ROWS = {"one_group_short_of_the_rows": ([24], 32)}


def _both_weights(sixteenths, column_tiles, rows=None):
    k = n = 256
    sizes = np.asarray(sixteenths, np.int32) * (gm.row_tile(2) // 16)
    m, e = (rows or sum(sixteenths)) * (gm.row_tile(2) // 16), len(sizes)
    kx, kw, kd = jax.random.split(jax.random.PRNGKey(63), 3)
    x = jax.random.normal(kx, (m, k), jnp.bfloat16)
    w = jax.random.normal(kw, (e, k, n), jnp.float32)
    dy = jax.random.normal(kd, (m, n), jnp.bfloat16)
    assert gm.takes_kernel(x, w)
    plan = gm.grouped_plan(jnp.asarray(sizes), m, gm.row_tile(2))
    with pytest.MonkeyPatch.context() as patch:
        # a resident block of at most k x 128 bf16: two column tiles, forward
        # (k x n read as it is) and dX (n x k read transposed)
        if column_tiles == 2:
            patch.setattr(gm, "_WEIGHT_BLOCK_BYTES", k * 128 * 2)
        assert n // gm._column_tile(k, n, 2) == column_tiles
        out = []
        for weights in (w, w.astype(jnp.bfloat16)):
            y, vjp = jax.vjp(
                lambda x, w: gm.grouped_matmul(x, w, plan, True), x, weights)
            dx, dw = vjp(dy)
            # rows behind the groups' are never written
            live = int(sizes.sum())
            out.append({"forward": y[:live], "dx": dx[:live], "dw": dw})
    return out


@pytest.mark.parametrize("product", ["forward", "dx", "dw"])
@pytest.mark.parametrize("column_tiles", [1, 2])
@pytest.mark.parametrize("layout", ["borders_inside_tiles", "empty_groups",
                                    "groups_smaller_than_a_block",
                                    *SHORT_OF_THE_ROWS])
def test_f32_weights_rounded_in_vmem_equal_a_cast_beforehand(
        master_weights, layout, column_tiles, product):
    """bf16 rows against the float32 master weights, the resident block
    rounded inside the rows x weights kernel (forward as they are, dX read
    transposed), are BIT-equal to the products of the weights cast to bf16
    beforehand; dW arrives in the weights' dtype."""
    master, cast = (np.asarray(side[product]) for side in
                    master_weights(layout, column_tiles))
    want = np.float32 if product == "dw" else jnp.bfloat16
    assert master.dtype == want and cast.dtype == jnp.bfloat16
    assert np.isfinite(master.astype(np.float32)).all() and master.any()
    np.testing.assert_array_equal(master.astype(np.float32),
                                  cast.astype(np.float32))


@pytest.mark.parametrize("rows, weights, takes", [
    (jnp.bfloat16, jnp.bfloat16, True),
    (jnp.float32, jnp.float32, True),
    (jnp.bfloat16, jnp.float32, True),      # rounded in VMEM
    (jnp.float32, jnp.bfloat16, False),     # nothing widens a weight
], ids=["bf16_bf16", "f32_f32", "bf16_f32", "f32_bf16"])
def test_which_dtype_pairs_take_the_kernels(rows, weights, takes):
    x = jax.ShapeDtypeStruct((512, 128), rows)
    w = jax.ShapeDtypeStruct((4, 128, 256), weights)
    assert gm.takes_kernel(x, w) is takes


@pytest.mark.parametrize("sizes, tm", [
    ([100, 0, 28, 300, 84, 0], 128),
    ([0, 0, 512], 256),
    ([256, 256], 256),
    ([1, 1, 1, 125], 128),
])
def test_plan_visits_every_tile_a_group_has_rows_in(sizes, tm):
    rows, e = sum(sizes), len(sizes)
    offsets, groups, tiles, steps = (np.asarray(a) for a in gm.grouped_plan(
        jnp.asarray(sizes, jnp.int32), rows, tm))
    steps = int(steps[0])
    assert len(groups) == len(tiles) == rows // tm + e - 1 >= steps
    assert offsets.tolist() == np.concatenate([[0], np.cumsum(sizes)]).tolist()
    want = []
    for g, size in enumerate(sizes):
        first, last = offsets[g] // tm, (offsets[g + 1] - 1) // tm
        want += ([(g, t) for t in range(first, last + 1)] if size
                 else [(g, min(first, rows // tm - 1))])
    assert list(zip(groups[:steps], tiles[:steps])) == want
    # the steps past the end repeat the last one
    assert set(zip(groups[steps:], tiles[steps:])) <= {want[-1]}


@pytest.mark.parametrize("shape, dtype, takes", [
    ((512, 256, 128), jnp.bfloat16, True),
    ((256, 128, 128), jnp.float32, True),
    ((256, 128, 128), jnp.bfloat16, False),     # rows under the bf16 tile
    ((512, 64, 128), jnp.bfloat16, False),      # K not a multiple of 128
    ((512, 128, 96), jnp.float32, False),       # N not a multiple of 128
    ((512, 128, 128), jnp.float16, False),
])
def test_which_shapes_take_the_kernels(shape, dtype, takes):
    m, k, n = shape
    x = jax.ShapeDtypeStruct((m, k), dtype)
    w = jax.ShapeDtypeStruct((4, k, n), dtype)
    assert gm.takes_kernel(x, w) is takes


def test_tiles_follow_shapes_and_itemsize():
    # OLMoE's: the whole 2048 x 1024 bf16 block of an expert is resident
    assert gm._column_tile(2048, 1024, 2) == 1024
    assert gm._column_tile(1024, 2048, 2) == 2048
    assert gm._column_tile(2048, 1024, 4) == 512     # f32: half the columns
    # solar's 4096 x 1280: sized by the bf16 the block becomes under bf16
    # rows (the rows' itemsize), whether the weights arrive bf16 or f32
    assert gm._column_tile(4096, 1280, 2) == 640
    assert gm.row_tile(2) == 512 and gm.row_tile(4) == 256
    # the weight gradient accumulates an expert's whole block in f32
    assert gm._weight_grad_tiles(2048, 1024) == (2048, 1024)
    assert gm._weight_grad_tiles(1024, 2048) == (1024, 2048)
    assert gm._weight_grad_tiles(4096, 2048) == (1024, 2048)
    assert gm._weight_grad_tiles(128, 384) == (128, 384)
    assert gm.border_overhead(131072, 64) == pytest.approx(1087 / 1024)


def test_compiled_kernels_without_tpu_raise():
    """The interpreter is the caller's to ask for, never inferred from the
    platform: on this CPU an aligned layer built without ``interpret``
    raises at lowering, as a flash model without ``flash_interpret`` does."""
    from horovod_tpu.models import MoEMLP

    layer = MoEMLP(dim=128, hidden=128, n_experts=4, top_k=2)
    x = jnp.ones((1, 256, 128), jnp.float32)
    with pytest.raises(ValueError, match="[Ii]nterpret"):
        layer.init(jax.random.PRNGKey(0), x)
    tiny = MoEMLP(dim=16, hidden=8, n_experts=4, top_k=2)    # ragged_dot
    tiny.init(jax.random.PRNGKey(0), jnp.ones((1, 8, 16), jnp.float32))
