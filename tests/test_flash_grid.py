"""The causal-dense flash grids hold only live block steps: the folded
triangle's steps, decoded by the functions the index maps and the kernels use,
visit every live block exactly once in the parent's order; the grids a call
really traces; the gauge that counts the dead steps of that grid; and the one
trace of each kernel body that a model's layers share."""

import collections
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops.flash_attention import (_crossed, _k_major_grid,
                                             _k_major_step, _q_major_grid,
                                             _q_major_step, block_census,
                                             flash_attention)

NQ, RATIO, GROUP = (1, 2, 3, 4, 8, 16), (1, 2), (1, 4, 6)
BLOCK_K = 8


def _grid_steps(rows, steps):
    """Every (row, step) of a grid's two inner axes, in the order it runs."""
    p, s = np.meshgrid(np.arange(rows, dtype=np.int32),
                       np.arange(steps, dtype=np.int32), indexing="ij")
    return p.ravel(), s.ravel()


def _arrays(*xs):
    return [np.asarray(x) for x in np.broadcast_arrays(
        *(np.asarray(x) for x in xs))]


def _runs(qi, ki, ratio):
    """The kernels' own predicates: a step computes something where the block
    lies below the diagonal or is one of the ``ratio`` it crosses."""
    block_q = ratio * BLOCK_K
    return ~_crossed(qi, ki, block_q, BLOCK_K) | (
        (ki >= qi * ratio) & (ki < (qi + 1) * ratio))


def _contiguous(blocks):
    """Each output block is visited in one run of consecutive steps."""
    changes = int(np.count_nonzero(np.diff(blocks, axis=0).any(axis=1)))
    return changes + 1 == len({tuple(b) for b in blocks})


@pytest.mark.parametrize("ratio", RATIO)
@pytest.mark.parametrize("nq", NQ)
def test_forward_and_dq_grid_holds_each_live_block_once(nq, ratio):
    block_q = ratio * BLOCK_K
    t, nk = nq * block_q, nq * ratio
    rows, steps, q_block, k_block = _q_major_grid(t, block_q, BLOCK_K, True,
                                                  None)
    if nq == 1:     # one block a row: today's grid
        assert (rows, steps) == (1, nk)
    else:
        assert (rows, steps) == ((nq + 1) // 2, ratio * (nq + 1))
    p, s = _grid_steps(rows, steps)
    qi, ki, i, count = _arrays(*_q_major_step(
        p, s, block_q, BLOCK_K, nk, True, None))
    first, last = i == 0, i == count - 1
    runs = _runs(qi, ki, ratio)
    live = [(i, j) for i in range(nq) for j in range(ratio * (i + 1))]
    # every live block exactly once, a q block's k blocks in ascending order
    visited = list(zip(qi[runs].tolist(), ki[runs].tolist()))
    assert sorted(visited) == live
    for i in range(nq):
        assert [j for q, j in visited if q == i] == list(
            range(ratio * (i + 1)))
    # nothing else but the odd count's clamped tail, at the last row's end
    dead = steps // 2 if nq > 1 and nq % 2 else 0
    assert np.count_nonzero(~runs) == dead == rows * steps - len(live)
    assert runs[:len(runs) - dead].all()
    assert len(live) == block_census(t, block_q, BLOCK_K, True)[0]
    # _init at a q block's first step, _finalize at its last, nowhere else
    assert (first == (runs & (ki == 0))).all()
    assert (last == (runs & (ki == (qi + 1) * ratio - 1))).all()
    assert first.sum() == last.sum() == nq
    # the index maps name the kernel's blocks; on a dead step, the blocks of
    # the step before it (nothing is fetched, the output block stays)
    mq, mk = _arrays(q_block(p, s), k_block(p, s))
    assert (mq[runs] == qi[runs]).all() and (mk[runs] == ki[runs]).all()
    for at in np.flatnonzero(~runs):
        assert (mq[at], mk[at]) == (mq[at - 1], mk[at - 1])
    assert _contiguous(mq[:, None])


@pytest.mark.parametrize("group", GROUP)
@pytest.mark.parametrize("ratio", RATIO)
@pytest.mark.parametrize("nq", NQ)
def test_dkv_grid_holds_each_live_block_once(nq, ratio, group):
    block_q = ratio * BLOCK_K
    t, nk = nq * block_q, nq * ratio
    rows, steps, *blocks = _k_major_grid(t, block_q, BLOCK_K, group, True,
                                         None)
    if nq == 1:
        assert (rows, steps) == (nk, group)
    else:
        assert (rows, steps) == ((nk + 1) // 2, group * (nq + 1))
    p, s = _grid_steps(rows, steps)
    qi, ki, i, count = _arrays(*_k_major_step(
        p, s, block_q, BLOCK_K, nq, group, True, None))
    first, last = i == 0, i == count - 1
    mk, mg, mq = _arrays(*(block(p, s) for block in blocks))
    runs = _runs(qi, ki, ratio)
    live = [(j, g, i) for j in range(nk) for g in range(group)
            for i in range(j // ratio, nq)]
    # every live (k block, head of the group, q block) exactly once; a k
    # block's sweep runs over the heads and, inside a head, up the q blocks
    visited = list(zip(ki[runs].tolist(), mg[runs].tolist(),
                       qi[runs].tolist()))
    assert sorted(visited) == live
    for j in range(nk):
        assert [(g, i) for k, g, i in visited if k == j] == [
            (g, i) for g in range(group) for i in range(j // ratio, nq)]
    # an odd count's middle k block: what its partner would have held
    dead = (steps - group * (nq - (nk // 2) // ratio)
            if nq > 1 and nk % 2 else 0)
    assert np.count_nonzero(~runs) == dead == rows * steps - len(live)
    assert runs[:len(runs) - dead].all()
    assert len(live) == group * block_census(t, block_q, BLOCK_K, True)[0]
    lo = ki // ratio
    assert (first == (runs & (mg == 0) & (qi == lo))).all()
    assert (last == (runs & (mg == group - 1) & (qi == nq - 1))).all()
    assert first.sum() == last.sum() == nk
    assert (mk[runs] == ki[runs]).all() and (mq[runs] == qi[runs]).all()
    for at in np.flatnonzero(~runs):
        assert (mk[at], mg[at], mq[at]) == (mk[at - 1], mg[at - 1],
                                            mq[at - 1])
    assert _contiguous(mk[:, None])


def _traced_grids(causal, block_q, block_k, window, t=128, h=2, hkv=2,
                  shared=0):
    """{kernel name: grid} of a forward + backward trace; ``shared``: that
    many of k's 16 lanes are ONE head's for all (``k_shared``)."""
    q = jax.ShapeDtypeStruct((1, t, h, 16), jnp.float32)
    k = jax.ShapeDtypeStruct((1, t, hkv, 16), jnp.float32)
    jaxpr = jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k[..., :16 - shared], v, causal, block_q, block_k, True, None,
        window, k[:, :, :1, 16 - shared:] if shared else None)),
        argnums=(0, 1, 2)))(q, k, k)
    grids = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                grids[eqn.params["name"]] = tuple(
                    eqn.params["grid_mapping"].grid)
            for value in eqn.params.values():
                if hasattr(value, "jaxpr"):
                    walk(value.jaxpr)
                elif hasattr(value, "eqns"):
                    walk(value)

    walk(jaxpr.jaxpr)
    return grids


def test_a_shared_key_part_leaves_the_grids_and_names_what_they_are():
    """The shared part is one more operand behind k's own index map: the
    three grids and the kernels' device names are the dense call's."""
    for causal, block_q, block_k in ((True, 32, 16), (True, 128, 128),
                                     (False, 32, 16)):
        assert (_traced_grids(causal, block_q, block_k, None, shared=4)
                == _traced_grids(causal, block_q, block_k, None))


def test_windowed_and_non_causal_calls_trace_the_grids_they_had():
    # non-causal: the rectangle, all of it live
    assert _traced_grids(False, 32, 16, None) == {
        "hvd_flash_fwd": (2, 4, 8), "hvd_flash_bwd_dq": (2, 4, 8),
        "hvd_flash_bwd_dkv": (2, 8, 4)}
    # a window of 40 at 32 / 16: a q block's band touches 2 + 3 k blocks, a
    # k block's 1 + 2 q blocks; grouped-query heads sweep inside a k block
    assert _traced_grids(True, 32, 16, 40, h=4) == {
        "hvd_flash_win_fwd": (4, 4, 5), "hvd_flash_win_bwd_dq": (4, 4, 5),
        "hvd_flash_win_bwd_dkv": (2, 8, 6)}


@pytest.mark.parametrize("t,block_q,block_k,h,grids", [
    # 4 q blocks: 2 rows of 5 steps for the 10 live blocks
    (128, 32, 32, 2, ((2, 2, 5), (2, 2, 5))),
    # 3 q blocks: the middle one a row of its own, half of it dead
    (96, 32, 32, 2, ((2, 2, 4), (2, 2, 4))),
    # block_q = 2 x block_k, 6 heads over 2: 8 k blocks in 4 rows
    (128, 32, 16, 6, ((6, 2, 10), (2, 4, 15))),
    # one block a row is its own partner: today's grid
    (64, 64, 64, 2, ((2, 1, 1), (2, 1, 1))),
    (64, 64, 32, 2, ((2, 1, 2), (2, 2, 1))),
])
def test_causal_dense_calls_trace_the_folded_grids(t, block_q, block_k, h,
                                                   grids):
    assert _traced_grids(True, block_q, block_k, None, t, h) == {
        "hvd_flash_fwd": grids[0], "hvd_flash_bwd_dq": grids[0],
        "hvd_flash_bwd_dkv": grids[1]}


def _dead_step_share():
    from horovod_tpu.metrics import registry
    return registry().snapshot()["gauges"].get(
        "horovod_flash_dead_step_share")


def _trace(t, block, causal=True, window=None):
    x = jax.ShapeDtypeStruct((1, t, 1, 16), jnp.bfloat16)
    jax.make_jaxpr(lambda *a: flash_attention(
        *a, causal, block, block, True, None, window))(x, x, x)


@pytest.mark.parametrize("t,block,share", [
    (16384, None, 0.0),     # lm217m_long, granite4h, laguna's full layers
    (8192, None, 0.0),      # kanana2_seq8192_1chip
    (4096, None, 0.0),      # olmoe_seq4096_1chip
    (1024, None, 0.0),      # lm217m_short_1chip: one block a row
    (3072, None, 0.25),     # three q blocks: 6 live of 8 steps
    (160, 32, 1 / 6),       # five
])
def test_dead_step_share_gauge_counts_the_traced_grid(t, block, share):
    """The gauge is counted from the grid the call runs: the parent's
    rectangles read 0.469 / 0.4375 / 0.375 at 16,384 / 8,192 / 4,096."""
    _trace(t, block)
    assert _dead_step_share() == pytest.approx(share)
    rows, steps = _q_major_grid(t, block or 1024, block or 1024, True,
                                None)[:2]
    live = block_census(t, block or 1024, block or 1024, True)[0]
    assert _dead_step_share() == pytest.approx(1 - live / (rows * steps))


def test_dead_step_share_is_left_alone_by_other_calls():
    _trace(3072, None)
    assert _dead_step_share() == pytest.approx(0.25)
    _trace(3072, None, window=512)          # its grid holds the band
    assert _dead_step_share() == pytest.approx(0.25)
    _trace(3072, None, causal=False)        # all of its rectangle is live
    assert _dead_step_share() == pytest.approx(0.25)
    _trace(3072, None, window=4096)         # no window at all: causal-dense
    assert _dead_step_share() == pytest.approx(0.25)
    _trace(4096, None)
    assert _dead_step_share() == 0.0


@pytest.mark.parametrize("kind,backward,scale", [
    ("dense", False, 0.0611), ("dense", True, 0.0612),
    ("windowed", False, 0.0613), ("windowed", True, 0.0614),
    ("selected", False, 0.0615), ("selected", True, 0.0616),
    ("shared_key", False, 0.0617), ("shared_key", True, 0.0618),
    ("kv_in_one", False, 0.0619), ("kv_in_one", True, 0.0620),
])
def test_layers_of_one_signature_share_one_trace_of_each_kernel(
        monkeypatch, kind, backward, scale):
    """A step is traced and lowered on every start and the kernels' bodies
    are unrolled: a kernel traced once a LAYER is set-up time (``setup_s``,
    ``trace_lower_s``). The jitted boundaries hold one copy a signature;
    ``scale`` is one no other test calls with, so none is held yet."""
    from horovod_tpu.ops import sparse_attention as dsa

    fa = importlib.import_module("horovod_tpu.ops.flash_attention")

    traced = collections.Counter()

    def counted(name, body):
        def kernel(*refs, **static):
            traced[name] += 1
            return body(*refs, **static)
        return kernel

    for name in ("_fwd_kernel", "_dq_kernel", "_dkv_kernel"):
        monkeypatch.setattr(fa, name, counted(name, getattr(fa, name)))
    t = 64
    words = dsa.pack(jnp.tril(jnp.ones((1, t, t), bool)), 16)

    def layer(x, k, v):
        if kind == "selected":
            return fa.selected_attention(x, k, v, words, 32, 32, True, scale,
                                         16)[0]
        if kind == "shared_key":    # k in two parts, one of them ONE head's
            return fa.flash_attention(x, k[..., :12], v, True, 32, 32, True,
                                      scale, None, k[:, :, :1, 12:])
        if kind == "kv_in_one":     # latent attention's call: [k | v] whole too
            out = fa.flash_attention(
                x, jnp.concatenate([k[..., :8], v[..., :8]], axis=-1), None,
                True, 32, 32, True, scale, None, k[:, :, :1, 8:])
            return jnp.concatenate([out, out], axis=-1)     # q's 16 lanes
        return fa.flash_attention(x, k, v, True, 32, 32, True, scale,
                                  24 if kind == "windowed" else None)

    def two_layers(q, k, v):
        return jnp.sum(layer(layer(q, k, v), k, v))

    q = jax.ShapeDtypeStruct((1, t, 2, 16), jnp.float32)
    jax.make_jaxpr(jax.grad(two_layers, argnums=(0, 1, 2)) if backward
                   else two_layers)(q, q, q)
    assert traced == {name: 1 for name in (
        ("_fwd_kernel", "_dq_kernel", "_dkv_kernel") if backward
        else ("_fwd_kernel",))}
