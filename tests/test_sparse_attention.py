"""Learned sparse attention's pieces (``ops/sparse_attention.py``,
``ops.flash_attention.selected_attention``) in the Pallas interpreter: the
packed selection and its inverse; the exact top-k of a chunk against
``lax.top_k``, ties included; the three selected flash kernels against dense
attention under the same mask, at blocks that fold the grid, at a chunk
narrower than a sub-tile, with a block that holds no selected pair (a dead
step, counted) and on a row shorter than ``topk`` (the causal-dense call's
bits); the alignment loss and the indexer's backward against ``jax.grad`` of
their definition, over several q tiles, dead tiles between live ones and
ReLU products at exactly zero, and the products a tile of it runs; the fetch
table; and a recomputed block that keeps the forward's selection."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import sparse_attention as dsa
from horovod_tpu.ops.flash_attention import flash_attention, selected_attention

B, T, H, KV, D, HI, DI = 2, 128, 4, 2, 16, 2, 8


def _operands(seed, t=T, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    shapes = [(B, t, H, D), (B, t, KV, D), (B, t, KV, D), (B, t, H, D),
              (B, t, HI, DI), (B, t, DI), (B, t, HI)]
    return [jax.random.normal(k, s, jnp.float32).astype(dtype)
            for k, s in zip(ks, shapes)]


def _causal(t):
    return jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]


def _top_k_mask(scores, topk):
    """The definition: ``lax.top_k`` over the causal scores, ties to the
    lower position; all causal keys while there are no more than ``topk``."""
    t = scores.shape[-1]
    _, best = jax.lax.top_k(jnp.where(_causal(t), scores, -jnp.inf),
                            min(topk, t))
    picked = jnp.zeros(scores.shape, bool)
    picked = picked.at[jnp.arange(scores.shape[0])[:, None, None],
                       jnp.arange(t)[None, :, None], best].set(True)
    return picked & _causal(t)


def _index_scores(qi, ki, w):
    z = jnp.einsum("bthd,bsd->bhts", qi, ki)
    return jnp.einsum("bth,bhts->bts", w, jax.nn.relu(z))


def _masked_attention(q, k, v, mask):
    """Dense attention of (B, T, H, D) over the pairs ``mask`` (B, T, T)
    keeps; -> (out, per-head logsumexp (B, H, T))."""
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    s = jnp.where(mask[:, None], s, -jnp.inf)
    return (jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v),
            jax.nn.logsumexp(s, axis=-1))


def close(got, want, tol):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=tol,
                               rtol=tol)


@pytest.mark.parametrize("t,chunk", [(128, 16), (96, 32), (2048, 32)])
def test_pack_and_unpack_are_inverses(t, chunk):
    mask = jax.random.bernoulli(jax.random.PRNGKey(t), 0.3, (3, 5, t))
    words = dsa.pack(mask, chunk)
    assert words.shape == (3, 5, dsa.word_columns(t, chunk))
    assert words.dtype == jnp.int32
    np.testing.assert_array_equal(dsa.unpack(words, t, chunk), mask)
    # key s: bit (s // chunk) % 32 of column (s // chunk // 32) * chunk + s % chunk
    one = dsa.pack(jnp.arange(t) == t - 1, chunk)
    n, c = (t - 1) // chunk, (t - 1) % chunk
    assert int(one[n // 32 * chunk + c]) == np.int32(np.uint32(1 << (n % 32)))
    assert int(jnp.sum(one != 0)) == 1


# (T, chunk, q_chunk, topk, levels, the weights' sign). ``levels`` distinct
# values make ties at the threshold in most rows (0: every score equal); none
# are left to chance.
SELECT_CASES = {
    **{f"topk{topk}-levels{levels}": (T, 16, 32, topk, levels, 1)
       for topk in (8, 24, 200) for levels in (0, 3, 1000)},
    # row0 < topk <= row0 + rows: some rows keep all their keys, some choose
    "a-chunk-straddles-topk": (T, 16, 32, 40, 1000, 1),
    # every score <= 0 and many exactly 0: the ties are on top
    "negative-scores": (T, 16, 32, 24, 3, -1),
    # 40 key chunks: two word groups, the second 8 bits deep; every score equal
    "all-equal-over-two-word-groups": (640, 16, 64, 24, 0, 1),
    # three key chunks in a word of 32
    "a-word-group-partly-padding": (96, 32, 32, 24, 3, 1),
    # scores of its own: see _signed_zero_scores
    "signed-zeros-at-the-threshold": (T, 16, 32, 24, None, 1),
}


def _signed_zero_scores(t):
    """(B, T, T) causal scores of -2, -0.0, +0.0 and 1 in turn: from the 25th
    query on the threshold lies among the zeros of both signs, which count as
    equal and go to the lower position."""
    values = jnp.asarray([-2.0, -0.0, 0.0, 1.0, 0.0, -0.0], jnp.float32)
    at = (jnp.arange(t)[None, :] * 5 + jnp.arange(t)[:, None] * 3) % 6
    return jnp.broadcast_to(jnp.where(_causal(t), values[at], -jnp.inf),
                            (B, t, t))


@pytest.mark.parametrize("case", sorted(SELECT_CASES))
def test_select_is_the_exact_top_k_with_ties_to_the_lower_position(case):
    t, chunk, q_chunk, topk, levels, sign = SELECT_CASES[case]
    if levels is None:      # the kernel alone, chunk by chunk as select does
        scores = _signed_zero_scores(t)
        assert bool(jnp.any(jnp.signbit(scores) & (scores == 0)))
        parts = [[dsa._select_call(row[at:at + q_chunk], at, topk, chunk, True)
                  for at in range(0, t, q_chunk)] for row in scores]
        words, lse_i = (jnp.stack([jnp.concatenate([p[i] for p in row])
                                   for row in parts]) for i in (0, 1))
    else:
        _, _, _, _, qi, ki, w = _operands(topk + levels, t)
        if levels:
            qi, ki, w = (jnp.round(x * 2) / 2 for x in (qi, ki, w))
            if levels < 10:
                w = jnp.round(w)
        else:
            w = jnp.zeros_like(w)
        w = sign * jnp.abs(w) if sign < 0 else w
        scores = _index_scores(qi, ki, w)
        words, lse_i = dsa.select(qi, ki, w, topk, chunk, q_chunk, True)
    want = _top_k_mask(scores + 0.0, topk)      # -0.0 as 0.0
    np.testing.assert_array_equal(words, dsa.pack(want, chunk))   # bit-equal
    got = dsa.unpack(words, t, chunk)
    # all of its causal keys while a query has no more than topk
    np.testing.assert_array_equal(got[:, :min(topk, t)],
                                  jnp.broadcast_to(_causal(t), got.shape)[
                                      :, :min(topk, t)])
    assert int(jnp.sum(got)) == B * sum(min(s + 1, topk) for s in range(t))
    close(lse_i, jax.nn.logsumexp(jnp.where(want, scores, -jnp.inf), axis=-1),
          1e-6)


@pytest.mark.parametrize("t,chunk,q_chunk", [(16384, 512, 512), (T, 16, 32)])
def test_the_gauge_says_which_share_of_the_keys_the_passes_visit(
        hvd, t, chunk, q_chunk):
    """From shapes, at trace time: a row tile (128 queries, or the chunk's if
    fewer) visits the key chunks at or before its last query."""
    shape = jax.ShapeDtypeStruct
    jax.eval_shape(
        lambda qi, ki, w: dsa.select(qi, ki, w, 24, chunk, q_chunk, True),
        shape((1, t, HI, DI), jnp.float32), shape((1, t, DI), jnp.float32),
        shape((1, t, HI), jnp.float32))
    tile = min(q_chunk, 128)
    visited = sum((((query // tile + 1) * tile - 1) // chunk + 1) * chunk
                  for query in range(t))
    share = hvd.metrics.registry().snapshot()["gauges"][
        "horovod_dsa_select_visited_share"]
    assert share == pytest.approx(visited / (t * t), rel=1e-12)
    assert 0.5 < share < (0.53 if t == 16384 else 0.7)


def _mask_with_a_dead_block(t=T, block=32):
    """Every query keeps its own block's causal keys and the first ``block``
    keys: at the defaults the blocks (2, 1), (3, 1) and (3, 2) of 32 x 32 hold
    no pair; in a wider tile's row the k tiles between the first and the
    tile's own are DEAD between live ones."""
    pos = jnp.arange(t)
    own = (pos[:, None] // block == pos[None, :] // block) | (
        pos[None, :] < block)
    return jnp.broadcast_to(own & _causal(t), (B, t, t))


@pytest.mark.parametrize("block_q,block_k,chunk", [
    (32, 32, 16),       # four q blocks: the folded grid
    (64, 32, 32),       # ratio 2: two crossed blocks a q block
    (128, 128, 16),     # one block: sub-tiles wider than a chunk
    (96, 32, 32),       # never: 128 % 96 - fitted down to 64
    (32, 16, 32),       # a k block narrower than a chunk (interpreter only)
])
@pytest.mark.parametrize("mask_kind", ["top_k", "dead_block"])
def test_selected_kernels_are_dense_attention_under_the_mask(
        block_q, block_k, chunk, mask_kind):
    q, k, v, g, qi, ki, w = _operands(1)
    if mask_kind == "top_k":
        mask = _top_k_mask(_index_scores(qi, ki, w), 24)
    else:
        mask = _mask_with_a_dead_block()
    words = dsa.pack(mask, chunk)

    def system(q, k, v):
        return selected_attention(q, k, v, words, block_q, block_k, True,
                                  None, chunk)

    (out, lse), vjp = jax.vjp(system, q, k, v)
    (want, want_lse), want_vjp = jax.vjp(
        lambda q, k, v: _masked_attention(q, k, v, mask), q, k, v)
    close(out, want, 2e-5)
    close(lse, want_lse, 2e-5)
    for got, ref in zip(vjp((g, jnp.zeros_like(lse))),
                        want_vjp((g, jnp.zeros_like(want_lse)))):
        close(got, ref, 5e-5)


def test_a_block_without_a_selected_pair_is_a_dead_step_and_counted():
    mask = _mask_with_a_dead_block()
    words = dsa.pack(mask, 16)
    live = dsa.block_liveness(words, 32, 32, 16)
    want = np.array([[1, 0, 0, 0], [1, 1, 0, 0], [1, 0, 1, 0], [1, 0, 0, 1]],
                    bool)
    np.testing.assert_array_equal(live, np.broadcast_to(want, (B, 4, 4)))
    pairs, steps = dsa.census(words, 32, 32, 16)
    assert int(pairs) == int(jnp.sum(mask)) and int(steps) == B * 7
    # a dead step's index maps name the block already resident
    np.testing.assert_array_equal(dsa.fetch_table(jnp.asarray(want)),
                                  [[0, 0, 0, 0], [0, 1, 1, 1], [0, 0, 2, 2],
                                   [0, 0, 0, 3]])
    np.testing.assert_array_equal(
        dsa.fetch_table(jnp.asarray([[False, False, True, False],
                                     [False, False, False, False]])),
        [[2, 2, 2, 2], [0, 1, 2, 3]])
    # keys nobody selects receive no gradient
    q, k, v, g, *_ = _operands(2)
    lonely = mask & (jnp.arange(T)[None, :] != 40)[None]
    _, dk, dv = jax.grad(lambda q, k, v: jnp.sum(selected_attention(
        q, k, v, dsa.pack(lonely, 16), 32, 32, True, None, 16)[0] * g),
        argnums=(0, 1, 2))(q, k, v)
    assert float(jnp.max(jnp.abs(dk[:, 40]))) == 0.0
    assert float(jnp.max(jnp.abs(dv[:, 40]))) == 0.0
    assert float(jnp.max(jnp.abs(dv[:, 41]))) > 0.0


def test_the_gauges_say_what_the_steps_selected(hvd):
    hvd.metrics.record_dsa_census(np.zeros((0, 2), np.int32), 20)
    hvd.metrics.record_dsa_census(np.asarray([[1000, 14], [1200, 16]]), 20)
    gauges = hvd.metrics.registry().snapshot()["gauges"]
    assert gauges["horovod_dsa_selected_pairs_per_step"] == 1100
    assert gauges["horovod_dsa_live_block_steps_per_step"] == 15
    assert gauges["horovod_dsa_dense_block_steps_per_step"] == 20
    assert gauges["horovod_flash_dead_step_share"] == pytest.approx(0.25)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_a_row_shorter_than_topk_is_the_causal_dense_call(dtype):
    """While ``t < topk`` the selection is every causal key: out and the
    three gradients are the causal-dense kernels', bit for bit."""
    q, k, v, g, qi, ki, w = _operands(3, 64, dtype)
    words, _ = dsa.select(qi, ki, w, 100, 16, 16, True)
    np.testing.assert_array_equal(
        dsa.unpack(words, 64, 16), jnp.broadcast_to(_causal(64), (B, 64, 64)))

    def selected(q, k, v):
        return selected_attention(q, k, v, words, 32, 32, True, None, 16)[0]

    def dense(q, k, v):
        return flash_attention(q, k, v, True, 32, 32, True)

    for fn in (lambda f: f(q, k, v),
               lambda f: jax.vjp(f, q, k, v)[1](g.astype(dtype))):
        for got, want in zip(jax.tree_util.tree_leaves(fn(selected)),
                             jax.tree_util.tree_leaves(fn(dense))):
            np.testing.assert_array_equal(np.asarray(got, np.float32),
                                          np.asarray(want, np.float32))


def _align_definition(q, k, qi, ki, w, mask):
    """The loss as it is written: p a constant, r the softmax of the
    indexer's score over the selection."""
    group = q.shape[2] // k.shape[2]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, group, axis=2)
                   ) * q.shape[-1] ** -0.5
    p = jax.lax.stop_gradient(jnp.mean(jax.nn.softmax(
        jnp.where(mask[:, None], s, -jnp.inf), axis=-1), axis=1))
    log_r = jax.nn.log_softmax(jnp.where(mask, _index_scores(qi, ki, w),
                                         -jnp.inf), axis=-1)
    kept = mask & (p > 0)
    return jnp.mean(jnp.sum(jnp.where(kept, p * (
        jnp.log(jnp.where(kept, p, 1.0)) - jnp.where(kept, log_r, 0.0)), 0.0),
        axis=-1))


# (T, chunk, the selection, whether the indexer's operands are whole numbers:
# then many ReLU products are EXACTLY zero, where the gradient is zero). A q
# tile is 256 queries: rows of 512 and 768 have two and three.
ALIGN_CASES = {
    "one-q-tile-chunk16": (T, 16, "top_k", False),
    "one-q-tile-chunk64": (T, 64, "top_k", False),
    "two-q-tiles": (512, 64, "top_k", False),
    "two-q-tiles-zero-products": (512, 64, "top_k", True),
    "dead-tiles-between-live-ones": (512, 64, "own_chunk", False),
    "dead-tiles-and-zero-products": (768, 128, "own_chunk", True),
}


@pytest.mark.parametrize("case", sorted(ALIGN_CASES))
def test_alignment_loss_and_the_indexers_backward(case):
    t, chunk, selection, whole = ALIGN_CASES[case]
    q, k, v, _, qi, ki, w = _operands(4, t)
    if whole:
        qi, ki = jnp.round(qi), jnp.round(ki)
        zero = jnp.einsum("bthd,bsd->bhts", qi, ki) == 0
        assert float(jnp.mean(zero)) > 0.05
    if selection == "top_k":
        words, lse_i = dsa.select(qi, ki, w, 24, chunk, 32, True)
        mask = dsa.unpack(words, t, chunk)
    else:
        mask = _mask_with_a_dead_block(t, chunk)
        words = dsa.pack(mask, chunk)
        lse_i = jax.nn.logsumexp(
            jnp.where(mask, _index_scores(qi, ki, w), -jnp.inf), axis=-1)
        live = np.asarray(dsa.block_liveness(words, 256, chunk, chunk)[0, -1])
        first_dead, last_live = np.argmin(live), np.flatnonzero(live)[-1]
        assert live[0] and 0 < first_dead < last_live     # dead between live
    block = 32 if t == T else 128
    _, lse = selected_attention(q, k, v, words, block, block, True, None, chunk)

    def system(qi, ki, w):
        return dsa.align_loss(q, k, lse, qi, ki, w, words, lse_i, None, chunk,
                              True)

    got, grads = jax.value_and_grad(system, argnums=(0, 1, 2))(qi, ki, w)
    want, want_grads = jax.value_and_grad(
        lambda *a: _align_definition(q, k, *a, mask), argnums=(0, 1, 2))(
        qi, ki, w)
    assert float(want) > 1e-2
    close(got, want, 1e-5)
    close(system(qi, ki, w), want, 1e-5)        # the undifferentiated call
    for g, r in zip(grads, want_grads):
        assert float(jnp.max(jnp.abs(r))) > 1e-5
        close(g, r, 2e-5 * float(jnp.max(jnp.abs(r))) / 1e-2 + 1e-7)
    # q, k and the logsumexp are constants of it (once is enough: the short row)
    assert t > T or all(float(jnp.max(jnp.abs(g))) == 0.0 for g in jax.grad(
        lambda q, k, lse: dsa.align_loss(q, k, lse, qi, ki, w, words, lse_i,
                                         None, chunk, True),
        argnums=(0, 1, 2))(q, k, lse))


def _equations(jaxpr, name):
    """The equations of one primitive in a jaxpr and everything it nests."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _equations(sub, name)


@pytest.mark.parametrize("grads", [True, False])
def test_a_tile_of_the_alignment_pass_computes_each_score_once(grads):
    """The products of the kernel's body at the cell's 16 indexer heads: one
    a main head, ONE an indexer head for its score tile (a (tq, tk) product
    over ``index_dim``; the parent ran each twice) and with ``grads`` two an
    indexer head for dqI and dkI^T - the gradient loop reads the loss loop's
    ReLU(z) from a VMEM scratch. Without ``grads`` there is no second loop
    and no scratch."""
    t, heads, kv_heads, d, index_heads, di, chunk = 512, 4, 2, 16, 16, 8, 64
    shape = jax.ShapeDtypeStruct
    f32 = jnp.float32
    jaxpr = jax.make_jaxpr(
        lambda *a: dsa._align_call(*a, d ** -0.5, chunk, False, grads))(
        shape((heads, t, d), f32), shape((kv_heads, t, d), f32),
        shape((t, heads), f32), shape((index_heads, t, di), f32),
        shape((t, di), f32), shape((t, index_heads), f32), shape((t, 1), f32),
        shape((t, dsa.word_columns(t, chunk)), jnp.int32))
    (call,) = _equations(jaxpr.jaxpr, "pallas_call")
    products = list(_equations(call.params["jaxpr"], "dot_general"))
    scores = [eqn for eqn in products
              if eqn.outvars[0].aval.shape == (256, chunk)
              and eqn.invars[0].aval.shape == (256, di)]
    scratch = call.params["grid_mapping"].num_scratch_operands
    assert len(scores) == index_heads
    assert len(products) == heads + (3 if grads else 1) * index_heads
    assert scratch == int(grads)


def _model(remat, **fields):
    from horovod_tpu.models import RotaryScheme, SparseDims, TransformerLM

    return TransformerLM(
        vocab=64, dim=32, heads=4, kv_heads=2, head_dim=16, layers=2,
        dtype=jnp.float32, attention="flash", flash_interpret=True,
        block_q=32, block_k=32, qk_head_norm=True, rope_theta=1e4,
        rotary=RotaryScheme(theta=1e4, sections=(2, 3, 3)),
        sparse=SparseDims(index_heads=2, index_dim=8, topk=12, kv_chunk=16,
                          q_chunk=16),
        moe_experts=4, moe_top_k=2, moe_hidden=16, moe_every=1,
        moe_norm_topk=True, remat=remat, **fields)


def test_a_recomputed_block_keeps_the_forwards_selection():
    """``TransformerLM(remat=True)`` saves the selection (the name
    ``ops.sparse_attention.SELECTED``) and the alignment loss's gradients:
    the backward runs no second score pass, top-k or alignment pass, and on a
    seeded tie (the indexer's weights zero: every score equal, the first
    ``topk`` keys kept) its gradients are the unrecomputed model's."""
    from horovod_tpu.common import device_names
    from horovod_tpu.models import align_losses

    tokens = jnp.arange(64, dtype=jnp.int32).reshape(1, 64) % 64
    variables = _model(False).init(jax.random.PRNGKey(0), tokens)
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: jnp.zeros_like(x) if any(
            getattr(p, "key", None) == "index_w" for p in path) else x,
        variables["params"])

    def grad_of(remat):
        def loss(params):
            logits, state = _model(remat).apply(
                {"params": params}, tokens, mutable=["intermediates"])
            return jnp.sum(logits ** 2) * 1e-3 + align_losses(
                state["intermediates"])[0]
        return jax.grad(loss)

    plain, recomputed = grad_of(False), grad_of(True)
    for name, calls in ((device_names.DSA_INDEXER_SCORES, 2),
                        (device_names.DSA_ALIGN_TILES, 2),
                        (device_names.FLASH_SEL_FWD, None)):
        counts = [str(jax.make_jaxpr(f)(params)).count(name)
                  for f in (plain, recomputed)]
        if calls is None:       # the forward kernel alone runs again
            assert counts[1] == 2 * counts[0] > 0
        else:
            assert counts[0] == counts[1] > 0, (name, counts)
    for got, want in zip(*(jax.tree_util.tree_leaves(f(params))
                           for f in (recomputed, plain))):
        close(got, want, 1e-5)
    # the tie: every query kept its first 12 keys
    _, state = _model(False).apply({"params": params}, tokens,
                                   mutable=["intermediates"])
    kept = dsa.unpack(state["intermediates"]["block_0"]["dsa_words"][0], 64, 16)
    np.testing.assert_array_equal(
        kept[0], _causal(64) & (jnp.arange(64)[None, :] < 12))


def test_the_selection_carries_no_gradient_and_the_losses_do_not_mix():
    """The indexer's parameters move by the alignment loss alone, every other
    parameter by the language-model loss alone."""
    from horovod_tpu.models import align_losses

    tokens = (jnp.arange(64, dtype=jnp.int32).reshape(1, 64) * 7) % 64
    model = _model(False)
    params = model.init(jax.random.PRNGKey(1), tokens)["params"]

    def terms(params):
        logits, state = model.apply({"params": params}, tokens,
                                    mutable=["intermediates"])
        return jnp.sum(logits ** 2), align_losses(state["intermediates"])[0]

    def is_indexer(path):
        return any(str(getattr(p, "key", "")).startswith("index_") for p in path)

    for which, own in ((0, False), (1, True)):
        grads = jax.grad(lambda p: terms(p)[which])(params)
        for path, leaf in jax.tree_util.tree_flatten_with_path(grads)[0]:
            moved = float(jnp.max(jnp.abs(leaf))) > 0.0
            assert moved == (is_indexer(path) == own), (which, path)


def test_a_choice_moved_by_hand_is_held_under_the_systems_choice(
        hvd, monkeypatch):
    """The keye configuration's own check (``benchmarks/configs``, at the
    tiny sizes of ``tests/benchmark/test_benchmark_keye.py``) with the
    selection's kernel replaced by one that exchanges query 90's last kept
    key for the first it left out - the two bits flipped in ``words``,
    ``lse_i`` recomputed over the kept - as a float32 tie between its 24th
    and 25th score would, and a token whose 2nd expert is exchanged for its
    3rd: both shares are held as shares, and losses, logits and gradients
    against the reference computed under the system's choices
    (``selection(forced=)``, ``route(forced=)``), at the float32 limits."""
    import os

    monkeypatch.syspath_prepend(
        os.path.join(os.path.dirname(__file__), "benchmark"))
    from test_benchmark_keye import check_alone

    from horovod_tpu.models import moe as models_moe

    real_call, real_route = dsa._select_call, models_moe.topk_route

    def other_key(scores, row0, topk, chunk, interpret):
        words, _ = real_call(scores, row0, topk, chunk, interpret)
        rows, t = scores.shape
        mask = dsa.unpack(words, t, chunk)
        pos = jnp.arange(t)
        last = jnp.max(jnp.where(mask, pos, -1), axis=1)
        free = jnp.min(jnp.where((scores > -jnp.inf) & ~mask, pos, t), axis=1)
        here = (row0 + jnp.arange(rows) == 90) & (free < t)
        mask ^= here[:, None] & ((pos == last[:, None]) | (pos == free[:, None]))
        kept = jnp.where(mask, scores, -jnp.inf)
        return dsa.pack(mask, chunk), jax.nn.logsumexp(kept, axis=1)

    def other_expert(logits, top_k, renormalise=False):
        probs, _, experts = real_route(logits, top_k, renormalise)
        _, wider = jax.lax.top_k(probs, top_k + 1)
        first = jnp.arange(experts.shape[0])[:, None] == 0      # token 0 alone
        last = jnp.arange(top_k)[None, :] == top_k - 1
        experts = jnp.where(first & last, wider[:, top_k:], experts)
        onehot = experts[:, :, None] == jnp.arange(probs.shape[-1])
        weights = jnp.sum(jnp.where(onehot, probs[:, None, :], 0.0), axis=-1)
        return probs, weights / weights.sum(-1, keepdims=True), experts

    # the ops' jitted calls keep their traces: start and end without them
    jax.clear_caches()
    monkeypatch.setattr(dsa, "_select_call", other_key)
    monkeypatch.setattr(models_moe, "topk_route", other_expert)
    try:
        resolved, check = check_alone(hvd)
        resolved["config"]["tolerance"] = {
            **resolved["config"]["tolerance"], "f32_flipped_share": 0.05,
            "f32_selection_share": 0.01, "bf16_logits_rel": 1.0}
        observed = check()["observed"]
    finally:
        jax.clear_caches()
    f32 = observed["f32"]
    assert f32["held_under"] == "the system's choice"
    # token 0 by hand; the query whose key moved may choose others after it
    assert 1 / 96 - 1e-6 <= f32["flipped_share"] <= 3 / 96 + 1e-6
    kept = sum(min(t + 1, 24) for t in range(96))
    # a pair a layer by hand; the query's later selection may follow it
    assert 2 / (2 * kept) - 1e-9 <= f32["selection_share"] <= 8 / (2 * kept)
    assert f32["logits"] <= 2e-6 and f32["lm"] <= 1e-6 and f32["align"] <= 4e-5
    assert max(f32["grads_rel"].values()) <= 3e-5
    assert observed["bf16"]["held_under"] == "the reference's own choice"
