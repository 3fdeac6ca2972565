"""What a sparse-attention mixture of experts' step needs, from shapes alone:
the selected pairs, the flash kernel calls over them at grouped-query heads,
and one expert-parallel rank's share of the whole step. Conventions as at the
top of ``flops.py``: a multiply-add is 2 operations, forward + backward = 3 x
forward, recompute not counted - with ONE stated exception,
:func:`flash_calls_step_cost`, as in ``swa_cost.py``. Every count is of the
work the MODEL needs, whatever implements it: kernels that run every live
block step dense under a mask read low against it, and a later kernel cannot
move its own yardstick."""

from __future__ import annotations

from benchmarks.swa_cost import roofline_pct  # noqa: F401  (the readers' import)

SELECTED = ("hvd_flash_sel_fwd", "hvd_flash_sel_bwd_dq", "hvd_flash_sel_bwd_dkv")
INDEXER = ("hvd_dsa_indexer", "hvd_dsa_indexer_scores")
SELECT = ("hvd_dsa_select",)
ALIGN = ("hvd_dsa_align", "hvd_dsa_align_tiles")


def causal_pairs(seq):
    """(query, key) pairs ``s <= t`` of one row: the diagonal included."""
    return seq * (seq + 1) // 2


def selected_pairs(seq, topk):
    """Pairs one row's queries keep: the query at ``t`` keeps ``min(t + 1,
    topk)`` of its ``t + 1`` causal keys."""
    k = min(seq, topk)
    return k * (k + 1) // 2 + (seq - k) * k


def flash_calls_step_cost(seq, rows, heads, kv_heads, head_dim, layers, topk,
                          forward_calls=1, itemsize=2):
    """What the selected flash kernel CALLS of one step need at ``heads``
    query heads over ``kv_heads`` of ``head_dim`` in ``layers`` layers: the
    roofline share's numerator, over the time of those same calls. A product
    is ``selected_pairs`` x head_dim multiply-adds a query head. The forward
    kernel's two products count ``forward_calls`` times (under ``remat`` it
    runs twice a step and both runs are in the measured time); the backward's
    five count once (``swa_cost.flash_calls_step_cost``'s convention). Bytes:
    as there, and each of the three kernels reads the selection once, one bit
    a causal-or-not pair (``seq * seq / 8``)."""
    product = 2 * selected_pairs(seq, topk) * head_dim * heads
    per_query_head = rows * seq * heads * head_dim * itemsize      # q, o, dO, dq
    per_kv_head = rows * seq * kv_heads * head_dim * itemsize      # k, v, dk, dv
    lse = rows * seq * heads * 4
    bits = rows * seq * seq // 8
    forward = 2 * per_query_head + 2 * per_kv_head + lse + bits
    backward = 4 * per_query_head + 4 * per_kv_head + lse + 2 * bits
    return {"flops": rows * layers * product * (2 * forward_calls + 5),
            "bytes": layers * (forward_calls * forward + backward)}


def share_step_flops(seq, rows, vocab, dim, layers, heads, kv_heads, head_dim,
                     index_heads, index_dim, topk, expert_width, experts,
                     top_k, held):
    """One expert-parallel rank's share of the stack, ``rows`` sequences.
    Forward + backward (3 x forward) of: q and o (dim x heads x head_dim
    each), k and v, QK^T and PV over the SELECTED pairs, the indexer's three
    projections, the router, the HELD experts' pairs at a balanced router's
    load (``top_k * held / experts`` a token), the head over ``vocab`` rows.
    ONCE (no gradient flows through the selection): the indexer's scores over
    the causal pairs. The alignment loss: the main heads' QK^T and the
    indexer's scores over the selected pairs forward (p is a constant), and
    the two products of the indexer's backward (into its queries and its
    key). Norms, rotary, softmax and the top-k are not MXU work."""
    kept, causal = selected_pairs(seq, topk), causal_pairs(seq)
    index_width = index_heads * index_dim
    attention = (seq * 2 * dim * (2 * heads * head_dim + 2 * kv_heads * head_dim)
                 + 2 * 2 * kept * head_dim * heads)
    indexer = seq * 2 * dim * (index_width + index_dim + index_heads)
    expert = seq * (2 * dim * experts
                    + 3 * 2 * dim * expert_width * top_k * held / experts)
    align = 2 * kept * head_dim * heads + 3 * 2 * kept * index_width
    layer = 3 * (attention + indexer + expert) + 2 * causal * index_width + align
    return rows * (layers * layer + 3 * seq * 2 * dim * vocab)
