"""What a step of one tensor-parallel / expert-parallel rank's share of a
Solar-Open2 stack needs, from shapes alone. Conventions as at the top of
``flops.py``: a multiply-add is 2 operations, forward + backward = 3 x
forward, causal half, recompute not counted. The delta rule's own count is
``kda_cost.kda_forward_flops`` (the needed work does not depend on beta)."""

from __future__ import annotations

from benchmarks import kda_cost


def kda_layer_forward_flops(seq, dim, heads, head_dim, gate_rank, chunk):
    """A KDA mixer of ``heads`` held heads on one row: q, k, v and o (dim x
    heads x head_dim each), the two low-rank gates (dim -> gate_rank, whole
    on every rank, -> heads x head_dim), beta (dim x heads) and the chunked
    delta rule."""
    inner = heads * head_dim
    return (seq * 2 * (4 * dim * inner
                       + 2 * (dim * gate_rank + gate_rank * inner)
                       + dim * heads)
            + kda_cost.kda_forward_flops(seq, heads, head_dim, head_dim, chunk))


def gated_gqa_layer_forward_flops(seq, dim, heads, kv_heads, head_dim):
    """Grouped-query softmax attention with a gate an element: q, the gate
    and o (dim x heads x head_dim each), k and v (dim x kv_heads x head_dim
    each), QK^T and PV on the causal half at the query heads."""
    inner = heads * head_dim
    return (seq * 2 * (3 * dim * inner + 2 * dim * kv_heads * head_dim)
            + seq * seq * 2 * head_dim * heads)


def expert_layer_forward_flops(seq, dim, experts, top_k, held, expert_width,
                               shared_width):
    """The router (all ``experts`` outputs), the shared expert's held columns
    and the HELD experts' pairs at a balanced router's load, ``top_k * held /
    experts`` a token; SwiGLU: three products a width."""
    swiglu = 3 * 2 * dim
    return seq * (2 * dim * experts + swiglu * shared_width
                  + swiglu * expert_width * top_k * held / experts)


def share_step_flops(seq, rows, vocab, dim, kinds, kda_heads, heads, kv_heads,
                     head_dim, gate_rank, chunk, experts, top_k, held,
                     expert_width, shared_width):
    """Forward + backward of ``rows`` sequences through the share: a
    ``"kda"`` or ``"gqa"`` mixer a layer, experts in every layer, the head
    over ``vocab`` held rows. Convolutions, norms and gates are not MXU
    work."""
    mixer = {"kda": kda_layer_forward_flops(seq, dim, kda_heads, head_dim,
                                            gate_rank, chunk),
             "gqa": gated_gqa_layer_forward_flops(seq, dim, heads, kv_heads,
                                                  head_dim)}
    forward = rows * (sum(mixer[kind] for kind in kinds)
                      + len(kinds) * expert_layer_forward_flops(
                          seq, dim, experts, top_k, held, expert_width,
                          shared_width)
                      + seq * 2 * dim * vocab)
    return 3 * forward
