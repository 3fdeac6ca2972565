"""What a step of a Nemotron-3-class hybrid needs, from shapes alone: layers
that are ONE mixer (Mamba-2, attention, or LatentMoE: experts without a gate
in a latent), a multi-token-prediction module, one tensor- / expert-parallel
rank's share. Conventions as at the top of ``flops.py``: a multiply-add is 2
operations, forward + backward = 3 x forward, causal half, recompute not
counted in the model's operations; a KERNEL's roofline counts the calls made
of it (a forward that ``remat`` runs twice is two calls)."""

from __future__ import annotations

from benchmarks import flops, ssd_cost

# The grouped products' kernels by the program's own names
# (horovod_tpu/common/device_names.py).
GROUPED = ("hvd_moe_experts_gmm", "hvd_moe_experts_tgmm")


def mamba_layer_forward_flops(seq, dim, heads, head_dim, state, groups, chunk):
    """An ``M`` layer on one row: ``in_proj`` (dim -> 2 inner + 2 G N +
    heads), ``out_proj`` (inner -> dim), the chunked scan."""
    inner = heads * head_dim
    return (seq * 2 * dim * (2 * inner + 2 * groups * state + heads)
            + seq * 2 * inner * dim
            + ssd_cost.ssd_forward_flops(seq, heads, head_dim, state, groups,
                                         chunk))


def attention_layer_forward_flops(seq, dim, heads, kv_heads, head_dim):
    """A ``*`` layer on one row: q and o (dim x heads x head_dim), k and v
    (dim x kv_heads x head_dim), causal attention."""
    return (seq * 2 * dim * head_dim * (2 * heads + 2 * kv_heads)
            + flops.attention_forward_flops(seq, heads, head_dim))


def experts_layer_forward_flops(seq, dim, experts, top_k, held, latent,
                                expert_width, shared_width):
    """An ``E`` layer on one row: the router (dim x experts), both latent
    projections (dim x latent each), the shared expert's two products (dim x
    width) and the HELD experts' pairs at a balanced router's load, ``top_k x
    held / experts`` a token, two products of latent x width each."""
    return seq * (2 * dim * experts + 2 * 2 * dim * latent
                  + 2 * 2 * dim * shared_width
                  + 2 * 2 * latent * expert_width * top_k * held / experts)


def share_step_flops(seq, rows, vocab, dim, kinds, mtp_kinds, mamba, attention,
                     experts):
    """Forward + backward of ``rows`` sequences through the rank's share:
    ``kinds`` and ``mtp_kinds`` are the letters of the two patterns (``M``,
    ``*``, ``E``); ``mamba`` = (heads, head_dim, state, groups, chunk),
    ``attention`` = (heads, kv_heads, head_dim), ``experts`` = (experts,
    top_k, held, latent, expert_width, shared_width), each as held here. The
    module adds its projection (2 dim -> dim), its layers and a second pass of
    the head over ``vocab`` rows. Norms, the convolution, activations, the
    loss and the optimizer are not MXU work."""
    layer = {"M": mamba_layer_forward_flops(seq, dim, *mamba),
             "*": attention_layer_forward_flops(seq, dim, *attention),
             "E": experts_layer_forward_flops(seq, dim, *experts)}
    head = seq * 2 * dim * vocab
    forward = (sum(layer[k] for k in kinds) + head
               + seq * 2 * 2 * dim * dim + sum(layer[k] for k in mtp_kinds)
               + head)
    return 3 * rows * forward


def grouped_step_cost(tokens, layers, experts, top_k, held, latent,
                      expert_width, forward_calls=1, itemsize=2):
    """What the held experts' grouped products of one step need, over the
    rows a balanced router sends this rank (``tokens x top_k x held /
    experts`` a layer). Experts without a gate: TWO products forward (up,
    down), run ``forward_calls`` times (2 under ``remat``), and FOUR backward
    (each one's input gradient and weight gradient). Operations: each is
    rows x latent x width multiply-adds. Bytes: a product reads its two
    operands and writes its result once; of Y = X W the three products (Y,
    dX, dW) each touch X, W and Y once. Both are affine in the rows:
    ``rows`` is the step's (all layers'), ``flops_per_row`` and
    ``bytes_per_row`` what one row more or fewer adds, for a reader that
    knows the rows a step really sent (:func:`at_rows`)."""
    rows = tokens * top_k * held / experts
    product = 2 * rows * latent * expert_width
    touched = (rows * latent + rows * expert_width
               + held * latent * expert_width)     # X + Y + W of one product
    calls = 2 * forward_calls + 4
    return {"flops": layers * calls * product,
            "bytes": layers * calls * touched * itemsize,
            "rows": layers * rows,
            "flops_per_row": calls * 2 * latent * expert_width,
            "bytes_per_row": calls * (latent + expert_width) * itemsize}


def at_rows(cost, rows):
    """``grouped_step_cost``'s operations and bytes at ``rows`` rows a step
    (all layers') where the routing sent other than a balanced router's."""
    more = rows - cost["rows"]
    return {"flops": cost["flops"] + more * cost["flops_per_row"],
            "bytes": cost["bytes"] + more * cost["bytes_per_row"]}
