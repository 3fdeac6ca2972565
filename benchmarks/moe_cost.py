"""What a top-k mixture-of-experts step needs, from shapes alone, and where
its grouped products are in a reduced trace. Conventions as at the top of
``flops.py``: a multiply-add is 2 operations, forward + backward = 3 x
forward, causal half, recompute not counted (the chunked loss computes the
head's product twice; once is needed)."""

from __future__ import annotations

from benchmarks import flops

# Labels of ``breakdown.device_ops`` that are the experts' grouped products:
# the program's scope (horovod_tpu/common/device_names.py), and the name libtpu
# gives the Mosaic kernel it lowers ``lax.ragged_dot`` to, whose ``op_name`` it
# overwrites (so the scope is not on that line). That second name is libtpu's
# own: a release that renames it turns both readers' results into ``None``,
# and another ``ragged_dot`` in a step would be counted as experts (PERF.md §3).
EXPERT_LABELS = ("hvd_moe_experts", "ragged-dot-none")


def moe_layer_forward_flops(seq, dim, heads, experts, top_k, expert_width):
    """One OLMoE layer on one sequence: q, k, v and o projections (4 of
    dim x dim), the router (dim -> experts), ``top_k`` SwiGLU experts a token
    (gate, up, down: 3 products of dim x width), causal attention."""
    per_token = (2 * dim * 4 * dim + 2 * dim * experts
                 + top_k * 3 * 2 * dim * expert_width)
    return seq * per_token + flops.attention_forward_flops(seq, heads,
                                                           dim // heads)


def moe_step_flops(seq, rows, vocab, dim, heads, layers, experts, top_k,
                   expert_width):
    forward = rows * (layers * moe_layer_forward_flops(
        seq, dim, heads, experts, top_k, expert_width) + seq * 2 * dim * vocab)
    return 3 * forward


def grouped_products_step_cost(tokens, dim, layers, experts, top_k,
                               expert_width, itemsize=2):
    """The nine grouped products of one layer's step (forward: gate, up,
    down; backward: each one's input gradient and weight gradient), over
    ``tokens * top_k`` rows whatever the routing. Operations: 9 products of
    rows x dim x width multiply-adds. Bytes: every product reads its two
    operands and writes its result once; for Y = X W the three products
    (Y, dX, dW) each touch X, W and Y once."""
    rows = tokens * top_k
    product = 2 * rows * dim * expert_width
    wide, narrow = rows * dim, rows * expert_width
    weights = experts * dim * expert_width
    touched = 3 * (wide + narrow + weights)     # gate, up, down: X + Y + W
    return {"flops": layers * 9 * product,
            "bytes": layers * 3 * touched * itemsize}


def experts_seconds_per_step(trace):
    """Seconds a step of the traced window spent in the experts' grouped
    products on the first device, from the labels the reduction kept (its ten
    longest: a lower bound, exact while the products are among them), or
    ``None`` where no label names them."""
    found = [seconds for label, seconds in trace["breakdown"]["device_ops"]
             if any(mark in label for mark in EXPERT_LABELS)]
    return sum(found) / trace["steps"] if found else None
