"""What a step of one expert-parallel rank's share of an LFM2 mixture of
experts needs, from shapes alone, and the program's names for its
short-convolution mixer. Conventions as at the top of ``flops.py``: a
multiply-add is 2 operations, forward + backward = 3 x forward, causal half,
recompute not counted - with ONE stated exception,
:func:`sconv_conv_step_cost`, which counts the forward as often as the step
calls it (``mla_cost.flash_calls_step_cost`` says why).

It counts what the MODEL needs, whatever implements it: a kernel PR cannot
move its own yardstick."""

from __future__ import annotations

# The program's names (horovod_tpu/common/device_names.py) by part.
PROJ = ("hvd_sconv_proj",)
CONV = ("hvd_sconv_conv", "hvd_sconv_conv_fwd", "hvd_sconv_conv_bwd")


def conv_layer_forward_flops(seq, dim):
    """A gated short-convolution mixer on one row: the input projection (dim
    -> 3 dim) and the output projection (dim -> dim). The two products and
    the taps are elementwise: no MXU work."""
    return seq * 2 * 4 * dim * dim


def attention_layer_forward_flops(seq, dim, heads, kv_heads, head_dim):
    """Grouped-query softmax attention on one row: q and o (dim x heads x
    head_dim each), k and v (dim x kv_heads x head_dim each), QK^T and PV on
    the causal half at the query heads."""
    return (seq * 2 * 2 * dim * (heads + kv_heads) * head_dim
            + seq * seq * 2 * head_dim * heads)


def dense_forward_flops(seq, dim, width):
    """A dense SwiGLU: three products of dim x width."""
    return seq * 3 * 2 * dim * width


def expert_layer_forward_flops(seq, dim, experts, top_k, held, width):
    """The router (all ``experts`` outputs) and the HELD experts' pairs at a
    balanced router's load, ``top_k * held / experts`` a token; SwiGLU."""
    return seq * (2 * dim * experts
                  + 3 * 2 * dim * width * top_k * held / experts)


def share_forward_parts(seq, rows, vocab, dim, kinds, dense_layers, heads,
                        kv_heads, head_dim, dense_width, experts, top_k, held,
                        expert_width):
    """Forward operations of ``rows`` sequences through the share, by part:
    a ``"conv"`` or ``"attention"`` mixer a layer, a dense SwiGLU in the first
    ``dense_layers`` layers and the held experts in every later one, the tied
    head over ``vocab`` held rows."""
    n_expert_layers = len(kinds) - dense_layers
    attention = attention_layer_forward_flops(seq, dim, heads, kv_heads,
                                              head_dim)
    scores = seq * seq * 2 * head_dim * heads
    return {
        "conv_mixers": rows * kinds.count("conv") * conv_layer_forward_flops(
            seq, dim),
        "attention_scores": rows * kinds.count("attention") * scores,
        "attention_projections": rows * kinds.count("attention") * (
            attention - scores),
        "dense": rows * dense_layers * dense_forward_flops(seq, dim,
                                                           dense_width),
        "experts": rows * n_expert_layers * expert_layer_forward_flops(
            seq, dim, experts, top_k, held, expert_width),
        "head": rows * seq * 2 * dim * vocab,
    }


def share_step_flops(*args):
    """Forward + backward of the share (:func:`share_forward_parts`'s
    arguments)."""
    return 3 * sum(share_forward_parts(*args).values())


def sconv_conv_step_cost(seq, rows, layers, dim, forward_calls=1, itemsize=2):
    """What the gated convolution's PASSES of one step need: the roofline
    share's numerator, over the time of those same passes. A pass is bound by
    bytes: the forward reads ``[B | C | X]`` (3 runs of ``dim``) and writes
    ``y`` (1), and counts ``forward_calls`` times (under ``remat`` it runs
    twice a step and both runs are in the measured time); the backward reads
    those three and ``dy`` and writes three gradients (7). The taps and their
    gradient are a few KB. Operations (2 K + 1 a channel and token forward)
    are elementwise and no MXU work: not counted."""
    run = rows * seq * dim * itemsize
    return {"bytes": layers * (4 * forward_calls + 7) * run}
