"""What a looped language model's step needs (ONE stack of layers that the
residual stream passes through ``passes`` times over shared weights, the one
head reading every pass), from shapes alone, and the program's name for the
exit gates' work. Conventions as at the top of ``flops.py``: a multiply-add
is 2 operations, forward + backward = 3 x forward, causal half, recomputed
operations not counted. Every product counts ``passes`` times, layers and
head alike: a parameter of a looped model is applied ``passes`` times a
token, and the model needs every one of them.

It counts what the MODEL needs, whatever implements it."""

from __future__ import annotations

from benchmarks import mla_cost

# The program's names (horovod_tpu/common/device_names.py) by part.
EXIT = ("hvd_loop_exit",)
HEAD = ("hvd_lm_head",)


def layer_forward_flops(seq, dim, heads, head_dim, mlp_width):
    """One layer on one row, forward: q, k, v and o (dim x heads x head_dim
    each), causal attention, a SwiGLU MLP of ``mlp_width`` columns. Norms
    (four a layer) are not MXU work."""
    return (seq * 2 * 4 * dim * heads * head_dim
            + mla_cost.attention_forward_flops(seq, heads, head_dim, head_dim)
            + seq * 3 * 2 * dim * mlp_width)


def loop_step_flops(seq, rows, vocab, dim, layers, passes, heads, head_dim,
                    mlp_width):
    """Forward + backward of ``rows`` sequences through ``passes`` passes of
    a stack of ``layers`` layers, the head over ``vocab`` rows once a pass.
    The gate's product (dim -> 1 a pass) is a rounding error and counted."""
    forward = rows * passes * (
        layers * layer_forward_flops(seq, dim, heads, head_dim, mlp_width)
        + seq * 2 * dim * vocab + seq * 2 * dim)
    return 3 * forward


def loop_head_step_cost(seq, rows, vocab, dim, passes, stream_itemsize=2,
                        kernel_itemsize=4):
    """What the head's ``passes`` readings and their loss need in a step: the
    roofline share's numerator, over the time under ``hvd_lm_head``.
    Operations: three vocabulary products (logits, the streams' gradient, the
    kernel's) a pass over every row. Bytes: the kernel read ONCE and its
    gradient written once (the passes share both: float32 parameters), every
    pass's stream read and its gradient written once (bf16). Logits, softmax
    and their gradient need not leave the chip's near memory and are not
    counted; a loop over chunks that reads the kernel again for every chunk
    and product is its implementation's cost, not the model's need."""
    tokens = rows * seq
    return {"flops": passes * 3 * 2 * tokens * dim * vocab,
            "bytes": 2 * dim * vocab * kernel_itemsize
            + passes * 2 * tokens * dim * stream_itemsize}
