"""Operations and bytes the algorithm needs, from shapes alone.

Conventions, stated once: a multiply-add is 2 operations; the backward pass of
a matrix multiplication or convolution needs twice its forward (one product
for the input's gradient, one for the weight's), so forward + backward = 3 x
forward; causal attention needs only the lower triangle, so its products count
HALF of T x T ("causal half"); recomputed operations (remat, and the score
recompute inside the flash backward kernels) do not count as model operations.
Normalisations, activations, softmax, the loss and the optimizer are left out:
they are not MXU work, and the utilisation these counts feed is the MXU's.
"""

from __future__ import annotations


# -------------------------------------------------------------- transformer

def attention_forward_flops(seq, heads, head_dim, causal=True):
    """QK^T and PV for one sequence: 2 products of 2*T*T*D per head, halved
    for the causal triangle."""
    full = 2 * (2 * seq * seq * head_dim) * heads
    return full // 2 if causal else full


def transformer_layer_forward_flops(seq, dim, heads, mlp_ratio=4):
    """One decoder layer on one sequence of ``seq`` tokens: qkv (dim -> 3 dim),
    o_proj (dim -> dim), ungated MLP (dim -> r dim -> dim), causal attention."""
    per_token = 2 * dim * 3 * dim + 2 * dim * dim + 2 * 2 * dim * mlp_ratio * dim
    return seq * per_token + attention_forward_flops(seq, heads, dim // heads)


def transformer_step_flops(seq, rows, vocab, dim, heads, layers, mlp_ratio=4):
    """Forward + backward of ``rows`` sequences through the whole model; the
    embedding lookup is a gather and counts nothing."""
    forward = rows * (layers * transformer_layer_forward_flops(
        seq, dim, heads, mlp_ratio) + seq * 2 * dim * vocab)
    return 3 * forward


def flash_step_cost(seq, rows, heads, head_dim, layers, itemsize=2):
    """What the three flash kernels of one step need: operations and HBM
    bytes. Forward: 2 products. Backward of the flash algorithm: 5 (the scores
    are recomputed once because P is never stored; dV, dP, dQ, dK) - the
    standard 2.5 x forward. Each product is T*T*D multiply-adds per head on
    the causal half. Bytes: the forward reads q, k, v and writes o; the
    backward reads q, k, v, o, dO and writes dq, dk, dv - 12 passes over a
    (rows, T, heads, D) array, plus the f32 logsumexp written once and read
    once."""
    product = seq * seq * head_dim * heads      # 2*T*T*D*H ops, causal half
    flops = rows * layers * 7 * product
    array = rows * seq * heads * head_dim * itemsize
    lse = rows * seq * heads * 4
    return {"flops": flops, "bytes": layers * (12 * array + 2 * lse)}


# -------------------------------------------------------------------- resnet

def conv_flops(out_hw, k, c_in, c_out):
    return 2 * out_hw * out_hw * k * k * c_in * c_out


def bottleneck_forward_flops(in_hw, c_in, filters, stride):
    """1x1 (c_in -> f) at the input size, 3x3 (f -> f) with the stride (v1.5),
    1x1 (f -> 4f), and a 1x1 projection with the stride where the shape
    changes. Returns (operations, output size, output channels)."""
    out_hw = in_hw // stride
    flops = (conv_flops(in_hw, 1, c_in, filters)
             + conv_flops(out_hw, 3, filters, filters)
             + conv_flops(out_hw, 1, filters, 4 * filters))
    if stride != 1 or c_in != 4 * filters:
        flops += conv_flops(out_hw, 1, c_in, 4 * filters)
    return flops, out_hw, 4 * filters


def resnet_forward_flops(image, stage_sizes, num_filters, num_classes):
    """One image through a bottleneck ResNet: 7x7/2 stem, 3x3/2 max pool,
    the stages, global mean, dense head."""
    hw = image // 2
    flops = conv_flops(hw, 7, 3, num_filters)
    hw //= 2
    channels = num_filters
    for i, blocks in enumerate(stage_sizes):
        for j in range(blocks):
            stride = 2 if i > 0 and j == 0 else 1
            f, hw, channels = bottleneck_forward_flops(
                hw, channels, num_filters * 2 ** i, stride)
            flops += f
    return flops + 2 * channels * num_classes


def resnet_step_flops(batch, image, stage_sizes, num_filters, num_classes):
    return 3 * batch * resnet_forward_flops(image, stage_sizes, num_filters,
                                            num_classes)
