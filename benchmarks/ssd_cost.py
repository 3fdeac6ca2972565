"""What a Mamba-2 hybrid's step needs, from shapes alone, and where its mixer
and its state-space scan are in a reduced trace. Conventions as at the top of
``flops.py``: a multiply-add is 2 operations, forward + backward = 3 x
forward, causal half (inside a chunk too), recompute not counted."""

from __future__ import annotations

from benchmarks import flops

# Labels of ``breakdown.device_ops`` by the program's scopes
# (horovod_tpu/common/device_names.py): the mixer is its projections,
# convolution, gated norm (``hvd_mamba_*``) and the scan (``hvd_ssd_*``).
MIXER_LABELS = ("hvd_mamba", "hvd_ssd")


def ssd_forward_flops(seq, heads, head_dim, state, groups, chunk):
    """The chunked scan on one row, forward. A chunk of L positions: the
    scores C B^T of each group (L x L x N, causal half), the masked scores
    times the inputs of each head (L x L x P, causal half), the chunk's state
    (L x P x N a head) and the carried state's product with C (the same);
    the recurrence over chunks is elementwise and counts nothing."""
    chunk = min(chunk, seq)
    per_chunk = (groups * chunk * chunk * state            # 2 L L N / 2
                 + heads * chunk * chunk * head_dim        # 2 L L P / 2
                 + 2 * 2 * chunk * heads * head_dim * state)
    return seq // chunk * per_chunk


def ssd_step_cost(seq, rows, layers, heads, head_dim, state, groups, chunk,
                  itemsize=2):
    """The scans of one step. Bytes: a pass reads ``u``, ``B``, ``C``
    (activations) and ``dt`` (f32) and writes ``y`` once; forward + backward
    are three passes (the backward reads what the forward read plus dy, and
    writes four gradients: twice a forward)."""
    one_pass = seq * (itemsize * (2 * heads * head_dim + 2 * groups * state)
                      + 4 * heads)
    return {"flops": 3 * rows * layers * ssd_forward_flops(
                seq, heads, head_dim, state, groups, chunk),
            "bytes": 3 * rows * layers * one_pass}


def hybrid_step_flops(seq, rows, vocab, dim, layer_types, heads, kv_heads,
                      mlp_width, mamba_heads, mamba_head_dim, state, groups,
                      chunk):
    """Forward + backward of ``rows`` sequences through a Granite 4.0-H
    stack: per Mamba-2 layer the two projections (dim -> 2 inner + 2 G N +
    heads, inner -> dim) and the scan; per attention layer q and o (dim x
    dim), k and v (dim x kv_heads x head_dim each) and causal attention;
    every layer a SwiGLU MLP (3 products of dim x width); the head. The
    convolution, norms and gates are not MXU work."""
    inner = mamba_heads * mamba_head_dim
    mlp = 3 * 2 * dim * mlp_width
    mamba = (seq * (2 * dim * (2 * inner + 2 * groups * state + mamba_heads)
                    + 2 * inner * dim + mlp)
             + ssd_forward_flops(seq, mamba_heads, mamba_head_dim, state,
                                 groups, chunk))
    head_dim = dim // heads
    attention = (seq * (2 * 2 * dim * dim + 2 * 2 * dim * kv_heads * head_dim
                        + mlp)
                 + flops.attention_forward_flops(seq, heads, head_dim))
    forward = rows * (sum(mamba if kind == "mamba" else attention
                          for kind in layer_types)
                      + seq * 2 * dim * vocab)
    return 3 * forward


def seconds_per_step(trace, marks):
    """Seconds a step of the traced window spent, on the first device, under
    the labels holding one of ``marks`` among those the reduction kept (its
    ten longest: a lower bound, exact while they are all among them), or
    ``None`` where no label holds one."""
    found = [seconds for label, seconds in trace["breakdown"]["device_ops"]
             if any(mark in label for mark in marks)]
    return sum(found) / trace["steps"] if found else None
