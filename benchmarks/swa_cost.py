"""What a window / full hybrid's step needs, from shapes alone: the flash
kernel calls under a window and without one at grouped-query heads, and one
expert-parallel rank's share of the whole step. Conventions as at the top of
``flops.py``: a multiply-add is 2 operations, forward + backward = 3 x
forward, recompute not counted - with ONE stated exception,
:func:`flash_calls_step_cost`, as in ``mla_cost.py``."""

from __future__ import annotations

from benchmarks import flops

WINDOWED = ("hvd_flash_win_fwd", "hvd_flash_win_bwd_dq", "hvd_flash_win_bwd_dkv")
FULL = ("hvd_flash_fwd", "hvd_flash_bwd_dq", "hvd_flash_bwd_dkv")


def attended_pairs(seq, window=None):
    """(query, key) pairs of one head's scores that the mask keeps. Under a
    window the query at p sees ``min(p + 1, window)`` keys: the BAND's area,
    counted exactly, whatever blocks a kernel covers it with. Without one the
    causal half, ``seq * seq / 2``, as ``flops.py`` counts it."""
    if window is None or window >= seq:
        return seq * seq // 2
    return seq * window - window * (window - 1) // 2


def flash_calls_step_cost(seq, rows, heads, kv_heads, head_dim, layers,
                          window=None, forward_calls=1, itemsize=2):
    """What the flash kernel CALLS of one step need at ``heads`` query heads
    over ``kv_heads`` of ``head_dim``, in ``layers`` layers of one kind: the
    roofline share's numerator, over the time of those same calls. A product
    is ``attended_pairs`` x head_dim multiply-adds a query head. The forward
    kernel's two products count ``forward_calls`` times: under ``remat`` it
    runs twice a step and both runs are in the measured time; the backward's
    five count once (the scores recomputed once, though the dq and dkv
    kernels each recompute them and dP: ``flops.flash_step_cost``'s
    convention). Bytes: a forward call reads q, k, v and writes o and the f32
    logsumexp; the backward reads q, k, v, o, dO and the logsumexp and writes
    dq, dk, dv; k, v, dk and dv have ``kv_heads`` heads, the others
    ``heads``."""
    product = 2 * attended_pairs(seq, window) * head_dim * heads
    per_query_head = rows * seq * heads * head_dim * itemsize      # q, o, dO, dq
    per_kv_head = rows * seq * kv_heads * head_dim * itemsize      # k, v, dk, dv
    lse = rows * seq * heads * 4
    forward = 2 * per_query_head + 2 * per_kv_head + lse
    backward = 4 * per_query_head + 4 * per_kv_head + lse
    return {"flops": rows * layers * product * (2 * forward_calls + 5),
            "bytes": layers * (forward_calls * forward + backward)}


def attention_layer_forward_flops(seq, dim, heads, kv_heads, head_dim,
                                  window=None):
    """One sequence through one attention layer: q and o (dim x heads x
    head_dim each), k and v (dim x kv_heads x head_dim each), the gate
    (dim x heads), QK^T and PV over the pairs the mask keeps (the causal half
    through ``flops.attention_forward_flops`` without a window)."""
    projections = 2 * dim * (2 * heads * head_dim + 2 * kv_heads * head_dim
                             + heads)
    attention = (flops.attention_forward_flops(seq, heads, head_dim)
                 if window is None or window >= seq
                 else 2 * 2 * attended_pairs(seq, window) * head_dim * heads)
    return seq * projections + attention


def share_step_flops(seq, rows, vocab, dim, heads_per_layer, windows,
                     kv_heads, head_dim, dense_layers, dense_width,
                     expert_width, shared_width, experts, top_k, held):
    """Forward + backward of ``rows`` sequences through one expert-parallel
    rank's share of the stack: layer l with ``heads_per_layer[l]`` query
    heads and the window ``windows[l]`` (None: full); the first
    ``dense_layers`` a SwiGLU MLP of ``dense_width``; every later one the
    router (dim x experts), the shared expert and the HELD experts' pairs at
    a balanced router's load, ``top_k * held / experts`` a token; the head
    over ``vocab`` rows. Norms, rotary, the sigmoid gates are not MXU work."""
    swiglu = 3 * 2 * dim
    attention = sum(attention_layer_forward_flops(
        seq, dim, h, kv_heads, head_dim, w)
        for h, w in zip(heads_per_layer, windows))
    dense = seq * swiglu * dense_width
    expert = seq * (2 * dim * experts + swiglu * shared_width
                    + swiglu * expert_width * top_k * held / experts)
    layers = len(heads_per_layer)
    forward = rows * (attention + dense_layers * dense
                      + (layers - dense_layers) * expert
                      + seq * 2 * dim * vocab)
    return 3 * forward


def roofline_pct(run, needed, names, what):
    """100 x the least time the chip could take for ``needed`` (operations
    and bytes) over the device time per step under the program's ``names``;
    nothing where either is missing. It counts NEEDED work: a kernel that
    covers the band with blocks wider than it reads low."""
    from benchmarks.named_device_time import ms

    spent = ms(run, *names)
    if needed is None or not spent:
        return None
    by_flops = needed["flops"] / run["peaks"]["bf16_flops_per_s"]
    by_bytes = needed["bytes"] / run["peaks"]["hbm_bytes_per_s"]
    run["log"](f"{what}: least time {max(by_flops, by_bytes) * 1e3:.3f} ms per "
               f"step, bound by "
               f"{'compute' if by_flops >= by_bytes else 'HBM bandwidth'} "
               f"({by_flops * 1e3:.3f} ms by operations, "
               f"{by_bytes * 1e3:.3f} ms by bytes), {spent:.3f} ms measured")
    return 100.0 * max(by_flops, by_bytes) * 1e3 / spent
