"""What a latent-attention (MLA) mixture-of-experts step needs, from shapes
alone, and where its mixer and its flash kernels are in a reduced trace.
Conventions as at the top of ``flops.py``: a multiply-add is 2 operations,
forward + backward = 3 x forward, causal half, recompute not counted - with
ONE stated exception, :func:`flash_calls_step_cost`."""

from __future__ import annotations

from benchmarks.ssd_cost import seconds_per_step  # noqa: F401  (the readers' import)

# Labels of ``breakdown.device_ops`` by the program's names
# (horovod_tpu/common/device_names.py): the mixer is its projections and the
# latent's norm (``hvd_mla_proj``), the split / rotary / assembling of q and
# k (``hvd_mla_rope``) and the three flash kernels (``hvd_flash_*``).
MLA_LABELS = ("hvd_mla", "hvd_flash_")
FLASH_LABELS = ("hvd_flash_",)


def attention_forward_flops(seq, heads, d_qk, d_v):
    """QK^T at ``d_qk`` and PV at ``d_v`` for one sequence, causal half."""
    return seq * seq * (d_qk + d_v) * heads


def flash_calls_step_cost(seq, rows, heads, d_qk, d_v, layers,
                          forward_calls=1, itemsize=2):
    """What the flash kernel CALLS of one step need: the roofline share's
    numerator, over the time of those same calls. A product is T*T*D
    multiply-adds a head on the causal half, counted at ``d_qk`` (QK^T, the
    scores again, dQ, dK) and at ``d_v`` (PV, dP, dV) apart. The forward
    kernel's two products count ``forward_calls`` times: under ``remat`` it
    runs twice a step and both runs are in the measured time (as
    ``ssd_cost`` counts its three passes); the backward's five count once
    (flash_step_cost's convention: the scores recomputed once, though the dq
    and dkv kernels each recompute them and dP). Bytes: a forward call reads
    q, k, v and writes o; the backward reads q, k, v, o, dO and writes dq,
    dk, dv; the f32 logsumexp written by each forward and read once."""
    at_qk = seq * seq * d_qk * heads        # 2*T*T*D*H operations, causal half
    at_v = seq * seq * d_v * heads
    flops = rows * layers * (forward_calls * (at_qk + at_v)
                             + 3 * at_qk + 2 * at_v)
    wide = rows * seq * heads * d_qk * itemsize     # q, k, dq, dk
    narrow = rows * seq * heads * d_v * itemsize    # v, o, dO, dv
    lse = rows * seq * heads * 4
    forward = 2 * wide + 2 * narrow + lse
    backward = 4 * wide + 4 * narrow + lse
    return {"flops": flops, "bytes": layers * (forward_calls * forward
                                               + backward)}


def share_step_flops(seq, rows, vocab, dim, layers, dense_layers, heads,
                     qk_nope, qk_rope, d_v, kv_rank, dense_width,
                     expert_width, shared_experts, experts, top_k, held):
    """Forward + backward of ``rows`` sequences through one expert-parallel
    rank's share of a DeepSeek-V3-family stack: every layer the four
    projections of latent attention (dim -> heads x (nope + rope), dim ->
    kv_rank + rope, kv_rank -> heads x (nope + v), heads x v -> dim) and
    causal attention at nope + rope | v; the first ``dense_layers`` a SwiGLU
    MLP of ``dense_width``; every later one the router (dim x experts), the
    shared expert (width shared_experts x expert_width) and the HELD experts'
    pairs at a balanced router's load, ``top_k * held / experts`` a token;
    the head over ``vocab`` rows. Norms, rotary and gates are not MXU work."""
    d_qk = qk_nope + qk_rope
    attention = (seq * 2 * (dim * heads * d_qk + dim * (kv_rank + qk_rope)
                            + kv_rank * heads * (qk_nope + d_v)
                            + heads * d_v * dim)
                 + attention_forward_flops(seq, heads, d_qk, d_v))
    swiglu = 3 * 2 * dim
    dense = seq * swiglu * dense_width
    expert = seq * (2 * dim * experts
                    + swiglu * shared_experts * expert_width
                    + swiglu * expert_width * top_k * held / experts)
    forward = rows * (layers * attention + dense_layers * dense
                      + (layers - dense_layers) * expert
                      + seq * 2 * dim * vocab)
    return 3 * forward
