"""What a Kimi Delta Attention hybrid's step needs, from shapes and the
CONFIGURATION's chunk alone, and the program's names for its mixer.
Conventions as at the top of ``flops.py``: a multiply-add is 2 operations,
forward + backward = 3 x forward, causal half (inside a chunk too) - with ONE
stated exception, :func:`kda_scan_step_cost`, which counts the forward as
often as the step calls it (``mla_cost.flash_calls_step_cost`` says why).

It counts what the MODEL needs in its chunked form, whatever implements it:
a kernel PR cannot move its own yardstick."""

from __future__ import annotations

from benchmarks import mla_cost

# The program's names (horovod_tpu/common/device_names.py) by part.
PROJ = ("hvd_kda_proj",)
CONV = ("hvd_kda_conv", "hvd_kda_conv_fwd", "hvd_kda_conv_bwd")
GATE = ("hvd_kda_gate",)
# (a reader asks for names the program knows: kernels of the scan, once
# built, bring ``hvd_kda_scan_fwd`` / ``_bwd`` to both lists)
SCAN = ("hvd_kda_scan",)
OUT_NORM = ("hvd_kda_out_norm",)
MIXER = PROJ + CONV + GATE + SCAN + OUT_NORM


def kda_forward_flops(seq, heads, d_k, d_v, chunk):
    """The chunked gated delta rule on one row, forward. A chunk of C
    positions and a head, as matrix products:

    - ``K+ K-^T`` and ``Q+ K-^T`` on the causal half (C x C x d_k each:
      2 C C d_k / 2 operations each);
    - the unit-triangular solve applied to ``K+`` and to ``V`` (``W = A K+``,
      ``U = A V``: A is lower triangular, C x C x d_k and C x C x d_v on the
      causal half); forming ``A`` itself (a solve of C x C against C x C, a
      third of a product) is not counted: a kernel may fold it into the two;
    - ``W S`` and ``Q+ S`` (C x d_k x d_v each, whole);
    - the scores times ``D`` (C x C x d_v, causal half);
    - the state's update ``(K exp(G_C - G))^T D`` (C x d_k x d_v, whole).

    The decays, running sums, norms and the recurrence's scaling are
    elementwise and count nothing."""
    chunk = min(chunk, seq)
    half = chunk * chunk            # 2 C C / 2
    per_chunk_head = (2 * half * d_k                # K+ K-^T, Q+ K-^T
                      + half * (d_k + d_v)          # W, U
                      + half * d_v                  # scores x D
                      + 3 * 2 * chunk * d_k * d_v)  # W S, Q+ S, the update
    return seq // chunk * heads * per_chunk_head


def kda_scan_step_cost(seq, rows, layers, heads, d_k, d_v, chunk,
                       forward_calls=1):
    """What the delta-rule CALLS of one step need: the roofline share's
    numerator, over the time of those same calls. The forward counts
    ``forward_calls`` times (under ``remat`` it runs twice a step and both
    runs are in the measured time), the backward at twice a forward. Bytes: a
    pass reads q, k, v (bf16 activations), beta and g (f32) and writes o once;
    the backward is two passes (it reads what the forward read plus do, and
    writes five gradients). The carried states an implementation's backward
    keeps are not counted: a backward that recomputes them needs none, and
    the numerator is the MODEL's."""
    one_pass = seq * heads * (2 * (2 * d_k + 2 * d_v) + 4 * d_k + 4)
    passes = forward_calls + 2
    return {"flops": rows * layers * passes * kda_forward_flops(
                seq, heads, d_k, d_v, chunk),
            "bytes": rows * layers * passes * one_pass}


def share_step_flops(seq, rows, vocab, dim, kinds, dense_layers, heads,
                     kda_head_dim, gate_rank, chunk, qk_nope, qk_rope, d_v,
                     kv_rank, dense_width, expert_width, shared_experts,
                     experts, top_k, held):
    """Forward + backward of ``rows`` sequences through one expert-parallel
    rank's share of a Kimi-Linear stack. A ``"kda"`` layer: q, k, v and o
    (dim x heads x d each), the two low-rank gates (dim -> gate_rank ->
    heads x d each), beta (dim x heads) and the chunked delta rule; a
    ``"full"`` layer: latent attention's four projections and causal
    attention at nope + rope | v. The first ``dense_layers`` a SwiGLU MLP of
    ``dense_width``; every later one the router, the shared expert and the
    HELD experts' pairs at a balanced router's load; the head over ``vocab``
    rows. Convolutions, norms and gates are not MXU work."""
    inner = heads * kda_head_dim
    kda = (seq * 2 * (4 * dim * inner + 2 * (dim * gate_rank + gate_rank * inner)
                      + dim * heads)
           + kda_forward_flops(seq, heads, kda_head_dim, kda_head_dim, chunk))
    d_qk = qk_nope + qk_rope
    full = (seq * 2 * (dim * heads * d_qk + dim * (kv_rank + qk_rope)
                       + kv_rank * heads * (qk_nope + d_v) + heads * d_v * dim)
            + mla_cost.attention_forward_flops(seq, heads, d_qk, d_v))
    swiglu = 3 * 2 * dim
    dense = seq * swiglu * dense_width
    expert = seq * (2 * dim * experts + swiglu * shared_experts * expert_width
                    + swiglu * expert_width * top_k * held / experts)
    layers = len(kinds)
    forward = rows * (sum(kda if kind == "kda" else full for kind in kinds)
                      + dense_layers * dense + (layers - dense_layers) * expert
                      + seq * 2 * dim * vocab)
    return 3 * forward
