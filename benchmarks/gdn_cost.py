"""What a Gated DeltaNet hybrid's step needs (a delta rule with ONE decay a
head, keys and values of different widths), from shapes and the
CONFIGURATION's chunk alone, and the program's names for its mixer.
Conventions as at the top of ``flops.py``: a multiply-add is 2 operations,
forward + backward = 3 x forward, causal half (inside a chunk too) - with ONE
stated exception, :func:`gdn_scan_step_cost`, which counts the forward as
often as the step calls it (``mla_cost.flash_calls_step_cost`` says why).

It counts what the MODEL needs in its chunked form at its OWN widths (96 |
192 a head), whatever implements it: the lanes a kernel pads to are the
kernel's cost, not the model's need."""

from __future__ import annotations

from benchmarks import mla_cost
from benchmarks.kda_cost import kda_forward_flops

# The program's names (horovod_tpu/common/device_names.py) by part.
PROJ = ("hvd_gdn_proj",)
CONV = ("hvd_gdn_conv", "hvd_gdn_conv_fwd", "hvd_gdn_conv_bwd")
GATE = ("hvd_gdn_gate",)
SCAN = ("hvd_gdn_scan", "hvd_gdn_scan_fwd", "hvd_gdn_scan_bwd")
OUT_NORM = ("hvd_gdn_out_norm",)
MIXER = PROJ + CONV + GATE + SCAN + OUT_NORM


def gdn_forward_flops(seq, heads, d_k, d_v, chunk):
    """The chunked rule on one row, forward: the products of
    ``kda_cost.kda_forward_flops`` (``K K^T`` and ``Q K^T`` on the causal
    half, the solve applied to ``K`` and ``V``, ``W S``, ``Q S``, the scores
    times ``D``, the state's update). One decay a head changes where the
    decay multiplies (the (chunk x chunk) matrices, after they are formed),
    not which products the rule is made of."""
    return kda_forward_flops(seq, heads, d_k, d_v, chunk)


def gdn_scan_step_cost(seq, rows, layers, heads, d_k, d_v, chunk,
                       forward_calls=1):
    """What the delta-rule CALLS of one step need: the roofline share's
    numerator, over the time of those same calls. The forward counts
    ``forward_calls`` times (under ``remat`` it runs twice a step and both
    runs are in the measured time), the backward at twice a forward. Bytes: a
    pass reads q, k (d_k), v (d_v) in bf16, g and beta (one f32 a head each)
    and writes o (d_v) once; the backward is two passes (it reads what the
    forward read plus do, and writes five gradients). The carried states an
    implementation's backward keeps are not counted."""
    one_pass = seq * heads * (2 * (2 * d_k + 2 * d_v) + 4 + 4)
    passes = forward_calls + 2
    return {"flops": rows * layers * passes * gdn_forward_flops(
                seq, heads, d_k, d_v, chunk),
            "bytes": rows * layers * passes * one_pass}


def share_step_flops(seq, rows, vocab, dim, kinds, linear_heads, d_k, d_v,
                     chunk, heads, head_dim, mlp_width):
    """Forward + backward of ``rows`` sequences through one tensor-parallel
    rank's share of an Olmo-Hybrid stack. A ``"linear_attention"`` layer: q,
    k (dim x heads x d_k each), v, the gate and o (dim x heads x d_v each),
    the decay's and beta's (dim x heads each) and the chunked delta rule; a
    ``"full_attention"`` layer: q, k, v and o (dim x heads x head_dim each)
    and causal attention; every layer a SwiGLU MLP of ``mlp_width`` columns;
    the head over ``vocab`` rows. Convolutions, norms and gates are not MXU
    work."""
    linear = (seq * 2 * dim * linear_heads * (2 * d_k + 3 * d_v + 2)
              + gdn_forward_flops(seq, linear_heads, d_k, d_v, chunk))
    full = (seq * 2 * 4 * dim * heads * head_dim
            + mla_cost.attention_forward_flops(seq, heads, head_dim, head_dim))
    mlp = seq * 3 * 2 * dim * mlp_width
    forward = rows * (sum(linear if kind == "linear_attention" else full
                          for kind in kinds)
                      + len(kinds) * mlp + seq * 2 * dim * vocab)
    return 3 * forward
