"""Names the program gives its own device work.

Scopes are HLO metadata (``op_name``) and kernel names are the Mosaic custom
calls' own, so neither adds an operation to the step; a device profile, or
the benchmark's ``breakdown``, shows each piece under its name.
"""

FLASH_FWD = "hvd_flash_fwd"
FLASH_BWD_DQ = "hvd_flash_bwd_dq"
FLASH_BWD_DKV = "hvd_flash_bwd_dkv"
# The same three kernels under a window (``flash_attention(window=)``): names
# of their own, so that a device profile prices band and causal-dense apart.
FLASH_WIN_FWD = "hvd_flash_win_fwd"
FLASH_WIN_BWD_DQ = "hvd_flash_win_bwd_dq"
FLASH_WIN_BWD_DKV = "hvd_flash_win_bwd_dkv"
# The same three kernels under a selection that is data
# (``ops.flash_attention.selected_attention``): the mask one more operand, a
# block step with no selected pair fetched and run by nobody.
FLASH_SEL_FWD = "hvd_flash_sel_fwd"
FLASH_SEL_BWD_DQ = "hvd_flash_sel_bwd_dq"
FLASH_SEL_BWD_DKV = "hvd_flash_sel_bwd_dkv"
RING_FLASH_FWD = "hvd_ring_flash_fwd"
RING_FLASH_BWD_DQ = "hvd_ring_flash_bwd_dq"
RING_FLASH_BWD_DKV = "hvd_ring_flash_bwd_dkv"
# Round the exchange of ``parallel/fusion.py``. On the flat path a bucket's
# leaves go to the collective as they are (PR 59), so the two scopes hold the
# casts of a wire format (and the legacy compress / decompress) alone and no
# operation where none is set; under ``hierarchical=True`` also the copy into
# the padded buffer and back. What the copies cost on the flat path: 0.87 +
# 6.07 ms of ``solar_open2_seq8192_1chip``'s 181.56 ms step, 0.45 + 0.25 ms
# in ``resnet50_4chip`` (PERF_LEDGER.jsonl, PR 58).
FUSION_PACK = "hvd_fusion_pack"         # compress + the wire cast (+ fuse)
FUSION_UNPACK = "hvd_fusion_unpack"     # the cast back + decompress (+ unfuse)
OPTIMIZER_UPDATE = "hvd_optimizer_update"   # the wrapped optax update
FUSED_ALLREDUCE = "hvd_fused_allreduce_k"   # + the number of buckets
MOE_ROUTE = "hvd_moe_route"             # softmax + top-k of the router
MOE_DISPATCH = "hvd_moe_dispatch"       # sort by expert + gather into that order
MOE_EXPERTS = "hvd_moe_experts"         # the grouped SwiGLU products
# The grouped products' own kernels (ops/grouped_matmul.py). Both names hold
# MOE_EXPERTS: the benchmark finds the experts' time by that substring.
MOE_EXPERTS_GMM = "hvd_moe_experts_gmm"     # rows x weights: Y and dX
MOE_EXPERTS_TGMM = "hvd_moe_experts_tgmm"   # rows^T x rows: dW
MOE_COMBINE = "hvd_moe_combine"         # back to token order + weighted sum
MOE_SHARED = "hvd_moe_shared"           # the shared expert every token takes
# Experts that live in a latent (models/moe.py, ``MoEMLP.latent``): the
# projection down before the dispatch and the one up after the weighted sum.
MOE_LATENT = "hvd_moe_latent"
# The multi-token-prediction module's own work (models/transformer.py,
# ``TransformerLM.mtp_layer_types``): its two norms, the concatenation, the
# projection 2 dim -> dim, its final norm and its pass of the shared head
# (``lm_loss_with_mtp``). Its blocks' work goes by the blocks' own names.
MTP = "hvd_mtp"
# Latent attention (models/transformer.py, ``Block.mla``). The benchmark finds
# the mixer's time by the substrings ``hvd_mla`` and ``hvd_flash_``.
MLA_PROJ = "hvd_mla_proj"               # q, kv-down, kv-up, o + the latent's norm
MLA_ROPE = "hvd_mla_rope"               # split, rotary, assembling q and k
# Multi-head attention's parts that only some models have (models/transformer.py,
# ``Block.rotary`` / ``Block.attn_gate``).
ATTN_ROPE = "hvd_attn_rope"             # a rotary scheme: part of a head, YaRN
# The sigmoid gate on softmax attention's output, in either form: a gate a
# HEAD (Laguna: its projection dim -> heads, the sigmoid, the product) or a
# gate an ELEMENT (Solar-Open2: the sigmoid and the product; its projection,
# as wide as q's, goes by ``hvd_attn_proj``).
ATTN_GATE = "hvd_attn_gate"
# The Mamba-2 mixer (models/mamba.py) and its chunked state-space scan
# (ops/ssd.py). The benchmark finds the mixer's time by the substrings
# ``hvd_mamba`` and ``hvd_ssd``, the scan's by ``hvd_ssd``.
MAMBA_PROJ = "hvd_mamba_proj"           # the input and output projections
MAMBA_CONV = "hvd_mamba_conv"           # causal depthwise convolution + silu
MAMBA_GATE_NORM = "hvd_mamba_gate_norm"     # y * silu(z), then RMSNorm
# The two chains' own kernels (ops/mamba_fused.py), for the shapes they tile;
# the scopes above stay on the jax.numpy forms of every other shape. Four
# names, all holding ``hvd_mamba``: four labels of a few ms each, so that no
# one of them passes the scan's loop among the breakdown's longest.
MAMBA_CONV_FWD = "hvd_mamba_conv_fwd"
MAMBA_CONV_BWD = "hvd_mamba_conv_bwd"
MAMBA_GATE_NORM_FWD = "hvd_mamba_gate_norm_fwd"
MAMBA_GATE_NORM_BWD = "hvd_mamba_gate_norm_bwd"
SSD_SCAN = "hvd_ssd_scan"               # the scan over blocks of chunks: chunk
#                                         states, the recurrence, the outputs

# Kimi Delta Attention's mixer (models/kda.py) and its chunked gated delta
# rule (ops/kda.py). The benchmark finds the mixer's time by the names that
# start with ``hvd_kda``.
KDA_PROJ = "hvd_kda_proj"               # q, k, v, o and the low-rank gates' products
KDA_CONV = "hvd_kda_conv"               # the three causal depthwise convolutions + silu
# ops/mamba_fused.py's convolution kernels under this layer's names, for the
# shapes they tile (``conv_silu(names=)``).
KDA_CONV_FWD = "hvd_kda_conv_fwd"
KDA_CONV_BWD = "hvd_kda_conv_bwd"
KDA_GATE = "hvd_kda_gate"               # L2 norms, softplus, the log-decay, beta
KDA_SCAN = "hvd_kda_scan"               # the chunked delta rule, forward and backward
KDA_OUT_NORM = "hvd_kda_out_norm"       # the head-wise RMSNorm, then the sigmoid gate

# The gated delta rule's mixer with one decay a head (models/gdn.py; Gated
# DeltaNet's layer, Olmo-Hybrid's ``linear_attention``) and its chunked rule
# (ops/gdn.py). The benchmark finds the mixer's time by the names that start
# with ``hvd_gdn``.
GDN_PROJ = "hvd_gdn_proj"               # q, k, v, the decay's, beta's, the gate's and o's products
GDN_CONV = "hvd_gdn_conv"               # the three causal depthwise convolutions + silu
# ops/mamba_fused.py's convolution kernels under this layer's names, for the
# shapes they tile (``conv_silu(names=)``).
GDN_CONV_FWD = "hvd_gdn_conv_fwd"
GDN_CONV_BWD = "hvd_gdn_conv_bwd"
GDN_GATE = "hvd_gdn_gate"               # L2 norms, softplus, the log-decay, beta
GDN_SCAN = "hvd_gdn_scan"               # the chunked delta rule: the lanes' padding round the kernels,
#                                         and the jax.numpy scan of shapes that do not tile
GDN_SCAN_FWD = "hvd_gdn_scan_fwd"       # ops/gdn.py's two kernels
GDN_SCAN_BWD = "hvd_gdn_scan_bwd"
GDN_OUT_NORM = "hvd_gdn_out_norm"       # the head-wise RMSNorm, then the silu gate

# The gated short-convolution mixer (models/short_conv.py; LFM2's ``conv``
# layers). The benchmark finds the mixer's time by the names that start with
# ``hvd_sconv``.
SCONV_PROJ = "hvd_sconv_proj"           # the input (dim -> 3 dim) and output projections
SCONV_CONV = "hvd_sconv_conv"           # C * conv(B * x): the two products and the taps
# ops/mamba_fused.py's kernels of that pass, for the shapes they tile; the
# scope above stays on the jax.numpy form of every other shape.
SCONV_CONV_FWD = "hvd_sconv_conv_fwd"
SCONV_CONV_BWD = "hvd_sconv_conv_bwd"

# Learned sparse attention (ops/sparse_attention.py, ``Block.sparse``). The
# benchmark finds each part's time by the scope's name and its kernel's.
DSA_INDEXER = "hvd_dsa_indexer"         # the indexer's projections, norm, rotary
DSA_INDEXER_SCORES = "hvd_dsa_indexer_scores"   # its score tiles' kernel
DSA_SELECT = "hvd_dsa_select"           # the exact top-k of a chunk + packing
DSA_ALIGN = "hvd_dsa_align"             # the alignment loss's relayouts and sums
DSA_ALIGN_TILES = "hvd_dsa_align_tiles"     # its kernel: p, r, KL, the indexer's backward

# What the step is made of beside its mixers and experts
# (models/transformer.py, models/moe.py). None of these is opened INSIDE a
# scope above: the older name stays the last on every path it was the last
# on, so its metric reads what it read.
MLP = "hvd_mlp"                         # a block's dense MLP half, gelu or SwiGLU
# qkv / q_proj / kv_proj / o_proj of multi-head and selected attention
ATTN_PROJ = "hvd_attn_proj"
# The whole body of the three attention forms, as an OUTER scope: what inside
# it has no narrower name (head split and merge, qk norms, the grouped-query
# repeat, the dense einsum path); every kernel and scope inside keeps its time.
ATTN = "hvd_attn"
NORM_ADD = "hvd_norm_add"               # pre-norms, residual adds, the final norm
EMBED = "hvd_embed"                     # the token lookup (+ its scatter back)
# the MAIN head's products and its loss; a looped model's head passes, one a
# pass of the stack, and their weighted loss are all the main head's
LM_HEAD = "hvd_lm_head"
# A looped model's early exit (models/transformer.py, ``TransformerLM.passes``
# / ``exit_gate``, ``loop_lm_loss``): the gates' product and sigmoid, the exit
# distribution, its entropy and the weights handed to the loss, forward and
# backward.
LOOP_EXIT = "hvd_loop_exit"
MOE_LOGITS = "hvd_moe_logits"           # the router's float32 product + its cast
# the expert weights' cast to the rows' dtype before ``lax.ragged_dot``
# (``ops/moe.py``); the repo's kernels read the parameters: nothing under it
MOE_WEIGHT_CAST = "hvd_moe_weight_cast"

# Names that a number completes in the module (``hvd_fused_allreduce_k3``); a
# reader of a device profile finds these by prefix, every other by equality.
PREFIXES = (FUSED_ALLREDUCE,)
# Every name above, for the device-profile table (metrics/device_profile.py):
# built from the module itself, so a new name is in the table the day it is
# introduced.
ALL = tuple(value for key, value in list(globals().items())
            if key.isupper() and isinstance(value, str))
