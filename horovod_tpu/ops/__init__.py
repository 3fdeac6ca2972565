"""TPU compute ops beyond stock XLA: sequence-parallel attention schedules
(ring / Ulysses), mixture of experts (dropless top-k) with its grouped
products as pallas kernels (``grouped_matmul``), and a pallas
flash-attention kernel (fused, trainable) for the hot op, and Mamba-2's
chunked state-space scan with its causal depthwise convolution (the module
``ssd``: ``from horovod_tpu.ops.ssd import ssd, causal_depthwise_conv``) and
the mixer's two elementwise chains as pallas kernels (the module
``mamba_fused``: ``conv_silu``, ``gate_norm``), and learned sparse attention
(the module ``sparse_attention``: an indexer's scores, each query's exact
top-k, the selection as bits, the alignment loss; the flash kernels over the
selection are ``flash_attention.selected_attention``)."""

from .flash_attention import flash_attention  # noqa: F401

from .moe import (  # noqa: F401
    dropless_experts,
    record_expert_load,
    router_z_loss,
    topk_load_balancing_loss,
    topk_route,
)
from .ring_flash import ring_flash_attention  # noqa: F401
from .ring_attention import (  # noqa: F401
    causal_reference,
    ring_attention,
    ulysses_attention,
    zigzag_positions,
    zigzag_shard,
    zigzag_unshard,
)
