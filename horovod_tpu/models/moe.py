"""Flax MoE layer for the transformer: OLMoE's (arXiv:2409.02060).

Softmax, then the ``top_k`` largest probabilities, not renormalised, NO
capacity and no dropped pair, SwiGLU experts ``w_gate`` / ``w_up`` /
``w_down`` of width ``hidden`` (a number of its own, 1024 = dim / 2 in
OLMoE-1B-7B), computed as grouped products over the pairs sorted by expert
(``ops.moe.dropless_experts``). All experts live with the tokens
(data-parallel replicas).

What the layer sows under ``intermediates`` (read with
``mutable=["intermediates"]``; nothing is computed for a caller that does
not): ``moe_lb_loss``, ``moe_z_loss``, ``moe_router_logits`` (N, E; for
``ops.moe.record_expert_load``) and ``moe_chosen_experts`` (N, top_k).
:func:`aux_losses` sums the two losses over the layers for the caller's loss
function, which multiplies them by its coefficients (OLMoE: 0.01 and 0.001).
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.moe import (dropless_experts, router_z_loss,
                       topk_load_balancing_loss, topk_route)


class MoEMLP(nn.Module):
    dim: int
    hidden: int
    n_experts: int
    top_k: int
    dtype: Any = jnp.bfloat16
    # True runs the grouped-product kernels (ops/grouped_matmul.py), where
    # the shapes take them, in the Pallas interpreter: ``Block`` hands its
    # ``flash_interpret`` down, one flag for every Pallas kernel of a block.
    interpret: bool = False

    @nn.compact
    def __call__(self, x):
        b, t, d = x.shape
        return self._topk_swiglu(x.reshape(-1, d)).reshape(b, t, d)

    def _topk_swiglu(self, tokens):
        d, e, h = tokens.shape[-1], self.n_experts, self.hidden
        if not 0 < self.top_k <= e:
            raise ValueError(f"top_k {self.top_k} of {e} experts")
        router = self.param("router", nn.initializers.lecun_normal(), (d, e),
                            jnp.float32)
        # the expert axis is a batch axis: each expert's fan-in is its own
        init = nn.initializers.lecun_normal(batch_axis=(0,))
        w_gate, w_up, w_down = (
            self.param(name, init, shape, jnp.float32).astype(self.dtype)
            for name, shape in (("w_gate", (e, d, h)), ("w_up", (e, d, h)),
                                ("w_down", (e, h, d))))
        # The router runs in float32 at full precision whatever the
        # activations' dtype: 2*N*D*E operations, and a coarser product
        # flips a token's 8th expert against its 9th far more often.
        logits = jnp.dot(tokens.astype(jnp.float32), router,
                         precision=jax.lax.Precision.HIGHEST)
        probs, weights, experts = topk_route(logits, self.top_k)
        self.sow("intermediates", "moe_lb_loss",
                 topk_load_balancing_loss(probs, experts))
        self.sow("intermediates", "moe_z_loss", router_z_loss(logits))
        self.sow("intermediates", "moe_router_logits", logits)
        self.sow("intermediates", "moe_chosen_experts", experts)
        return dropless_experts(tokens.astype(self.dtype), weights, experts,
                                w_gate, w_up, w_down, self.interpret)


def aux_losses(intermediates):
    """(load-balancing loss, router z-loss), each summed over the MoE layers
    found in a model's ``intermediates`` collection; zeros where there is
    none."""
    sums = {"moe_lb_loss": 0.0, "moe_z_loss": 0.0}
    for path, leaf in jax.tree_util.tree_flatten_with_path(intermediates)[0]:
        for name in sums:
            if any(getattr(p, "key", None) == name for p in path):
                sums[name] = sums[name] + leaf
    return sums["moe_lb_loss"], sums["moe_z_loss"]


def ep_param_specs(params, ep_axis: str = "ep"):
    """PartitionSpecs sharding every MoE expert tensor over ``ep_axis``
    (leading expert dim), everything else replicated — compose with
    transformer.tp_param_specs for mixed tp x ep."""
    from jax.sharding import PartitionSpec as P

    def spec(path, leaf):
        names = "/".join(str(getattr(p, "key", getattr(p, "name", "")))
                         for p in path)
        if leaf.ndim == 3 and any(
                w in names for w in ("w_gate", "w_up", "w_down")):
            return P(ep_axis, None, None)
        return P()

    return jax.tree_util.tree_map_with_path(spec, params)
