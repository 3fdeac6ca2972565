"""Flax MoE layer for the transformer, in the published forms of three
families.

NO capacity and no dropped pair under any; experts of width ``hidden`` (a
number of its own: 1024 = dim / 2 in OLMoE-1B-7B, 768 in kanana-2-30b-a3b,
2688 in Nemotron-3-Super), computed as grouped products over the pairs sorted
by expert (``ops.moe.dropless_experts``): rows in the layer's ``dtype``
against the float32 expert parameters, which the products round to that dtype
where they read them (no copy of an expert stack in ``dtype`` is made where
the repo's kernels run). ``activation="swiglu"``: gated
experts ``w_gate`` / ``w_up`` / ``w_down``, ``down(silu(gate x) * up x)``.
``activation="relu2"`` (Nemotron-H's ``mlp_hidden_act``): experts WITHOUT a
gate, ``w_up`` / ``w_down`` alone, ``down(relu(up x)^2)``; the shared expert
takes the same activation as the routed ones.

``latent`` > 0 (Nemotron-3's LatentMoE, ``moe_latent_size``): the routed
experts live in a latent of that width. ``fc1_latent`` projects a token
``dim -> latent`` BEFORE the dispatch, the experts are ``latent -> hidden ->
latent``, and ``fc2_latent`` projects the weighted sum ``latent -> dim``
after it: a dispatched row is ``latent`` wide (a quarter of the bytes at
1,024 of 4,096). The router and the shared expert read the full hidden state.

``router="softmax"`` (OLMoE, arXiv:2409.02060): softmax, then the ``top_k``
largest probabilities, not renormalised - or, with ``norm_topk``
(Qwen3-MoE's ``norm_topk_prob``), divided by their sum. ``router="sigmoid"`` (DeepSeek-V3,
arXiv:2412.19437 §2.1.2, ``noaux_tc`` with one group): sigmoid scores, the
``top_k`` by score + bias, the chosen scores renormalised and multiplied by
``route_scale``; the bias is the variable ``router_bias`` of the collection
``moe_bias`` (not ``params``: no gradient, no optimizer, no weight decay),
moved by the caller after each step (``ops.moe.router_bias_update``) from the
``moe_expert_counts`` the layer sows. ``shared_hidden`` > 0 adds an expert
of that width (and the layer's ``activation``) that every token takes,
unweighted.

A rank holds every expert (``held`` None: data-parallel replicas) or the
experts ``[first, first + count)`` of the ``n_experts`` the router chooses
among (``held = (first, count)``: one expert-parallel rank's share, the
expert weights ``(count, ...)``); the layer then adds its own experts' part
and the shared expert's, and nothing for the absent ones.

What the layer sows under ``intermediates`` (read with
``mutable=["intermediates"]``; nothing is computed for a caller that does
not): ``moe_router_logits`` (N, E; for ``ops.moe.record_expert_load``) and
``moe_chosen_experts`` (N, top_k); under softmax ``moe_lb_loss`` and
``moe_z_loss``, which :func:`aux_losses` sums over the layers for the
caller's loss function (OLMoE's coefficients: 0.01 and 0.001); under sigmoid
``moe_expert_counts`` (E,), the pairs routed to each of ALL the experts, and
``moe_live_rows`` (scalar), those of them on the experts this rank holds: the
rows the layer's passes visit in the step, for a training loop to log.
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..common import device_names
from ..ops.moe import (_expert_counts, dropless_experts, router_logits,
                       router_z_loss, sigmoid_route, sigmoid_route_tokens,
                       topk_load_balancing_loss, topk_route)

BIAS_COLLECTION = "moe_bias"    # the sigmoid router's bias: state, not params


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
    # What a DeepSeek-V3-family configuration states (the module docstring
    # has the equations); the defaults are OLMoE's layer, operation for
    # operation.
    router: str = "softmax"
    route_scale: float = 1.0
    shared_hidden: int = 0
    held: Optional[tuple] = None    # (first, count) of n_experts; None: all
    norm_topk: bool = False         # softmax router: weights over their sum
    # What a Nemotron-3 configuration states (the module docstring has the
    # equations): experts without a gate, in a latent of this width.
    activation: str = "swiglu"      # "swiglu" | "relu2"
    latent: int = 0
    # What an LFM2 configuration's released code states: the epsilon under
    # the sigmoid router's chosen scores (``ops.moe.sigmoid_route``).
    route_eps: float = 1e-20

    @nn.compact
    def __call__(self, x):
        if self.activation not in ("swiglu", "relu2"):
            raise ValueError(f"activation {self.activation!r}: 'swiglu' "
                             f"(gated experts) or 'relu2' (experts without "
                             f"a gate)")
        b, t, d = x.shape
        tokens = x.reshape(-1, d)
        out = self._routed(tokens)
        if self.shared_hidden:
            out = out + self._shared(tokens)
        return out.reshape(b, t, d)

    def _shared(self, tokens):
        def dense(width, name):
            return nn.Dense(width, use_bias=False, dtype=self.dtype, name=name)

        with jax.named_scope(device_names.MOE_SHARED):
            if self.activation == "relu2":
                hidden = jnp.square(nn.relu(
                    dense(self.shared_hidden, "shared_up")(tokens)))
            else:
                gate, up = (dense(self.shared_hidden, name)(tokens)
                            for name in ("shared_gate", "shared_up"))
                hidden = nn.silu(gate) * up
            return dense(tokens.shape[-1], "shared_down")(hidden)

    def _routed(self, tokens):
        d, e, h = tokens.shape[-1], self.n_experts, self.hidden
        if not 0 < self.top_k <= e:
            raise ValueError(f"top_k {self.top_k} of {e} {self.activation} "
                             f"experts")
        if self.router not in ("softmax", "sigmoid"):
            raise ValueError(f"router {self.router!r}: 'softmax' or 'sigmoid'")
        first, here = (0, e) if self.held is None else self.held
        if not 0 <= first < first + here <= e:
            raise ValueError(f"held {self.held} of {e} experts")
        router = self.param("router", nn.initializers.lecun_normal(), (d, e),
                            jnp.float32)
        # the expert axis is a batch axis: each expert's fan-in is its own
        init = nn.initializers.lecun_normal(batch_axis=(0,))
        width = self.latent or d    # what the experts read and write
        shapes = {"w_gate": (here, width, h), "w_up": (here, width, h),
                  "w_down": (here, h, width)}
        if self.activation == "relu2":
            del shapes["w_gate"]    # experts without a gate
        # The float32 parameters as they are: ``self.dtype`` reaches the
        # products through the rows, and ``dropless_experts`` rounds the
        # weights to it where they are multiplied (in the kernels' VMEM, or
        # by a cast under ``hvd_moe_weight_cast`` before ``lax.ragged_dot``).
        experts_w = {name: self.param(name, init, shape, jnp.float32)
                     for name, shape in shapes.items()}
        if self.router == "sigmoid":
            bias = self.variable(BIAS_COLLECTION, "router_bias", jnp.zeros,
                                 (e,), jnp.float32).value
            # The product and the rule as ONE unit, whose backward is formed
            # from the (N, top_k) chosen scores: a recomputed block reads
            # them from memory and runs no router. The rule is this module's
            # name at the time of the call: the benchmark's tests swap it.
            logits, weights, experts = sigmoid_route_tokens(
                tokens, router, bias, self.top_k, self.route_scale,
                self.route_eps, sigmoid_route)
            counts = _expert_counts(experts.reshape(-1), e)
            self.sow("intermediates", "moe_expert_counts", counts)
            self.sow("intermediates", "moe_live_rows",
                     jnp.sum(counts[first:first + here]))
        else:
            logits = router_logits(tokens, router)
            probs, weights, experts = (
                topk_route(logits, self.top_k, True) if self.norm_topk
                else topk_route(logits, self.top_k))    # OLMoE's call, as ever
            self.sow("intermediates", "moe_lb_loss",
                     topk_load_balancing_loss(probs, experts))
            self.sow("intermediates", "moe_z_loss", router_z_loss(logits))
        self.sow("intermediates", "moe_router_logits", logits)
        self.sow("intermediates", "moe_chosen_experts", experts)
        tokens = tokens.astype(self.dtype)
        if self.latent:
            with jax.named_scope(device_names.MOE_LATENT):
                tokens = nn.Dense(self.latent, use_bias=False, dtype=self.dtype,
                                  name="fc1_latent")(tokens)
        out = dropless_experts(
            tokens, weights, experts, experts_w.get("w_gate"),
            experts_w["w_up"], experts_w["w_down"], self.interpret,
            None if self.held is None else (first, here, e))
        if self.latent:
            with jax.named_scope(device_names.MOE_LATENT):
                out = nn.Dense(d, use_bias=False, dtype=self.dtype,
                               name="fc2_latent")(out)
        return out


def sown_sums(intermediates, names):
    """``{name: sum over the layers}`` of what the layers sowed under each of
    ``names`` in a model's ``intermediates`` collection; 0 where none did."""
    sums = dict.fromkeys(names, 0)
    for path, leaf in jax.tree_util.tree_flatten_with_path(intermediates)[0]:
        for name in names:
            if any(getattr(p, "key", None) == name for p in path):
                sums[name] = sums[name] + leaf
    return sums


def aux_losses(intermediates):
    """(load-balancing loss, router z-loss), each summed over the MoE layers
    found in a model's ``intermediates`` collection; zeros where there is
    none."""
    sums = sown_sums(intermediates, ("moe_lb_loss", "moe_z_loss"))
    return sums["moe_lb_loss"], sums["moe_z_loss"]


def expert_counts(intermediates):
    """The ``moe_expert_counts`` the sigmoid-routed layers sowed, as ``{block
    name: (E,) int32}``: what the step hands, summed over ranks, to
    ``ops.moe.router_bias_update`` for that block's bias."""
    return {block: leaves["moe"]["moe_expert_counts"][0]
            for block, leaves in intermediates.items()
            if "moe_expert_counts" in leaves.get("moe", {})}


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
