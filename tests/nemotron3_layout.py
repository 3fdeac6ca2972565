"""``tests/references/nemotron3.py``'s parameter layout as the system's tree
(``TransformerLM`` with one-sub-layer blocks and a multi-token-prediction
module): the inverse of ``benchmarks/configs/nemotron_3_super_120b_a12b.py``'s
``to_reference``, for the CPU tests that start from the reference's seeded
parameters."""

from __future__ import annotations

import jax.numpy as jnp


def layer_to_system(layer):
    """One reference layer (its keys say which kind) as a ``Block``'s
    parameters."""
    block = {"RMSNorm_0": {"scale": layer["norm"]}}
    if "w_in" in layer:
        block["mixer"] = {
            "in_proj": {"kernel": layer["w_in"]},
            "conv_kernel": layer["conv_w"], "conv_bias": layer["conv_b"],
            "dt_bias": layer["dt_bias"], "A_log": layer["A_log"],
            "D": layer["D"], "gate_norm": layer["gate_norm"],
            "out_proj": {"kernel": layer["w_out"]}}
    elif "router" in layer:
        block["moe"] = {
            "router": layer["router"], "w_up": layer["w_up"],
            "w_down": layer["w_down"],
            "fc1_latent": {"kernel": layer["w_fc1"]},
            "fc2_latent": {"kernel": layer["w_fc2"]},
            "shared_up": {"kernel": layer["s_up"]},
            "shared_down": {"kernel": layer["s_down"]}}
    else:       # fewer key/value heads than query heads: q_proj + kv_proj
        block.update(
            q_proj={"kernel": layer["wq"]},
            kv_proj={"kernel": jnp.concatenate([layer["wk"], layer["wv"]],
                                               axis=1)},
            o_proj={"kernel": layer["wo"]})
    return block


def to_system(params):
    """The reference's whole parameter tree as ``TransformerLM``'s."""
    mtp = params["mtp"]
    tree = {"embed": {"embedding": params["embed"]},
            "RMSNorm_0": {"scale": params["final_norm"]},
            "lm_head": {"kernel": params["head"]},
            "mtp_hidden_norm": {"scale": mtp["hidden_norm"]},
            "mtp_embed_norm": {"scale": mtp["embed_norm"]},
            "mtp_proj": {"kernel": mtp["proj"]},
            "mtp_norm": {"scale": mtp["norm"]}}
    tree.update({f"block_{i}": layer_to_system(layer)
                 for i, layer in enumerate(params["layers"])})
    tree.update({f"mtp_block_{i}": layer_to_system(layer)
                 for i, layer in enumerate(mtp["layers"])})
    return tree


def biases_to_system(biases, kinds, mtp_kinds):
    """The reference's list of biases (main layers', then the module's) as the
    ``moe_bias`` collection."""
    names = ([f"block_{i}" for i, k in enumerate(kinds) if k == "E"]
             + [f"mtp_block_{i}" for i, k in enumerate(mtp_kinds) if k == "E"])
    return {name: {"moe": {"router_bias": b}}
            for name, b in zip(names, biases, strict=True)}
