"""olmoe_1b_7b: OLMoE-1B-7B through ``TransformerLM`` and
``hvd.jax.DistributedOptimizer`` in the jitted ``shard_map`` step every
language-model cell runs (``lm217m.py`` builds the same one), its plain
float32 reference, its costs.

The same three functions as every configuration module:
``build(config, traffic, mesh, seed, **model_overrides)``,
``reference(config, traffic, mesh, seed, **model_overrides)`` and
``cost(config, traffic, chips)``. The configuration file carries the Hugging
Face keys as published; ``layers`` is the depth that is run.
"""

from __future__ import annotations

import numpy as np

SAMPLED_EXPERTS = 3     # experts whose gate/up/down gradients the check reads


def _model(config, **overrides):
    from horovod_tpu.models import TransformerLM

    if (config["num_key_value_heads"] != config["num_attention_heads"]
            or config["norm_topk_prob"] or config["tie_word_embeddings"]
            or config["hidden_act"] != "silu" or config["rope_theta"] != 10000
            or config["clip_qkv"] is not None or config["attention_bias"]):
        raise ValueError("olmoe_1b_7b: the configuration file states a layer "
                         "equation this module does not build")
    kw = dict(vocab=config["vocab_size"], dim=config["hidden_size"],
              heads=config["num_attention_heads"], layers=config["layers"],
              attention=config["attention"], qk_norm=True,
              rms_norm_eps=config["rms_norm_eps"],
              moe_experts=config["num_experts"], moe_every=1,
              moe_top_k=config["num_experts_per_tok"],
              moe_hidden=config["intermediate_size"])
    kw.update(overrides)
    return TransformerLM(**kw)


def _optimizer(config):
    import optax

    o = config["optimizer"]
    if o["name"] != "adamw":
        raise ValueError(f"olmoe_1b_7b trains with adamw, not {o}")
    return optax.adamw(o["learning_rate"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                       weight_decay=o["weight_decay"])


def _loss_parts(model, config):
    """``(params, tokens) -> (total, (cross entropy, load-balancing loss,
    router z-loss, intermediates))``: the chunked cross entropy on f32 logits
    plus OLMoE's two auxiliary terms with the file's coefficients."""
    import jax.numpy as jnp

    from horovod_tpu.models import aux_losses
    from horovod_tpu.models.transformer import chunked_lm_loss

    def parts(params, tokens):
        hidden, state = model.apply({"params": params}, tokens,
                                    return_hidden=True,
                                    mutable=["intermediates"])
        ce = chunked_lm_loss(hidden, params["lm_head"]["kernel"],
                             jnp.roll(tokens, -1, axis=1))
        lb, z = aux_losses(state["intermediates"])
        total = (ce + config["load_balancing_coef"] * lb
                 + config["router_z_coef"] * z)
        return total, (ce, lb, z, state["intermediates"])

    return parts


def _loss_fn(model, config):
    parts = _loss_parts(model, config)
    return lambda params, tokens: parts(params, tokens)[0]


def _init_params(model):
    import jax.numpy as jnp

    # Parameter shapes do not depend on the sequence length: init short.
    return lambda key: model.init(
        key, jnp.zeros((1, 128), jnp.int32))["params"]


def _tokens_fn(config, rows, seq):
    import jax
    import jax.numpy as jnp

    return lambda key: jax.random.randint(key, (rows, seq), 0,
                                          config["vocab_size"], jnp.int32)


def build(config, traffic, mesh, seed, **model_overrides):
    import jax
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.compat import shard_map

    from benchmarks.reduce_trace import SCOPE_FWD_BWD, SCOPE_OPTIMIZER

    rows, seq = traffic["global_rows"], traffic["seq"]
    if rows % mesh.size:
        raise ValueError(f"{rows} rows do not divide over {mesh.size} chips")
    model = _model(config, **model_overrides)
    opt = hvd.jax.DistributedOptimizer(_optimizer(config))
    replicated = NamedSharding(mesh, P())
    key = jax.random.PRNGKey(seed)
    init_params = _init_params(model)

    def init(key):
        params = init_params(key)
        return params, opt.init(params)

    params, opt_state = jax.jit(init, out_shardings=replicated)(key)
    tokens = jax.jit(_tokens_fn(config, rows, seq),
                     out_shardings=NamedSharding(mesh, P(hvd.HVD_AXIS)))(
        jax.random.fold_in(key, 1))
    loss_fn = _loss_fn(model, config)

    def train_step(params, opt_state, tokens):
        with jax.named_scope(SCOPE_FWD_BWD):
            loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
        with jax.named_scope(SCOPE_OPTIMIZER):
            updates, opt_state = opt.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return params, opt_state, jax.lax.pmean(loss, hvd.HVD_AXIS)

    step = jax.jit(
        shard_map(train_step, mesh=mesh,
                  in_specs=(P(), P(), P(hvd.HVD_AXIS)),
                  out_specs=(P(), P(), P()), check_vma=False),
        donate_argnums=(0, 1))
    return {"step": step, "state": [params, opt_state], "batch": (tokens,),
            "samples_per_step": rows * seq}


# ----------------------------------------------------------------- reference

def reference_config(config):
    """The reference's own few keys, from the file's."""
    return {"hidden": config["hidden_size"],
            "heads": config["num_attention_heads"],
            "experts": config["num_experts"],
            "top_k": config["num_experts_per_tok"],
            "expert_width": config["intermediate_size"],
            "vocab": config["vocab_size"], "eps": config["rms_norm_eps"],
            "rope_theta": float(config["rope_theta"]),
            "lb_coef": config["load_balancing_coef"],
            "z_coef": config["router_z_coef"]}


def to_reference(tree):
    """The system's parameter tree (or its gradients) in the reference's
    layout: the fused ``qkv`` kernel split into its three, the rest renamed."""
    import jax.numpy as jnp

    layers = []
    for i in range(sum(k.startswith("block_") for k in tree)):
        block = tree[f"block_{i}"]
        wq, wk, wv = jnp.split(block["qkv"]["kernel"], 3, axis=1)
        layers.append({
            "attn_norm": block["RMSNorm_0"]["scale"],
            "mlp_norm": block["RMSNorm_1"]["scale"],
            "q_norm": block["q_norm"]["scale"],
            "k_norm": block["k_norm"]["scale"],
            "wq": wq, "wk": wk, "wv": wv, "wo": block["o_proj"]["kernel"],
            **block["moe"]})
    return {"embed": tree["embed"]["embedding"], "layers": layers,
            "final_norm": tree["RMSNorm_0"]["scale"],
            "head": tree["lm_head"]["kernel"]}


def _sample(grads, experts):
    """What the check reads of a gradient tree in the reference's layout: the
    router, the QK-norm weights, an attention projection, the norms, and
    gate / up / down of the seeded ``experts`` (an index array, traced so that
    one compiled program serves every seed), of every layer."""
    out = {}
    for i, layer in enumerate(grads["layers"]):
        for name in ("router", "q_norm", "k_norm", "wo", "attn_norm"):
            out[f"layer{i}.{name}"] = layer[name]
        for name in ("w_gate", "w_up", "w_down"):
            out[f"layer{i}.{name}[sampled]"] = layer[name][experts]
    out["final_norm"] = grads["final_norm"]
    return out


def _share(got, want):
    """max|got - want| as a share of max|want|."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


def _l2_share(got, want):
    """|got - want| as a share of |want|, both in the Euclidean norm."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


def _chosen(experts, n_experts):
    """(N, E) boolean from the (N, top_k) indices the system chose."""
    out = np.zeros((len(experts), n_experts), bool)
    np.put_along_axis(out, np.asarray(experts), True, axis=-1)
    return out


def _sown(intermediates, name, layers):
    return [intermediates[f"block_{i}"]["moe"][name][0] for i in range(layers)]


def check_programs(config, **model_overrides):
    """The three jitted programs of the check, each ``(params, tokens,
    experts, weights) -> dict``: the plain reference, the system's model in
    float32 (run it under ``highest``), and the system's model as trained.
    ``total`` is the loss as the step computes it (chunked, every position).
    ``grads`` are the sampled gradients: with ``weights`` None, of that same
    ``total``, through ``chunked_lm_loss``'s own backward as the step takes
    them; with ``weights`` (1, T), of the cross entropy on full logits
    weighted by them plus the auxiliary terms (the reference has the one
    form: its cross entropy is a plain mean or a weighted one)."""
    import jax
    import jax.numpy as jnp
    import optax

    from horovod_tpu.models import aux_losses

    from benchmarks.reference import olmoe

    cfg, layers = reference_config(config), config["layers"]

    def system(model):
        trained_loss = _loss_parts(model, config)

        def weighted(params, tokens, weights):
            logits, state = model.apply({"params": params}, tokens,
                                        mutable=["intermediates"])
            nll = optax.softmax_cross_entropy_with_integer_labels(
                logits.astype(jnp.float32), jnp.roll(tokens, -1, axis=1))
            lb, z = aux_losses(state["intermediates"])
            return (jnp.sum(nll * weights) / jnp.sum(weights)
                    + config["load_balancing_coef"] * lb
                    + config["router_z_coef"] * z)

        def run(params, tokens, experts, weights):
            if weights is None:
                (total, (ce, lb, z, inter)), grads = jax.value_and_grad(
                    trained_loss, has_aux=True)(params, tokens)
            else:
                total, (ce, lb, z, inter) = trained_loss(params, tokens)
                grads = jax.grad(weighted)(params, tokens, weights)
            return {"total": total, "ce": ce, "lb": lb, "z": z,
                    "logits": model.apply({"params": params}, tokens),
                    "router_logits": _sown(inter, "moe_router_logits", layers),
                    "experts": _sown(inter, "moe_chosen_experts", layers),
                    "grads": _sample(to_reference(grads), experts)}

        return jax.jit(run)

    @jax.jit
    def plain(params, tokens, experts, weights):
        total, parts = olmoe.loss_parts(to_reference(params), tokens, cfg)
        grads = jax.grad(lambda p: olmoe.loss_parts(p, tokens, cfg, weights)[0])(
            to_reference(params))
        return {"total": total, **parts, "grads": _sample(grads, experts)}

    # The float32 leg runs the flash kernels at 512 / 512 blocks: with float32
    # operands the default 1024 blocks need 17.04 MiB of scoped VMEM in the dq
    # kernel against a limit of 16 (compiled for a described v5e, PR 26).
    exact = {**model_overrides, "dtype": jnp.float32, "block_q": 512,
             "block_k": 512}
    return (plain, system(_model(config, **exact)),
            system(_model(config, **model_overrides)))


def reference(config, traffic, mesh, seed, **model_overrides):
    """The model itself against ``benchmarks/reference/olmoe.py`` on the first
    row of the cell's batch with the cell's seeded parameters, at the widths
    of the file (a second training state cannot be held beside the first, so
    no plain STEP is played), in two legs: (a) the system's model in float32
    at ``highest``, which proves the mathematics; (b) the model as trained
    (bf16 activations, flash kernels, bf16 grouped products). Each leg holds
    the loss and its three terms, the share of tokens whose experts differ
    from the reference's, the logits on the tokens that agree, and the
    sampled gradients (float32: each leaf's largest error over max|ref|; as
    trained: its Euclidean norm over the reference's), each at the leg's own
    limits. Everything observed is
    logged; raises ``AssertionError`` for what lies beyond the file's
    tolerance. Also sets ``horovod_moe_expert_load_max_over_mean`` from the
    as-trained router on that row.

    Where every token of a leg picks the reference's experts, the leg's
    gradients are those of the step's own loss, through ``chunked_lm_loss``
    and its backward. A token that picks another expert moves a gradient by
    O(1) of that expert's rows, whatever the precision of the rest: in
    float32 one seed in fourteen has one such token among 4096 (its 8th and 9th
    probabilities lie closer than float32's rounding of the router's sum), as
    trained 4-5% of the tokens are such (PERF.md, PR 26). The leg's gradients
    are then taken once more on both sides with those positions weighted 0
    in the cross entropy, so that they are compared on the tokens that agree,
    as the logits are (with one layer a token's experts reach no other
    token's logits)."""
    import json

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from horovod_tpu.ops.moe import record_expert_load

    tol = config["tolerance"]
    cfg = reference_config(config)
    one = SingleDeviceSharding(mesh.devices.flat[0])
    key = jax.random.PRNGKey(seed)
    params = jax.jit(_init_params(_model(config, **model_overrides)),
                     out_shardings=one)(key)
    tokens = jax.jit(_tokens_fn(config, traffic["global_rows"], traffic["seq"]),
                     out_shardings=one)(jax.random.fold_in(key, 1))[:1]
    experts = np.sort(np.random.default_rng(seed).choice(
        cfg["experts"], min(SAMPLED_EXPERTS, cfg["experts"]), replace=False))
    plain, exact, trained = check_programs(config, **model_overrides)

    def same_experts(got, want):
        agree = np.ones(tokens.size, bool)
        for chosen_by_system, chosen in zip(got["experts"], want["chosen"]):
            agree &= (_chosen(chosen_by_system, cfg["experts"])
                      == chosen).all(axis=-1)
        return agree

    @jax.jit
    def logits_share(got, want, agree):
        """max|got - want| over max|want| on the agreeing tokens, on the
        device: the (T, vocab) float32 arrays are 0.8 GB each, too much to
        fetch and compare on the host in every run's set-up."""
        got, want = (x.reshape(-1, x.shape[-1]).astype(jnp.float32)
                     for x in (got, want))
        keep = agree[:, None]
        return (jnp.max(jnp.where(keep, jnp.abs(got - want), 0.0))
                / jnp.max(jnp.where(keep, jnp.abs(want), 0.0)))

    def ran(program, weights, precision=None):
        """One program's results: (logits left on the device, the rest)."""
        with jax.default_matmul_precision(precision):
            out = program(params, tokens, experts, weights)
        logits = out.pop("logits")
        return logits, jax.device_get(out)

    # Gradients: in float32 by a leaf's largest error (tight, and a fault in
    # one place shows); as trained by its Euclidean norm, because there a
    # leaf's LARGEST error is heavy-tailed over seeds (0.8e-2..1.2e-1 of
    # max|ref| over 20 seeds, a few tokens' rows) and its norm is not.
    legs = {"f32": (exact, "highest", _share, "grads_rel"),
            "bf16": (trained, None, _l2_share, "grads_l2_rel")}
    want_logits, want = ran(plain, jnp.ones(tokens.shape, jnp.float32))
    got, agree, logits_rel = {}, {}, {}
    for name, (program, precision, _, _) in legs.items():
        logits, got[name] = ran(program, None, precision)
        agree[name] = same_experts(got[name], want)
        logits_rel[name] = float(logits_share(logits, want_logits, agree[name]))
        del logits
    del want_logits     # 0.8 GB: the programs below run without it beside them

    beyond = []

    def held(name, value, limit):
        if not (np.isfinite(value) and value <= limit):
            beyond.append(f"{name} = {value:.3e} (> {limit})")
        return value

    observed = {"sampled_experts": experts.tolist()}
    for name, (program, precision, distance, limit) in legs.items():
        seen = observed[name] = {
            "flipped_share": held(f"{name} share of tokens whose experts differ",
                                  1.0 - float(agree[name].mean()),
                                  tol[f"{name}_flipped_share"]),
            "logits": held(f"{name} logits on the agreeing tokens",
                           logits_rel[name], tol[f"{name}_logits_rel"])}
        for term in ("total", "ce", "lb", "z"):
            seen[term] = held(f"{name} {term}", abs(
                float(got[name][term]) - float(want[term]))
                / abs(float(want[term])), tol[f"{name}_loss_rel"])
        grads, want_grads = got[name]["grads"], want["grads"]
        seen["grads_through"] = "chunked_lm_loss"
        if not agree[name].all():
            weights = jnp.asarray(agree[name].reshape(tokens.shape), jnp.float32)
            want_grads = ran(plain, weights)[1]["grads"]
            grads = ran(program, weights, precision)[1]["grads"]
            seen["grads_through"] = "agreeing tokens"
        seen[limit] = {
            leaf: held(f"{name} gradient of {leaf}", distance(grads[leaf], g),
                       tol[f"{name}_{limit}"])
            for leaf, g in want_grads.items()}
    del params
    observed["expert_load_max_over_mean"] = [      # the gauge: the last layer's
        record_expert_load(logits, cfg["top_k"])
        for logits in got["bf16"]["router_logits"]]
    if beyond:
        raise AssertionError(
            "olmoe_1b_7b against its float32 reference: " + "; ".join(beyond)
            + "; observed " + json.dumps(observed))
    return {"kind": "kernel", "observed": observed}


def cost(config, traffic, chips):
    """Needed operations per step per chip (forward + backward, causal half,
    recompute not counted), what the flash kernels need, and what the nine
    grouped products of the experts need."""
    from benchmarks import flops, moe_cost

    rows, seq = traffic["global_rows"] // chips, traffic["seq"]
    dim, heads = config["hidden_size"], config["num_attention_heads"]
    moe = (config["num_experts"], config["num_experts_per_tok"],
           config["intermediate_size"])
    return {
        "model_flops": moe_cost.moe_step_flops(
            seq, rows, config["vocab_size"], dim, heads, config["layers"], *moe),
        "kernel": flops.flash_step_cost(seq, rows, heads, dim // heads,
                                        config["layers"]),
        "experts": moe_cost.grouped_products_step_cost(
            rows * seq, dim, config["layers"], *moe),
    }
