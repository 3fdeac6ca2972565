"""kanana_2_30b_a3b: one expert-parallel rank's share of kanana-2-30b-a3b's
first layers (latent attention, a dense layer, then sigmoid-routed experts
beside a shared one) through ``TransformerLM`` and
``hvd.jax.DistributedOptimizer`` in the jitted ``shard_map`` step every
language-model cell runs (``olmoe_1b_7b.py`` builds the same one, plus the
router's bias as a third carry), its plain float32 reference, its costs.

The same three functions as every configuration module:
``build(config, traffic, mesh, seed, **model_overrides)``,
``reference(config, traffic, mesh, seed, **model_overrides)`` and
``cost(config, traffic, chips)``. The configuration file carries the Hugging
Face keys as published; ``layers``, ``experts_held`` (from ``experts_first``)
and ``vocab_held`` are what this chip runs of them.
"""

from __future__ import annotations

import numpy as np

SAMPLED_ROWS = 64   # rows of a matrix's gradient the check reads
INIT_STD = 0.02     # the file's ``assumed``: normal, std 0.02; norm weights 1


def _held(config):
    return (config["experts_first"], config["experts_held"])


def _model(config, **overrides):
    from horovod_tpu.models import LatentDims, TransformerLM

    if (config["model_type"] != "deepseek_v3" or config["q_lora_rank"] is not None
            or config["n_group"] != 1 or config["topk_group"] != 1
            or config["scoring_func"] != "sigmoid"
            or config["topk_method"] != "noaux_tc"
            or not config["norm_topk_prob"] or config["hidden_act"] != "silu"
            or config["attention_bias"] or config["tie_word_embeddings"]
            or config["rope_scaling"] is not None
            or config["moe_layer_freq"] != 1
            or config["num_key_value_heads"] != config["num_attention_heads"]
            or config["qk_head_dim"] != (config["qk_nope_head_dim"]
                                         + config["qk_rope_head_dim"])):
        raise ValueError("kanana_2_30b_a3b: the configuration file states a "
                         "layer equation this module does not build")
    kw = dict(vocab=config["vocab_held"], dim=config["hidden_size"],
              heads=config["num_attention_heads"], layers=config["layers"],
              mla=LatentDims(kv_rank=config["kv_lora_rank"],
                             qk_nope=config["qk_nope_head_dim"],
                             qk_rope=config["qk_rope_head_dim"],
                             v=config["v_head_dim"]),
              rope_theta=float(config["rope_theta"]),
              rope_interleave=config["rope_interleave"],
              first_k_dense=config["first_k_dense_replace"],
              mlp_hidden=config["intermediate_size"],
              moe_experts=config["n_routed_experts"], moe_every=1,
              moe_top_k=config["num_experts_per_tok"],
              moe_hidden=config["moe_intermediate_size"],
              moe_router="sigmoid",
              moe_route_scale=config["routed_scaling_factor"],
              moe_shared_hidden=(config["n_shared_experts"]
                                 * config["moe_intermediate_size"]),
              moe_held=_held(config), rms_norm_eps=config["rms_norm_eps"],
              attention=config["attention"], remat=config["remat"])
    kw.update(overrides)
    return TransformerLM(**kw)


def _optimizer(config):
    import jax
    import optax

    o = config["optimizer"]
    if o["name"] != "adamw":
        raise ValueError(f"kanana_2_30b_a3b trains with adamw, not {o}")
    # weight decay on matrices only: every norm weight has one axis, and the
    # router's bias is no parameter at all
    return optax.adamw(
        o["learning_rate"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
        weight_decay=o["weight_decay"],
        mask=lambda params: jax.tree_util.tree_map(lambda x: x.ndim >= 2,
                                                   params))


def _loss_parts(model):
    """``(params, bias, tokens) -> (loss, (hidden, intermediates))``: the
    chunked cross entropy on f32 logits over the held rows of the vocabulary,
    the final hidden states and what the layers sowed."""
    import jax.numpy as jnp

    from horovod_tpu.models import BIAS_COLLECTION
    from horovod_tpu.models.transformer import chunked_lm_loss

    def parts(params, bias, tokens):
        hidden, state = model.apply(
            {"params": params, BIAS_COLLECTION: bias}, tokens,
            return_hidden=True, mutable=["intermediates"])
        loss = chunked_lm_loss(hidden, params["lm_head"]["kernel"],
                               jnp.roll(tokens, -1, axis=1))
        return loss, (hidden, state["intermediates"])

    return parts


def _loss_fn(model):
    """``(params, bias, tokens) -> (loss, {block: counts (E,)})``: the loss
    and the pairs each expert layer routed to each of ALL the experts."""
    from horovod_tpu.models import expert_counts

    parts = _loss_parts(model)

    def loss_fn(params, bias, tokens):
        loss, (_, intermediates) = parts(params, bias, tokens)
        return loss, expert_counts(intermediates)

    return loss_fn


def _update_bias(bias, counts, rate, reduce=lambda c: c):
    """The rule after a step, each expert layer's bias from its own counts;
    ``reduce`` sums the counts over ranks."""
    from horovod_tpu.ops.moe import router_bias_update

    return {block: {"moe": {"router_bias": router_bias_update(
        leaves["moe"]["router_bias"], reduce(counts[block]), rate)}}
        for block, leaves in bias.items()}


def _init_state(model):
    """``key -> (params, bias)``: every leaf with two or more axes normal with
    std 0.02, norm weights 1, the router's bias 0. The shapes come from the
    model's own ``init``, traced and never run: its forward pass would be one
    more program to compile in every set-up."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import BIAS_COLLECTION

    def init(key):
        # Parameter shapes do not depend on the sequence length: trace short.
        shapes = jax.eval_shape(model.init, key, jnp.zeros((1, 128), jnp.int32))
        leaves, tree = jax.tree_util.tree_flatten(shapes["params"])
        keys = jax.random.split(jax.random.fold_in(key, 7), len(leaves))
        params = jax.tree_util.tree_unflatten(tree, [
            INIT_STD * jax.random.normal(k, leaf.shape, leaf.dtype)
            if leaf.ndim >= 2 else jnp.ones(leaf.shape, leaf.dtype)
            for k, leaf in zip(keys, leaves)])
        bias = jax.tree_util.tree_map(
            lambda b: jnp.zeros(b.shape, b.dtype), shapes[BIAS_COLLECTION])
        return params, bias

    return init


def _tokens_fn(config, rows, seq):
    import jax
    import jax.numpy as jnp

    return lambda key: jax.random.randint(key, (rows, seq), 0,
                                          config["vocab_held"], jnp.int32)


def build(config, traffic, mesh, seed, **model_overrides):
    import jax
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.compat import shard_map
    from horovod_tpu.parallel.collectives import ReduceOp, allreduce

    from benchmarks.reduce_trace import SCOPE_FWD_BWD, SCOPE_OPTIMIZER

    rows, seq = traffic["global_rows"], traffic["seq"]
    if rows % mesh.size:
        raise ValueError(f"{rows} rows do not divide over {mesh.size} chips")
    model = _model(config, **model_overrides)
    opt = hvd.jax.DistributedOptimizer(_optimizer(config))
    replicated = NamedSharding(mesh, P())
    key = jax.random.PRNGKey(seed)
    init_state = _init_state(model)
    rate = config["router_bias"]["update_rate"]

    def init(key):
        params, bias = init_state(key)
        return params, opt.init(params), bias

    params, opt_state, bias = jax.jit(init, out_shardings=replicated)(key)
    tokens = jax.jit(_tokens_fn(config, rows, seq),
                     out_shardings=NamedSharding(mesh, P(hvd.HVD_AXIS)))(
        jax.random.fold_in(key, 1))
    loss_fn = _loss_fn(model)

    def train_step(params, opt_state, bias, tokens):
        with jax.named_scope(SCOPE_FWD_BWD):
            (loss, counts), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, bias, tokens)
        with jax.named_scope(SCOPE_OPTIMIZER):
            updates, opt_state = opt.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            # the one collective of the step that carries no gradient: the
            # experts' load summed over ranks, so that replicas keep ONE bias
            bias = _update_bias(bias, counts, rate, lambda c: allreduce(
                c, hvd.HVD_AXIS, ReduceOp.SUM))
        return params, opt_state, bias, jax.lax.pmean(loss, hvd.HVD_AXIS)

    step = jax.jit(
        shard_map(train_step, mesh=mesh,
                  in_specs=(P(), P(), P(), P(hvd.HVD_AXIS)),
                  out_specs=(P(), P(), P(), P()), check_vma=False),
        donate_argnums=(0, 1, 2))
    return {"step": step, "state": [params, opt_state, bias],
            "batch": (tokens,), "samples_per_step": rows * seq}


# ----------------------------------------------------------------- reference

def reference_config(config):
    """The reference's own few keys, from the file's."""
    return {"heads": config["num_attention_heads"],
            "qk_nope": config["qk_nope_head_dim"],
            "qk_rope": config["qk_rope_head_dim"],
            "v_dim": config["v_head_dim"], "kv_rank": config["kv_lora_rank"],
            "eps": config["rms_norm_eps"],
            "rope_theta": float(config["rope_theta"]),
            "top_k": config["num_experts_per_tok"],
            "route_scale": config["routed_scaling_factor"],
            "held": _held(config)}


def to_reference(tree):
    """The system's parameter tree (or its gradients) in the reference's
    layout: leaves renamed, nothing split or transposed."""
    layers = []
    for i in range(sum(k.startswith("block_") for k in tree)):
        block = tree[f"block_{i}"]
        layer = {"attn_norm": block["RMSNorm_0"]["scale"],
                 "mlp_norm": block["RMSNorm_1"]["scale"],
                 "wq": block["q_proj"]["kernel"],
                 "wkva": block["kv_a_proj"]["kernel"],
                 "kv_norm": block["kv_a_norm"]["scale"],
                 "wkvb": block["kv_b_proj"]["kernel"],
                 "wo": block["o_proj"]["kernel"]}
        if "moe" in block:
            moe = block["moe"]
            layer.update(router=moe["router"], w_gate=moe["w_gate"],
                         w_up=moe["w_up"], w_down=moe["w_down"],
                         s_gate=moe["shared_gate"]["kernel"],
                         s_up=moe["shared_up"]["kernel"],
                         s_down=moe["shared_down"]["kernel"])
        else:
            layer.update(w_gate=block["mlp_gate"]["kernel"],
                         w_up=block["mlp_up"]["kernel"],
                         w_down=block["mlp_down"]["kernel"])
        layers.append(layer)
    return {"embed": tree["embed"]["embedding"], "layers": layers,
            "final_norm": tree["RMSNorm_0"]["scale"],
            "head": tree["lm_head"]["kernel"]}


def _in_layer_order(blocks):
    """``block_<i>`` names by ``i`` (block_10 after block_9)."""
    return sorted(blocks, key=lambda b: int(b.split("_")[1]))


def biases_to_reference(bias):
    """The ``moe_bias`` collection as the reference's list, in layer order."""
    return [bias[block]["moe"]["router_bias"]
            for block in _in_layer_order(bias)]


def _sample(grads, rows):
    """What the check reads of a gradient tree in the reference's layout:
    every leaf of every layer, the embedding, the head and the final norm; of
    a matrix (or of each expert's, flattened to rows) the seeded ``rows`` (an
    index array, traced so that one compiled program serves every seed; taken
    modulo the leaf's height), of a vector all of it."""
    import jax

    def take(path, leaf):
        if leaf.ndim < 2:
            return leaf
        flat = leaf.reshape(-1, leaf.shape[-1])     # experts' rows end to end
        return flat[rows % flat.shape[0]]

    flat = jax.tree_util.tree_flatten_with_path(
        jax.tree_util.tree_map_with_path(take, grads))[0]
    return {"".join(f".{getattr(p, 'key', getattr(p, 'idx', p))}"
                    for p in path).lstrip(".").replace("layers.", "layer"): leaf
            for path, leaf in flat}


def _distances(got, want):
    """On the device, per leaf: (max|got - want| over max|want|, the same in
    the Euclidean norm)."""
    import jax.numpy as jnp

    def one(g, w):
        g, w = g.astype(jnp.float32), w.astype(jnp.float32)
        return (jnp.max(jnp.abs(g - w)) / jnp.maximum(jnp.max(jnp.abs(w)), 1e-30),
                jnp.linalg.norm(g - w) / jnp.maximum(jnp.linalg.norm(w), 1e-30))

    return {name: one(got[name], want[name]) for name in want}


def _chosen_mask(experts, n_experts):
    """(N, E) boolean from the (N, top_k) indices the system chose."""
    import jax.numpy as jnp

    return jnp.any(experts[:, :, None] == jnp.arange(n_experts), axis=1)


def check_programs(config, **model_overrides):
    """The jitted programs of the model check, each ``(params, bias, tokens,
    rows) -> dict``: the plain reference (which also takes ``forced``, the
    chosen sets to compute under in place of its own: a second program,
    compiled only in a run that needs it), the system's model in float32 (run
    it under ``highest``), and the system's model as trained. The system's
    ``loss`` and ``grads`` are the step's own: through ``chunked_lm_loss``
    and its backward. ``chosen`` is a list of (N, E) masks, ``bias_after`` the
    biases one application of the rule later."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import expert_counts

    from benchmarks.reference import kanana2 as plain_model

    cfg = reference_config(config)
    rate = config["router_bias"]["update_rate"]
    n_experts = config["n_routed_experts"]

    def system(model):
        parts = _loss_parts(model)

        def run(params, bias, tokens, rows):
            # ONE forward: the logits are the head's product with the hidden
            # states the loss was taken from, as ``TransformerLM`` forms them
            (loss, (hidden, inter)), grads = jax.value_and_grad(
                parts, has_aux=True)(params, bias, tokens)
            logits = hidden.astype(jnp.float32) @ params["lm_head"]["kernel"]
            counts = expert_counts(inter)
            blocks = _in_layer_order(counts)
            return {"loss": loss, "logits": logits,
                    "chosen": [_chosen_mask(
                        inter[b]["moe"]["moe_chosen_experts"][0], n_experts)
                        for b in blocks],
                    "counts": [counts[b] for b in blocks],
                    "bias_after": biases_to_reference(
                        _update_bias(bias, counts, rate)),
                    "grads": _sample(to_reference(grads), rows)}

        return jax.jit(run)

    @jax.jit
    def plain(params, bias, tokens, rows, forced=None):
        ref_params, biases = to_reference(params), biases_to_reference(bias)
        (loss, parts), grads = jax.value_and_grad(
            plain_model.loss_parts, has_aux=True)(ref_params, biases, tokens,
                                                  cfg, forced)
        return {"loss": loss, "logits": parts["logits"],
                "chosen": parts["chosen"], "counts": parts["counts"],
                "bias_after": [plain_model.bias_update(b, c, rate)
                               for b, c in zip(biases, parts["counts"])],
                "grads": _sample(grads, rows)}

    # The float32 leg runs the flash kernels at 512 / 512 blocks: with float32
    # operands the default 1024 blocks overflow the dq kernel's scoped VMEM
    # (olmoe_1b_7b.check_programs, PR 26).
    exact = {**model_overrides, "dtype": jnp.float32, "block_q": 512,
             "block_k": 512}
    return (plain, system(_model(config, **exact)),
            system(_model(config, **model_overrides)))


def _bf16_values(key, shape, scale=1.0):
    """Normal values that bf16 holds exactly, as float32."""
    import jax
    import jax.numpy as jnp

    return (scale * jax.random.normal(key, shape, jnp.float32)).astype(
        jnp.bfloat16).astype(jnp.float32)


def flash_programs(config, seq, slice_len, **model_overrides):
    """``inputs(key)`` and the two jitted programs of the flash kernels' own
    check at (seq, heads, 192 | 128): the kernels as the model calls them
    (default blocks, the default scale 192 ** -0.5), and a per-head float32
    reference of the LAST ``slice_len`` query positions against the whole
    context (under a causal mask that is enough for exact out and dq on those
    queries, and dk and dv on the same positions as keys). Each returns (out,
    dq, dk, dv) on the slice."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops.flash_attention import (DEFAULT_BLOCK_K,
                                                 DEFAULT_BLOCK_Q,
                                                 flash_attention)

    heads = config["num_attention_heads"]
    d_qk, d_v = config["qk_head_dim"], config["v_head_dim"]
    interpret = model_overrides.get("flash_interpret", False)
    start = seq - slice_len

    @jax.jit
    def inputs(key):
        ks = jax.random.split(key, 4)
        q, k = (_bf16_values(kk, (1, seq, heads, d_qk)).astype(jnp.bfloat16)
                for kk in ks[:2])
        v = _bf16_values(ks[2], (1, seq, heads, d_v)).astype(jnp.bfloat16)
        return q, k, v, _bf16_values(ks[3], (1, seq, heads, d_v))

    @jax.jit
    def system(q, k, v, g):
        out, vjp = jax.vjp(       # positional: custom_vjp nondiff_argnums
            lambda q, k, v: flash_attention(q, k, v, True, DEFAULT_BLOCK_Q,
                                            DEFAULT_BLOCK_K, interpret, None),
            q, k, v)
        dq, dk, dv = vjp(g.astype(out.dtype))
        return tuple(t[:, start:].astype(jnp.float32) for t in (out, dq, dk, dv))

    @jax.jit
    def reference(q, k, v, g):
        f32 = jnp.float32

        def attend(qs, kh, vh):     # (slice, 192), (T, 192), (T, 128)
            s = (qs @ kh.T) * d_qk ** -0.5
            seen = (jnp.arange(slice_len) + start)[:, None] >= jnp.arange(seq)
            return jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1) @ vh

        def one_head(args):
            qs, kh, vh, gs = args
            out, vjp = jax.vjp(attend, qs, kh, vh)
            dq, dk, dv = vjp(gs)
            return out, dq, dk[start:], dv[start:]

        def heads_first(t):
            return jnp.moveaxis(t[0].astype(f32), 1, 0)

        with jax.default_matmul_precision("highest"):
            outs = jax.lax.map(
                one_head, (heads_first(q[:, start:]), heads_first(k),
                           heads_first(v), heads_first(g[:, start:])))
        return tuple(jnp.moveaxis(t, 0, 1)[None] for t in outs)

    return inputs, system, reference


def reference(config, traffic, mesh, seed, **model_overrides):
    """Two checks at the widths of the file, outside the timed window, each
    logged whole; raises ``AssertionError`` for what lies beyond the file's
    ``tolerance``.

    (1) The flash kernels alone at the cell's full row, 32 heads of 192 | 128,
    their last ``flash_slice`` positions against a per-head float32
    reference: out, dq, dk, dv.
    (2) The model itself against ``benchmarks/reference/kanana2.py`` on the
    first ``reference_prefix`` tokens of the cell's first row with the cell's
    seeded parameters and bias (a second training state cannot be held beside
    the first, so no plain STEP is played), in two legs: the system's model in
    float32 at ``highest``, which proves the mathematics, and the model as
    trained (bf16 activations, flash kernels, bf16 grouped products,
    recomputation). Each leg holds the loss, the share of tokens whose experts
    differ from the reference's in some layer, the logits on the tokens that
    agree, 64 sampled rows of EVERY leaf's gradient through
    ``chunked_lm_loss``'s own backward (float32: a leaf's largest error over
    max|ref|; as trained: its Euclidean norm over the reference's), and the
    share of the experts whose bias differs after one application of the
    rule. A float32 leg in which a token or two broke a tie the other way
    holds that share, and the rest against the reference computed under the
    system's choice (``held_under``). Also logged: the share of each layer's
    pairs that falls on the held experts (``held_share``; 1 / 8 when the
    router is balanced)."""
    import json

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    tol = config["tolerance"]
    one = SingleDeviceSharding(mesh.devices.flat[0])
    key = jax.random.PRNGKey(seed)
    seq = traffic["seq"]
    beyond, observed = [], {}

    def held(name, value, limit):
        value = float(value)
        if not (np.isfinite(value) and value <= limit):
            beyond.append(f"{name} = {value:.3e} (> {limit})")
        return value

    @jax.jit
    def share(got, want):
        got, want = got.astype(jnp.float32), want.astype(jnp.float32)
        return jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want))

    # -- (1) the flash kernels alone: before the model's parameters are made
    # a second time, while the memory is there
    inputs, system, plain = flash_programs(
        config, seq, min(traffic["flash_slice"], seq), **model_overrides)
    args = jax.device_put(inputs(jax.random.fold_in(key, 3)), one)
    observed["flash"] = {
        name: held(f"flash {name}", share(got, want), tol["flash_rel"])
        for name, got, want in zip(("out", "dq", "dk", "dv"), system(*args),
                                   plain(*args))}
    del args

    # -- (2) the model
    prefix = min(traffic["reference_prefix"], seq)
    params, bias = jax.jit(_init_state(_model(config, **model_overrides)),
                           out_shardings=one)(key)
    tokens = jax.jit(_tokens_fn(config, traffic["global_rows"], seq),
                     out_shardings=one)(jax.random.fold_in(key, 1))[:1, :prefix]
    rows = jnp.asarray(np.random.default_rng(seed).integers(
        0, 2 ** 31 - 1, SAMPLED_ROWS), jnp.int32)
    plain, exact, trained = check_programs(config, **model_overrides)

    @jax.jit
    def agreeing(got, want):
        """(T,) tokens whose chosen experts are the reference's in every
        expert layer."""
        agree = jnp.ones(got[0].shape[0], bool)
        for g, w in zip(got, want):
            agree &= jnp.all(g == w, axis=-1)
        return agree

    @jax.jit
    def logits_share(got, want, agree):
        got, want = (x.reshape(-1, x.shape[-1]).astype(jnp.float32)
                     for x in (got, want))
        keep = agree[:, None]
        return (jnp.max(jnp.where(keep, jnp.abs(got - want), 0.0))
                / jnp.max(jnp.where(keep, jnp.abs(want), 0.0)))

    with jax.default_matmul_precision("highest"):
        want = plain(params, bias, tokens, rows)
    compare = jax.jit(_distances)
    legs = {"f32": (exact, "highest", 0, "grads_rel"),
            "bf16": (trained, None, 1, "grads_l2_rel")}
    for name, (program, precision, which, limit) in legs.items():
        with jax.default_matmul_precision(precision):
            got = program(params, bias, tokens, rows)
        agree = agreeing(got["chosen"], want["chosen"])
        flipped = 1.0 - float(jnp.mean(agree))
        ref, under = want, "the reference's own choice"
        if name == "f32" and 0.0 < flipped <= tol["f32_flipped_share"]:
            # In float32 one seed in five has ONE token of 2048 whose 6th and
            # 7th score + bias lie closer than the rounding of the router's
            # sum: system and reference break the tie differently, both
            # rightly, and where a held expert is in it that token moves a
            # leaf's gradient by up to 0.17 of max|ref| (my chip runs, PR 32).
            # Its share is held as it is; everything else is then held
            # against the reference computed under the SYSTEM's choice.
            with jax.default_matmul_precision("highest"):
                ref = plain(params, bias, tokens, rows, got["chosen"])
            under = "the system's choice"
            agree = jnp.ones_like(agree)
        distances = jax.device_get(compare(got["grads"], ref["grads"]))
        observed[name] = {
            "loss": held(f"{name} loss", abs(float(got["loss"])
                                            - float(ref["loss"]))
                         / abs(float(ref["loss"])), tol[f"{name}_loss_rel"]),
            "flipped_share": held(
                f"{name} share of tokens whose experts differ",
                flipped, tol[f"{name}_flipped_share"]),
            "held_under": under,
            "logits": held(f"{name} logits on the agreeing tokens",
                           logits_share(got["logits"], ref["logits"], agree),
                           tol[f"{name}_logits_rel"]),
            "bias_differ_share": held(
                f"{name} share of experts whose bias differs after one step",
                float(np.mean([np.asarray(g) != np.asarray(w) for g, w in
                               zip(got["bias_after"], ref["bias_after"])])),
                tol[f"{name}_bias_differ_share"]),
            limit: {leaf: held(f"{name} gradient of {leaf}", pair[which],
                               tol[f"{name}_{limit}"])
                    for leaf, pair in distances.items()},
            "grads_other_distance_max": float(max(
                pair[1 - which] for pair in distances.values()))}
        counts = got["counts"]
        del got, ref
        first, count = _held(config)
        observed[name]["held_share"] = [       # of a layer's pairs, on this rank
            float(np.sum(np.asarray(c)[first:first + count]) / np.sum(np.asarray(c)))
            for c in counts]
    observed["bias_after_abs_max"] = float(max(
        np.max(np.abs(np.asarray(b))) for b in want["bias_after"]))
    del params, want
    if beyond:
        raise AssertionError(
            "kanana_2_30b_a3b against its float32 references: "
            + "; ".join(beyond) + "; observed " + json.dumps(observed))
    return {"kind": "kernel", "observed": observed}


def cost(config, traffic, chips):
    """Needed operations per step per chip (forward + backward, causal half,
    recompute not counted; the held experts at a balanced router's load), and
    what the flash kernel calls of a step need at 192 | 128."""
    from benchmarks import mla_cost

    rows, seq = traffic["global_rows"] // chips, traffic["seq"]
    heads = config["num_attention_heads"]
    sizes = (config["qk_head_dim"], config["v_head_dim"])
    return {
        "model_flops": mla_cost.share_step_flops(
            seq, rows, config["vocab_held"], config["hidden_size"],
            config["layers"], config["first_k_dense_replace"], heads,
            config["qk_nope_head_dim"], config["qk_rope_head_dim"],
            config["v_head_dim"], config["kv_lora_rank"],
            config["intermediate_size"], config["moe_intermediate_size"],
            config["n_shared_experts"], config["n_routed_experts"],
            config["num_experts_per_tok"], config["experts_held"]),
        "mla_flash": mla_cost.flash_calls_step_cost(
            seq, rows, heads, *sizes, config["layers"],
            forward_calls=2 if config["remat"] else 1),
    }
