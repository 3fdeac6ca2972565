"""nemotron_3_super_120b_a12b: one tensor- / expert-parallel rank's share of
Nemotron-3-Super-120B-A12B's first period (layers that are ONE sub-layer: a
Mamba-2 mixer, an attention or LatentMoE, relu² experts in a 1,024-wide latent
chosen 22 of 512) and its multi-token-prediction module, through
``TransformerLM`` and ``hvd.jax.DistributedOptimizer`` in the jitted
``shard_map`` step every language-model cell runs (``kanana_2_30b_a3b.py``
builds the same one, the router's bias as a third carry), its plain float32
reference, its costs.

The same three functions as every configuration module:
``build(config, traffic, mesh, seed, **model_overrides)``,
``reference(config, traffic, mesh, seed, **model_overrides)`` and
``cost(config, traffic, chips)``. The configuration file carries the Hugging
Face keys as published; ``layers``, the ``*_held`` keys and ``experts_first``
are what this chip runs of them (the file's ``cut``).
"""

from __future__ import annotations

import numpy as np

SAMPLED_ROWS = 64   # rows of a matrix's gradient the check reads
KINDS = {"M": "mamba_only", "*": "attention_only", "E": "experts_only"}


def _held(config):
    return (config["experts_first"], config["experts_held"])


def _patterns(config):
    """The letters of the layers this chip runs (the model's own first
    ``layers``) and of the module's."""
    return (config["hybrid_override_pattern"][:config["layers"]],
            config["mtp_hybrid_override_pattern"])


def _model(config, **overrides):
    from horovod_tpu.models import TransformerLM
    from horovod_tpu.models.mamba import Mamba2Dims

    pattern, mtp_pattern = _patterns(config)
    if (config["model_type"] != "nemotron_h" or config["n_group"] != 1
            or config["topk_group"] != 1 or not config["norm_topk_prob"]
            or config["mlp_hidden_act"] != "relu2"
            or config["mamba_hidden_act"] != "silu"
            or config["attention_bias"] or config["mlp_bias"]
            or config["use_bias"] or config["mamba_proj_bias"]
            or not config["use_conv_bias"] or config["tie_word_embeddings"]
            or config["n_shared_experts"] != 1
            or config["num_nextn_predict_layers"] != 1
            or config["moe_shared_expert_overlap"]
            or config["norm_eps"] != config["layer_norm_epsilon"]
            or (config["mamba_num_heads"] * config["mamba_head_dim"]
                != config["expand"] * config["hidden_size"])
            or set(pattern + mtp_pattern) - set(KINDS)):
        raise ValueError("nemotron_3_super_120b_a12b: the configuration file "
                         "states a layer equation this module does not build")
    kw = dict(vocab=config["vocab_held"], dim=config["hidden_size"],
              heads=config["attention_heads_held"],
              kv_heads=config["kv_heads_held"], head_dim=config["head_dim"],
              layers=len(pattern),
              layer_types=tuple(KINDS[k] for k in pattern),
              mtp_layer_types=tuple(KINDS[k] for k in mtp_pattern),
              mamba=Mamba2Dims(heads=config["mamba_heads_held"],
                               head_dim=config["mamba_head_dim"],
                               state=config["ssm_state_size"],
                               groups=config["mamba_groups_held"],
                               conv=config["conv_kernel"],
                               chunk=config["chunk_size"]),
              rope=False, moe_experts=config["n_routed_experts"],
              moe_top_k=config["num_experts_per_tok"],
              moe_hidden=config["moe_intermediate_size"],
              moe_router="sigmoid",
              moe_route_scale=float(config["routed_scaling_factor"]),
              moe_shared_hidden=config["shared_width_held"],
              moe_held=_held(config), moe_activation="relu2",
              moe_latent=config["moe_latent_size"],
              rms_norm_eps=config["norm_eps"],
              attention=config["attention"], remat=config["remat"])
    kw.update(overrides)
    return TransformerLM(**kw)


def _optimizer(config):
    import jax
    import optax

    o = config["optimizer"]
    if o["name"] != "adamw":
        raise ValueError(f"nemotron_3_super_120b_a12b trains with adamw, not {o}")
    # weight decay on matrices only: A_log, D, dt_bias, the convolution's
    # bias and every norm weight have one axis, and the router's bias is no
    # parameter at all
    return optax.adamw(
        o["learning_rate"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
        weight_decay=o["weight_decay"],
        mask=lambda params: jax.tree_util.tree_map(lambda x: x.ndim >= 2,
                                                   params))


def _loss_parts(model, config):
    """``(params, bias, tokens) -> (loss, (terms, hidden, intermediates))``:
    ``L_main + mtp_loss_weight x L_mtp``, each the chunked cross entropy on
    f32 logits over the held rows of the vocabulary through the SAME head;
    ``terms`` the two, ``hidden`` both final hidden states."""
    from horovod_tpu.models import BIAS_COLLECTION
    from horovod_tpu.models.transformer import lm_loss_with_mtp

    def parts(params, bias, tokens):
        hidden, state = model.apply(
            {"params": params, BIAS_COLLECTION: bias}, tokens,
            return_hidden=True, mutable=["intermediates"])
        loss, terms = lm_loss_with_mtp(
            *hidden, params["lm_head"]["kernel"], tokens,
            config["mtp_loss_weight"], config["loss_chunk"])
        return loss, (terms, hidden, state["intermediates"])

    return parts


def _loss_fn(model, config):
    """``(params, bias, tokens) -> (loss, ({block: counts (E,)}, live))``:
    the loss, the pairs each expert layer routed to each of ALL the experts,
    and the rows on the held ones that each layer's passes visited
    (``moe_live_rows``, sown by the layer), (layers,) in layer order."""
    import jax.numpy as jnp

    from horovod_tpu.models import expert_counts

    parts = _loss_parts(model, config)

    def loss_fn(params, bias, tokens):
        loss, (_, _, intermediates) = parts(params, bias, tokens)
        counts = expert_counts(intermediates)
        live = jnp.stack([intermediates[block]["moe"]["moe_live_rows"][0]
                          for block in _in_layer_order(counts)])
        return loss, (counts, live)

    return loss_fn


def _update_bias(bias, counts, rate, reduce=lambda c: c):
    """The rule after a step, each expert layer's bias from its own counts;
    ``reduce`` sums the counts over ranks."""
    from horovod_tpu.ops.moe import router_bias_update

    return {block: {"moe": {"router_bias": router_bias_update(
        leaves["moe"]["router_bias"], reduce(counts[block]), rate)}}
        for block, leaves in bias.items()}


def _init_state(model, config):
    """``key -> (params, bias)``, the file's ``assumed`` initialisation:
    every matrix normal with std ``initializer_std`` (the embedding's rows
    with ``init.embedding_std``), the out-projections of
    the mixers (``out_proj``, ``o_proj``) divided by sqrt(2 x the published
    depth) (``rescale_prenorm_residual``), norm weights 1, the Mamba-2
    leaves as ``models/mamba.py`` draws them (``A`` uniform in [1, 16], ``dt``
    log-uniform in [0.001, 0.1], ``D`` 1, the convolution lecun-normal with a
    zero bias), the routers' bias 0. The shapes come from the model's own
    ``init``, traced and never run."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import BIAS_COLLECTION
    from horovod_tpu.models import mamba

    std = config["initializer_std"]
    embed_std = config["init"]["embedding_std"]
    rescale = (2 * config["num_hidden_layers"]) ** -0.5
    special = {"A_log": mamba._a_log_init, "dt_bias": mamba._dt_bias_init,
               "conv_kernel": nn.initializers.lecun_normal(),
               "conv_bias": nn.initializers.zeros}

    def draw(path, key, leaf):
        names = [getattr(p, "key", None) for p in path]
        if names[-1] in special:
            return special[names[-1]](key, leaf.shape, leaf.dtype)
        if leaf.ndim < 2:       # norm weights, D
            return jnp.ones(leaf.shape, leaf.dtype)
        if names[-1] == "embedding":
            scale = embed_std
        else:
            scale = std * (rescale if {"out_proj", "o_proj"} & set(names)
                           else 1.0)
        return scale * jax.random.normal(key, leaf.shape, leaf.dtype)

    def init(key):
        # Parameter shapes do not depend on the sequence length: trace short.
        shapes = jax.eval_shape(model.init, key, jnp.zeros((1, 128), jnp.int32))
        leaves, tree = jax.tree_util.tree_flatten_with_path(shapes["params"])
        keys = jax.random.split(jax.random.fold_in(key, 7), len(leaves))
        params = jax.tree_util.tree_unflatten(tree, [
            draw(path, k, leaf) for k, (path, leaf) in zip(keys, leaves)])
        bias = jax.tree_util.tree_map(
            lambda b: jnp.zeros(b.shape, b.dtype), shapes[BIAS_COLLECTION])
        return params, bias

    return init


_published = {}    # the collector of the latest build, which a newer replaces


def _publish_live_rows(built, window):
    """Have the program's registry say, whenever it is asked, what the expert
    layers visited in the latest steps: a collector reads the fourth carry of
    ``built`` (``live`` (steps, layers), the ring the step writes; -1 where
    no step has written yet) and hands it to
    ``metrics.overlap.record_moe_live_rows``. Nothing is read while nobody
    asks: the timed window is not touched."""
    from horovod_tpu.metrics import record_moe_live_rows, registry

    def collect(_registry):
        ring = np.asarray(built["state"][3]["live"])
        record_moe_live_rows(ring[ring[:, 0] >= 0], window)

    forget_live_rows()
    _published["collect"] = collect
    registry().register_collector(collect)


def forget_live_rows():
    """Take the latest build's collector off the registry (and with it the
    build's state, which it holds)."""
    from horovod_tpu.metrics import registry

    if "collect" in _published:
        registry().unregister_collector(_published.pop("collect"))


def _tokens_fn(config, rows, seq):
    import jax
    import jax.numpy as jnp

    return lambda key: jax.random.randint(key, (rows, seq), 0,
                                          config["vocab_held"], jnp.int32)


def build(config, traffic, mesh, seed, **model_overrides):
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.compat import shard_map
    from horovod_tpu.ops.moe import _window
    from horovod_tpu.parallel.collectives import ReduceOp, allreduce

    from benchmarks.reduce_trace import SCOPE_FWD_BWD, SCOPE_OPTIMIZER

    rows, seq = traffic["global_rows"], traffic["seq"]
    if rows % mesh.size:
        raise ValueError(f"{rows} rows do not divide over {mesh.size} chips")
    model = _model(config, **model_overrides)
    opt = hvd.jax.DistributedOptimizer(_optimizer(config))
    replicated = NamedSharding(mesh, P())
    key = jax.random.PRNGKey(seed)
    init_state = _init_state(model, config)
    rate = config["router_bias"]["update_rate"]

    # What the expert layers visited in the latest steps, one row a step: the
    # routing decides it, and with it the time of the held path's passes.
    kept = traffic["trace_groups"] * traffic["fence_every"]

    def init(key):
        params, bias = init_state(key)
        tally = {"live": -jnp.ones((kept, len(bias)), jnp.int32),
                 "steps": jnp.zeros((), jnp.int32)}
        return params, opt.init(params), bias, tally

    params, opt_state, bias, tally = jax.jit(
        init, out_shardings=replicated)(key)
    tokens = jax.jit(_tokens_fn(config, rows, seq),
                     out_shardings=NamedSharding(mesh, P(hvd.HVD_AXIS)))(
        jax.random.fold_in(key, 1))
    loss_fn = _loss_fn(model, config)

    def train_step(params, opt_state, bias, tally, tokens):
        with jax.named_scope(SCOPE_FWD_BWD):
            (loss, (counts, live)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, bias, tokens)
        with jax.named_scope(SCOPE_OPTIMIZER):
            updates, opt_state = opt.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            # the one collective of the step that carries no gradient: the
            # experts' load summed over ranks, so that replicas keep ONE bias
            bias = _update_bias(bias, counts, rate, lambda c: allreduce(
                c, hvd.HVD_AXIS, ReduceOp.SUM))
            # this rank's own rows (the first rank's, where there are more)
            tally = {"live": tally["live"].at[tally["steps"] % kept].set(live),
                     "steps": tally["steps"] + 1}
        return (params, opt_state, bias, tally,
                jax.lax.pmean(loss, hvd.HVD_AXIS))

    step = jax.jit(
        shard_map(train_step, mesh=mesh,
                  in_specs=(P(), P(), P(), P(), P(hvd.HVD_AXIS)),
                  out_specs=(P(), P(), P(), P(), P()), check_vma=False),
        donate_argnums=(0, 1, 2, 3))
    built = {"step": step, "state": [params, opt_state, bias, tally],
             "batch": (tokens,), "samples_per_step": rows * seq}
    _publish_live_rows(built, _window(rows // mesh.size * seq
                                      * config["num_experts_per_tok"]))
    return built


# ----------------------------------------------------------------- reference

def reference_config(config):
    """The reference's own few keys, from the file's."""
    pattern, mtp_pattern = _patterns(config)
    return {"layer_types": pattern, "mtp_layer_types": mtp_pattern,
            "heads": config["attention_heads_held"],
            "kv_heads": config["kv_heads_held"],
            "mamba_heads": config["mamba_heads_held"],
            "mamba_head_dim": config["mamba_head_dim"],
            "mamba_state": config["ssm_state_size"],
            "mamba_groups": config["mamba_groups_held"],
            "eps": config["norm_eps"],
            "top_k": config["num_experts_per_tok"],
            "route_scale": float(config["routed_scaling_factor"]),
            "held": _held(config), "mtp_weight": config["mtp_loss_weight"]}


def _layer_to_reference(block):
    """One block of the system's tree (or of its gradients) in the
    reference's layout: the fused ``kv_proj`` kernel split into its two, the
    rest renamed."""
    import jax.numpy as jnp

    layer = {"norm": block["RMSNorm_0"]["scale"]}
    if "mixer" in block:
        mixer = block["mixer"]
        layer.update(w_in=mixer["in_proj"]["kernel"],
                     conv_w=mixer["conv_kernel"], conv_b=mixer["conv_bias"],
                     dt_bias=mixer["dt_bias"], A_log=mixer["A_log"],
                     D=mixer["D"], gate_norm=mixer["gate_norm"],
                     w_out=mixer["out_proj"]["kernel"])
    elif "moe" in block:
        moe = block["moe"]
        layer.update(router=moe["router"], w_fc1=moe["fc1_latent"]["kernel"],
                     w_up=moe["w_up"], w_down=moe["w_down"],
                     w_fc2=moe["fc2_latent"]["kernel"],
                     s_up=moe["shared_up"]["kernel"],
                     s_down=moe["shared_down"]["kernel"])
    elif "qkv" in block:        # as many key/value heads as query heads
        wq, wk, wv = jnp.split(block["qkv"]["kernel"], 3, axis=1)
        layer.update(wq=wq, wk=wk, wv=wv, wo=block["o_proj"]["kernel"])
    else:
        wk, wv = jnp.split(block["kv_proj"]["kernel"], 2, axis=1)
        layer.update(wq=block["q_proj"]["kernel"], wk=wk, wv=wv,
                     wo=block["o_proj"]["kernel"])
    return layer


def _blocks(tree, prefix):
    """``<prefix><i>`` entries of ``tree`` by ``i``."""
    names = [k for k in tree if k.startswith(prefix)]
    return sorted(names, key=lambda b: int(b[len(prefix):]))


def to_reference(tree):
    """The system's parameter tree (or its gradients) in the reference's
    layout: the main model's layers, the module's parts under ``mtp``."""
    return {"embed": tree["embed"]["embedding"],
            "layers": [_layer_to_reference(tree[b])
                       for b in _blocks(tree, "block_")],
            "final_norm": tree["RMSNorm_0"]["scale"],
            "head": tree["lm_head"]["kernel"],
            "mtp": {"hidden_norm": tree["mtp_hidden_norm"]["scale"],
                    "embed_norm": tree["mtp_embed_norm"]["scale"],
                    "proj": tree["mtp_proj"]["kernel"],
                    "layers": [_layer_to_reference(tree[b])
                               for b in _blocks(tree, "mtp_block_")],
                    "norm": tree["mtp_norm"]["scale"]}}


def _in_layer_order(blocks):
    """Expert layers' names as the reference counts them: the main model's
    by index, then the module's."""
    return _blocks(blocks, "block_") + _blocks(blocks, "mtp_block_")


def biases_to_reference(bias):
    """The ``moe_bias`` collection as the reference's list, in layer order."""
    return [bias[block]["moe"]["router_bias"]
            for block in _in_layer_order(bias)]


def _sample(grads, rows):
    """What the check reads of a gradient tree in the reference's layout:
    EVERY leaf; of a matrix (or of each expert's, flattened to rows) the
    seeded ``rows`` (an index array, traced so that one compiled program
    serves every seed; taken modulo the leaf's height), of a vector all of
    it."""
    import jax

    def take(leaf):
        if leaf.ndim < 2:
            return leaf
        flat = leaf.reshape(-1, leaf.shape[-1])     # experts' rows end to end
        return flat[rows % flat.shape[0]]

    flat = jax.tree_util.tree_flatten_with_path(
        jax.tree_util.tree_map(take, grads))[0]
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
    """The jitted programs of the model check. The system's model in float32
    (run it under ``highest``) and as trained, each ``(params, bias, tokens,
    rows) -> dict`` with the step's own ``loss`` and ``grads`` (through
    ``lm_loss_with_mtp``'s two ``chunked_lm_loss`` passes and their
    backward), both loss terms, both sets of logits, the experts each layer
    chose (a list of (N, E) masks) and the biases one application of the rule
    later. And the plain reference, ``(params, bias, tokens, rows, forced) ->
    dict``: everything computed UNDER THE CHOICE ``forced`` (a leg's
    ``chosen``), beside it ``own_chosen``, what its own router picks in each
    layer from the same upstream state: a tie broken the other way early in
    the row then costs that token's flag and nothing downstream (PERF.md §7,
    Laguna's seed 5151515101). Loss and logits come by the state-space
    layer's recurrence, gradients through its quadratic form, and
    ``forms_logits_rel`` says how far the two forms lie apart."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import expert_counts

    from benchmarks.reference import nemotron3 as plain_model

    cfg = reference_config(config)
    rate = config["router_bias"]["update_rate"]
    n_experts = config["n_routed_experts"]

    def system(model):
        parts = _loss_parts(model, config)

        def run(params, bias, tokens, rows):
            # ONE forward: the logits are the head's product with the hidden
            # states the losses were taken from
            (loss, ((main, mtp), hidden, inter)), grads = jax.value_and_grad(
                parts, has_aux=True)(params, bias, tokens)
            logits, mtp_logits = (
                h.astype(jnp.float32) @ params["lm_head"]["kernel"]
                for h in hidden)
            counts = expert_counts(inter)
            blocks = _in_layer_order(counts)
            return {"loss": loss, "main": main, "mtp": mtp, "logits": logits,
                    "mtp_logits": mtp_logits,
                    "chosen": [_chosen_mask(
                        inter[b]["moe"]["moe_chosen_experts"][0], n_experts)
                        for b in blocks],
                    "counts": [counts[b] for b in blocks],
                    "bias_after": biases_to_reference(
                        _update_bias(bias, counts, rate)),
                    "grads": _sample(to_reference(grads), rows)}

        return jax.jit(run)

    @jax.jit
    def plain(params, bias, tokens, rows, forced):
        ref_params, biases = to_reference(params), biases_to_reference(bias)
        # under the system's choice: by the recurrence, then the gradients
        # through the quadratic form
        loss, parts = plain_model.loss_parts(ref_params, biases, tokens, cfg,
                                             forced=forced)
        (_, quadratic), grads = plain_model.loss_and_grads(
            ref_params, biases, tokens, cfg, forced=forced)
        return {"loss": loss, "main": parts["main"], "mtp": parts["mtp"],
                "logits": parts["logits"], "mtp_logits": parts["mtp_logits"],
                "own_chosen": parts["own_chosen"], "counts": parts["counts"],
                "forms_logits_rel": (
                    jnp.max(jnp.abs(quadratic["logits"] - parts["logits"]))
                    / jnp.max(jnp.abs(parts["logits"]))),
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
    """Normal values that bf16 holds exactly, as float32: the bf16 leg and
    the float32 reference then read the same numbers."""
    import jax
    import jax.numpy as jnp

    return (scale * jax.random.normal(key, shape, jnp.float32)).astype(
        jnp.bfloat16).astype(jnp.float32)


def scan_programs(config, rows, seq, slice_len):
    """``inputs(key)`` and the two jitted programs of the scan's own check at
    (rows, seq, heads, head_dim, state) as held: ``ops.ssd.ssd`` and the
    float32 recurrence of the reference over the whole row, each returning
    the last ``slice_len`` positions. ``u``, ``B`` and ``C`` are
    bf16-representable; ``dt`` and ``A`` are drawn as Mamba-2's
    initialisation draws them."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops.ssd import ssd

    from benchmarks.reference import nemotron3 as plain_model

    h, p, n, g = (config[k] for k in ("mamba_heads_held", "mamba_head_dim",
                                      "ssm_state_size", "mamba_groups_held"))
    chunk = config["chunk_size"]

    @jax.jit
    def inputs(key):
        ks = jax.random.split(key, 6)
        u = _bf16_values(ks[0], (rows, seq, h, p))
        B = _bf16_values(ks[1], (rows, seq, g, n), n ** -0.25)
        C = _bf16_values(ks[2], (rows, seq, g, n), n ** -0.25)
        dt0 = jnp.exp(jax.random.uniform(ks[3], (h,)) * np.log(100.0)
                      + np.log(1e-3))
        dt = dt0 * jnp.exp(0.5 * jax.random.normal(ks[4], (rows, seq, h)))
        A = -jax.random.uniform(ks[5], (h,), minval=1.0, maxval=16.0)
        return u, dt, A, B, C, jnp.ones((h,), jnp.float32)

    def chunked(dtype):
        @jax.jit
        def run(u, dt, A, B, C, D):
            y = ssd(u.astype(dtype), dt, A, B.astype(dtype), C.astype(dtype),
                    D, chunk)
            return y[:, -slice_len:].astype(jnp.float32)
        return run

    @jax.jit
    def recurrence(u, dt, A, B, C, D):
        with jax.default_matmul_precision("highest"):
            return plain_model.ssm_recurrence(u, dt, A, B, C, D)[:, -slice_len:]

    return inputs, chunked, recurrence


def flash_programs(config, seq, slice_len, **model_overrides):
    """``inputs(key)`` and the two jitted programs of the flash kernels' own
    check at (seq, the held query heads over the held key/value heads,
    head_dim), scale ``head_dim ** -0.5``: the kernels as the model calls
    them, and a per-head float32 reference of the LAST ``slice_len`` query
    positions against the whole context (under a causal mask that is enough
    for exact out and dq on those queries, and dk and dv on the same
    positions as keys). Each returns (out, dq, dk, dv) on the slice."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops.flash_attention import (DEFAULT_BLOCK_K,
                                                 DEFAULT_BLOCK_Q,
                                                 flash_attention)

    heads, kv = config["attention_heads_held"], config["kv_heads_held"]
    d = config["head_dim"]
    interpret = model_overrides.get("flash_interpret", False)
    start = seq - slice_len

    @jax.jit
    def inputs(key):
        ks = jax.random.split(key, 4)
        q = _bf16_values(ks[0], (1, seq, heads, d)).astype(jnp.bfloat16)
        k, v = (_bf16_values(kk, (1, seq, kv, d)).astype(jnp.bfloat16)
                for kk in ks[1:3])
        return q, k, v, _bf16_values(ks[3], (1, seq, heads, d))

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

        def attend(qs, kh, vh):     # (slice, D), (T, D), (T, D)
            s = (qs @ kh.T) * d ** -0.5
            seen = (jnp.arange(slice_len) + start)[:, None] >= jnp.arange(seq)
            return jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1) @ vh

        def one_head(args):
            qs, kh, vh, gs = args
            out, vjp = jax.vjp(attend, qs, kh, vh)
            dq, dk, dv = vjp(gs)
            return out, dq, dk[start:], dv[start:]

        def heads_first(t):
            return jnp.moveaxis(t[0].astype(f32), 1, 0)

        def shared(t):              # each kv head, once per query head
            return jnp.repeat(heads_first(t), heads // kv, axis=0)

        with jax.default_matmul_precision("highest"):
            out, dq, dk, dv = jax.lax.map(
                one_head, (heads_first(q[:, start:]), shared(k), shared(v),
                           heads_first(g[:, start:])))
        dk, dv = (t.reshape(kv, heads // kv, slice_len, d).sum(axis=1)
                  for t in (dk, dv))
        return tuple(jnp.moveaxis(t, 0, 1)[None] for t in (out, dq, dk, dv))

    return inputs, system, reference


def reference(config, traffic, mesh, seed, **model_overrides):
    """Three checks at the widths of the file, outside the timed window, each
    logged whole; raises ``AssertionError`` for what lies beyond the file's
    ``tolerance``.

    (c) ``ops.ssd.ssd`` alone at the cell's rows and full row length, the
    held heads, float32 and bf16, its last ``scan_slice`` positions against
    the float32 recurrence over the whole row.
    (d) The flash kernels alone at the full row, the held grouped-query
    heads, their last ``flash_slice`` positions against a per-head float32
    reference: out, dq, dk, dv.
    (a, b) The model itself against ``benchmarks/reference/nemotron3.py`` on
    the first ``reference_prefix`` tokens of the cell's first row with the
    cell's seeded parameters and bias (a second training state cannot be held
    beside the first, so no plain STEP is played), in two legs: the system's
    model in float32 at ``highest``, which proves the mathematics, and the
    model as trained (bf16 activations, flash kernels, bf16 grouped products,
    recomputation). Each leg is held against the reference computed UNDER
    THE LEG'S OWN CHOICE of experts (``check_programs``): ``L_main`` and
    ``L_mtp`` separately, both sets of logits, 64 sampled rows of EVERY
    leaf's gradient through the step's own loss (float32: a leaf's largest
    error over max|ref|; as trained: its Euclidean norm over the
    reference's), and, each under a limit of its own, how far the leg's
    choice lies from what the reference's router picks when fed the same
    upstream state: the share of tokens with another 22 in some layer
    (``flipped_share``; held in the float32 leg, logged in the as-trained
    one, where it is near 1 by nature), the share of the chosen pairs the
    reference does not choose (``pairs_differ_share``). The biases one
    application of the rule later are held EXACTLY, no tolerance: the
    system's rule on its own counts of all the experts against the
    reference's rule on the counts of the same choice (integers and a sign:
    no precision moves them; the choice itself is held by the shares above).
    Also logged: the share of each layer's pairs that falls on the held
    experts (``held_share``; 8 / 512 when the router is balanced)."""
    import json

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    tol = config["tolerance"]
    one = SingleDeviceSharding(mesh.devices.flat[0])
    key = jax.random.PRNGKey(seed)
    seq, rows_a_chip = traffic["seq"], traffic["global_rows"] // mesh.size
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

    # -- (c) the scan alone, (d) the flash kernels alone: before the model's
    # parameters are made a second time, while the memory is there
    inputs, chunked, recurrence = scan_programs(
        config, rows_a_chip, seq, min(traffic["scan_slice"], seq))
    args = jax.device_put(inputs(jax.random.fold_in(key, 2)), one)
    want = recurrence(*args)
    observed["scan"] = {}
    for name, dtype, precision in (("f32", jnp.float32, "highest"),
                                   ("bf16", jnp.bfloat16, None)):
        with jax.default_matmul_precision(precision):
            got = chunked(dtype)(*args)
        observed["scan"][name] = held(f"scan {name}", share(got, want),
                                      tol[f"{name}_scan_rel"])
    del args, want, got

    inputs, system, plain = flash_programs(
        config, seq, min(traffic["flash_slice"], seq), **model_overrides)
    args = jax.device_put(inputs(jax.random.fold_in(key, 3)), one)
    observed["flash"] = {
        name: held(f"flash {name}", share(got, want), tol["flash_rel"])
        for name, got, want in zip(("out", "dq", "dk", "dv"), system(*args),
                                   plain(*args))}
    del args

    # -- (a, b) the model
    prefix = min(traffic["reference_prefix"], seq)
    params, bias = jax.jit(
        _init_state(_model(config, **model_overrides), config),
        out_shardings=one)(key)
    tokens = jax.jit(_tokens_fn(config, traffic["global_rows"], seq),
                     out_shardings=one)(jax.random.fold_in(key, 1))[:1, :prefix]
    rows = jnp.asarray(np.random.default_rng(seed).integers(
        0, 2 ** 31 - 1, SAMPLED_ROWS), jnp.int32)
    plain, exact, trained = check_programs(config, **model_overrides)

    @jax.jit
    def choice_distance(got, want):
        """(share of the tokens whose chosen experts differ in some layer,
        share of all layers' chosen pairs that the other side did not
        choose): the first saturates once most tokens differ somewhere, the
        second does not."""
        agree = jnp.ones(got[0].shape[0], bool)
        missed = 0.0
        for g, w in zip(got, want):
            agree &= jnp.all(g == w, axis=-1)
            missed += jnp.sum(g & ~w) / jnp.sum(g)
        return 1.0 - jnp.mean(agree), missed / len(got)

    def relative(got, want):
        return abs(float(got) - float(want)) / abs(float(want))

    compare = jax.jit(_distances)
    legs = {"f32": (exact, "highest", 0, "grads_rel"),
            "bf16": (trained, None, 1, "grads_l2_rel")}
    first, count = _held(config)
    for name, (program, precision, which, limit) in legs.items():
        with jax.default_matmul_precision(precision):
            got = program(params, bias, tokens, rows)
        with jax.default_matmul_precision("highest"):
            ref = plain(params, bias, tokens, rows, got["chosen"])
        distances = jax.device_get(compare(got["grads"], ref["grads"]))
        flipped, pairs_differ = choice_distance(got["chosen"],
                                                ref["own_chosen"])
        observed[name] = {
            "main_loss": held(f"{name} main loss",
                              relative(got["main"], ref["main"]),
                              tol[f"{name}_loss_rel"]),
            "mtp_loss": held(f"{name} multi-token-prediction loss",
                             relative(got["mtp"], ref["mtp"]),
                             tol[f"{name}_loss_rel"]),
            "loss": held(f"{name} loss", relative(got["loss"], ref["loss"]),
                         tol[f"{name}_loss_rel"]),
            # the as-trained leg's tokens nearly all differ in SOME of the
            # layers: its choice is held by the pairs, the share is logged
            "flipped_share": held(
                f"{name} share of tokens whose experts differ", flipped,
                tol.get(f"{name}_flipped_share", 1.0)),
            "pairs_differ_share": held(
                f"{name} share of chosen pairs the reference does not choose",
                pairs_differ, tol[f"{name}_pairs_differ_share"]),
            "held_under": "the system's choice",
            "logits": held(f"{name} logits",
                           share(got["logits"], ref["logits"]),
                           tol[f"{name}_logits_rel"]),
            "mtp_logits": held(f"{name} multi-token-prediction logits",
                               share(got["mtp_logits"], ref["mtp_logits"]),
                               tol[f"{name}_logits_rel"]),
            "bias_differ_share": held(
                f"{name} share of experts whose bias differs after one step",
                float(np.mean([np.asarray(g) != np.asarray(w) for g, w in
                               zip(got["bias_after"], ref["bias_after"])])),
                0.0),
            limit: {leaf: held(f"{name} gradient of {leaf}", pair[which],
                               tol[f"{name}_{limit}"])
                    for leaf, pair in distances.items()},
            "grads_other_distance_max": float(max(
                pair[1 - which] for pair in distances.values())),
            "held_share": [       # of a layer's pairs, on this rank
                float(np.sum(np.asarray(c)[first:first + count])
                      / np.sum(np.asarray(c))) for c in got["counts"]]}
        if name == "f32":
            observed["reference_forms"] = {"logits": held(
                "reference, quadratic form against the recurrence: logits",
                ref["forms_logits_rel"], tol["f32_logits_rel"])}
            observed["bias_after_abs_max"] = float(max(
                np.max(np.abs(np.asarray(b))) for b in ref["bias_after"]))
        del got, ref
    del params
    if beyond:
        raise AssertionError(
            "nemotron_3_super_120b_a12b against its float32 references: "
            + "; ".join(beyond) + "; observed " + json.dumps(observed))
    return {"kind": "kernel", "observed": observed}


def cost(config, traffic, chips):
    """Needed operations per step per chip (forward + backward, causal half,
    recompute not counted; the held experts at a balanced router's load), and
    what the held experts' grouped-product kernel calls of a step need."""
    from benchmarks import latent_moe_cost

    rows, seq = traffic["global_rows"] // chips, traffic["seq"]
    pattern, mtp_pattern = _patterns(config)
    experts = (config["n_routed_experts"], config["num_experts_per_tok"],
               config["experts_held"], config["moe_latent_size"],
               config["moe_intermediate_size"])
    return {
        "model_flops": latent_moe_cost.share_step_flops(
            seq, rows, config["vocab_held"], config["hidden_size"], pattern,
            mtp_pattern,
            (config["mamba_heads_held"], config["mamba_head_dim"],
             config["ssm_state_size"], config["mamba_groups_held"],
             config["chunk_size"]),
            (config["attention_heads_held"], config["kv_heads_held"],
             config["head_dim"]),
            (*experts, config["shared_width_held"])),
        "latent_moe": latent_moe_cost.grouped_step_cost(
            rows * seq, (pattern + mtp_pattern).count("E"), *experts,
            forward_calls=2 if config["remat"] else 1),
    }
