"""solar_open2_250b: tensor rank 0 / expert rank 0's share of
Solar-Open2-250B's first period of four layers (softmax grouped-query
attention with no position information and a sigmoid gate an element of its
output in the first; Kimi Delta Attention with beta in (0, 2), negative
eigenvalues allowed, in the three others; sigmoid-routed experts beside a
shared one in every layer) through ``TransformerLM`` and
``hvd.jax.DistributedOptimizer`` in the jitted ``shard_map`` step every
language-model cell runs (``kimi_linear_48b_a3b.py`` builds the same one, the
router's bias a third carry), its plain float32 reference, its costs.

The same three functions as every configuration module:
``build(config, traffic, mesh, seed, **model_overrides)``,
``reference(config, traffic, mesh, seed, **model_overrides)`` and
``cost(config, traffic, chips)``. The configuration file carries the Hugging
Face keys as published; ``layers``, ``kda_heads_held``,
``attention_heads_held``, ``kv_heads_held``, ``shared_width_held``,
``experts_held`` (from ``experts_first``) and ``vocab_held`` are what this
chip runs of them: a tensor rank's heads and columns, an expert rank's
experts.
"""

from __future__ import annotations

import numpy as np

SAMPLED_ROWS = 64   # rows of a matrix's gradient the check reads
# seed -> the (params, bias) the latest ``build`` of that seed made, until its
# check takes them: 786 M float32 parameters are 3.1 GB, and beside 9.4 GB of
# training state the check's programs (3.3 GB of temporaries and outputs, 1.5
# GB of code) leave no room for a second copy of them (PERF.md §6, PR 53)
_SEEDED = {}
INIT_STD = 0.02     # the file's ``assumed``: normal, std 0.02; norm weights 1
# ... and the embedding's rows at a scale of their own, so that a seeded
# model's tokens stay apart and its routers balanced (``init.embedding_std``
# in the file says what it is and why)


def _held(config):
    return (config["experts_first"], config["experts_held"])


def kinds(config):
    """The kinds of the layers this chip runs, from the published 0-based
    list ``gqa_layers``: ``"gqa"``, or ``"kda"`` for every other layer."""
    gqa = config["gqa_layers"]
    if gqa != list(range(0, config["num_hidden_layers"],
                         config["gqa_interval"] + 1)):
        raise ValueError("solar_open2_250b: gqa_layers is not one layer in "
                         "every gqa_interval + 1")
    return tuple("gqa" if i in gqa else "kda" for i in range(config["layers"]))


def _share(config):
    """What a tensor rank holds of the published counts, checked against the
    stated layout: (KDA heads, query heads, key/value heads, the shared
    expert's columns)."""
    tp, ep = config["tensor_parallel"], config["expert_parallel"]
    whole = (config["linear_attn_config"]["num_heads"],
             config["num_attention_heads"], config["num_key_value_heads"],
             config["n_shared_experts"] * config["moe_intermediate_size"])
    held = (config["kda_heads_held"], config["attention_heads_held"],
            config["kv_heads_held"], config["shared_width_held"])
    if any(w % tp or w // tp != h for w, h in zip(whole, held)) or (
            config["n_routed_experts"] != ep * config["experts_held"]):
        raise ValueError(
            f"solar_open2_250b: the held share {held} / "
            f"{config['experts_held']} experts is not the published {whole} "
            f"/ {config['n_routed_experts']} over tensor_parallel {tp} / "
            f"expert_parallel {ep}")
    return held


def _model(config, **overrides):
    from horovod_tpu.models import KDADims, TransformerLM

    linear = config["linear_attn_config"]
    if (config["model_type"] != "solar_open2" or config["use_rope"]
            or not config["use_gqa_gate"] or config["kda_use_full_proj"]
            or not config["kda_allow_neg_eigval"]
            or not config["norm_topk_prob"] or config["tie_word_embeddings"]
            or config["first_k_dense_replace"] != 0
            or linear["num_kv_heads"] is not None
            or linear["head_dim"] != config["head_dim"]):
        raise ValueError("solar_open2_250b: the configuration file states a "
                         "layer equation this module does not build")
    kda_heads, heads, kv_heads, shared_width = _share(config)
    kw = dict(vocab=config["vocab_held"], dim=config["hidden_size"],
              heads=heads, kv_heads=kv_heads, head_dim=config["head_dim"],
              layers=config["layers"],
              layer_types=tuple({"kda": "kda", "gqa": "full_attention"}[k]
                                for k in kinds(config)),
              kda=KDADims(heads=kda_heads, head_dim=linear["head_dim"],
                          conv=linear["short_conv_kernel_size"],
                          chunk=config["kda_chunk"],
                          allow_neg_eigval=config["kda_allow_neg_eigval"]),
              rope=False, attn_gate="element",
              moe_experts=config["n_routed_experts"], moe_every=1,
              moe_top_k=config["num_experts_per_tok"],
              moe_hidden=config["moe_intermediate_size"],
              moe_router="sigmoid",
              moe_route_scale=config["routed_scaling_factor"],
              moe_shared_hidden=shared_width,
              moe_held=_held(config), rms_norm_eps=config["rms_norm_eps"],
              attention=config["attention"], remat=config["remat"])
    kw.update(overrides)
    return TransformerLM(**kw)


def _leaf_name(path):
    return getattr(path[-1], "key", None)


def _is_matrix(path, leaf):
    """Leaves AdamW decays: two or more axes and no convolution's taps."""
    return leaf.ndim >= 2 and not str(_leaf_name(path)).endswith("_conv")


def _optimizer(config):
    import jax
    import optax

    o = config["optimizer"]
    if o["name"] != "adamw":
        raise ValueError(f"solar_open2_250b trains with adamw, not {o}")
    # weight decay on matrices only: not on A_log, dt_bias, norm weights or
    # the convolutions' taps; the router's bias is no parameter at all
    return optax.adamw(
        o["learning_rate"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
        weight_decay=o["weight_decay"],
        mask=lambda params: jax.tree_util.tree_map_with_path(_is_matrix,
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


def _init_state(model, config):
    """``key -> (params, bias)``: matrices normal with std 0.02 (the
    embedding's rows with ``init.embedding_std``), the convolutions' taps
    uniform in +-K^-0.5, ``A_log`` the log of uniform(1, 16) a head,
    ``dt_bias`` by Mamba-2's inverse-softplus rule, norm weights 1, the
    router's bias 0. The shapes come from the model's own ``init``, traced
    and never run."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import BIAS_COLLECTION
    from horovod_tpu.models.mamba import _a_log_init, _dt_bias_init

    embed_std = config["init"]["embedding_std"]

    def draw(key, path, leaf):
        name = str(_leaf_name(path))
        if name.endswith("_conv"):
            edge = leaf.shape[0] ** -0.5
            return jax.random.uniform(key, leaf.shape, leaf.dtype, -edge, edge)
        if name == "A_log":
            return _a_log_init(key, leaf.shape, leaf.dtype)
        if name == "dt_bias":
            return _dt_bias_init(key, leaf.shape, leaf.dtype)
        if leaf.ndim >= 2:
            std = embed_std if name == "embedding" else INIT_STD
            return std * jax.random.normal(key, leaf.shape, leaf.dtype)
        return jnp.ones(leaf.shape, leaf.dtype)

    def init(key):
        # Parameter shapes do not depend on the sequence length: trace short.
        shapes = jax.eval_shape(model.init, key, jnp.zeros((1, 128), jnp.int32))
        leaves, tree = jax.tree_util.tree_flatten_with_path(shapes["params"])
        keys = jax.random.split(jax.random.fold_in(key, 7), len(leaves))
        params = jax.tree_util.tree_unflatten(tree, [
            draw(k, path, leaf) for k, (path, leaf) in zip(keys, leaves)])
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
    init_state = _init_state(model, config)
    rate = config["router_bias"]["update_rate"]

    def init(key):
        params, bias = init_state(key)
        return params, opt.init(params), bias

    params, opt_state, bias = jax.jit(init, out_shardings=replicated)(key)
    _SEEDED.clear()
    _SEEDED[seed] = (params, bias)
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
    kda_heads, heads, kv_heads, _ = _share(config)
    return {"kinds": kinds(config), "kda_heads": kda_heads, "heads": heads,
            "kv_heads": kv_heads, "head_dim": config["head_dim"],
            "eps": config["rms_norm_eps"],
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
                 "mlp_norm": block["RMSNorm_1"]["scale"]}
        if "mixer" in block:
            m = block["mixer"]
            layer.update(
                wq=m["q_proj"]["kernel"], wk=m["k_proj"]["kernel"],
                wv=m["v_proj"]["kernel"], conv_q=m["q_conv"],
                conv_k=m["k_conv"], conv_v=m["v_conv"],
                wf_a=m["f_a_proj"]["kernel"], wf_b=m["f_b_proj"]["kernel"],
                dt_bias=m["dt_bias"], a_log=m["A_log"],
                wb=m["b_proj"]["kernel"], wg_a=m["g_a_proj"]["kernel"],
                wg_b=m["g_b_proj"]["kernel"], o_norm=m["o_norm"],
                wo=m["o_proj"]["kernel"])
        else:
            layer.update(wq=block["q_proj"]["kernel"],
                         wkv=block["kv_proj"]["kernel"],
                         wg=block["gate_proj"]["kernel"],
                         wo=block["o_proj"]["kernel"])
        moe = block["moe"]
        layer.update(router=moe["router"], w_gate=moe["w_gate"],
                     w_up=moe["w_up"], w_down=moe["w_down"],
                     s_gate=moe["shared_gate"]["kernel"],
                     s_up=moe["shared_up"]["kernel"],
                     s_down=moe["shared_down"]["kernel"])
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
    rows) -> dict``: the plain reference (which also takes ``forced``, one
    chosen set a layer, and ``use``: computed under those sets where ``use``
    is true and under its own choice otherwise, ONE program for both, so that
    a run whose float32 leg breaks a tie compiles no more than one that does
    not), the system's model in float32 (run it under ``highest``: its
    chunked delta rule included), and the system's model as trained. The system's ``loss`` and ``grads`` are the step's own:
    through ``chunked_lm_loss`` and its backward. ``chosen`` is a list of
    (N, E) masks, ``bias_after`` the biases one application of the rule
    later."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import expert_counts

    from benchmarks.reference import solar_open2 as plain_model

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
    def plain(params, bias, tokens, rows, forced, use):
        ref_params, biases = to_reference(params), biases_to_reference(bias)
        (loss, parts), grads = jax.value_and_grad(
            plain_model.loss_parts, has_aux=True)(
                ref_params, biases, tokens, cfg, [(use, f) for f in forced])
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


SCAN_GRADS = ("dq", "dk", "dv", "dg", "dbeta")
STRONGEST_PREACTIVATION = 20.0      # softplus's argument at the strongest decay
# The delta rule's own check runs under these gates, in order: as the mixer
# initialises them (beta = 2 sigmoid(normal)), at the strongest decay, and at
# the strongest CORRECTION: every beta at 2 and all keys of a chunk equal,
# the decay as initialised (the transition is then a reflection along the
# chunk's key, and the solve's entries alternate between -2 and 2).
SCAN_CASES = ("as_initialised", "strongest_decay", "beta2_equal_keys")


def scan_programs(config, seq, slice_len, interpret=False):
    """``inputs(key, case)`` and the jitted programs of the delta rule's own
    check at (seq, held heads, 128, 128): ``ops.kda.kda`` with beta in (0, 2)
    (``neg_eigval``) in bf16 and in float32 (each with the matmul precision
    to call it under), and the float32 recurrence of the reference over the
    whole row. Each returns the last ``slice_len`` positions of (o, dq, dk,
    dv, dg, dbeta) for a cotangent that is zero before them: the outputs
    there and the gradients that reach those positions. q and k are drawn,
    normed a head and rounded to bf16 (q times d^-0.5, as the mixer hands
    them over), v is bf16-representable; ``g = -exp(A_log) softplus(x +
    dt_bias)`` with ``A_log`` and ``dt_bias`` as the mixer initialises them
    and ``x`` normal (0.5); beta is ``2 sigmoid(normal)``. ``case`` (an index
    into ``SCAN_CASES``, traced) 1: every ``A`` at 16 and ``x`` at 20, a
    log-decay of -320 a token, at which every factorised exponent would
    overflow; 2: every beta at 2 and every key of a chunk its first one."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models.mamba import _a_log_init, _dt_bias_init
    from horovod_tpu.ops.kda import kda

    from benchmarks.reference import solar_open2 as plain_model

    linear = config["linear_attn_config"]
    h, d, chunk = config["kda_heads_held"], linear["head_dim"], config["kda_chunk"]
    start = seq - slice_len

    @jax.jit
    def inputs(key, case):
        ks = jax.random.split(key, 8)
        strongest, equal = case == 1, case == 2

        def unit(k):
            x = jax.random.normal(k, (1, seq, h, d), jnp.float32)
            return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

        q = (unit(ks[0]) * d ** -0.5).astype(jnp.bfloat16).astype(jnp.float32)
        k = unit(ks[1]).astype(jnp.bfloat16).astype(jnp.float32)
        first = jnp.arange(seq) // min(chunk, seq) * min(chunk, seq)
        k = jnp.where(equal, k[:, first], k)
        v = _bf16_values(ks[2], (1, seq, h, d))
        a = jnp.where(strongest, 16.0, jnp.exp(_a_log_init(ks[3], (h,))))
        x = jnp.where(strongest, STRONGEST_PREACTIVATION,
                      0.5 * jax.random.normal(ks[4], (1, seq, h, d)))
        g = -a[:, None] * jax.nn.softplus(
            x + _dt_bias_init(ks[5], (h * d,)).reshape(h, d))
        beta = jnp.where(equal, 2.0, 2.0 * jax.nn.sigmoid(
            jax.random.normal(ks[6], (1, seq, h))))
        do = _bf16_values(ks[7], (1, seq, h, d)) * (
            jnp.arange(seq) >= start)[None, :, None, None]
        return q, k, v, g, beta, do

    def on_slice(o, vjp, do):
        return tuple(t[:, start:].astype(jnp.float32)
                     for t in (o, *vjp(do.astype(o.dtype))))

    def in_dtype(dtype):
        @jax.jit
        def run(q, k, v, g, beta, do):
            o, vjp = jax.vjp(
                lambda q, k, v, g, beta: kda(q, k, v, g, beta, chunk,
                                             interpret=interpret,
                                             neg_eigval=True),
                q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta)
            return on_slice(o, vjp, do)
        return run

    # (program, the precision to call it under), one jitted copy a leg
    chunked = {"bf16": (in_dtype(jnp.bfloat16), None),
               "f32": (in_dtype(jnp.float32), "highest")}

    @jax.jit
    def recurrence(q, k, v, g, beta, do):
        with jax.default_matmul_precision("highest"):
            o, vjp = jax.vjp(plain_model.delta_rule, q, k, v, g, beta)
            return on_slice(o, vjp, do)

    return inputs, chunked, recurrence


def flash_programs(config, seq, slice_len, **model_overrides):
    """``inputs(key)`` and the two jitted programs of the flash kernels' own
    check at (seq, held query heads | held key/value heads, 128): the kernels
    as the model calls them (grouped-query: the shared head through the index
    map; default blocks, the default scale 128 ** -0.5, no rotation), and a
    per-head float32 reference of the LAST ``slice_len`` query positions
    against the whole context (under a causal mask that is enough for exact
    out and dq on those queries, and dk and dv on the same positions as
    keys, summed over the query heads a key/value head serves). Each returns
    (out, dq, dk, dv) on the slice."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops.flash_attention import (DEFAULT_BLOCK_K,
                                                 DEFAULT_BLOCK_Q,
                                                 flash_attention)

    heads, kv_heads = config["attention_heads_held"], config["kv_heads_held"]
    d = config["head_dim"]
    group = heads // kv_heads
    interpret = model_overrides.get("flash_interpret", False)
    start = seq - slice_len

    @jax.jit
    def inputs(key):
        ks = jax.random.split(key, 4)
        q = _bf16_values(ks[0], (1, seq, heads, d)).astype(jnp.bfloat16)
        k, v = (_bf16_values(kk, (1, seq, kv_heads, d)).astype(jnp.bfloat16)
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

        def attend(qs, kh, vh):     # (slice, 128), (T, 128), (T, 128)
            s = (qs @ kh.T) * d ** -0.5
            seen = (jnp.arange(slice_len) + start)[:, None] >= jnp.arange(seq)
            return jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1) @ vh

        def one_head(args):
            qs, kh, vh, gs = args
            out, vjp = jax.vjp(attend, qs, kh, vh)
            dq, dk, dv = vjp(gs)
            return out, dq, dk[start:], dv[start:]

        def heads_first(t, repeat=1):
            return jnp.repeat(jnp.moveaxis(t[0].astype(f32), 1, 0), repeat,
                              axis=0)

        with jax.default_matmul_precision("highest"):
            out, dq, dk, dv = jax.lax.map(
                one_head, (heads_first(q[:, start:]), heads_first(k, group),
                           heads_first(v, group), heads_first(g[:, start:])))
        # a key/value head's gradient: the sum over the query heads it serves
        dk, dv = (t.reshape(kv_heads, group, *t.shape[1:]).sum(1)
                  for t in (dk, dv))
        return tuple(jnp.moveaxis(t, 0, 1)[None] for t in (out, dq, dk, dv))

    return inputs, system, reference


def reference(config, traffic, mesh, seed, **model_overrides):
    """Three checks at the widths of the file, outside the timed window, each
    logged whole; raises ``AssertionError`` for what lies beyond the file's
    ``tolerance``.

    (1) The flash kernels alone at the cell's full row, the held 8 query
    heads over 1 key/value head of 128, no rotation, their last
    ``flash_slice`` positions against a per-head float32 reference: out, dq,
    dk, dv.
    (2) The chunked delta rule alone at the full row, the held 8 heads of
    128 | 128, beta in (0, 2), in bf16 and in float32 (under ``highest``),
    its last ``scan_slice`` positions (o and the gradients of q, k, v, g,
    beta that reach them) against the float32 recurrence over the whole row,
    under each of ``SCAN_CASES``: gates as the mixer initialises them, the
    strongest decay, and every beta at 2 with all keys of a chunk equal;
    every value must also be finite.
    (3) The model itself against ``benchmarks/reference/solar_open2.py`` on
    the first ``reference_prefix`` tokens of the cell's first row with the
    cell's seeded parameters and bias, in two legs: the system's model in
    float32 at ``highest``, which proves the mathematics (its chunked path
    included), and the model as trained (bf16 activations, flash kernels,
    bf16 grouped products, recomputation). Each leg holds the loss, the share
    of tokens whose experts differ from the reference's in some layer, the
    logits on the tokens that agree, 64 sampled rows of EVERY leaf's gradient
    through ``chunked_lm_loss``'s own backward (float32: a leaf's largest
    error over max|ref|; as trained: its Euclidean norm over the
    reference's), and the share of the experts whose bias differs after one
    application of the rule. A float32 leg in which a token or two broke a
    tie the other way holds that share, and the rest against the reference
    computed under the system's choice (``held_under``). Also logged: the
    share of each layer's pairs that falls on the held experts
    (``held_share``; 8 / 320 when the router is balanced)."""
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
    def shares(got, want):
        """max|got - want| over max|want|, of each pair: one program a leg."""
        return [jnp.max(jnp.abs(g - w)) / jnp.maximum(jnp.max(jnp.abs(w)), 1e-30)
                for g, w in zip(got, want)]

    # -- (1) the flash kernels alone, (2) the delta rule alone: before the
    # model's parameters are made a second time, while the memory is there
    inputs, system, plain = flash_programs(
        config, seq, min(traffic["flash_slice"], seq), **model_overrides)
    args = jax.device_put(inputs(jax.random.fold_in(key, 3)), one)
    observed["flash"] = {
        name: held(f"flash {name}", value, tol["flash_rel"])
        for name, value in zip(("out", "dq", "dk", "dv"),
                               shares(system(*args), plain(*args)))}
    del args

    inputs, chunked, recurrence = scan_programs(
        config, seq, min(traffic["scan_slice"], seq),
        model_overrides.get("flash_interpret", False))
    observed["scan"] = {}
    for case, gates in enumerate(SCAN_CASES):
        args = jax.device_put(inputs(jax.random.fold_in(key, 4), case), one)
        want = recurrence(*args)
        for name, (program, precision) in chunked.items():
            with jax.default_matmul_precision(precision):
                got = program(*args)
            # the strongest correction has a limit of its own where bf16
            # operands lose more to it (``bf16_scan_beta2_rel``)
            limit = tol[f"{name}_scan_rel"]
            if gates == "beta2_equal_keys":
                limit = tol.get(f"{name}_scan_beta2_rel", limit)
            observed["scan"][f"{name}_{gates}"] = {
                part: held(f"scan {name} {gates} {part}", value, limit)
                for part, value in zip(("o",) + SCAN_GRADS, shares(got, want))}
        del args, want, got

    # -- (3) the model
    prefix = min(traffic["reference_prefix"], seq)
    # the seeded state ``build`` made, while the step has not yet taken (and
    # donated) it; made anew where this is called without a ``build`` before
    params, bias = _SEEDED.pop(seed, None) or jax.jit(
        _init_state(_model(config, **model_overrides), config),
        out_shardings=one)(key)
    if mesh.size > 1:       # replicated over the mesh: the first chip's copy
        params, bias = jax.device_put((params, bias), one)
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

    no_sets = [jnp.zeros((tokens.size, config["n_routed_experts"]),
                         bool)] * config["layers"]
    with jax.default_matmul_precision("highest"):
        want = plain(params, bias, tokens, rows, no_sets, False)
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
            # a token whose 8th and 9th score + bias lie closer than float32's
            # rounding of the router's sum: system and reference break the
            # tie differently, both rightly (kimi_linear_48b_a3b.py). Its
            # share is held as it is; everything else is then held against
            # the reference computed under the SYSTEM's choice.
            with jax.default_matmul_precision("highest"):
                ref = plain(params, bias, tokens, rows, got["chosen"], True)
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
            "solar_open2_250b against its float32 references: "
            + "; ".join(beyond) + "; observed " + json.dumps(observed))
    return {"kind": "kernel", "observed": observed}


def cost(config, traffic, chips):
    """Needed operations per step per chip (forward + backward, causal half,
    recompute not counted; the held experts at a balanced router's load) and
    what the delta-rule calls need at the held heads (the carried states an
    implementation saves for its backward are its own choice, not the
    model's need: not counted; the needed work does not depend on beta). No
    flash roofline is listed for this cell (one softmax layer of four, 8
    heads), so the flash calls' needed work is not counted apart."""
    from benchmarks import kda_cost, solar_cost

    rows, seq = traffic["global_rows"] // chips, traffic["seq"]
    kda_heads, heads, kv_heads, shared_width = _share(config)
    d = config["head_dim"]
    of_kind = kinds(config)
    return {
        "model_flops": solar_cost.share_step_flops(
            seq, rows, config["vocab_held"], config["hidden_size"], of_kind,
            kda_heads, heads, kv_heads, d, d, config["kda_chunk"],
            config["n_routed_experts"], config["num_experts_per_tok"],
            config["experts_held"], config["moe_intermediate_size"],
            shared_width),
        "kda_scan": kda_cost.kda_scan_step_cost(
            seq, rows, of_kind.count("kda"), kda_heads, d, d,
            config["kda_chunk"], forward_calls=2 if config["remat"] else 1),
    }
