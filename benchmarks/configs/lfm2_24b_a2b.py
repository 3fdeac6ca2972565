"""lfm2_24b_a2b: expert rank 0's share of LFM2-24B-A2B's layers 0 and 2-7 (a
gated short-convolution mixer in five of the seven, rotary grouped-query
attention at heads of 64 with a norm a head in the other two; a dense SwiGLU
in the leading layer and 8 of 64 sigmoid-routed top-4 experts under a
selection bias in the six others; the head tied to the embedding) through
``TransformerLM`` and ``hvd.jax.DistributedOptimizer`` in the jitted
``shard_map`` step every language-model cell runs (``kanana_2_30b_a3b.py``
builds the same one, the router's bias a third carry), its plain float32
reference, its costs.

The same three functions as every configuration module:
``build(config, traffic, mesh, seed, **model_overrides)``,
``reference(config, traffic, mesh, seed, **model_overrides)`` and
``cost(config, traffic, chips)``. The configuration file carries the Hugging
Face keys as published; ``layers`` / ``layers_run``, ``dense_layers_held``,
``experts_held`` (from ``experts_first``) and ``vocab_held`` are what this
chip runs of them: an expert rank's experts and rows of the vocabulary.
"""

from __future__ import annotations

import numpy as np

SAMPLED_ROWS = 64   # rows of a matrix's gradient the check reads
# seed -> the (params, bias) the latest ``build`` of that seed made, until its
# check takes them: 648 M float32 parameters are 2.6 GB, and beside 10.4 GB of
# training state the check's programs leave no room for a second copy of them
# (``solar_open2_250b.py``, PERF.md §6, PR 53)
_SEEDED = {}
INIT_STD = 0.02     # the file's ``assumed``: normal, std 0.02; norm weights 1
TAPS = "conv_kernel"    # the mixer's leaf that is no matrix to AdamW


def _held(config):
    return (config["experts_first"], config["experts_held"])


def kinds(config):
    """The kinds of the layers this chip runs, ``layers_run`` out of the
    published ``layer_types``: ``"conv"`` or ``"full_attention"``."""
    run = config["layers_run"]
    dense = config["dense_layers_held"]
    if (len(run) != config["layers"] or sorted(set(run)) != run
            or len(config["layer_types"]) != config["num_hidden_layers"]
            or any(i >= config["num_dense_layers"] for i in run[:dense])
            or any(i < config["num_dense_layers"] for i in run[dense:])):
        raise ValueError("lfm2_24b_a2b: layers_run is not dense_layers_held "
                         "of the model's leading dense layers and then "
                         "layers of its own that carry experts")
    return tuple(config["layer_types"][i] for i in run)


def _model(config, **overrides):
    from horovod_tpu.models import ShortConvDims, TransformerLM

    rope = config["rope_parameters"]
    if (config["model_type"] != "lfm2_moe" or config["conv_bias"]
            or not config["use_expert_bias"] or not config["norm_topk_prob"]
            or not config["tie_word_embeddings"]
            or rope["rope_type"] != "default"
            or config["hidden_size"] % config["num_attention_heads"]
            or config["num_experts"]
            != config["expert_parallel"] * config["experts_held"]
            or config["vocab_size"]
            != config["expert_parallel"] * config["vocab_held"]):
        raise ValueError("lfm2_24b_a2b: the configuration file states a "
                         "layer equation or a share this module does not "
                         "build")
    kw = dict(vocab=config["vocab_held"], dim=config["hidden_size"],
              heads=config["num_attention_heads"],
              kv_heads=config["num_key_value_heads"],
              layers=config["layers"], layer_types=kinds(config),
              conv=ShortConvDims(taps=config["conv_L_cache"]),
              qk_head_norm=True, rope_theta=float(rope["rope_theta"]),
              mlp_hidden=config["intermediate_size"],
              first_k_dense=config["dense_layers_held"],
              moe_experts=config["num_experts"], moe_every=1,
              moe_top_k=config["num_experts_per_tok"],
              moe_hidden=config["moe_intermediate_size"],
              moe_router="sigmoid",
              moe_route_scale=config["routed_scaling_factor"],
              moe_route_eps=config["router_norm_eps"],
              moe_held=_held(config), tie_embeddings=True,
              rms_norm_eps=config["norm_eps"],
              attention=config["attention"], remat=config["remat"])
    kw.update(overrides)
    return TransformerLM(**kw)


def _leaf_name(path):
    return getattr(path[-1], "key", None)


def _is_matrix(path, leaf):
    """Leaves AdamW decays: two or more axes and no convolution's taps."""
    return leaf.ndim >= 2 and _leaf_name(path) != TAPS


def _optimizer(config):
    import jax
    import optax

    o = config["optimizer"]
    if o["name"] != "adamw":
        raise ValueError(f"lfm2_24b_a2b trains with adamw, not {o}")
    # weight decay on matrices only: not on norm weights or the convolutions'
    # taps; the router's bias is no parameter at all
    return optax.adamw(
        o["learning_rate"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
        weight_decay=o["weight_decay"],
        mask=lambda params: jax.tree_util.tree_map_with_path(_is_matrix,
                                                             params))


def _loss_parts(model):
    """``(params, bias, tokens) -> (loss, (hidden, intermediates))``: the
    chunked cross entropy on f32 logits over the held rows of the vocabulary,
    the transposed embedding as the head (one leaf, both gradients), the
    final hidden states and what the layers sowed."""
    import jax.numpy as jnp

    from horovod_tpu.models import BIAS_COLLECTION
    from horovod_tpu.models.transformer import chunked_lm_loss

    def parts(params, bias, tokens):
        hidden, state = model.apply(
            {"params": params, BIAS_COLLECTION: bias}, tokens,
            return_hidden=True, mutable=["intermediates"])
        loss = chunked_lm_loss(hidden, params["embed"]["embedding"].T,
                               jnp.roll(tokens, -1, axis=1))
        # (nothing is sown where no layer of a test's model has experts)
        return loss, (hidden, state.get("intermediates", {}))

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
    """``key -> (params, bias)``: matrices normal with std 0.02 (the tied
    table included), the convolutions' taps uniform in +-K^-0.5, norm weights
    1, the router's bias 0. The shapes come from the model's own ``init``,
    traced and never run."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import BIAS_COLLECTION

    def draw(key, path, leaf):
        if _leaf_name(path) == TAPS:
            edge = leaf.shape[0] ** -0.5
            return jax.random.uniform(key, leaf.shape, leaf.dtype, -edge, edge)
        if leaf.ndim >= 2:
            return INIT_STD * jax.random.normal(key, leaf.shape, leaf.dtype)
        return jnp.ones(leaf.shape, leaf.dtype)

    def init(key):
        # Parameter shapes do not depend on the sequence length: trace short.
        shapes = jax.eval_shape(model.init, key, jnp.zeros((1, 128), jnp.int32))
        leaves, tree = jax.tree_util.tree_flatten_with_path(shapes["params"])
        keys = jax.random.split(jax.random.fold_in(key, 7), len(leaves))
        params = jax.tree_util.tree_unflatten(tree, [
            draw(k, path, leaf) for k, (path, leaf) in zip(keys, leaves)])
        bias = jax.tree_util.tree_map(
            lambda b: jnp.zeros(b.shape, b.dtype),
            shapes.get(BIAS_COLLECTION, {}))     # none without an expert layer
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
    return {"kinds": tuple({"conv": "conv", "full_attention": "attention"}[k]
                           for k in kinds(config)),
            "heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "head_dim": config["hidden_size"] // config["num_attention_heads"],
            "eps": config["norm_eps"],
            "theta": float(config["rope_parameters"]["rope_theta"]),
            "top_k": config["num_experts_per_tok"],
            "route_scale": config["routed_scaling_factor"],
            "route_eps": config["router_norm_eps"],
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
            layer.update(w_in=m["in_proj"]["kernel"], taps=m[TAPS],
                         w_out=m["out_proj"]["kernel"])
        else:
            layer.update(wq=block["q_proj"]["kernel"],
                         wkv=block["kv_proj"]["kernel"],
                         q_norm=block["q_head_norm"]["scale"],
                         k_norm=block["k_head_norm"]["scale"],
                         wo=block["o_proj"]["kernel"])
        if "moe" in block:
            moe = block["moe"]
            layer.update(router=moe["router"], w_gate=moe["w_gate"],
                         w_up=moe["w_up"], w_down=moe["w_down"])
        else:
            layer.update(w_gate=block["mlp_gate"]["kernel"],
                         w_up=block["mlp_up"]["kernel"],
                         w_down=block["mlp_down"]["kernel"])
        layers.append(layer)
    return {"embed": tree["embed"]["embedding"], "layers": layers,
            "final_norm": tree["RMSNorm_0"]["scale"]}


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
    chosen set an expert layer, and ``use``: computed under those sets where
    ``use`` is true and under its own choice otherwise, ONE program for both,
    so that a run whose float32 leg breaks a tie compiles no more than one
    that does not), the system's model in float32 (run it under ``highest``)
    and the system's model as trained. The system's ``loss`` and ``grads``
    are the step's own: through ``chunked_lm_loss`` and its backward, the
    tied table receiving the lookup's and the head's gradients. ``chosen`` is
    a list of (N, E) masks, ``bias_after`` the biases one application of the
    rule later."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import expert_counts

    from benchmarks.reference import lfm2_moe as plain_model

    cfg = reference_config(config)
    rate = config["router_bias"]["update_rate"]
    n_experts = config["num_experts"]

    def system(model):
        parts = _loss_parts(model)

        def run(params, bias, tokens, rows):
            # ONE forward: the logits are the table's product with the hidden
            # states the loss was taken from, as ``TransformerLM`` forms them
            (loss, (hidden, inter)), grads = jax.value_and_grad(
                parts, has_aux=True)(params, bias, tokens)
            logits = (hidden.astype(jnp.float32)
                      @ params["embed"]["embedding"].T)
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
    """Normal values that bf16 holds exactly, as float32. The barrier keeps
    the rounding: XLA on the TPU is allowed excess precision and drops a
    float32 -> bf16 -> float32 round trip it can see whole."""
    import jax
    import jax.numpy as jnp

    return jax.lax.optimization_barrier(
        (scale * jax.random.normal(key, shape, jnp.float32)).astype(
            jnp.bfloat16)).astype(jnp.float32)


CONV_PARTS = ("y", "d_bcx", "d_taps")


def conv_programs(config, rows, seq, interpret=False):
    """``inputs(key)`` and the jitted programs of the gated convolution's own
    check at (rows, seq, 3 x hidden): the pass as the mixer runs it
    (``models.short_conv.gated_conv_pass``: the kernels where the shape
    tiles, the ``jax.numpy`` form otherwise) in bf16 and in float32,
    and the plain reference's float32 definition (K shifted products). Each
    returns (y, the gradient of ``[B | C | X]``, the gradient of the taps)
    for a drawn cotangent, over the WHOLE rows: the zero history at each
    row's start and every row tile's border are in what is compared.
    ``[B | C | X]`` and the cotangent are bf16-representable normal values
    (the projection's output as trained), the taps uniform in +-K^-0.5 as
    initialised."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models.short_conv import gated_conv_pass

    from benchmarks.reference import lfm2_moe as plain_model

    d, k = config["hidden_size"], config["conv_L_cache"]

    @jax.jit
    def inputs(key):
        ks = jax.random.split(key, 3)
        edge = k ** -0.5
        return (_bf16_values(ks[0], (rows, seq, 3 * d)),
                jax.random.uniform(ks[1], (k, d), jnp.float32, -edge, edge),
                _bf16_values(ks[2], (rows, seq, d)))

    def the_pass(bcx, taps):
        return gated_conv_pass(bcx, taps, interpret)[0]

    def in_dtype(dtype):
        @jax.jit
        def run(bcx, taps, dy):
            y, vjp = jax.vjp(the_pass, bcx.astype(dtype), taps)
            return tuple(t.astype(jnp.float32)
                         for t in (y, *vjp(dy.astype(dtype))))
        return run

    @jax.jit
    def definition(bcx, taps, dy):
        with jax.default_matmul_precision("highest"):
            y, vjp = jax.vjp(plain_model.gated_conv, bcx, taps)
            return (y, *vjp(dy))

    return inputs, {"bf16": in_dtype(jnp.bfloat16),
                    "f32": in_dtype(jnp.float32)}, definition


def flash_programs(config, seq, slice_len, **model_overrides):
    """``inputs(key)`` and the two jitted programs of the flash kernels' own
    check at (seq, 32 query heads | 8 key/value heads, 64): the kernels as
    the model calls them (grouped-query: the shared head through the index
    map; default blocks, the default scale 64 ** -0.5) on operands as the
    layer hands them over - q and k drawn, normed a head (weight 1) and
    TURNED by their positions at the model's base (the plain reference's
    ``rms`` and ``rotate``), then rounded to bf16 - and a per-head float32
    reference of the LAST ``slice_len`` query positions against the whole
    context (under a causal mask that is enough for exact out and dq on
    those queries, and dk and dv on the same positions as keys, summed over
    the query heads a key/value head serves). Each returns (out, dq, dk, dv)
    on the slice."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops.flash_attention import (DEFAULT_BLOCK_K,
                                                 DEFAULT_BLOCK_Q,
                                                 flash_attention)

    from benchmarks.reference import lfm2_moe as plain_model

    cfg = reference_config(config)
    heads, kv_heads, d = cfg["heads"], cfg["kv_heads"], cfg["head_dim"]
    group = heads // kv_heads
    interpret = model_overrides.get("flash_interpret", False)
    start = seq - slice_len

    @jax.jit
    def inputs(key):
        ks = jax.random.split(key, 4)

        def turned(k, n):
            x = jax.random.normal(k, (1, seq, n, d), jnp.float32)
            return plain_model.rotate(
                plain_model.rms(x, jnp.ones((d,)), cfg["eps"]),
                cfg["theta"]).astype(jnp.bfloat16)

        return (turned(ks[0], heads), turned(ks[1], kv_heads),
                _bf16_values(ks[2], (1, seq, kv_heads, d)).astype(jnp.bfloat16),
                _bf16_values(ks[3], (1, seq, heads, d)))

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

        def attend(qs, kh, vh):     # (slice, 64), (T, 64), (T, 64)
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

    (1) The flash kernels alone at the cell's full row, 32 query heads over 8
    key/value heads of 64, on normed and turned operands, their last
    ``flash_slice`` positions against a per-head float32 reference: out, dq,
    dk, dv.
    (2) The gated convolution's pass alone at ``conv_slice`` positions of
    every row of the step (the whole rows) by 3 x 2,048 channels, in bf16
    and in float32, against the float32 definition: y and the gradients of
    ``[B | C | X]`` and of the taps.
    (3) The model itself against ``benchmarks/reference/lfm2_moe.py`` on the
    first ``reference_prefix`` tokens of the cell's first row with the cell's
    seeded parameters and bias, in two legs: the system's model in float32 at
    ``highest``, which proves the mathematics, and the model as trained (bf16
    activations, flash kernels, the convolution's kernels, bf16 grouped
    products, recomputation). Each leg holds the loss, the share of tokens
    whose experts differ from the reference's in some layer, the logits on
    the tokens that agree, 64 sampled rows of EVERY leaf's gradient through
    ``chunked_lm_loss``'s own backward (float32: a leaf's largest error over
    max|ref|; as trained: its Euclidean norm over the reference's), and the
    share of the experts whose bias differs after one application of the
    rule. A float32 leg in which a token or two broke a tie the other way
    holds that share, and the rest against the reference computed under the
    system's choice (``held_under``). Also logged: the share of each expert
    layer's pairs that falls on the held experts (``held_share``; 8 / 64 when
    the router is balanced)."""
    import json

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    tol = config["tolerance"]
    one = SingleDeviceSharding(mesh.devices.flat[0])
    key = jax.random.PRNGKey(seed)
    seq = traffic["seq"]
    interpret = model_overrides.get("flash_interpret", False)
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

    # -- (1) the flash kernels alone, (2) the convolution's pass alone
    inputs, system, plain = flash_programs(
        config, seq, min(traffic["flash_slice"], seq), **model_overrides)
    args = jax.device_put(inputs(jax.random.fold_in(key, 3)), one)
    observed["flash"] = {
        name: held(f"flash {name}", value, tol["flash_rel"])
        for name, value in zip(("out", "dq", "dk", "dv"),
                               shares(system(*args), plain(*args)))}
    del args

    inputs, passes, definition = conv_programs(
        config, traffic["global_rows"] // mesh.size,
        min(traffic["conv_slice"], seq), interpret)
    args = jax.device_put(inputs(jax.random.fold_in(key, 4)), one)
    want = definition(*args)
    observed["conv"] = {}
    for name, program in passes.items():
        observed["conv"][name] = {
            part: held(f"conv {name} {part}", value, tol[f"{name}_conv_rel"])
            for part, value in zip(CONV_PARTS, shares(program(*args), want))}
    del args, want

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

    no_sets = [jnp.zeros((tokens.size, config["num_experts"]), bool)] * (
        config["layers"] - config["dense_layers_held"])
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
            # a token whose 4th and 5th score + bias lie closer than float32's
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
            "loss_value": float(got["loss"]),
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
        observed[name]["held_share"] = [   # of an expert layer's pairs, here
            float(np.sum(np.asarray(c)[first:first + count]) / np.sum(np.asarray(c)))
            for c in counts]
    observed["bias_after_abs_max"] = float(max(
        np.max(np.abs(np.asarray(b))) for b in want["bias_after"]))
    del params, want
    if beyond:
        raise AssertionError(
            "lfm2_24b_a2b against its float32 references: "
            + "; ".join(beyond) + "; observed " + json.dumps(observed))
    return {"kind": "kernel", "observed": observed}


def _cost_args(config, traffic, chips):
    """``lfm2_cost.share_forward_parts``' arguments, from the file's keys."""
    cfg = reference_config(config)
    return (traffic["seq"], traffic["global_rows"] // chips,
            config["vocab_held"], config["hidden_size"], cfg["kinds"],
            config["dense_layers_held"], cfg["heads"], cfg["kv_heads"],
            cfg["head_dim"], config["intermediate_size"],
            config["num_experts"], config["num_experts_per_tok"],
            config["experts_held"], config["moe_intermediate_size"])


def cost(config, traffic, chips):
    """Needed operations per step per chip (forward + backward, causal half,
    recompute not counted; the held experts at a balanced router's load) and
    the bytes the gated convolution's passes need (the forward as often as
    the step runs it). No flash roofline is listed for this cell, so the
    flash calls' needed work is not counted apart."""
    from benchmarks import lfm2_cost

    return {
        "model_flops": lfm2_cost.share_step_flops(
            *_cost_args(config, traffic, chips)),
        "sconv_conv": lfm2_cost.sconv_conv_step_cost(
            traffic["seq"], traffic["global_rows"] // chips,
            kinds(config).count("conv"), config["hidden_size"],
            forward_calls=2 if config["remat"] else 1),
    }
