"""keye_vl_2_0_30b_a3b: one expert-parallel rank's share of the first layers of
Keye-VL-2.0-30B-A3B's language model (grouped-query attention over a LEARNED
selection: an indexer scores every earlier token, each query keeps its 2,048
best, the flash kernels attend over that selection alone and the indexer
trains on its alignment loss; per-head QK-norm, rotary in three sections;
softmax top-8 of 128 experts renormalised) through ``TransformerLM`` and
``hvd.jax.DistributedOptimizer`` in the jitted ``shard_map`` step every
language-model cell runs, its plain float32 reference, its costs.

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
# ... and the embedding's rows normal with std 3: at 0.02 a row is a fifth of
# the first attention's output, randomly initialised attention averages what
# tells tokens apart away and passes what they share, and from the second
# layer on every token chooses the same experts (docs/sparse-attention.md)
EMBED_STD = 3.0


def _held(config):
    return (config["experts_first"], config["experts_held"])


def _sparse(config):
    from horovod_tpu.models import SparseDims

    sa = config["sa_config"]
    if sa["indexer_num_kv_heads"] != 1:
        raise ValueError("keye_vl_2_0_30b_a3b: the indexer shares ONE key head")
    return SparseDims(index_heads=sa["indexer_num_heads"],
                      index_dim=sa["indexer_head_dim"], topk=sa["topk"],
                      kv_chunk=sa["kv_chunk_size"], q_chunk=sa["q_chunk_size"])


def _model(config, **overrides):
    from horovod_tpu.models import RotaryScheme, TransformerLM

    rope = config["rope_scaling"]
    if (config["model_type"] != "KeyeVL2" or config["attention_bias"]
            or config["tie_word_embeddings"] or config["mlp_only_layers"]
            or config["decoder_sparse_step"] != 1 or config["use_sliding_window"]
            or config["norm_topk_prob"] is not True
            or config["hidden_act"] != "silu" or rope["rope_type"] != "default"):
        raise ValueError("keye_vl_2_0_30b_a3b: the configuration file states a "
                         "layer equation this module does not build")
    kw = dict(vocab=config["vocab_held"], dim=config["hidden_size"],
              heads=config["num_attention_heads"], layers=config["layers"],
              kv_heads=config["num_key_value_heads"],
              head_dim=config["head_dim"], qk_head_norm=True,
              rope_theta=float(config["rope_theta"]),
              rotary=RotaryScheme(theta=float(config["rope_theta"]),
                                  sections=tuple(rope["mrope_section"])),
              sparse=_sparse(config),
              moe_experts=config["num_experts"], moe_every=1,
              moe_top_k=config["num_experts_per_tok"],
              moe_hidden=config["moe_intermediate_size"],
              moe_router="softmax", moe_norm_topk=True,
              moe_held=_held(config), rms_norm_eps=config["rms_norm_eps"],
              attention=config["attention"], remat=config["remat"])
    kw.update(overrides)
    return TransformerLM(**kw)


def _optimizer(config):
    import jax
    import optax

    o = config["optimizer"]
    if o["name"] != "adamw":
        raise ValueError(f"keye_vl_2_0_30b_a3b trains with adamw, not {o}")
    # weight decay on matrices only: norm weights and the bias have one axis
    return optax.adamw(
        o["learning_rate"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
        weight_decay=o["weight_decay"],
        mask=lambda params: jax.tree_util.tree_map(lambda x: x.ndim >= 2,
                                                   params))


def _shapes(model):
    """``{"params": ...}`` of the model's own ``init``, traced and never run.
    Shapes do not depend on the sequence length (the trace's is one chunk)."""
    import jax
    import jax.numpy as jnp

    return jax.eval_shape(model.init, jax.random.PRNGKey(0),
                          jnp.zeros((1, model.sparse.kv_chunk), jnp.int32))


def _loss_parts(model, config):
    """``(params, tokens, positions=None) -> (loss, (terms, hidden,
    intermediates))``: the training loss - the chunked cross entropy on f32
    logits over the held rows, plus the indexers' alignment loss and the
    routers' load-balancing loss at the file's weights, each summed over the
    layers - its three terms, the final hidden states and what the layers
    sowed."""
    import jax.numpy as jnp

    from horovod_tpu.models import align_losses, aux_losses
    from horovod_tpu.models.transformer import chunked_lm_loss

    weights = config["loss_weights"]

    def parts(params, tokens, positions=None):
        hidden, state = model.apply(
            {"params": params}, tokens, positions, return_hidden=True,
            mutable=["intermediates"])
        inter = state["intermediates"]
        terms = {"lm": chunked_lm_loss(hidden, params["lm_head"]["kernel"],
                                       jnp.roll(tokens, -1, axis=1)),
                 "align": align_losses(inter)[0],
                 "balance": aux_losses(inter)[0]}
        loss = (terms["lm"] + weights["align"] * terms["align"]
                + weights["balance"] * terms["balance"])
        return loss, (terms, hidden, inter)

    return parts


def _init_params(model):
    """``key -> params``: every leaf with two or more axes normal with std
    0.02 (the embedding's rows with std 3), norm weights 1, the indexer key's
    LayerNorm bias 0."""
    import jax
    import jax.numpy as jnp

    def init(key):
        leaves, tree = jax.tree_util.tree_flatten_with_path(
            _shapes(model)["params"])
        keys = jax.random.split(jax.random.fold_in(key, 7), len(leaves))

        def draw(k, path, leaf):
            name = getattr(path[-1], "key", None)
            if leaf.ndim >= 2:
                std = EMBED_STD if name == "embedding" else INIT_STD
                return std * jax.random.normal(k, leaf.shape, leaf.dtype)
            bias = name == "bias"
            return (jnp.zeros if bias else jnp.ones)(leaf.shape, leaf.dtype)

        return jax.tree_util.tree_unflatten(tree, [
            draw(k, path, leaf) for k, (path, leaf) in zip(keys, leaves)])

    return init


def _tokens_fn(config, rows, seq):
    import jax
    import jax.numpy as jnp

    return lambda key: jax.random.randint(key, (rows, seq), 0,
                                          config["vocab_held"], jnp.int32)


_published = {}    # the collector of the latest build, which a newer replaces


def _publish_census(built, dense_steps):
    """Have the program's registry say, whenever it is asked, what the
    selection was in the latest steps: a collector reads the third carry of
    ``built`` (``census`` (steps, 2): selected pairs and live block steps,
    summed over the layers; -1 where no step has written yet) and hands it
    to ``metrics.record_dsa_census``. Nothing is read while nobody asks."""
    from horovod_tpu.metrics import record_dsa_census, registry

    def collect(_registry):
        ring = np.asarray(built["state"][2]["census"])
        record_dsa_census(ring[ring[:, 0] >= 0], dense_steps)

    forget_census()
    _published["collect"] = collect
    registry().register_collector(collect)


def forget_census():
    """Take the latest build's collector off the registry (and with it the
    build's state, which it holds)."""
    from horovod_tpu.metrics import registry

    if "collect" in _published:
        registry().unregister_collector(_published.pop("collect"))


def _dense_block_steps(model, config, rows, seq):
    """Block steps of the causal-dense call at the blocks the selected kernels
    run at, over the layers and rows of a step."""
    from horovod_tpu.ops.flash_attention import _plan, block_census

    block_q, block_k, _ = _plan(seq, model.block_q, model.block_k,
                                model.flash_interpret, None)
    return rows * config["layers"] * block_census(seq, block_q, block_k,
                                                  True)[0]


def build(config, traffic, mesh, seed, **model_overrides):
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.compat import shard_map
    from horovod_tpu.models import align_losses

    from benchmarks.reduce_trace import SCOPE_FWD_BWD, SCOPE_OPTIMIZER

    rows, seq = traffic["global_rows"], traffic["seq"]
    if rows % mesh.size:
        raise ValueError(f"{rows} rows do not divide over {mesh.size} chips")
    model = _model(config, **model_overrides)
    opt = hvd.jax.DistributedOptimizer(_optimizer(config))
    replicated = NamedSharding(mesh, P())
    key = jax.random.PRNGKey(seed)
    init_params = _init_params(model)
    # the selection's census of the latest steps, one row a step
    kept = traffic["trace_groups"] * traffic["fence_every"]

    def init(key):
        params = init_params(key)
        tally = {"census": -jnp.ones((kept, 2), jnp.int32),
                 "steps": jnp.zeros((), jnp.int32)}
        return params, opt.init(params), tally

    params, opt_state, tally = jax.jit(init, out_shardings=replicated)(key)
    tokens = jax.jit(_tokens_fn(config, rows, seq),
                     out_shardings=NamedSharding(mesh, P(hvd.HVD_AXIS)))(
        jax.random.fold_in(key, 1))
    parts = _loss_parts(model, config)

    def loss_fn(params, tokens):
        loss, (_, _, inter) = parts(params, tokens)
        return loss, jnp.stack(align_losses(inter)[1:])

    def train_step(params, opt_state, tally, tokens):
        with jax.named_scope(SCOPE_FWD_BWD):
            (loss, census), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, tokens)
        with jax.named_scope(SCOPE_OPTIMIZER):
            updates, opt_state = opt.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            # this rank's own selection (the first rank's, where there are more)
            tally = {"census": tally["census"].at[tally["steps"] % kept].set(
                census.astype(jnp.int32)), "steps": tally["steps"] + 1}
        return params, opt_state, tally, jax.lax.pmean(loss, hvd.HVD_AXIS)

    step = jax.jit(
        shard_map(train_step, mesh=mesh,
                  in_specs=(P(), P(), P(), P(hvd.HVD_AXIS)),
                  out_specs=(P(), P(), P(), P()), check_vma=False),
        donate_argnums=(0, 1, 2))
    built = {"step": step, "state": [params, opt_state, tally],
             "batch": (tokens,), "samples_per_step": rows * seq}
    _publish_census(built, _dense_block_steps(model, config,
                                              rows // mesh.size, seq))
    return built


# ----------------------------------------------------------------- reference

def reference_config(config):
    """The reference's own few keys, from the file's."""
    sa = config["sa_config"]
    return {"heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "head_dim": config["head_dim"], "eps": config["rms_norm_eps"],
            "theta": float(config["rope_theta"]),
            "sections": tuple(config["rope_scaling"]["mrope_section"]),
            "index_heads": sa["indexer_num_heads"],
            "index_dim": sa["indexer_head_dim"], "topk": sa["topk"],
            "top_k": config["num_experts_per_tok"], "held": _held(config),
            "align_weight": config["loss_weights"]["align"],
            "balance_weight": config["loss_weights"]["balance"]}


def to_reference(tree):
    """The system's parameter tree (or its gradients) in the reference's
    layout: leaves renamed, the fused key/value projection cut in its two
    halves, nothing transposed."""
    import jax.numpy as jnp

    layers = []
    for i in range(sum(k.startswith("block_") for k in tree)):
        block = tree[f"block_{i}"]
        wk, wv = jnp.split(block["kv_proj"]["kernel"], 2, axis=-1)
        moe = block["moe"]
        layers.append({
            "attn_norm": block["RMSNorm_0"]["scale"],
            "mlp_norm": block["RMSNorm_1"]["scale"],
            "wq": block["q_proj"]["kernel"], "wk": wk, "wv": wv,
            "q_norm": block["q_head_norm"]["scale"],
            "k_norm": block["k_head_norm"]["scale"],
            "wo": block["o_proj"]["kernel"],
            "index_q": block["index_q"]["kernel"],
            "index_k": block["index_k"]["kernel"],
            "index_k_norm_w": block["index_k_norm"]["scale"],
            "index_k_norm_b": block["index_k_norm"]["bias"],
            "index_w": block["index_w"]["kernel"],
            "router": moe["router"], "w_gate": moe["w_gate"],
            "w_up": moe["w_up"], "w_down": moe["w_down"]})
    return {"embed": tree["embed"]["embedding"], "layers": layers,
            "final_norm": tree["RMSNorm_0"]["scale"],
            "head": tree["lm_head"]["kernel"]}


def _in_layer_order(blocks):
    """``block_<i>`` names by ``i`` (block_10 after block_9)."""
    return sorted(blocks, key=lambda b: int(b.split("_")[1]))


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
    """The jitted programs of the model check, each ``(params, tokens, rows,
    positions) -> dict``: the plain reference (which also takes ``forced``,
    the selections and chosen sets to compute under in place of its own: a
    second program, compiled only in a run that needs it), the system's model
    in float32 (run it under ``highest``), and the system's model as trained.
    The system's loss terms and ``grads`` are the step's own: through
    ``chunked_lm_loss`` and the kernels' backward. ``chosen`` is a list of (N,
    E) masks, ``selection`` a list of (B, T, T) masks, a layer each."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops.sparse_attention import unpack

    from benchmarks.reference import keye_vl2 as plain_model

    cfg = reference_config(config)
    n_experts = config["num_experts"]
    chunk = config["sa_config"]["kv_chunk_size"]

    def system(model):
        parts = _loss_parts(model, config)

        def run(params, tokens, rows, positions=None):
            # ONE forward: the logits are the head's product with the hidden
            # states the loss was taken from, as ``TransformerLM`` forms them
            (loss, (terms, hidden, inter)), grads = jax.value_and_grad(
                parts, has_aux=True)(params, tokens, positions)
            logits = hidden.astype(jnp.float32) @ params["lm_head"]["kernel"]
            blocks = _in_layer_order(inter)
            t = tokens.shape[1]
            return {"loss": loss, **terms, "logits": logits,
                    "chosen": [_chosen_mask(
                        inter[b]["moe"]["moe_chosen_experts"][0], n_experts)
                        for b in blocks],
                    "selection": [unpack(inter[b]["dsa_words"][0], t, chunk)
                                  for b in blocks],
                    "grads": _sample(to_reference(grads), rows)}

        return jax.jit(run)

    @jax.jit
    def plain(params, tokens, rows, positions=None, forced=None):
        (loss, parts), grads = jax.value_and_grad(
            plain_model.loss_parts, has_aux=True)(to_reference(params), tokens,
                                                  cfg, positions, forced)
        return {"loss": loss, **{k: parts[k] for k in (
            "lm", "align", "balance", "logits", "chosen", "selection")},
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
    """``inputs(key)`` and the two jitted programs of the selected kernels' own
    check at (seq, the file's heads and indexer sizes): the kernels as the
    model calls them (the selection by ``ops.sparse_attention.select``, the
    blocks their own choice, the default scale) with the alignment loss's
    tile loop, and a per-head float32 reference of the LAST ``slice_len``
    query positions against the whole context UNDER THE SAME SELECTION. Each
    returns (out, dq, dk, dv, the slice's alignment terms, their gradient
    into qI, into w): out and dq are exact on those queries, dk and dv
    (summed over a key/value head's query heads) on the same positions as
    keys; the alignment terms and their gradients into the indexer's queries
    and weights are the slice's rows (the sum over rows, not the mean)."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import sparse_attention as dsa
    from horovod_tpu.ops.flash_attention import selected_attention

    heads, kv_heads, d = (config["num_attention_heads"],
                          config["num_key_value_heads"], config["head_dim"])
    sp = _sparse(config)
    group = heads // kv_heads
    interpret = model_overrides.get("flash_interpret", False)
    blocks = (model_overrides.get("block_q"), model_overrides.get("block_k"))
    start = seq - slice_len

    @jax.jit
    def inputs(key):
        ks = jax.random.split(key, 7)
        q = _bf16_values(ks[0], (1, seq, heads, d)).astype(jnp.bfloat16)
        k, v = (_bf16_values(kk, (1, seq, kv_heads, d)).astype(jnp.bfloat16)
                for kk in ks[1:3])
        qi = _bf16_values(ks[4], (1, seq, sp.index_heads, sp.index_dim)
                          ).astype(jnp.bfloat16)
        ki = _bf16_values(ks[5], (1, seq, sp.index_dim)).astype(jnp.bfloat16)
        w = _bf16_values(ks[6], (1, seq, sp.index_heads),
                         sp.index_heads ** -0.5 * sp.index_dim ** -0.5)
        words, lse_i = dsa.select(qi, ki, w, sp.topk, sp.kv_chunk, sp.q_chunk,
                                  interpret)
        return (q, k, v, _bf16_values(ks[3], (1, seq, heads, d)), qi, ki, w,
                words, lse_i)

    @jax.jit
    def system(q, k, v, g, qi, ki, w, words, lse_i):
        (out, lse), vjp = jax.vjp(  # positional: custom_vjp nondiff_argnums
            lambda q, k, v: selected_attention(q, k, v, words, *blocks,
                                               interpret, None, sp.kv_chunk),
            q, k, v)
        dq, dk, dv = vjp((g.astype(out.dtype), jnp.zeros_like(lse)))
        terms, (dqi, _, dw) = dsa.align_terms(
            q, k, lse, qi, ki, w, words, lse_i, None, sp.kv_chunk, interpret)
        return tuple(t[:, start:].astype(jnp.float32)
                     for t in (out, dq, dk, dv, terms, dqi, dw))

    @jax.jit
    def reference(q, k, v, g, qi, ki, w, words, lse_i):
        f32 = jnp.float32
        seen = dsa.unpack(words[0, start:], seq, sp.kv_chunk)  # (slice, T)

        def probabilities(qs, kh):      # (slice, d), (T, d)
            s = (qs @ kh.T) * d ** -0.5
            return jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)

        def heads_first(t):
            return jnp.moveaxis(t[0].astype(f32), 1, 0)

        qs, kh, vh, gs = (heads_first(q[:, start:]), heads_first(k),
                          heads_first(v), heads_first(g[:, start:]))

        def one_head(a):
            out, vjp = jax.vjp(lambda qs, kh, vh: probabilities(qs, kh) @ vh,
                               qs[a], kh[a // group], vh[a // group])
            dq, dk, dv = vjp(gs[a])
            return out, dq, dk[start:], dv[start:]

        def terms_of(qis, ws):          # (slice, Hi, di), (slice, Hi)
            z = jnp.einsum("thd,sd->ths", qis, ki[0].astype(f32))
            index = jnp.sum(ws[:, :, None] * jax.nn.relu(z), axis=1)
            log_r = jax.nn.log_softmax(jnp.where(seen, index, -jnp.inf), -1)
            kept = seen & (mean_p > 0)
            return jnp.where(kept, mean_p * (
                jnp.log(jnp.where(kept, mean_p, 1.0))
                - jnp.where(kept, log_r, 0.0)), 0.0).sum(-1)

        with jax.default_matmul_precision("highest"):
            out, dq, dk, dv = jax.lax.map(one_head, jnp.arange(heads))
            mean_p = jax.lax.fori_loop(
                0, heads, lambda a, total: total + probabilities(
                    qs[a], kh[a // group]) / heads,
                jnp.zeros((slice_len, seq), f32))
            terms, vjp = jax.vjp(terms_of, qi[0, start:].astype(f32),
                                 w[0, start:].astype(f32))
            dqi, dw = vjp(jnp.ones_like(terms))
        dk, dv = (t.reshape(kv_heads, group, *t.shape[1:]).sum(axis=1)
                  for t in (dk, dv))
        return (*(jnp.moveaxis(t, 0, 1)[None] for t in (out, dq, dk, dv)),
                terms[None], dqi[None], dw[None])

    return inputs, system, reference


def reference(config, traffic, mesh, seed, **model_overrides):
    """Two checks at the widths of the file, outside the timed window, each
    logged whole; raises ``AssertionError`` for what lies beyond the file's
    ``tolerance``.

    (1) The selected kernels alone at the cell's full row, their last
    ``flash_slice`` positions against a per-head float32 reference under the
    same selection: out, dq, dk, dv, and the alignment loss's terms with
    their gradients into the indexer's queries and weights (``flash``).
    (2) The model itself against ``benchmarks/reference/keye_vl2.py`` on the
    first ``reference_prefix`` tokens of the cell's row with the cell's seeded
    parameters, in two legs: the system's model in float32 at ``highest``,
    which proves the mathematics, and the model as trained (bf16 activations,
    the selected kernels, bf16 grouped products, recomputation). Each leg
    holds the three loss terms, the share of tokens whose experts differ from
    the reference's in some layer, the share of selected (query, key) pairs
    that are not the reference's, the logits on the tokens that agree, and 64
    sampled rows of EVERY leaf's gradient, the indexer's included (float32: a
    leaf's largest error over max|ref|; as trained: its Euclidean norm over
    the reference's). A float32 leg in which a tie moved a choice holds that
    share, and the rest against the reference computed under the system's
    choices (``held_under``)."""
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

    # -- (1) the selected kernels alone: before the model's parameters are
    # made a second time, while the memory is there
    inputs, system, plain = flash_programs(
        config, seq, min(traffic["flash_slice"], seq), **model_overrides)
    args = jax.device_put(inputs(jax.random.fold_in(key, 3)), one)
    observed["flash"] = {
        part: held(f"flash {part}", share(got, want), tol[
            "align_rel" if part.startswith("align") else "flash_rel"])
        for part, got, want in zip(
            ("out", "dq", "dk", "dv", "align_terms", "align_dqi", "align_dw"),
            system(*args), plain(*args))}
    del args

    # -- (2) the model
    prefix = min(traffic["reference_prefix"], seq)
    params = jax.jit(_init_params(_model(config, **model_overrides)),
                     out_shardings=one)(key)
    tokens = jax.jit(_tokens_fn(config, traffic["global_rows"], seq),
                     out_shardings=one)(jax.random.fold_in(key, 1))[:1, :prefix]
    rows = jnp.asarray(np.random.default_rng(seed).integers(
        0, 2 ** 31 - 1, SAMPLED_ROWS), jnp.int32)
    plain, exact, trained = check_programs(config, **model_overrides)

    @jax.jit
    def agreeing(got, want):
        """(T,) tokens whose chosen experts are the reference's in every
        layer."""
        agree = jnp.ones(got[0].shape[0], bool)
        for g, w in zip(got, want):
            agree &= jnp.all(g == w, axis=-1)
        return agree

    @jax.jit
    def selection_flips(got, want):
        """Selected pairs of the system's that are not the reference's, over
        the reference's, all layers together."""
        return (sum(jnp.sum(g & ~w) for g, w in zip(got, want))
                / sum(jnp.sum(w) for w in want))

    @jax.jit
    def logits_share(got, want, agree):
        got, want = (x.reshape(-1, x.shape[-1]).astype(jnp.float32)
                     for x in (got, want))
        keep = agree[:, None]
        return (jnp.max(jnp.where(keep, jnp.abs(got - want), 0.0))
                / jnp.max(jnp.where(keep, jnp.abs(want), 0.0)))

    with jax.default_matmul_precision("highest"):
        want = plain(params, tokens, rows)
    compare = jax.jit(_distances)
    legs = {"f32": (exact, "highest", 0, "grads_rel"),
            "bf16": (trained, None, 1, "grads_l2_rel")}
    for name, (program, precision, which, limit) in legs.items():
        with jax.default_matmul_precision(precision):
            got = program(params, tokens, rows)
        agree = agreeing(got["chosen"], want["chosen"])
        flipped = 1.0 - float(jnp.mean(agree))
        moved = float(selection_flips(got["selection"], want["selection"]))
        ref, under = want, "the reference's own choice"
        if (name == "f32" and (flipped > 0.0 or moved > 0.0)
                and flipped <= tol["f32_flipped_share"]
                and moved <= tol["f32_selection_share"]):
            # A float32 tie (a token's 8th and 9th expert, a query's 2,048th
            # and 2,049th key) that system and reference break differently,
            # both rightly: its share is held as it is; everything else is
            # then held against the reference under the SYSTEM's choices.
            with jax.default_matmul_precision("highest"):
                ref = plain(params, tokens, rows, None, {
                    "selection": got["selection"], "experts": got["chosen"]})
            under = "the system's choice"
            agree = jnp.ones_like(agree)
        distances = jax.device_get(compare(got["grads"], ref["grads"]))
        observed[name] = {
            **{term: held(f"{name} {term} loss", abs(
                float(got[term]) - float(ref[term]))
                / max(abs(float(ref[term])), 1e-30), tol[f"{name}_{term}_rel"])
               for term in ("lm", "align", "balance")},
            "flipped_share": held(
                f"{name} share of tokens whose experts differ",
                flipped, tol[f"{name}_flipped_share"]),
            "selection_share": held(
                f"{name} share of selected pairs that differ",
                moved, tol[f"{name}_selection_share"]),
            "held_under": under,
            "logits": held(f"{name} logits on the agreeing tokens",
                           logits_share(got["logits"], ref["logits"], agree),
                           tol[f"{name}_logits_rel"]),
            limit: {leaf: held(f"{name} gradient of {leaf}", pair[which], tol[
                f"{name}_index_{limit}" if ".index_" in leaf
                else f"{name}_{limit}"]) for leaf, pair in distances.items()},
            "grads_other_distance_max": float(max(
                pair[1 - which] for pair in distances.values())),
            "losses": {term: float(got[term])
                       for term in ("lm", "align", "balance")}}
        first, count = _held(config)
        observed[name]["held_share"] = [    # of a layer's pairs, on this rank
            float(jnp.sum(c[:, first:first + count]) / jnp.sum(c))
            for c in got["chosen"]]
        del got, ref
    del params, want
    if beyond:
        raise AssertionError(
            "keye_vl_2_0_30b_a3b against its float32 references: "
            + "; ".join(beyond) + "; observed " + json.dumps(observed))
    return {"kind": "kernel", "observed": observed}


def cost(config, traffic, chips):
    """Needed operations per step per chip (forward + backward over the
    SELECTED pairs, the indexer's scores over the causal pairs once, the
    alignment loss's products once; recompute not counted; the held experts at
    a balanced router's load), what the selected kernel calls of a step need,
    and the pairs a step selects of the causal ones."""
    from benchmarks import dsa_cost

    rows, seq = traffic["global_rows"] // chips, traffic["seq"]
    sa = config["sa_config"]
    heads, kv_heads, d = (config["num_attention_heads"],
                          config["num_key_value_heads"], config["head_dim"])
    layers = config["layers"]
    return {
        "model_flops": dsa_cost.share_step_flops(
            seq, rows, config["vocab_held"], config["hidden_size"], layers,
            heads, kv_heads, d, sa["indexer_num_heads"],
            sa["indexer_head_dim"], sa["topk"],
            config["moe_intermediate_size"], config["num_experts"],
            config["num_experts_per_tok"], config["experts_held"]),
        "dsa_flash": dsa_cost.flash_calls_step_cost(
            seq, rows, heads, kv_heads, d, layers, sa["topk"],
            forward_calls=2 if config["remat"] else 1),
        "dsa_pairs": {
            "selected": rows * layers * dsa_cost.selected_pairs(seq, sa["topk"]),
            "causal": rows * layers * dsa_cost.causal_pairs(seq)},
    }
