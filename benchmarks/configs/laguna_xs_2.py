"""laguna_xs_2: one expert-parallel rank's share of Laguna-XS.2's first layers
(full and window-512 attention mixed layer by layer at 48 and 64 query heads
over 8, two rotary schemes, a per-head output gate; a dense layer, then
sigmoid-routed experts beside a shared one) through ``TransformerLM`` and
``hvd.jax.DistributedOptimizer`` in the jitted ``shard_map`` step every
language-model cell runs, its plain float32 reference, its costs.

The same three functions as every configuration module:
``build(config, traffic, mesh, seed, **model_overrides)``,
``reference(config, traffic, mesh, seed, **model_overrides)`` and
``cost(config, traffic, chips)``. The configuration file carries the Hugging
Face keys as published; ``layers``, ``experts_held`` (from ``experts_first``)
and ``vocab_held`` are what this chip runs of them. The router has no bias
rule (the file's ``assumed``): the model's ``router_bias`` is zero in every
program here and no step moves it.
"""

from __future__ import annotations

import numpy as np

SAMPLED_ROWS = 64   # rows of a matrix's gradient the check reads
INIT_STD = 0.02     # the file's ``assumed``: normal, std 0.02; norm weights 1
KINDS = ("full_attention", "sliding_attention")


def _held(config):
    return (config["experts_first"], config["experts_held"])


def _layers(config):
    """(kinds, query heads, dense layers) of the layers this chip runs: the
    model's own first ``layers``."""
    n = config["layers"]
    kinds = tuple(config["layer_types"][:n])
    mlps = config["mlp_layer_types"][:n]
    dense = mlps.count("dense")
    if (set(kinds) - set(KINDS) or mlps != ["dense"] * dense
            + ["sparse"] * (n - dense) or not 0 < dense < n):
        raise ValueError(f"laguna_xs_2: layers {kinds} / {mlps} are not "
                         f"dense layers followed by expert layers")
    return kinds, tuple(config["num_attention_heads_per_layer"][:n]), dense


def _rotary(config, kind):
    """The kind's ``rope_parameters`` as the program's ``RotaryScheme``."""
    from horovod_tpu.models import RotaryScheme

    rope = config["rope_parameters"][kind]
    dims = int(config["head_dim"] * rope["partial_rotary_factor"])
    if rope["rope_type"] == "default":
        return RotaryScheme(theta=float(rope["rope_theta"]), dims=dims)
    if rope["rope_type"] != "yarn":
        raise ValueError(f"laguna_xs_2: rope_type {rope['rope_type']!r}")
    return RotaryScheme(
        theta=float(rope["rope_theta"]), dims=dims,
        factor=float(rope["factor"]),
        original_max=rope["original_max_position_embeddings"],
        beta_fast=float(rope["beta_fast"]), beta_slow=float(rope["beta_slow"]),
        attention_factor=rope["attention_factor"])


def _model(config, **overrides):
    from horovod_tpu.models import TransformerLM

    if (config["model_type"] != "laguna" or config["attention_bias"]
            or config["tie_word_embeddings"] or config["gating"] is not True
            or config["moe_apply_router_weight_on_input"]):
        raise ValueError("laguna_xs_2: the configuration file states a layer "
                         "equation this module does not build")
    kinds, heads, dense = _layers(config)
    kw = dict(vocab=config["vocab_held"], dim=config["hidden_size"],
              heads=config["num_attention_heads"], layers=config["layers"],
              kv_heads=config["num_key_value_heads"],
              head_dim=config["head_dim"], heads_per_layer=heads,
              layer_types=kinds, sliding_window=config["sliding_window"],
              full_rotary=_rotary(config, "full_attention"),
              sliding_rotary=_rotary(config, "sliding_attention"),
              attn_gate=True, first_k_dense=dense,
              mlp_hidden=config["intermediate_size"],
              moe_experts=config["num_experts"], moe_every=1,
              moe_top_k=config["num_experts_per_tok"],
              moe_hidden=config["moe_intermediate_size"],
              moe_router="sigmoid",
              moe_route_scale=config["moe_routed_scaling_factor"],
              moe_shared_hidden=config["shared_expert_intermediate_size"],
              moe_held=_held(config), rms_norm_eps=config["rms_norm_eps"],
              attention=config["attention"], remat=config["remat"])
    kw.update(overrides)
    return TransformerLM(**kw)


def _optimizer(config):
    import jax
    import optax

    o = config["optimizer"]
    if o["name"] != "adamw":
        raise ValueError(f"laguna_xs_2 trains with adamw, not {o}")
    # weight decay on matrices only: every norm weight has one axis
    return optax.adamw(
        o["learning_rate"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
        weight_decay=o["weight_decay"],
        mask=lambda params: jax.tree_util.tree_map(lambda x: x.ndim >= 2,
                                                   params))


def _shapes(model):
    """``{"params": ..., "moe_bias": ...}`` of the model's own ``init``,
    traced and never run: its forward pass would be one more program to
    compile in every set-up. Shapes do not depend on the sequence length."""
    import jax
    import jax.numpy as jnp

    return jax.eval_shape(model.init, jax.random.PRNGKey(0),
                          jnp.zeros((1, 128), jnp.int32))


def _loss_parts(model):
    """``(params, tokens) -> (loss, (hidden, intermediates))``: the chunked
    cross entropy on f32 logits over the held rows of the vocabulary, the
    final hidden states and what the layers sowed. The routers' bias is the
    zeros it starts as: a constant of the program, no carry of the step."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import BIAS_COLLECTION
    from horovod_tpu.models.transformer import chunked_lm_loss

    bias_shapes = _shapes(model)[BIAS_COLLECTION]

    def parts(params, tokens):
        bias = jax.tree_util.tree_map(lambda b: jnp.zeros(b.shape, b.dtype),
                                      bias_shapes)
        hidden, state = model.apply(
            {"params": params, BIAS_COLLECTION: bias}, tokens,
            return_hidden=True, mutable=["intermediates"])
        loss = chunked_lm_loss(hidden, params["lm_head"]["kernel"],
                               jnp.roll(tokens, -1, axis=1))
        return loss, (hidden, state["intermediates"])

    return parts


def _live_rows(intermediates):
    """The rows each expert layer's passes visited (``moe_live_rows``, sown
    by the layer), summed over the layers: what a training loop would log."""
    return sum(leaves["moe"]["moe_live_rows"][0]
               for leaves in intermediates.values() if "moe" in leaves)


def _init_params(model):
    """``key -> params``: every leaf with two or more axes normal with std
    0.02, norm weights 1."""
    import jax
    import jax.numpy as jnp

    def init(key):
        leaves, tree = jax.tree_util.tree_flatten(_shapes(model)["params"])
        keys = jax.random.split(jax.random.fold_in(key, 7), len(leaves))
        return jax.tree_util.tree_unflatten(tree, [
            INIT_STD * jax.random.normal(k, leaf.shape, leaf.dtype)
            if leaf.ndim >= 2 else jnp.ones(leaf.shape, leaf.dtype)
            for k, leaf in zip(keys, leaves)])

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
    parts = _loss_parts(model)

    def loss_fn(params, tokens):
        loss, _ = parts(params, tokens)
        return loss

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
    return {"step": step, "state": [params, opt_state],
            "batch": (tokens,), "samples_per_step": rows * seq}


# ----------------------------------------------------------------- reference

def reference_config(config):
    """The reference's own few keys, from the file's."""
    kinds, heads, _ = _layers(config)
    ropes = config["rope_parameters"]
    full, sliding = ropes["full_attention"], ropes["sliding_attention"]
    return {"kinds": kinds, "heads": heads,
            "kv_heads": config["num_key_value_heads"],
            "head_dim": config["head_dim"], "eps": config["rms_norm_eps"],
            "window": config["sliding_window"],
            "rope_full": {
                "theta": float(full["rope_theta"]),
                "dims": int(config["head_dim"] * full["partial_rotary_factor"]),
                "factor": float(full["factor"]),
                "original_max": full["original_max_position_embeddings"],
                "beta_fast": float(full["beta_fast"]),
                "beta_slow": float(full["beta_slow"]),
                "attention_factor": full["attention_factor"]},
            "rope_sliding": {"theta": float(sliding["rope_theta"])},
            "top_k": config["num_experts_per_tok"],
            "route_scale": config["moe_routed_scaling_factor"],
            "held": _held(config)}


def to_reference(tree):
    """The system's parameter tree (or its gradients) in the reference's
    layout: leaves renamed, the fused key/value projection cut in its two
    halves, nothing transposed."""
    import jax.numpy as jnp

    layers = []
    for i in range(sum(k.startswith("block_") for k in tree)):
        block = tree[f"block_{i}"]
        wk, wv = jnp.split(block["kv_proj"]["kernel"], 2, axis=-1)
        layer = {"attn_norm": block["RMSNorm_0"]["scale"],
                 "mlp_norm": block["RMSNorm_1"]["scale"],
                 "wq": block["q_proj"]["kernel"], "wk": wk, "wv": wv,
                 "wg": block["gate_proj"]["kernel"],
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
    """The jitted programs of the model check, each ``(params, tokens, rows)
    -> dict``: the plain reference (which also takes ``forced``, the chosen
    sets to compute under in place of its own: a second program, compiled
    only in a run that needs it), the system's model in float32 (run it under
    ``highest``), and the system's model as trained. The system's ``loss``
    and ``grads`` are the step's own: through ``chunked_lm_loss`` and its
    backward. ``chosen`` is a list of (N, E) masks, ``counts`` the pairs each
    layer routed to each of ALL the experts, ``live_rows`` what the layers
    sowed."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import expert_counts

    from benchmarks.reference import laguna as plain_model

    cfg = reference_config(config)
    n_experts = config["num_experts"]

    def system(model):
        parts = _loss_parts(model)

        def run(params, tokens, rows):
            # ONE forward: the logits are the head's product with the hidden
            # states the loss was taken from, as ``TransformerLM`` forms them
            (loss, (hidden, inter)), grads = jax.value_and_grad(
                parts, has_aux=True)(params, tokens)
            logits = hidden.astype(jnp.float32) @ params["lm_head"]["kernel"]
            counts = expert_counts(inter)
            blocks = _in_layer_order(counts)
            return {"loss": loss, "logits": logits,
                    "chosen": [_chosen_mask(
                        inter[b]["moe"]["moe_chosen_experts"][0], n_experts)
                        for b in blocks],
                    "counts": [counts[b] for b in blocks],
                    "live_rows": _live_rows(inter),
                    "grads": _sample(to_reference(grads), rows)}

        return jax.jit(run)

    @jax.jit
    def plain(params, tokens, rows, forced=None):
        (loss, parts), grads = jax.value_and_grad(
            plain_model.loss_parts, has_aux=True)(to_reference(params), tokens,
                                                  cfg, forced)
        return {"loss": loss, "logits": parts["logits"],
                "chosen": parts["chosen"], "counts": parts["counts"],
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


def flash_programs(config, seq, slice_len, heads, window, **model_overrides):
    """``inputs(key)`` and the two jitted programs of the flash kernels' own
    check at (seq, ``heads`` over the file's key/value heads, head_dim) under
    ``window`` (None: full): the kernels as the model calls them (the blocks
    their own choice, the default scale), and a per-head float32 reference of
    the LAST ``slice_len`` query positions against the whole context (under a
    causal mask that is enough for exact out and dq on those queries, and dk
    and dv, summed over a key/value head's query heads, on the same positions
    as keys). Each returns (out, dq, dk, dv) on the slice."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops.flash_attention import flash_attention

    kv_heads, d = config["num_key_value_heads"], config["head_dim"]
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
            lambda q, k, v: flash_attention(q, k, v, True, None, None,
                                            interpret, None, window),
            q, k, v)
        dq, dk, dv = vjp(g.astype(out.dtype))
        return tuple(t[:, start:].astype(jnp.float32) for t in (out, dq, dk, dv))

    @jax.jit
    def reference(q, k, v, g):
        f32 = jnp.float32
        q_pos = (jnp.arange(slice_len) + start)[:, None]
        seen = q_pos >= jnp.arange(seq)
        if window is not None:
            seen &= jnp.arange(seq) > q_pos - window

        def attend(qs, kh, vh):     # (slice, d), (T, d), (T, d)
            s = (qs @ kh.T) * d ** -0.5
            return jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1) @ vh

        def heads_first(t):
            return jnp.moveaxis(t[0].astype(f32), 1, 0)

        qs, kh, vh, gs = (heads_first(q[:, start:]), heads_first(k),
                          heads_first(v), heads_first(g[:, start:]))

        def one_head(a):
            out, vjp = jax.vjp(attend, qs[a], kh[a // group], vh[a // group])
            dq, dk, dv = vjp(gs[a])
            return out, dq, dk[start:], dv[start:]

        with jax.default_matmul_precision("highest"):
            out, dq, dk, dv = jax.lax.map(one_head, jnp.arange(heads))
        dk, dv = (t.reshape(kv_heads, group, *t.shape[1:]).sum(axis=1)
                  for t in (dk, dv))
        return tuple(jnp.moveaxis(t, 0, 1)[None] for t in (out, dq, dk, dv))

    return inputs, system, reference


def reference(config, traffic, mesh, seed, **model_overrides):
    """Two checks at the widths of the file, outside the timed window, each
    logged whole; raises ``AssertionError`` for what lies beyond the file's
    ``tolerance``.

    (1) The flash kernels alone at the cell's full row, their last
    ``flash_slice`` positions against a per-head float32 reference (out, dq,
    dk, dv): under the window at the sliding layers' heads (``flash_window``),
    and causal-dense at the full layers' (``flash_full``).
    (2) The model itself against ``benchmarks/reference/laguna.py`` on the
    first ``reference_prefix`` tokens of the cell's row with the cell's seeded
    parameters (a second training state cannot be held beside the first, so no
    plain STEP is played), in two legs: the system's model in float32 at
    ``highest``, which proves the mathematics, and the model as trained (bf16
    activations, flash kernels, bf16 grouped products, recomputation). Each
    leg holds the loss, the share of tokens whose experts differ from the
    reference's in some layer, the logits on the tokens that agree, and 64
    sampled rows of EVERY leaf's gradient through ``chunked_lm_loss``'s own
    backward (float32: a leaf's largest error over max|ref|; as trained: its
    Euclidean norm over the reference's). A float32 leg in which a token or
    two broke a tie the other way holds that share, and the rest against the
    reference computed under the system's choice (``held_under``). Also
    logged: the share of each layer's pairs that falls on the held experts
    (``held_share``; 1 / 8 when the router is balanced) and the rows the
    layers' passes visited (``live_rows``)."""
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
    kinds, heads, _ = _layers(config)
    calls = {"flash_window": ("sliding_attention", config["sliding_window"]),
             "flash_full": ("full_attention", None)}
    for fold, (name, (kind, window)) in enumerate(calls.items()):
        if kind not in kinds:
            continue
        inputs, system, plain = flash_programs(
            config, seq, min(traffic["flash_slice"], seq),
            heads[kinds.index(kind)], window, **model_overrides)
        args = jax.device_put(inputs(jax.random.fold_in(key, 3 + fold)), one)
        observed[name] = {
            part: held(f"{name} {part}", share(got, want), tol["flash_rel"])
            for part, got, want in zip(("out", "dq", "dk", "dv"),
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
        want = plain(params, tokens, rows)
    compare = jax.jit(_distances)
    legs = {"f32": (exact, "highest", 0, "grads_rel"),
            "bf16": (trained, None, 1, "grads_l2_rel")}
    for name, (program, precision, which, limit) in legs.items():
        with jax.default_matmul_precision(precision):
            got = program(params, tokens, rows)
        agree = agreeing(got["chosen"], want["chosen"])
        flipped = 1.0 - float(jnp.mean(agree))
        ref, under = want, "the reference's own choice"
        if name == "f32" and 0.0 < flipped <= tol["f32_flipped_share"]:
            # In float32 a seed now and then has ONE token of 2048 whose 8th
            # and 9th score lie closer than the rounding of the router's sum:
            # system and reference break the tie differently, both rightly
            # (kanana_2_30b_a3b.py has the readings). Its share is held as it
            # is; everything else is then held against the reference computed
            # under the SYSTEM's choice.
            with jax.default_matmul_precision("highest"):
                ref = plain(params, tokens, rows, got["chosen"])
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
            limit: {leaf: held(f"{name} gradient of {leaf}", pair[which],
                               tol[f"{name}_{limit}"])
                    for leaf, pair in distances.items()},
            "grads_other_distance_max": float(max(
                pair[1 - which] for pair in distances.values())),
            "live_rows": int(got["live_rows"])}
        counts = got["counts"]
        del got, ref
        first, count = _held(config)
        observed[name]["held_share"] = [       # of a layer's pairs, on this rank
            float(np.sum(np.asarray(c)[first:first + count]) / np.sum(np.asarray(c)))
            for c in counts]
    del params, want
    if beyond:
        raise AssertionError(
            "laguna_xs_2 against its float32 references: "
            + "; ".join(beyond) + "; observed " + json.dumps(observed))
    return {"kind": "kernel", "observed": observed}


def cost(config, traffic, chips):
    """Needed operations per step per chip (forward + backward, the band's
    area on the sliding layers and the causal half on the full ones,
    recompute not counted; the held experts at a balanced router's load), and
    what the flash kernel calls of a step need, windowed and full apart."""
    from benchmarks import swa_cost

    rows, seq = traffic["global_rows"] // chips, traffic["seq"]
    kinds, heads, dense = _layers(config)
    kv_heads, d = config["num_key_value_heads"], config["head_dim"]
    window = config["sliding_window"]

    def flash(kind, window):
        at = [h for h, k in zip(heads, kinds) if k == kind]
        if len(set(at)) > 1:
            raise ValueError(f"laguna_xs_2: {kind} layers at {set(at)} heads")
        return swa_cost.flash_calls_step_cost(
            seq, rows, at[0], kv_heads, d, len(at), window,
            forward_calls=2 if config["remat"] else 1) if at else None

    return {
        "model_flops": swa_cost.share_step_flops(
            seq, rows, config["vocab_held"], config["hidden_size"], heads,
            [window if k == "sliding_attention" else None for k in kinds],
            kv_heads, d, dense, config["intermediate_size"],
            config["moe_intermediate_size"],
            config["shared_expert_intermediate_size"], config["num_experts"],
            config["num_experts_per_tok"], config["experts_held"]),
        "swa_flash": flash("sliding_attention", window),
        "full_flash": flash("full_attention", None),
    }
