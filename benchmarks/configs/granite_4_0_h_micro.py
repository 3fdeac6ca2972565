"""granite_4_0_h_micro: Granite 4.0-H Micro's first layers (Mamba-2 mixers
beside grouped-query attention) through ``TransformerLM`` and
``hvd.jax.DistributedOptimizer`` in the jitted ``shard_map`` step every
language-model cell runs (``olmoe_1b_7b.py`` builds the same one), its plain
float32 reference, its costs.

The same three functions as every configuration module:
``build(config, traffic, mesh, seed, **model_overrides)``,
``reference(config, traffic, mesh, seed, **model_overrides)`` and
``cost(config, traffic, chips)``. The configuration file carries the Hugging
Face keys as published; ``layers`` is the depth that is run, the model's own
first ``layers`` entries of ``layer_types``.
"""

from __future__ import annotations

import numpy as np

SAMPLED_ROWS = 64   # rows of a matrix's gradient the check reads


def _layer_types(config):
    return tuple(config["layer_types"][:config["layers"]])


def _model(config, **overrides):
    from horovod_tpu.models import TransformerLM
    from horovod_tpu.models.mamba import Mamba2Dims

    inner = config["mamba_n_heads"] * config["mamba_d_head"]
    if (config["hidden_act"] != "silu" or config["attention_bias"]
            or config["mamba_proj_bias"] or not config["mamba_conv_bias"]
            or not config["tie_word_embeddings"]
            or config["position_embedding_type"] != "nope"
            or config["normalization_function"] != "rmsnorm"
            or config["num_local_experts"] or config["num_experts_per_tok"]
            or inner != config["mamba_expand"] * config["hidden_size"]):
        raise ValueError("granite_4_0_h_micro: the configuration file states "
                         "a layer equation this module does not build")
    kw = dict(vocab=config["vocab_size"], dim=config["hidden_size"],
              heads=config["num_attention_heads"],
              kv_heads=config["num_key_value_heads"],
              layers=config["layers"], layer_types=_layer_types(config),
              mamba=Mamba2Dims(heads=config["mamba_n_heads"],
                               head_dim=config["mamba_d_head"],
                               state=config["mamba_d_state"],
                               groups=config["mamba_n_groups"],
                               conv=config["mamba_d_conv"],
                               chunk=config["mamba_chunk_size"]),
              mlp_hidden=config["shared_intermediate_size"], rope=False,
              tie_embeddings=True, rms_norm_eps=config["rms_norm_eps"],
              embedding_multiplier=config["embedding_multiplier"],
              attention_multiplier=config["attention_multiplier"],
              residual_multiplier=config["residual_multiplier"],
              logits_scaling=config["logits_scaling"],
              attention=config["attention"], remat=config["remat"])
    kw.update(overrides)
    return TransformerLM(**kw)


def _optimizer(config):
    import jax
    import optax

    o = config["optimizer"]
    if o["name"] != "adamw":
        raise ValueError(f"granite_4_0_h_micro trains with adamw, not {o}")
    # weight decay on matrices only: A_log, D, dt_bias, the convolution's
    # bias and every norm weight have one axis
    return optax.adamw(
        o["learning_rate"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
        weight_decay=o["weight_decay"],
        mask=lambda params: jax.tree_util.tree_map(lambda x: x.ndim >= 2,
                                                   params))


def _loss_fn(model):
    """The chunked cross entropy on f32 logits, the transposed embedding as
    the head's kernel (the hidden states come back divided by
    ``logits_scaling``)."""
    import jax.numpy as jnp

    from horovod_tpu.models.transformer import chunked_lm_loss

    def loss_fn(params, tokens):
        hidden = model.apply({"params": params}, tokens, return_hidden=True)
        return chunked_lm_loss(hidden, params["embed"]["embedding"].T,
                               jnp.roll(tokens, -1, axis=1))

    return loss_fn


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
    loss_fn = _loss_fn(model)

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
    return {"layer_types": _layer_types(config),
            "heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "mamba_heads": config["mamba_n_heads"],
            "mamba_head_dim": config["mamba_d_head"],
            "mamba_state": config["mamba_d_state"],
            "mamba_groups": config["mamba_n_groups"],
            "eps": config["rms_norm_eps"],
            "emb_mult": float(config["embedding_multiplier"]),
            "attn_mult": float(config["attention_multiplier"]),
            "res_mult": float(config["residual_multiplier"]),
            "logits_scaling": float(config["logits_scaling"])}


def to_reference(tree):
    """The system's parameter tree (or its gradients) in the reference's
    layout: the fused ``kv_proj`` kernel split into its two, the rest
    renamed."""
    import jax.numpy as jnp

    layers = []
    for i in range(sum(k.startswith("block_") for k in tree)):
        block = tree[f"block_{i}"]
        layer = {"norm": block["RMSNorm_0"]["scale"],
                 "mlp_norm": block["RMSNorm_1"]["scale"],
                 "w_gate": block["mlp_gate"]["kernel"],
                 "w_up": block["mlp_up"]["kernel"],
                 "w_down": block["mlp_down"]["kernel"]}
        if "mixer" in block:
            mixer = block["mixer"]
            layer.update(w_in=mixer["in_proj"]["kernel"],
                         conv_w=mixer["conv_kernel"],
                         conv_b=mixer["conv_bias"], dt_bias=mixer["dt_bias"],
                         A_log=mixer["A_log"], D=mixer["D"],
                         gate_norm=mixer["gate_norm"],
                         w_out=mixer["out_proj"]["kernel"])
        else:
            wk, wv = jnp.split(block["kv_proj"]["kernel"], 2, axis=1)
            layer.update(wq=block["q_proj"]["kernel"], wk=wk, wv=wv,
                         wo=block["o_proj"]["kernel"])
        layers.append(layer)
    return {"embed": tree["embed"]["embedding"], "layers": layers,
            "final_norm": tree["RMSNorm_0"]["scale"]}


def _sample(grads, rows):
    """What the check reads of a gradient tree in the reference's layout:
    every leaf of every layer, the tied embedding and the final norm; of a
    matrix the seeded ``rows`` (an index array, traced so that one compiled
    program serves every seed; taken modulo the leaf's height), of a vector
    all of it."""
    import jax

    def take(path, leaf):
        return leaf[rows % leaf.shape[0]] if leaf.ndim >= 2 else leaf

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


def check_programs(config, **model_overrides):
    """The jitted programs of the model check, each ``(params, tokens, rows)
    -> dict``: the plain reference (loss and logits by the recurrence, loss
    and sampled gradients through the quadratic form, and how far the two
    forms' logits lie apart), the system's model in float32 (run it under
    ``highest``), and the system's model as trained. The system's ``loss``
    and ``grads`` are the step's own: through ``chunked_lm_loss`` and its
    backward, the tied embedding receiving the scatter-add and the head's
    product."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import granite_hybrid as plain_model

    cfg = reference_config(config)

    def system(model):
        loss_fn = _loss_fn(model)

        def run(params, tokens, rows):
            loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
            return {"loss": loss,
                    "logits": model.apply({"params": params}, tokens),
                    "grads": _sample(to_reference(grads), rows)}

        return jax.jit(run)

    @jax.jit
    def plain(params, tokens, rows):
        ref_params = to_reference(params)
        loss, logits = plain_model.loss(ref_params, tokens, cfg)
        (loss_q, logits_q), grads = plain_model.loss_and_grads(
            ref_params, tokens, cfg)
        return {"loss": loss, "logits": logits, "loss_quadratic": loss_q,
                "forms_logits_rel": (jnp.max(jnp.abs(logits_q - logits))
                                     / jnp.max(jnp.abs(logits))),
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


def scan_programs(config, seq, slice_len):
    """``inputs(key)`` and the two jitted programs of the scan's own check at
    (seq, heads, head_dim, state): ``ops.ssd.ssd`` and the float32 recurrence
    of the reference over the whole row, each returning the last
    ``slice_len`` positions. ``u``, ``B`` and ``C`` are bf16-representable;
    ``dt`` and ``A`` are drawn as Mamba-2's initialisation draws them."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops.ssd import ssd

    from benchmarks.reference import granite_hybrid as plain_model

    h, p, n, g = (config[k] for k in ("mamba_n_heads", "mamba_d_head",
                                      "mamba_d_state", "mamba_n_groups"))
    chunk = config["mamba_chunk_size"]

    @jax.jit
    def inputs(key):
        ks = jax.random.split(key, 6)
        u = _bf16_values(ks[0], (1, seq, h, p))
        B = _bf16_values(ks[1], (1, seq, g, n), n ** -0.25)
        C = _bf16_values(ks[2], (1, seq, g, n), n ** -0.25)
        dt0 = jnp.exp(jax.random.uniform(ks[3], (h,)) * np.log(100.0)
                      + np.log(1e-3))
        dt = dt0 * jnp.exp(0.5 * jax.random.normal(ks[4], (1, seq, h)))
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
    check at (seq, heads over kv_heads, head_dim) with the configuration's
    softmax scale: the kernels as the model calls them, and a per-head
    float32 reference of the LAST ``slice_len`` query positions against the
    whole context (under a causal mask that is enough for exact out and dq on
    those queries, and dk and dv on the same positions as keys). Each returns
    (out, dq, dk, dv) on the slice."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops.flash_attention import (DEFAULT_BLOCK_K,
                                                 DEFAULT_BLOCK_Q,
                                                 flash_attention)

    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    d = config["hidden_size"] // heads
    scale = config["attention_multiplier"]
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
                                            DEFAULT_BLOCK_K, interpret, scale),
            q, k, v)
        dq, dk, dv = vjp(g.astype(out.dtype))
        return tuple(t[:, start:].astype(jnp.float32) for t in (out, dq, dk, dv))

    @jax.jit
    def reference(q, k, v, g):
        f32 = jnp.float32

        def attend(qs, kh, vh):     # (slice, D), (T, D), (T, D)
            s = (qs @ kh.T) * scale
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

    (1) The model itself against ``benchmarks/reference/granite_hybrid.py``
    on the first ``reference_prefix`` tokens of the cell's row with the
    cell's seeded parameters (a second training state cannot be held beside
    the first, so no plain STEP is played), in two legs: the system's model
    in float32 at ``highest``, which proves the mathematics, and the model as
    trained (bf16 activations, flash kernels, recomputation). Each leg holds
    the loss, the logits and the sampled gradients of every leaf - in float32
    by a leaf's largest error over max|ref|, as trained by its Euclidean norm
    over the reference's (a leaf's largest error is heavy-tailed over seeds
    in bf16, olmoe_1b_7b's lesson). The reference's two forms of the
    state-space layer are held to each other on the way.
    (2) ``ops.ssd.ssd`` alone at the cell's full row, float32 and bf16, its
    last ``scan_slice`` positions against the float32 recurrence over the
    whole context.
    (3) The flash kernels alone at the full row, grouped-query heads and the
    configuration's softmax scale, their last ``flash_slice`` positions
    against a per-head float32 reference."""
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

    # -- (2) the scan alone, (3) the flash kernels alone: before the model's
    # parameters are made a second time, while the memory is there
    inputs, chunked, recurrence = scan_programs(config, seq,
                                                min(traffic["scan_slice"], seq))
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

    # -- (1) the model
    prefix = min(traffic["reference_prefix"], seq)
    params = jax.jit(_init_params(_model(config, **model_overrides)),
                     out_shardings=one)(key)
    tokens = jax.jit(_tokens_fn(config, traffic["global_rows"], seq),
                     out_shardings=one)(jax.random.fold_in(key, 1))[:1, :prefix]
    rows = jnp.asarray(np.random.default_rng(seed).integers(
        0, 2 ** 31 - 1, SAMPLED_ROWS), jnp.int32)
    plain, exact, trained = check_programs(config, **model_overrides)
    with jax.default_matmul_precision("highest"):
        want = plain(params, tokens, rows)
    observed["reference_forms"] = {
        "logits": held("reference, quadratic form against the recurrence: logits",
                       want["forms_logits_rel"], tol["f32_logits_rel"]),
        "loss": held("reference, quadratic form against the recurrence: loss",
                     abs(float(want["loss_quadratic"]) - float(want["loss"]))
                     / abs(float(want["loss"])), tol["f32_loss_rel"])}
    compare = jax.jit(_distances)
    legs = {"f32": (exact, "highest", 0, "grads_rel"),
            "bf16": (trained, None, 1, "grads_l2_rel")}
    for name, (program, precision, which, limit) in legs.items():
        with jax.default_matmul_precision(precision):
            got = program(params, tokens, rows)
        distances = jax.device_get(compare(got["grads"], want["grads"]))
        observed[name] = {
            "loss": held(f"{name} loss", abs(float(got["loss"])
                                            - float(want["loss"]))
                         / abs(float(want["loss"])), tol[f"{name}_loss_rel"]),
            "logits": held(f"{name} logits", share(got["logits"], want["logits"]),
                           tol[f"{name}_logits_rel"]),
            limit: {leaf: held(f"{name} gradient of {leaf}", pair[which],
                               tol[f"{name}_{limit}"])
                    for leaf, pair in distances.items()},
            "grads_other_distance_max": float(max(
                pair[1 - which] for pair in distances.values()))}
        del got
    del params, want
    if beyond:
        raise AssertionError(
            "granite_4_0_h_micro against its float32 references: "
            + "; ".join(beyond) + "; observed " + json.dumps(observed))
    return {"kind": "kernel", "observed": observed}


def cost(config, traffic, chips):
    """Needed operations per step per chip (forward + backward, causal half,
    recompute not counted), what the flash kernels of the attention layers
    need, and what the state-space scans need."""
    from benchmarks import flops, ssd_cost

    rows, seq = traffic["global_rows"] // chips, traffic["seq"]
    kinds = _layer_types(config)
    dim, heads = config["hidden_size"], config["num_attention_heads"]
    mamba = (config["mamba_n_heads"], config["mamba_d_head"],
             config["mamba_d_state"], config["mamba_n_groups"],
             config["mamba_chunk_size"])
    return {
        "model_flops": ssd_cost.hybrid_step_flops(
            seq, rows, config["vocab_size"], dim, kinds, heads,
            config["num_key_value_heads"], config["shared_intermediate_size"],
            *mamba),
        "kernel": flops.flash_step_cost(seq, rows, heads, dim // heads,
                                        kinds.count("attention")),
        "ssd": ssd_cost.ssd_step_cost(seq, rows, kinds.count("mamba"), *mamba),
    }
