"""olmo_hybrid_7b: one tensor-parallel rank's share of Olmo-Hybrid-7B's first
period of four layers (a gated delta rule with ONE decay a head, keys of 96
under values of 192, in three of four layers; whole-projection QK-normed
attention without rotation in the fourth; every half under OLMo 2's
reordered norm; a dense SwiGLU in every layer) through ``TransformerLM`` and
``hvd.jax.DistributedOptimizer`` in the jitted ``shard_map`` step every
language-model cell runs, its plain float32 reference, its costs.

The same three functions as every configuration module:
``build(config, traffic, mesh, seed, **model_overrides)``,
``reference(config, traffic, mesh, seed, **model_overrides)`` and
``cost(config, traffic, chips)``. The configuration file carries the Hugging
Face keys as published; ``layers``, ``*_held`` and ``vocab_held`` are what
this chip runs of them.
"""

from __future__ import annotations

import numpy as np

SAMPLED_ROWS = 64   # rows of a matrix's gradient the check reads
INIT_STD = 0.02     # the file's ``assumed``: normal, std 0.02; norm weights 1


def kinds(config):
    """The kinds of the layers this chip runs: the model's own first
    ``layers`` of ``layer_types``."""
    return tuple(config["layer_types"][:config["layers"]])


def _model(config, **overrides):
    from horovod_tpu.models import GDNDims, TransformerLM

    if (config["model_type"] != "olmo_hybrid" or config["hidden_act"] != "silu"
            or config["attention_bias"] or config["tie_word_embeddings"]
            or config["rope_parameters"]["rope_theta"] is not None
            or config["num_key_value_heads"] != config["num_attention_heads"]
            or config["linear_num_key_heads"] != config["linear_num_value_heads"]
            or config["linear_heads_held"] * config["tensor_parallel"]
            != config["linear_num_key_heads"]
            or config["attention_heads_held"] * config["tensor_parallel"]
            != config["num_attention_heads"]
            or config["mlp_columns_held"] * config["tensor_parallel"]
            != config["intermediate_size"]
            or set(config["layer_types"]) - {"linear_attention",
                                             "full_attention"}):
        raise ValueError("olmo_hybrid_7b: the configuration file states a "
                         "layer equation this module does not build")
    kw = dict(vocab=config["vocab_held"], dim=config["hidden_size"],
              heads=config["attention_heads_held"],
              head_dim=config["hidden_size"] // config["num_attention_heads"],
              layers=config["layers"], layer_types=kinds(config),
              gdn=GDNDims(heads=config["linear_heads_held"],
                          key_dim=config["linear_key_head_dim"],
                          value_dim=config["linear_value_head_dim"],
                          conv=config["linear_conv_kernel_dim"],
                          chunk=config["gdn_chunk"],
                          allow_neg_eigval=config["linear_allow_neg_eigval"]),
              norm_after=True, qk_norm=True, rope=False,
              mlp_hidden=config["mlp_columns_held"],
              rms_norm_eps=config["rms_norm_eps"],
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
        raise ValueError(f"olmo_hybrid_7b trains with adamw, not {o}")
    # weight decay on matrices only: not on A_log, dt_bias, norm weights or
    # the convolutions' taps
    return optax.adamw(
        o["learning_rate"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
        weight_decay=o["weight_decay"],
        mask=lambda params: jax.tree_util.tree_map_with_path(_is_matrix,
                                                             params))


def _loss_parts(model):
    """``(params, tokens) -> (loss, hidden)``: the chunked cross entropy on
    f32 logits over the held rows of the vocabulary, and the final hidden
    states."""
    import jax.numpy as jnp

    from horovod_tpu.models.transformer import chunked_lm_loss

    def parts(params, tokens):
        hidden = model.apply({"params": params}, tokens, return_hidden=True)
        loss = chunked_lm_loss(hidden, params["lm_head"]["kernel"],
                               jnp.roll(tokens, -1, axis=1))
        return loss, hidden

    return parts


def _init_state(model, config):
    """``key -> params``: matrices normal with std 0.02, the convolutions'
    taps uniform in +-K^-0.5, ``A_log`` the log of uniform(1, 16) a head,
    ``dt_bias`` by Mamba-2's inverse-softplus rule, norm weights 1. The
    shapes come from the model's own ``init``, traced and never run."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models.mamba import _a_log_init, _dt_bias_init

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
            return INIT_STD * jax.random.normal(key, leaf.shape, leaf.dtype)
        return jnp.ones(leaf.shape, leaf.dtype)

    def init(key):
        # Parameter shapes do not depend on the sequence length: trace short.
        shapes = jax.eval_shape(model.init, key, jnp.zeros((1, 128), jnp.int32))
        leaves, tree = jax.tree_util.tree_flatten_with_path(shapes["params"])
        keys = jax.random.split(jax.random.fold_in(key, 7), len(leaves))
        return jax.tree_util.tree_unflatten(tree, [
            draw(k, path, leaf) for k, (path, leaf) in zip(keys, leaves)])

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
    init_state = _init_state(model, config)

    def init(key):
        params = init_state(key)
        return params, opt.init(params)

    params, opt_state = jax.jit(init, out_shardings=replicated)(key)
    tokens = jax.jit(_tokens_fn(config, rows, seq),
                     out_shardings=NamedSharding(mesh, P(hvd.HVD_AXIS)))(
        jax.random.fold_in(key, 1))
    parts = _loss_parts(model)

    def train_step(params, opt_state, tokens):
        with jax.named_scope(SCOPE_FWD_BWD):
            loss, grads = jax.value_and_grad(
                lambda p, t: parts(p, t)[0])(params, tokens)
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
    return {"kinds": kinds(config), "heads": config["attention_heads_held"],
            "head_dim": config["hidden_size"] // config["num_attention_heads"],
            "linear_heads": config["linear_heads_held"],
            "key_dim": config["linear_key_head_dim"],
            "value_dim": config["linear_value_head_dim"],
            "neg_eigval": config["linear_allow_neg_eigval"],
            "eps": config["rms_norm_eps"]}


def to_reference(tree):
    """The system's parameter tree (or its gradients) in the reference's
    layout: leaves renamed, attention's one q | k | v matrix cut into its
    three, nothing transposed."""
    import jax.numpy as jnp

    layers = []
    for i in range(sum(k.startswith("block_") for k in tree)):
        block = tree[f"block_{i}"]
        layer = {"attn_norm": block["RMSNorm_0"]["scale"],
                 "mlp_norm": block["RMSNorm_1"]["scale"],
                 "w_gate": block["mlp_gate"]["kernel"],
                 "w_up": block["mlp_up"]["kernel"],
                 "w_down": block["mlp_down"]["kernel"]}
        if "mixer" in block:
            m = block["mixer"]
            layer.update(wq=m["q_proj"], wk=m["k_proj"], wv=m["v_proj"],
                         conv_q=m["q_conv"], conv_k=m["k_conv"],
                         conv_v=m["v_conv"], wa=m["a_proj"], wb=m["b_proj"],
                         dt_bias=m["dt_bias"], a_log=m["A_log"],
                         wg=m["g_proj"], o_norm=m["o_norm"], wo=m["o_proj"])
        else:
            wq, wk, wv = jnp.split(block["qkv"]["kernel"], 3, axis=1)
            layer.update(wq=wq, wk=wk, wv=wv,
                         q_norm=block["q_norm"]["scale"],
                         k_norm=block["k_norm"]["scale"],
                         wo=block["o_proj"]["kernel"])
        layers.append(layer)
    return {"embed": tree["embed"]["embedding"], "layers": layers,
            "final_norm": tree["RMSNorm_0"]["scale"],
            "head": tree["lm_head"]["kernel"]}


def _sample(grads, rows):
    """What the check reads of a gradient tree in the reference's layout:
    every leaf of every layer, the embedding, the head and the final norm; of
    a matrix the seeded ``rows`` (an index array, traced so that one compiled
    program serves every seed; taken modulo the leaf's height), of a vector
    all of it."""
    import jax

    def take(path, leaf):
        return leaf if leaf.ndim < 2 else leaf[rows % leaf.shape[0]]

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
    -> dict``: the plain reference, the system's model in float32 (run it
    under ``highest``: its chunked delta rule included), and the system's
    model as trained. The system's ``loss`` and ``grads`` are the step's own:
    through ``chunked_lm_loss`` and its backward."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import olmo_hybrid as plain_model

    cfg = reference_config(config)

    def system(model):
        parts = _loss_parts(model)

        @jax.jit
        def run(params, tokens, rows):
            # ONE forward: the logits are the head's product with the hidden
            # states the loss was taken from, as ``TransformerLM`` forms them
            (loss, hidden), grads = jax.value_and_grad(parts, has_aux=True)(
                params, tokens)
            logits = hidden.astype(jnp.float32) @ params["lm_head"]["kernel"]
            return {"loss": loss, "logits": logits,
                    "grads": _sample(to_reference(grads), rows)}

        return run

    @jax.jit
    def plain(params, tokens, rows):
        (loss, logits), grads = jax.value_and_grad(
            plain_model.loss_parts, has_aux=True)(to_reference(params),
                                                  tokens, cfg)
        return {"loss": loss, "logits": logits, "grads": _sample(grads, rows)}

    # The float32 leg runs the flash kernels at 512 / 512 blocks: with float32
    # operands the default 1024 blocks overflow the dq kernel's scoped VMEM
    # (olmoe_1b_7b.check_programs, PR 26).
    exact = {**model_overrides, "dtype": jnp.float32, "block_q": 512,
             "block_k": 512}
    return (plain, system(_model(config, **exact)),
            system(_model(config, **model_overrides)))


def _bf16_values(key, shape, scale=1.0):
    """Normal values that bf16 holds exactly, as float32 (behind a barrier:
    XLA on the TPU drops a round trip it can see whole)."""
    import jax
    import jax.numpy as jnp

    return jax.lax.optimization_barrier(
        (scale * jax.random.normal(key, shape, jnp.float32)).astype(
            jnp.bfloat16)).astype(jnp.float32)


SCAN_GRADS = ("dq", "dk", "dv", "dg", "dbeta")
STRONGEST_PREACTIVATION = 20.0      # softplus's argument at the strongest decay


def scan_programs(config, seq, slice_len, **model_overrides):
    """``inputs(key, strongest)`` and the jitted programs of the delta rule's
    own check at (seq, held heads, 96 | 192): ``ops.gdn.gdn`` in bf16 and in
    float32 (each with the matmul precision to call it under), and the float32
    recurrence of the reference over the whole row. Each returns the last
    ``slice_len`` positions of (o, dq, dk, dv, dg, dbeta) for a cotangent that
    is zero before them: the outputs there and the gradients that reach those
    positions. q and k are drawn, normed a head and rounded to bf16 (q times
    dk^-0.5, as the mixer hands them over), v is bf16-representable; ``g =
    -exp(A_log) softplus(x + dt_bias)`` with ``A_log`` and ``dt_bias`` as the
    mixer initialises them and ``x`` normal (0.5) - or, ``strongest``, every
    ``A`` at 16 and ``x`` at 20: a log-decay of -320 a token; beta = 2
    sigmoid(normal) in (0, 2)."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models.mamba import _a_log_init, _dt_bias_init
    from horovod_tpu.ops.gdn import gdn

    from benchmarks.reference import olmo_hybrid as plain_model

    h, dk, dv = (config["linear_heads_held"], config["linear_key_head_dim"],
                 config["linear_value_head_dim"])
    chunk, neg = config["gdn_chunk"], config["linear_allow_neg_eigval"]
    interpret = model_overrides.get("flash_interpret", False)
    start = seq - slice_len

    @jax.jit
    def inputs(key, strongest):
        ks = jax.random.split(key, 8)

        def unit(k):
            x = jax.random.normal(k, (1, seq, h, dk), jnp.float32)
            return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

        def rounded(x):
            return jax.lax.optimization_barrier(
                x.astype(jnp.bfloat16)).astype(jnp.float32)

        q, k = rounded(unit(ks[0]) * dk ** -0.5), rounded(unit(ks[1]))
        v = _bf16_values(ks[2], (1, seq, h, dv))
        a = jnp.where(strongest, 16.0, jnp.exp(_a_log_init(ks[3], (h,))))
        x = jnp.where(strongest, STRONGEST_PREACTIVATION,
                      0.5 * jax.random.normal(ks[4], (1, seq, h)))
        g = -a * jax.nn.softplus(x + _dt_bias_init(ks[5], (h,)))
        beta = jax.nn.sigmoid(jax.random.normal(ks[6], (1, seq, h))) * (
            2.0 if neg else 1.0)
        do = _bf16_values(ks[7], (1, seq, h, dv)) * (
            jnp.arange(seq) >= start)[None, :, None, None]
        return q, k, v, g, beta, do

    def on_slice(o, vjp, do):
        return tuple(t[:, start:].astype(jnp.float32)
                     for t in (o, *vjp(do.astype(o.dtype))))

    def in_dtype(dtype):
        @jax.jit
        def run(q, k, v, g, beta, do):
            o, vjp = jax.vjp(
                lambda q, k, v, g, beta: gdn(q, k, v, g, beta, chunk,
                                             interpret=interpret,
                                             neg_eigval=neg),
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
    check at (seq, held heads, 128): the kernels as the model calls them
    (default blocks, the default scale 128 ** -0.5, no rotation), and a
    per-head float32 reference of the LAST ``slice_len`` query positions
    against the whole context. Each returns (out, dq, dk, dv) on the slice."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops.flash_attention import (DEFAULT_BLOCK_K,
                                                 DEFAULT_BLOCK_Q,
                                                 flash_attention)

    heads = config["attention_heads_held"]
    d = config["hidden_size"] // config["num_attention_heads"]
    interpret = model_overrides.get("flash_interpret", False)
    start = seq - slice_len

    @jax.jit
    def inputs(key):
        ks = jax.random.split(key, 4)
        q, k, v = (_bf16_values(kk, (1, seq, heads, d)).astype(jnp.bfloat16)
                   for kk in ks[:3])
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

        def attend(qs, kh, vh):     # (slice, d), (T, d), (T, d)
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

        with jax.default_matmul_precision("highest"):
            outs = jax.lax.map(
                one_head, (heads_first(q[:, start:]), heads_first(k),
                           heads_first(v), heads_first(g[:, start:])))
        return tuple(jnp.moveaxis(t, 0, 1)[None] for t in outs)

    return inputs, system, reference


def reference(config, traffic, mesh, seed, **model_overrides):
    """Three checks at the widths of the file, outside the timed window, each
    logged whole; raises ``AssertionError`` for what lies beyond the file's
    ``tolerance``.

    (1) The flash kernels alone at the cell's full row, the held heads of
    128, no rotation, their last ``flash_slice`` positions against a per-head
    float32 reference: out, dq, dk, dv.
    (2) The chunked delta rule alone at the full row, the held heads of 96 |
    192, in bf16 and in float32 (under ``highest``), its last ``scan_slice``
    positions (o and the gradients of q, k, v, g, beta that reach them)
    against the float32 recurrence over the whole row, once with gates as the
    mixer initialises them and once at the strongest decay, where every value
    must also be finite.
    (3) The model itself against ``benchmarks/reference/olmo_hybrid.py`` on
    the first ``reference_prefix`` tokens of the cell's first row with the
    cell's seeded parameters, in two legs: the system's model in float32 at
    ``highest``, which proves the mathematics (its chunked path included),
    and the model as trained (bf16 activations, flash kernels,
    recomputation). Each leg holds the loss, the logits and 64 sampled rows
    of EVERY leaf's gradient through ``chunked_lm_loss``'s own backward
    (float32: a leaf's largest error over max|ref|; as trained: its Euclidean
    norm over the reference's)."""
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
        config, seq, min(traffic["scan_slice"], seq), **model_overrides)
    observed["scan"] = {}
    for gates, strongest in (("as_initialised", False), ("strongest", True)):
        args = jax.device_put(inputs(jax.random.fold_in(key, 4), strongest),
                              one)
        want = recurrence(*args)
        for name, (program, precision) in chunked.items():
            with jax.default_matmul_precision(precision):
                got = program(*args)
            observed["scan"][f"{name}_{gates}"] = {
                part: held(f"scan {name} {gates} {part}", value,
                           tol[f"{name}_scan_rel"])
                for part, value in zip(("o",) + SCAN_GRADS, shares(got, want))}
        del args, want, got

    # -- (3) the model
    prefix = min(traffic["reference_prefix"], seq)
    params = jax.jit(_init_state(_model(config, **model_overrides), config),
                     out_shardings=one)(key)
    tokens = jax.jit(_tokens_fn(config, traffic["global_rows"], seq),
                     out_shardings=one)(jax.random.fold_in(key, 1))[:1, :prefix]
    rows = jnp.asarray(np.random.default_rng(seed).integers(
        0, 2 ** 31 - 1, SAMPLED_ROWS), jnp.int32)
    plain, exact, trained = check_programs(config, **model_overrides)

    @jax.jit
    def logits_share(got, want):
        got, want = got.astype(jnp.float32), want.astype(jnp.float32)
        return jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want))

    with jax.default_matmul_precision("highest"):
        want = plain(params, tokens, rows)
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
            "logits": held(f"{name} logits",
                           logits_share(got["logits"], want["logits"]),
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
            "olmo_hybrid_7b against its float32 references: "
            + "; ".join(beyond) + "; observed " + json.dumps(observed))
    return {"kind": "kernel", "observed": observed}


def cost(config, traffic, chips):
    """Needed operations per step per chip (forward + backward, causal half,
    recompute not counted) and what the delta-rule calls need (the carried
    states an implementation saves for its backward are its own choice, not
    the model's need: not counted)."""
    from benchmarks import gdn_cost

    rows, seq = traffic["global_rows"] // chips, traffic["seq"]
    of_kind = kinds(config)
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    forward_calls = 2 if config["remat"] else 1
    return {
        "model_flops": gdn_cost.share_step_flops(
            seq, rows, config["vocab_held"], config["hidden_size"], of_kind,
            config["linear_heads_held"], dk, dv, config["gdn_chunk"],
            config["attention_heads_held"],
            config["hidden_size"] // config["num_attention_heads"],
            config["mlp_columns_held"]),
        "gdn_scan": gdn_cost.gdn_scan_step_cost(
            seq, rows, of_kind.count("linear_attention"),
            config["linear_heads_held"], dk, dv, config["gdn_chunk"],
            forward_calls=forward_calls),
    }
