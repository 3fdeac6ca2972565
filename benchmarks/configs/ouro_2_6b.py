"""ouro_2_6b: the first pipeline stage of Ouro-2.6B, the looped language
model of arXiv:2510.25741 (ONE stack of layers that the residual stream
passes through ``total_ut_steps`` = 4 times over shared weights, every layer
under a sandwich norm, the model's one final norm, one head and a one-number
exit gate reading every pass, and the loss EXPECTED over the exit
distribution the gates define, with its entropy term) through
``TransformerLM`` and ``hvd.jax.DistributedOptimizer`` in the jitted
``shard_map`` step every language-model cell runs, its plain float32
reference, its costs.

The same three functions as every configuration module:
``build(config, traffic, mesh, seed, **model_overrides)``,
``reference(config, traffic, mesh, seed, **model_overrides)`` and
``cost(config, traffic, chips)``. The configuration file carries the Hugging
Face keys as published; ``layers`` is what this chip runs of them.
"""

from __future__ import annotations

import numpy as np

SAMPLED_ROWS = 64   # rows of a matrix's gradient the check reads
INIT_STD = 0.02     # the file's ``assumed``: normal, std 0.02; norm weights 1
# {seed: the parameters ``build`` made}, until ``reference`` of the same run's
# check takes them (``solar_open2_250b.py``'s rule): 612 M float32 parameters
# are 2.3 GiB, and beside 6.8 GiB of training state the check's programs (the
# reference's 4.8 GiB of temporaries, 1.6 GiB of logits and 0.3 GiB of code;
# the as-trained leg's 6.7 GiB) leave no room for a second copy of them
_SEEDED = {}


def _model(config, **overrides):
    from horovod_tpu.models import TransformerLM

    if (config["model_type"] != "ouro" or config["hidden_act"] != "silu"
            or config["tie_word_embeddings"] or config["rope_scaling"]
            or config["use_sliding_window"]
            or config["num_key_value_heads"] != config["num_attention_heads"]
            or config["head_dim"] * config["num_attention_heads"]
            != config["hidden_size"]
            or set(config["layer_types"]) != {"full_attention"}):
        raise ValueError("ouro_2_6b: the configuration file states a layer "
                         "equation this module does not build")
    kw = dict(vocab=config["vocab_size"], dim=config["hidden_size"],
              heads=config["num_attention_heads"],
              head_dim=config["head_dim"], layers=config["layers"],
              mlp_hidden=config["intermediate_size"],
              rope_theta=float(config["rope_theta"]),
              rms_norm_eps=config["rms_norm_eps"], sandwich_norm=True,
              passes=config["total_ut_steps"], exit_gate=True,
              attention=config["attention"], remat=config["remat"])
    kw.update(overrides)
    return TransformerLM(**kw)


def _names(path):
    return [str(getattr(p, "key", "")) for p in path]


def _is_matrix(path, leaf):
    """Leaves AdamW decays: the projections, the embedding and the head; not
    a norm's weight, and not the exit gate's ``w_e`` and ``b_e`` (a vector
    and a number, whatever axes hold them)."""
    return leaf.ndim >= 2 and "exit_gate" not in _names(path)


def _optimizer(config):
    import jax
    import optax

    o = config["optimizer"]
    if o["name"] != "adamw":
        raise ValueError(f"ouro_2_6b trains with adamw, not {o}")
    return optax.adamw(
        o["learning_rate"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
        weight_decay=o["weight_decay"],
        mask=lambda params: jax.tree_util.tree_map_with_path(_is_matrix,
                                                             params))


def _loss_parts(model, config):
    """``(params, tokens) -> (loss, (parts, hidden, gate_logits))``: the loss
    expected over the exit distribution less ``beta`` times its entropy,
    through the weighted chunked loss (``loop_lm_loss``: the four passes of
    the one head on f32 logits in ONE loop), ``parts`` its terms and the mean
    exit mass a pass, and what the model handed over."""
    import jax.numpy as jnp

    from horovod_tpu.models.transformer import loop_lm_loss

    def parts(params, tokens):
        hidden, gates = model.apply({"params": params}, tokens,
                                    return_hidden=True)
        loss, terms = loop_lm_loss(hidden, gates, params["lm_head"]["kernel"],
                                   jnp.roll(tokens, -1, axis=1),
                                   config["beta"], config["loss_chunk"])
        return loss, (terms, hidden, gates)

    return parts


def _init_state(model, config):
    """``key -> params``: matrices (the gate's ``w_e`` too) normal with std
    0.02, norm weights 1, the gate's bias 0. The shapes come from the model's
    own ``init``, traced and never run."""
    import jax
    import jax.numpy as jnp

    def draw(key, path, leaf):
        if leaf.ndim >= 2:
            return INIT_STD * jax.random.normal(key, leaf.shape, leaf.dtype)
        if "exit_gate" in _names(path):
            return jnp.zeros(leaf.shape, leaf.dtype)
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
                                          config["vocab_size"], jnp.int32)


_published = {}    # the collector of the latest build, which a newer replaces


def _publish_exit_mass(built):
    """Have the program's registry say, whenever it is asked, where the
    tokens of the latest steps exit: a collector reads the third carry of
    ``built`` (``mass`` (steps, passes), the ring the step writes; -1 where
    no step has written yet) and hands it to
    ``metrics.overlap.record_loop_exit_mass``. Nothing is read while nobody
    asks: the timed window is not touched."""
    from horovod_tpu.metrics import record_loop_exit_mass, registry

    def collect(_registry):
        # ``built`` itself: the harness REBINDS its ``state`` in its first steps
        ring = np.asarray(built["state"][2]["mass"])
        record_loop_exit_mass(ring[ring[:, 0] >= 0])

    forget_exit_mass()
    _published["collect"] = collect
    registry().register_collector(collect)


def forget_exit_mass():
    """Take the latest build's collector off the registry (and with it the
    build's state, which it holds)."""
    from horovod_tpu.metrics import registry

    if "collect" in _published:
        registry().unregister_collector(_published.pop("collect"))


def build(config, traffic, mesh, seed, **model_overrides):
    import jax
    import jax.numpy as jnp
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
    # Where the tokens of the latest steps exit, one row a step: the gates
    # decide it, and a gate that died shows here and nowhere in the times.
    kept = traffic["trace_groups"] * traffic["fence_every"]

    def init(key):
        params = init_state(key)
        tally = {"mass": -jnp.ones((kept, model.passes), jnp.float32),
                 "steps": jnp.zeros((), jnp.int32)}
        return params, opt.init(params), tally

    params, opt_state, tally = jax.jit(init, out_shardings=replicated)(key)
    _SEEDED.clear()
    _SEEDED[seed] = params
    tokens = jax.jit(_tokens_fn(config, rows, seq),
                     out_shardings=NamedSharding(mesh, P(hvd.HVD_AXIS)))(
        jax.random.fold_in(key, 1))
    parts = _loss_parts(model, config)

    def train_step(params, opt_state, tally, tokens):
        with jax.named_scope(SCOPE_FWD_BWD):
            (loss, (terms, _, _)), grads = jax.value_and_grad(
                parts, has_aux=True)(params, tokens)
        with jax.named_scope(SCOPE_OPTIMIZER):
            updates, opt_state = opt.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            # this rank's own tokens (the first rank's, where there are more)
            tally = {"mass": tally["mass"].at[tally["steps"] % kept].set(
                         terms["exit_mass"]),
                     "steps": tally["steps"] + 1}
        return params, opt_state, tally, jax.lax.pmean(loss, hvd.HVD_AXIS)

    step = jax.jit(
        shard_map(train_step, mesh=mesh,
                  in_specs=(P(), P(), P(), P(hvd.HVD_AXIS)),
                  out_specs=(P(), P(), P(), P()), check_vma=False),
        donate_argnums=(0, 1, 2))
    built = {"step": step, "state": [params, opt_state, tally],
             "batch": (tokens,), "samples_per_step": rows * seq}
    _publish_exit_mass(built)
    return built


# ----------------------------------------------------------------- reference

def reference_config(config):
    """The reference's own few keys, from the file's."""
    return {"heads": config["num_attention_heads"],
            "head_dim": config["head_dim"],
            "theta": float(config["rope_theta"]),
            "eps": config["rms_norm_eps"], "passes": config["total_ut_steps"],
            "beta": config["beta"], "scan_passes": True}


def to_reference(tree):
    """The system's parameter tree (or its gradients) in the reference's
    layout: leaves renamed, attention's one q | k | v matrix cut into its
    three, nothing transposed."""
    import jax.numpy as jnp

    layers = []
    for i in range(sum(k.startswith("block_") for k in tree)):
        block = tree[f"block_{i}"]
        wq, wk, wv = jnp.split(block["qkv"]["kernel"], 3, axis=1)
        layers.append({"attn_in_norm": block["RMSNorm_0"]["scale"],
                       "attn_out_norm": block["RMSNorm_1"]["scale"],
                       "mlp_in_norm": block["RMSNorm_2"]["scale"],
                       "mlp_out_norm": block["RMSNorm_3"]["scale"],
                       "wq": wq, "wk": wk, "wv": wv,
                       "wo": block["o_proj"]["kernel"],
                       "w_gate": block["mlp_gate"]["kernel"],
                       "w_up": block["mlp_up"]["kernel"],
                       "w_down": block["mlp_down"]["kernel"]})
    return {"embed": tree["embed"]["embedding"], "layers": layers,
            "final_norm": tree["RMSNorm_0"]["scale"],
            "head": tree["lm_head"]["kernel"],
            "gate_w": tree["exit_gate"]["kernel"][:, 0],
            "gate_b": tree["exit_gate"]["bias"]}


def _sample(grads, rows):
    """What the check reads of a gradient tree in the reference's layout:
    every leaf of every layer (each the SUM of the passes'), the embedding,
    the head, the final norm and the gate; of a matrix the seeded ``rows``
    (an index array, traced so that one compiled program serves every seed;
    taken modulo the leaf's height), of a vector all of it. The gate's
    ``w_e`` and ``b_e`` are read as ONE leaf of 2,049 numbers: ``b_e``'s
    gradient is one number, the sum of every token's slope, which cancels to
    near nothing on some seeds, and held against ITSELF its float32 rounding
    read 8.4e-3 on one seed of ten where every other leaf read under 1.4e-3
    (PERF.md section 6, PR 67)."""
    import jax
    import jax.numpy as jnp

    def take(path, leaf):
        return leaf if leaf.ndim < 2 else leaf[rows % leaf.shape[0]]

    flat = jax.tree_util.tree_flatten_with_path(
        jax.tree_util.tree_map_with_path(take, grads))[0]
    read = {"".join(f".{getattr(p, 'key', getattr(p, 'idx', p))}"
                    for p in path).lstrip(".").replace("layers.", "layer"): leaf
            for path, leaf in flat}
    read["gate"] = jnp.concatenate([read.pop("gate_w"), read.pop("gate_b")])
    return read


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
    """The jitted programs of the model check: the plain reference ``(params,
    tokens, rows) -> dict`` with the four passes' logits whole; the system's
    model in float32 (run it under ``highest``) and as trained, each
    ``(params, tokens, rows) -> dict`` with the four normed streams in place
    of logits; and ``logits_share(streams, kernel, want_logits)``, the worst
    of a pass's ``max|stream @ kernel - want| / max|want|`` over the passes
    (call it under the leg's precision: the head's product as
    ``TransformerLM`` and the loss form it). The system's ``loss`` and
    ``grads`` are the step's own: through ``loop_lm_loss``, the weighted
    chunked loss and its backward. The four passes' float32 logits are 1.6
    GiB at the published vocabulary: the reference's alone are ever whole,
    and a leg's are formed a pass at a time against them."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import ouro as plain_model

    cfg = reference_config(config)

    def system(model):
        parts = _loss_parts(model, config)

        @jax.jit
        def run(params, tokens, rows):
            (loss, (terms, hidden, gates)), grads = jax.value_and_grad(
                parts, has_aux=True)(params, tokens)
            return {"loss": loss, "gates": jax.nn.sigmoid(gates),
                    "exit_mass": terms["exit_mass"], "streams": hidden,
                    "grads": _sample(to_reference(grads), rows)}

        return run

    @jax.jit
    def plain(params, tokens, rows):
        (loss, (logits, gates, p)), grads = jax.value_and_grad(
            plain_model.loss_parts, has_aux=True)(to_reference(params),
                                                  tokens, cfg)
        return {"loss": loss, "logits": logits, "gates": gates,
                "exit_mass": jnp.mean(p, axis=(1, 2)),
                "grads": _sample(grads, rows)}

    @jax.jit
    def logits_share(streams, kernel, want_logits):
        def one_pass(pair):
            stream, want = pair
            got = stream.astype(jnp.float32) @ kernel
            return jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want))

        return jnp.max(jax.lax.map(one_pass, (streams, want_logits)))

    # The float32 leg runs the flash kernels at 512 / 512 blocks: with float32
    # operands the default 1024 blocks overflow the dq kernel's scoped VMEM
    # (olmoe_1b_7b.check_programs, PR 26).
    exact = {**model_overrides, "dtype": jnp.float32, "block_q": 512,
             "block_k": 512}
    return (plain, system(_model(config, **exact)),
            system(_model(config, **model_overrides)), logits_share)


def _bf16_values(key, shape, scale=1.0):
    """Normal values that bf16 holds exactly, as float32 (behind a barrier:
    XLA on the TPU drops a round trip it can see whole)."""
    import jax
    import jax.numpy as jnp

    return jax.lax.optimization_barrier(
        (scale * jax.random.normal(key, shape, jnp.float32)).astype(
            jnp.bfloat16)).astype(jnp.float32)


def flash_programs(config, seq, slice_len, **model_overrides):
    """``inputs(key)`` and the two jitted programs of the flash kernels' own
    check at (seq, 16 heads, 128): the kernels as the model calls them
    (multi-head, default blocks, the default scale 128 ** -0.5) on operands
    as the layer hands them over - q and k drawn and TURNED by their
    positions at the model's base of 1e6 (the plain reference's ``rotate``),
    then rounded to bf16 - and a per-head float32 reference of the LAST
    ``slice_len`` query positions against the whole context. Each returns
    (out, dq, dk, dv) on the slice."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops.flash_attention import (DEFAULT_BLOCK_K,
                                                 DEFAULT_BLOCK_Q,
                                                 flash_attention)

    from benchmarks.reference import ouro as plain_model

    heads, d = config["num_attention_heads"], config["head_dim"]
    theta = float(config["rope_theta"])
    interpret = model_overrides.get("flash_interpret", False)
    start = seq - slice_len

    @jax.jit
    def inputs(key):
        ks = jax.random.split(key, 4)

        def turned(k):
            return plain_model.rotate(jax.random.normal(
                k, (1, seq, heads, d), jnp.float32), theta).astype(jnp.bfloat16)

        return (turned(ks[0]), turned(ks[1]),
                _bf16_values(ks[2], (1, seq, heads, d)).astype(jnp.bfloat16),
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
    """Two checks at the widths of the file, outside the timed window, each
    logged whole; raises ``AssertionError`` for what lies beyond the file's
    ``tolerance``.

    (1) The flash kernels alone at the cell's full row, 16 heads of 128 on
    rotated operands, their last ``flash_slice`` positions against a per-head
    float32 reference: out, dq, dk, dv.
    (2) The model itself, all ``layers`` x ``total_ut_steps`` block
    applications, against ``benchmarks/reference/ouro.py`` on the first
    ``reference_prefix`` tokens of the cell's first row with the cell's
    seeded parameters, in two legs: the system's model in float32 at
    ``highest``, which proves the mathematics (the loop, the sandwich norms,
    the gates, the weighted loss and its backward), and the model as trained
    (bf16 activations, flash kernels, recomputation). Each leg holds the
    loss, every pass's logits (the worst pass), every pass's gate (the
    largest difference of two ``lambda``), the mean exit mass a pass and 64
    sampled rows of EVERY leaf's gradient, the shared leaves' - each the sum
    of four passes' - included (float32: a leaf's largest error over
    max|ref|; as trained: its Euclidean norm over the reference's)."""
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
        """max|got - want| over max|want|, of each pair."""
        return [jnp.max(jnp.abs(g - w)) / jnp.maximum(jnp.max(jnp.abs(w)), 1e-30)
                for g, w in zip(got, want)]

    # -- (1) the flash kernels alone: before the model's parameters are made
    # a second time, while the memory is there
    inputs, system, plain = flash_programs(
        config, seq, min(traffic["flash_slice"], seq), **model_overrides)
    args = jax.device_put(inputs(jax.random.fold_in(key, 3)), one)
    observed["flash"] = {
        name: held(f"flash {name}", value, tol["flash_rel"])
        for name, value in zip(("out", "dq", "dk", "dv"),
                               shares(system(*args), plain(*args)))}
    del args

    # -- (2) the model: the seeded parameters ``build`` made, while the step
    # has not yet taken (and donated) them; made anew where this is called
    # without a ``build`` before
    prefix = min(traffic["reference_prefix"], seq)
    params = _SEEDED.pop(seed, None)
    if params is None:
        params = jax.jit(_init_state(_model(config, **model_overrides),
                                     config), out_shardings=one)(key)
    elif mesh.size > 1:     # replicated over the mesh: the first chip's copy
        params = jax.device_put(params, one)
    tokens = jax.jit(_tokens_fn(config, traffic["global_rows"], seq),
                     out_shardings=one)(jax.random.fold_in(key, 1))[:1, :prefix]
    rows = jnp.asarray(np.random.default_rng(seed).integers(
        0, 2 ** 31 - 1, SAMPLED_ROWS), jnp.int32)
    plain, exact, trained, logits_share = check_programs(config,
                                                         **model_overrides)

    # the two legs BEFORE the reference: each leg's 3.6 / 6.7 GiB of
    # temporaries and the reference's 1.6 GiB of logits are then never on the
    # chip together
    legs = {"f32": (exact, "highest", 0, "grads_rel"),
            "bf16": (trained, None, 1, "grads_l2_rel")}
    results = {}
    for name, (program, precision, _, _) in legs.items():
        with jax.default_matmul_precision(precision):
            results[name] = program(params, tokens, rows)
    with jax.default_matmul_precision("highest"):
        want = plain(params, tokens, rows)
    compare = jax.jit(_distances)
    kernel = params["lm_head"]["kernel"]
    for name, (_, precision, which, limit) in legs.items():
        got = results.pop(name)
        with jax.default_matmul_precision(precision):
            logits = logits_share(got["streams"], kernel, want["logits"])
        distances = jax.device_get(compare(got["grads"], want["grads"]))
        observed[name] = {
            "loss": held(f"{name} loss", abs(float(got["loss"])
                                            - float(want["loss"]))
                         / abs(float(want["loss"])), tol[f"{name}_loss_rel"]),
            "logits": held(f"{name} logits", logits,
                           tol[f"{name}_logits_rel"]),
            "gates": held(f"{name} gates", jnp.max(jnp.abs(
                got["gates"] - want["gates"])), tol[f"{name}_gates_abs"]),
            "exit_mass": [float(m) for m in got["exit_mass"]],
            limit: {leaf: held(f"{name} gradient of {leaf}", pair[which],
                               tol[f"{name}_{limit}"])
                    for leaf, pair in distances.items()},
            "grads_other_distance_max": float(max(
                pair[1 - which] for pair in distances.values()))}
        del got
    observed["exit_mass_reference"] = [float(m) for m in want["exit_mass"]]
    del params, kernel, want
    if beyond:
        raise AssertionError(
            "ouro_2_6b against its float32 references: "
            + "; ".join(beyond) + "; observed " + json.dumps(observed))
    return {"kind": "kernel", "observed": observed}


def cost(config, traffic, chips):
    """Needed operations per step per chip (forward + backward, causal half,
    every product ``total_ut_steps`` times, layers and head alike; recompute
    not counted) and what the loop's head passes need."""
    from benchmarks import loop_cost

    rows, seq = traffic["global_rows"] // chips, traffic["seq"]
    passes = config["total_ut_steps"]
    return {
        "model_flops": loop_cost.loop_step_flops(
            seq, rows, config["vocab_size"], config["hidden_size"],
            config["layers"], passes, config["num_attention_heads"],
            config["head_dim"], config["intermediate_size"]),
        "loop_head": loop_cost.loop_head_step_cost(
            seq, rows, config["vocab_size"], config["hidden_size"], passes),
    }
