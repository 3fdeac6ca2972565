"""lm217m: the step a user of horovod_tpu writes for ``TransformerLM``
(copy of ``chip_smoke.phase_transformer``), its plain reference, its costs.

Every configuration module exposes the same three functions, so a
``model_config`` PR adds a pair of files and touches none:
``build(config, traffic, mesh, seed, **model_overrides)``,
``reference(config, traffic, mesh, seed, **model_overrides)`` and
``cost(config, traffic, chips)``.
"""

from __future__ import annotations

import contextlib


def _model(config, **overrides):
    from horovod_tpu.models import TransformerLM

    if config["dim"] != config["heads"] * config["head_dim"]:
        raise ValueError("lm217m: dim must be heads x head_dim")
    kw = dict(vocab=config["vocab"], dim=config["dim"], heads=config["heads"],
              layers=config["layers"], mlp_ratio=config["mlp_ratio"],
              attention=config["attention"])
    kw.update(overrides)
    return TransformerLM(**kw)


def _optimizer(config):
    import optax

    if config["optimizer"]["name"] != "adamw":
        raise ValueError(f"lm217m trains with adamw, not {config['optimizer']}")
    return optax.adamw(config["optimizer"]["learning_rate"])


def _loss_fn(model):
    import jax.numpy as jnp
    import optax

    def loss_fn(params, tokens):
        logits = model.apply({"params": params}, tokens)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), jnp.roll(tokens, -1, axis=1)).mean()

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
                                          config["vocab"], jnp.int32)


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
    # State and batch are made on the device by jitted, seeded functions and
    # placed as the step lays them out, so the second call does not compile.
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


@contextlib.contextmanager
def _plain_attention_in_dense_path():
    """The reference model is ``TransformerLM(attention="dense")`` with the
    benchmark's own f32 attention in place of the program's dense one, for
    the time its gradient function is traced."""
    from benchmarks.reference.attention import plain_causal_attention
    from horovod_tpu.models import transformer

    saved = transformer.causal_attention    # AttributeError if it ever moves
    transformer.causal_attention = plain_causal_attention
    try:
        yield
    finally:
        transformer.causal_attention = saved


def reference(config, traffic, mesh, seed, **model_overrides):
    """What the system's first two steps are held against, as the traffic
    file says. ``step``: the plain single-worker step's losses and a seeded
    sample of its parameters before and after. ``kernel``: where a T x T
    reference cannot be held, the kernels alone at the cell's own shape
    (raises ``AssertionError`` beyond the tolerance)."""
    import jax
    from jax.sharding import SingleDeviceSharding

    from benchmarks.reference import attention, plain_step

    rows, seq = traffic["global_rows"], traffic["seq"]
    if traffic["reference"] == "kernel":
        from horovod_tpu.ops.flash_attention import (DEFAULT_BLOCK_K,
                                                     DEFAULT_BLOCK_Q,
                                                     flash_attention)

        interpret = model_overrides.get("flash_interpret", False)

        def kernel(q, k, v):    # positional: custom_vjp nondiff_argnums
            return flash_attention(q, k, v, True, DEFAULT_BLOCK_Q,
                                   DEFAULT_BLOCK_K, interpret)

        return {"kind": "kernel", "observed": attention.check_kernel_slice(
            kernel, seq, config["heads"], config["head_dim"], seed,
            slice_len=traffic["reference_slice"])}

    one = SingleDeviceSharding(mesh.devices.flat[0])
    key = jax.random.PRNGKey(seed)
    model = _model(config, **{**model_overrides, "attention": "dense"})
    params = jax.jit(_init_params(model), out_shardings=one)(key)
    tokens = jax.jit(_tokens_fn(config, rows, seq), out_shardings=one)(
        jax.random.fold_in(key, 1))
    micro = traffic["reference_micro_rows"]
    shards = [(tokens[i:i + micro],) for i in range(0, rows, micro)]
    loss_fn = _loss_fn(model)

    @jax.jit
    def grad_fn(params, aux, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
        return loss, aux, grads

    with _plain_attention_in_dense_path():
        return plain_step.reference_steps(
            grad_fn, _optimizer(config), params, [None] * len(shards), shards,
            seed)


def cost(config, traffic, chips):
    """Needed operations per step per chip (forward + backward, causal half,
    recompute not counted) and what the flash kernels of a step need."""
    from benchmarks import flops

    rows = traffic["global_rows"] // chips
    return {
        "model_flops": flops.transformer_step_flops(
            traffic["seq"], rows, config["vocab"], config["dim"],
            config["heads"], config["layers"], config["mlp_ratio"]),
        "kernel": flops.flash_step_cost(
            traffic["seq"], rows, config["heads"], config["head_dim"],
            config["layers"]),
    }
