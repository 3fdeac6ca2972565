"""resnet50: the step a user of horovod_tpu writes for ``ResNet50`` (copy of
``bench.build_resnet_step`` as PR 21 ran it, no environment knob read), its
plain reference and its costs. Same three functions as every configuration
module: ``build``, ``reference``, ``cost``.
"""

from __future__ import annotations


def _model(config, **overrides):
    from horovod_tpu.models.resnet import BottleneckBlock, ResNet

    # models.ResNet50 is exactly this at stage_sizes (3, 4, 6, 3).
    kw = dict(stage_sizes=tuple(config["stage_sizes"]),
              block_cls=BottleneckBlock, num_classes=config["num_classes"],
              num_filters=config["num_filters"])
    kw.update(overrides)
    return ResNet(**kw)


def _optimizer(config, n_chips):
    import optax

    o = config["optimizer"]
    if o["name"] != "sgd":
        raise ValueError(f"resnet50 trains with sgd, not {o}")
    return optax.sgd(o["learning_rate_per_chip"] * n_chips,
                     momentum=o["momentum"])


def _loss_fn(model):
    import optax

    def loss_fn(params, batch_stats, x, y):
        logits, new_state = model.apply(
            {"params": params, "batch_stats": batch_stats}, x, train=True,
            mutable=["batch_stats"])
        loss = optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()
        return loss, new_state["batch_stats"]

    return loss_fn


def _init_fn(model, image):
    import jax.numpy as jnp

    def init(key):
        variables = model.init(key, jnp.ones((2, image, image, 3), jnp.float32),
                               train=False)
        return variables["params"], variables["batch_stats"]

    return init


def _batch_fn(config, batch):
    import jax
    import jax.numpy as jnp

    image = config["image"]

    def make(key):
        kx, ky = jax.random.split(key)
        x = jax.random.normal(kx, (batch, image, image, 3), jnp.float32)
        y = jax.random.randint(ky, (batch,), 0, config["num_classes"], jnp.int32)
        return x, y

    return make


def build(config, traffic, mesh, seed, **model_overrides):
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.compat import shard_map

    from benchmarks.reduce_trace import SCOPE_FWD_BWD, SCOPE_OPTIMIZER

    n_dev = mesh.size
    batch = traffic["global_batch"]
    if batch % n_dev:
        raise ValueError(f"{batch} images do not divide over {n_dev} chips")
    A = hvd.HVD_AXIS
    model = _model(config, **model_overrides)
    opt = hvd.jax.DistributedOptimizer(
        _optimizer(config, n_dev),
        fusion_threshold=config["optimizer"]["fusion_threshold_bytes"])
    data = NamedSharding(mesh, P(A))
    replicated = NamedSharding(mesh, P())
    key = jax.random.PRNGKey(seed)

    def init(key):
        params, stats = _init_fn(model, config["image"])(key)
        # Per-rank BN statistics: a leading device-axis dim; each shard owns
        # row r and never syncs it in-step.
        stats = jax.tree_util.tree_map(
            lambda t: jnp.broadcast_to(t[None], (n_dev,) + t.shape), stats)
        return params, stats, opt.init(params)

    # Made on the device, placed as the step's specs lay them out: unplaced
    # state makes the SECOND call compile again.
    params, batch_stats, opt_state = jax.jit(
        init, out_shardings=(replicated, data, replicated))(key)
    x, y = jax.jit(_batch_fn(config, batch), out_shardings=(data, data))(
        jax.random.fold_in(key, 1))
    loss_fn = _loss_fn(model)

    def train_step(params, batch_stats, opt_state, x, y):
        local_stats = jax.tree_util.tree_map(lambda t: t[0], batch_stats)
        with jax.named_scope(SCOPE_FWD_BWD):
            (loss, local_stats), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, local_stats, x, y)
        with jax.named_scope(SCOPE_OPTIMIZER):
            updates, opt_state = opt.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        batch_stats = jax.tree_util.tree_map(lambda t: t[None], local_stats)
        return params, batch_stats, opt_state, jax.lax.pmean(loss, A)

    step = jax.jit(
        shard_map(train_step, mesh=mesh,
                  in_specs=(P(), P(A), P(), P(A), P(A)),
                  out_specs=(P(), P(A), P(), P()), check_vma=False),
        donate_argnums=(0, 1, 2))
    return {"step": step, "state": [params, batch_stats, opt_state],
            "batch": (x, y), "samples_per_step": batch}


def reference(config, traffic, mesh, seed, **model_overrides):
    """What the system's first two steps are held against: the plain
    single-worker step - each rank's shard in turn on one device with that
    rank's BatchNorm statistics, the gradients averaged, bare SGD. Returns
    its losses and a seeded sample of its parameters before and after."""
    import jax
    from jax.sharding import SingleDeviceSharding

    from benchmarks.reference import plain_step

    if traffic["reference"] != "step":
        raise ValueError(f"resnet50 has no {traffic['reference']!r} reference")
    n_dev = mesh.size
    per_chip = traffic["global_batch"] // n_dev
    one = SingleDeviceSharding(mesh.devices.flat[0])
    key = jax.random.PRNGKey(seed)
    model = _model(config, **model_overrides)
    params, stats = jax.jit(_init_fn(model, config["image"]),
                            out_shardings=one)(key)
    x, y = jax.jit(_batch_fn(config, traffic["global_batch"]),
                   out_shardings=(one, one))(jax.random.fold_in(key, 1))
    shards = [(x[r * per_chip:(r + 1) * per_chip],
               y[r * per_chip:(r + 1) * per_chip]) for r in range(n_dev)]
    loss_fn = _loss_fn(model)

    @jax.jit
    def grad_fn(params, stats, x, y):
        (loss, stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, stats, x, y)
        return loss, stats, grads

    return plain_step.reference_steps(
        grad_fn, _optimizer(config, n_dev), params, [stats] * n_dev, shards,
        seed)


def cost(config, traffic, chips):
    """Needed operations per step per chip (forward + backward); no kernel."""
    from benchmarks import flops

    return {
        "model_flops": flops.resnet_step_flops(
            traffic["global_batch"] // chips, config["image"],
            config["stage_sizes"], config["num_filters"],
            config["num_classes"]),
        "kernel": None,
    }
