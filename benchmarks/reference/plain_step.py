"""A plain single-worker training step to hold the system's step against.

``jax.value_and_grad`` of the configuration's own loss plus the bare optax
optimizer, jitted, on ONE device: no ``shard_map``, no
``DistributedOptimizer``, no fusion buffers, no collective. Data parallelism
is played out by hand: each rank's shard (or each micro-batch of one rank's
rows) goes through the same gradient function in turn, with that rank's own
auxiliary state (BatchNorm statistics), and the gradients are averaged in f32
before one optimizer update. Nothing here is imported from the program; the
configuration file hands in its model's loss.
"""

from __future__ import annotations

import functools

import numpy as np


def run_plain_steps(grad_fn, optimizer, params, aux, shards, steps):
    """``grad_fn(params, aux_r, *shard) -> (loss, new_aux_r, grads)`` is
    called once per shard per step. Returns (losses, params after the last
    step). ``aux`` is a list with one entry per shard (entries may be None).
    Shards must be of equal size: the loss is the mean of the shards' means."""
    import jax
    import optax

    @functools.partial(jax.jit, donate_argnums=0)
    def add(a, b):
        return jax.tree_util.tree_map(lambda x, y: x + y, a, b)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def apply(params, opt_state, summed):
        grads = jax.tree_util.tree_map(lambda g: g / len(shards), summed)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    opt_state = jax.jit(optimizer.init)(params)
    aux = list(aux)
    losses = []
    for _ in range(steps):
        summed, shard_losses = None, []
        for r, shard in enumerate(shards):
            loss, aux[r], grads = grad_fn(params, aux[r], *shard)
            summed = grads if summed is None else add(summed, grads)
            # Read each loss before the next shard is launched: a result's
            # buffers are allocated when its program is enqueued, so eight
            # micro-batches in flight would hold eight gradient trees.
            shard_losses.append(float(loss))
            del grads
        params, opt_state = apply(params, opt_state, summed)
        losses.append(float(np.mean(shard_losses)))
    return losses, params


def reference_steps(grad_fn, optimizer, params, aux, shards, seed, steps=2):
    """What a configuration's ``reference`` returns for the ``step`` kind: the
    plain steps' losses and a seeded sample of the parameters before and
    after them."""
    indices = sample_indices(params, seed)
    before = take_sample(params, indices)
    losses, params = run_plain_steps(grad_fn, optimizer, params, aux, shards,
                                     steps)
    return {"kind": "step", "indices": indices, "before": before,
            "after": take_sample(params, indices), "losses": losses}


def sample_indices(params, seed, leaves=16, per_leaf=2048):
    """A seeded sample of parameter positions: [(leaf number, flat indices)]."""
    import jax

    rng = np.random.default_rng(seed)
    sizes = [leaf.size for leaf in jax.tree_util.tree_leaves(params)]
    chosen = sorted(rng.choice(len(sizes), min(leaves, len(sizes)),
                               replace=False).tolist())
    return [(i, rng.integers(0, sizes[i], min(per_leaf, sizes[i])))
            for i in chosen]


def take_sample(params, indices):
    """The sampled positions of ``params`` as one float64 numpy vector."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def gather(params):
        flat = jax.tree_util.tree_leaves(params)
        return jnp.concatenate(
            [flat[i].reshape(-1)[idx].astype(jnp.float32)
             for i, idx in indices])

    return np.asarray(gather(params), np.float64)


def compare(system, reference, tolerance):
    """``system`` and ``reference`` are dicts with ``losses`` (per step) and
    ``before``/``after`` parameter samples. Returns the observed errors;
    raises ``AssertionError`` beyond ``tolerance`` (keys ``loss_rel`` and
    ``update_rel``)."""
    loss_rel = max(abs(a - b) / max(abs(b), 1e-12)
                   for a, b in zip(system["losses"], reference["losses"]))
    moved = reference["after"] - reference["before"]
    norm = float(np.linalg.norm(moved))
    if not norm > 0:
        raise AssertionError("the reference step did not move the sampled "
                             "parameters")
    update_rel = float(np.linalg.norm(
        (system["after"] - system["before"]) - moved)) / norm
    observed = {"loss_rel": loss_rel, "update_rel": update_rel,
                "system_losses": system["losses"],
                "reference_losses": reference["losses"]}
    if not (np.isfinite(loss_rel) and loss_rel <= tolerance["loss_rel"]):
        raise AssertionError(
            f"losses differ from the plain step's by {loss_rel:.3e} "
            f"(> {tolerance['loss_rel']}): {observed}")
    if not (np.isfinite(update_rel) and update_rel <= tolerance["update_rel"]):
        raise AssertionError(
            f"sampled parameter updates differ from the plain step's by "
            f"{update_rel:.3e} of their norm (> {tolerance['update_rel']}): "
            f"{observed}")
    return observed
