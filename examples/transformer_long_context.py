"""Long-context training demo: sequence parallelism with ring attention.

Beyond the reference's capability set (SURVEY.md §5.7 documents its absence
there): shard a long sequence across a mesh axis, compute exact causal
attention blockwise with K/V rotating over ICI, and average gradients over
the data-parallel axis — dp x sp in one shard_map.

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/transformer_long_context.py --seq-len 2048
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))  # run from repo without install

import argparse

import jax
import jax.numpy as jnp
import numpy as np
import optax
from horovod_tpu.compat import shard_map
from jax.sharding import Mesh, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models import TransformerLM


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--seq-len", type=int, default=2048)
    parser.add_argument("--dp", type=int, default=2)
    parser.add_argument("--dim", type=int, default=256)
    parser.add_argument("--layers", type=int, default=4)
    parser.add_argument("--steps", type=int, default=5)
    parser.add_argument("--attention", choices=["dense", "flash"],
                        default="dense",
                        help="'flash' fuses each ring step's local block "
                             "product as pallas kernels (ops/ring_flash.py) "
                             "— the schedule for very long per-shard blocks")
    parser.add_argument("--virtual-devices", type=int, default=0,
                        help="force an N-device virtual CPU mesh (for trying "
                             "the schedule without a pod)")
    args = parser.parse_args()

    if args.virtual_devices:
        try:
            from horovod_tpu.compat import set_num_cpu_devices

            set_num_cpu_devices(args.virtual_devices)
            jax.config.update("jax_platforms", "cpu")
        except RuntimeError as e:
            raise SystemExit(f"--virtual-devices must be set before jax "
                             f"initializes a backend: {e}")

    hvd.init()
    devs = jax.devices()
    if len(devs) < 2 * args.dp:
        raise SystemExit(
            f"need at least {2 * args.dp} devices for dp={args.dp} x sp>=2, "
            f"have {len(devs)}; rerun with --virtual-devices 8 to try the "
            "schedule on a virtual CPU mesh")
    sp = len(devs) // args.dp
    mesh = Mesh(np.asarray(devs).reshape(args.dp, sp), ("dp", "sp"))
    if args.seq_len % sp:
        raise SystemExit(f"--seq-len must be divisible by sp={sp}")

    model = TransformerLM(vocab=256, dim=args.dim, heads=8,
                          layers=args.layers, sp_axis="sp",
                          attention=args.attention,
                          # the virtual CPU mesh was asked for by flag, and
                          # with it the Pallas interpreter for the kernels
                          flash_interpret=bool(args.virtual_devices))
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 256, size=(2 * args.dp, args.seq_len)),
        jnp.int32)
    init_twin = TransformerLM(vocab=256, dim=args.dim, heads=8, layers=args.layers)
    params = init_twin.init(jax.random.PRNGKey(0), tokens[:1, :64])["params"]

    opt = hvd.jax.DistributedOptimizer(optax.adamw(3e-4), axis_name=("dp", "sp"))
    opt_state = opt.init(params)

    def loss_fn(params, tokens):
        t_local = tokens.shape[1]
        pos = (jax.lax.axis_index("sp") * t_local + jnp.arange(t_local))[None, :]
        logits = model.apply({"params": params}, tokens, pos)
        targets = jnp.roll(tokens, -1, axis=1)
        return optax.softmax_cross_entropy_with_integer_labels(logits, targets).mean()

    def train_step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, jax.lax.pmean(loss, ("dp", "sp"))

    step = jax.jit(shard_map(
        train_step, mesh=mesh,
        in_specs=(P(), P(), P("dp", "sp")),
        out_specs=(P(), P(), P()),
        check_vma=False,
    ))

    for i in range(args.steps):
        params, opt_state, loss = step(params, opt_state, tokens)
        if hvd.rank() == 0:
            print(f"step {i}: loss {float(loss):.4f} "
                  f"(seq {args.seq_len} over {sp} sequence shards)")
    hvd.shutdown()


if __name__ == "__main__":
    main()
