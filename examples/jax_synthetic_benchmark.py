"""Synthetic model benchmark — the reference's
examples/pytorch_synthetic_benchmark.py for the TPU build: reports img/sec
per device mean +/- 1.96 sigma and the aggregate (reference
pytorch_synthetic_benchmark.py:96-110).

    python examples/jax_synthetic_benchmark.py --model ResNet50 --batch-size 64
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))  # run from repo without install

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
from horovod_tpu.compat import shard_map
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu import models as model_zoo


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--model", default="ResNet50",
                        help="any name in horovod_tpu.models")
    parser.add_argument("--batch-size", type=int, default=64,
                        help="per-device batch size")
    parser.add_argument("--image-size", type=int, default=224)
    parser.add_argument("--num-iters", type=int, default=10)
    parser.add_argument("--num-batches-per-iter", type=int, default=10)
    parser.add_argument("--num-warmup-batches", type=int, default=10)
    parser.add_argument("--roofline", action="store_true",
                        help="after the throughput loop, profile the step "
                             "with the XLA device profiler and print where "
                             "its device time goes, by the program's names "
                             "(hvd.metrics.profile_step)")
    args = parser.parse_args()

    hvd.init()
    mesh = hvd.default_mesh()
    n_dev = mesh.size
    batch = args.batch_size * n_dev

    model = getattr(model_zoo, args.model)(num_classes=1000)
    x = jnp.ones((batch, args.image_size, args.image_size, 3), jnp.float32)
    y = jnp.zeros((batch,), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), x[:2], train=False)
    params, batch_stats = variables["params"], variables["batch_stats"]
    opt = hvd.jax.DistributedOptimizer(optax.sgd(0.01 * n_dev, momentum=0.9))
    opt_state = opt.init(params)

    def loss_fn(params, batch_stats, x, y):
        logits, new_state = model.apply(
            {"params": params, "batch_stats": batch_stats}, x, train=True,
            mutable=["batch_stats"])
        return (optax.softmax_cross_entropy_with_integer_labels(logits, y).mean(),
                new_state["batch_stats"])

    def train_step(params, batch_stats, opt_state, x, y):
        (loss, batch_stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch_stats, x, y)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        batch_stats = jax.tree_util.tree_map(
            lambda t: jax.lax.pmean(t, hvd.HVD_AXIS), batch_stats)
        return params, batch_stats, opt_state, jax.lax.pmean(loss, hvd.HVD_AXIS)

    step = jax.jit(shard_map(
        train_step, mesh=mesh,
        in_specs=(P(), P(), P(), P(hvd.HVD_AXIS), P(hvd.HVD_AXIS)),
        out_specs=(P(), P(), P(), P()),
        check_vma=False,
    ))

    def run_batches(n):
        nonlocal params, batch_stats, opt_state
        for _ in range(n):
            params, batch_stats, opt_state, loss = step(
                params, batch_stats, opt_state, x, y)
        float(loss)  # hard sync (host read)

    if hvd.rank() == 0:
        print(f"Model: {args.model}, batch {args.batch_size}/device x {n_dev} devices")
    run_batches(args.num_warmup_batches)

    img_secs = []
    for i in range(args.num_iters):
        t0 = time.perf_counter()
        run_batches(args.num_batches_per_iter)
        dt = time.perf_counter() - t0
        rate = batch * args.num_batches_per_iter / dt / n_dev
        img_secs.append(rate)
        if hvd.rank() == 0:
            print(f"Iter #{i}: {rate:.1f} img/sec per device")

    img_sec_mean = np.mean(img_secs)
    img_sec_conf = 1.96 * np.std(img_secs)
    if hvd.rank() == 0:
        print(f"Img/sec per device: {img_sec_mean:.1f} +- {img_sec_conf:.1f}")
        print(f"Total img/sec on {n_dev} device(s): "
              f"{n_dev * img_sec_mean:.1f} +- {n_dev * img_sec_conf:.1f}")

    if args.roofline:
        # EVERY rank must run the collective steps (rank-0-only would
        # deadlock a multi-process --jax-distributed world); only rank 0
        # prints its device's report.
        rep = hvd.metrics.profile_step(lambda: run_batches(1), steps=5)
        if hvd.rank() == 0:
            print(rep["text"] if rep["ok"] else
                  f"profile: unavailable ({rep['reason']})")

    hvd.shutdown()


if __name__ == "__main__":
    main()
