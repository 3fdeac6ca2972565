"""Real-data input pipeline vs synthetic: the measured gap, four ways.

The reference's benchmark doc has a real-data variant of its headline
ResNet measurement (reference docs/benchmarks.md:40-63: the same harness
with `--data-dir` pointing at an ImageNet tree through DistributedSampler).
This is that variant for the TPU build: the jitted ResNet-50 train step of
``chip_smoke._build()`` (224 px, 128 images a chip), fed four ways —

1. ``synthetic``  — device-resident tensors: the input-pipeline-free
   ceiling.
2. ``stream``     — per-step host pipeline: memmap gather
   (horovod_tpu.data.MemmapArrayDataset + DistributedSampler) -> uint8
   host->device upload -> on-device cast. The classic streaming shape.
3. ``device-cache`` — the TPU-native shape this framework recommends: the
   rank's dataset SHARD is uploaded to HBM once (uint8 — ImageNet's 192 GB
   decoded-uint8 train set is 750 MB/chip on a v5e-256 pod), and the
   DistributedSampler contract (per-epoch seeded reshuffle, disjoint 1/N
   shard, lockstep steps) runs INSIDE the jitted step: on-device
   jax.random.permutation + gather + cast, with the epoch/step counter
   carried in donated state. Zero host->device bytes per step — the input
   pipeline cannot be the bottleneck because it does not exist at step time.

Mode 3 exists because per-step streaming pays a host->device transfer
every step (what it costs has not been measured on this installation:
PERF.md §7 plans a `resnet50_hoststream_1chip` cell). Device-cache
wins everywhere the shard fits HBM.

4. ``device-cache-scan`` — mode 3 through the packaged API
   (``hvd.jax.make_scan_train_loop``): cache sampling AND ``--scan-steps``
   optimizer steps per dispatch in one jitted loop, additionally
   amortizing the per-dispatch latency.

Usage: python examples/realdata_benchmark.py [--json]
       [--modes synthetic,stream,device-cache,device-cache-scan]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def parse_args():
    p = argparse.ArgumentParser()
    p.add_argument("--data-dir", default="/tmp/hvd_realdata")
    p.add_argument("--n-images", type=int, default=4096)
    p.add_argument("--num-warmup", type=int, default=5)
    p.add_argument("--window", type=int, default=20, help="steps per window")
    p.add_argument("--reps", type=int, default=3, help="windows (median)")
    p.add_argument("--modes",
                   default="synthetic,stream,device-cache,device-cache-scan")
    p.add_argument("--scan-steps", type=int, default=4,
                   help="steps per dispatch for the device-cache-scan mode "
                        "(hvd.jax.make_scan_train_loop)")
    p.add_argument("--json", action="store_true")
    return p.parse_args()


def ensure_dataset(data_dir: str, n: int, image: int) -> None:
    """uint8 ImageNet-shaped shards (the decoded-JPEG storage format)."""
    img_path = os.path.join(data_dir, "images.npy")
    if os.path.exists(img_path):
        existing = np.load(img_path, mmap_mode="r")
        # Row count AND shape must match: a stale dataset generated at a
        # different resolution would otherwise feed the wrong image size
        # to the model.
        if len(existing) >= n and existing.shape[1:] == (image, image, 3):
            return
    os.makedirs(data_dir, exist_ok=True)
    rng = np.random.default_rng(0)
    out = np.lib.format.open_memmap(img_path, mode="w+", dtype=np.uint8,
                                    shape=(n, image, image, 3))
    for i in range(0, n, 512):
        m = min(512, n - i)
        out[i:i + m] = rng.integers(0, 256, (m, image, image, 3), dtype=np.uint8)
    out.flush()
    del out
    np.save(os.path.join(data_dir, "labels.npy"),
            rng.integers(0, 1000, size=(n,), dtype=np.int64))


def main() -> int:
    args = parse_args()
    modes = args.modes.split(",")
    import jax
    import jax.numpy as jnp

    import horovod_tpu as hvd
    from horovod_tpu.data import (DeviceCache, DistributedSampler,
                                  MemmapArrayDataset)

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    import chip_smoke

    hvd.init()
    step, state0, (x_syn, y_syn), batch, n_dev = chip_smoke._build()

    ensure_dataset(args.data_dir, args.n_images, 224)
    ds = MemmapArrayDataset(args.data_dir)
    sampler = DistributedSampler(len(ds))
    shard_idx = np.asarray(sampler.indices())  # this rank's disjoint 1/N
    cache = None
    if "device-cache" in modes or "device-cache-scan" in modes:
        imgs, labs = ds[shard_idx]
        # horovod_tpu.data.DeviceCache: this rank's shard in HBM + the
        # sampler contract in-jit, at the train step's batch size.
        cache = DeviceCache(imgs, labs, batch_size=batch, seed=sampler.seed)
        jax.block_until_ready(cache.data)

    @jax.jit
    def cast_norm(x_u8):
        # On-device decode tail: uint8 -> f32, [0,255] -> [-1,1). Fused by
        # XLA into the first conv's input.
        return x_u8.astype(jnp.float32) / 127.5 - 1.0

    def fresh_state():
        # step donates its state: give each mode its own device copy.
        return list(jax.tree_util.tree_map(lambda t: jnp.array(t, copy=True),
                                           tuple(state0)))

    def measure(run_step):
        """Chained dispatches, one loss fence per window,
        median over reps. run_step(state) -> (state, loss)."""
        state = fresh_state()
        loss = None
        for _ in range(args.num_warmup):
            state, loss = run_step(state)
        float(loss)
        rates = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            for _ in range(args.window):
                state, loss = run_step(state)
            float(loss)
            rates.append(args.window / (time.perf_counter() - t0))
        return float(np.median(rates)) * batch

    results = {}

    if "synthetic" in modes:
        def syn_step(state):
            *state, loss = step(*state, x_syn, y_syn)
            return state, loss

        results["synthetic"] = measure(syn_step)

    if "stream" in modes:
        stream: list = []
        epoch_box = [0]

        def refill():
            sampler.set_epoch(epoch_box[0])
            stream.extend(sampler.batches(batch))
            epoch_box[0] += 1

        refill()

        def stream_step(state):
            if not stream:
                refill()
            xb, yb = ds[stream.pop(0)]
            xd = cast_norm(jax.device_put(jnp.asarray(xb)))
            yd = jax.device_put(jnp.asarray(yb.astype(np.int32)))
            *state, loss = step(*state, xd, yd)
            return state, loss

        results["stream"] = measure(stream_step)

    if "device-cache" in modes:
        def cached_train(params, bstats, ostate, ctr, data, labels):
            # The sampler runs in-trace; the counter rides in donated state
            # so no scalar ever crosses host->device at step time. data /
            # labels cross the jit boundary as ARGUMENTS (closing over them
            # would bake the whole shard in as a compile-time constant).
            x, y, ctr = cache.sample(ctr, data, labels)
            out = step(params, bstats, ostate, x, y)
            return out + (ctr,)

        cached = jax.jit(cached_train, donate_argnums=(0, 1, 2, 3))

        def cache_step(state):
            if len(state) == 3:
                state = state + [cache.counter()]
            *state, loss, ctr = cached(*state[:4], cache.data, cache.labels)
            return state[:3] + [ctr], loss

        results["device-cache"] = measure(cache_step)

    if "device-cache-scan" in modes:
        # The packaged API: cache sampling + K steps per dispatch in ONE
        # jitted loop (hvd.jax.make_scan_train_loop) — amortizes dispatch
        # latency on top of eliminating per-step transfers. train_step
        # adapts the 4-state step to the loop's 3-state contract by
        # folding batch_stats into the optimizer-state slot.
        K = args.scan_steps  # <1 rejected by make_scan_train_loop

        def adapter(pb, ob, x, y):
            bstats, ostate = ob
            p, bstats, ostate, loss = step(pb, bstats, ostate, x, y)
            return p, (bstats, ostate), loss

        loop = hvd.jax.make_scan_train_loop(adapter, cache,
                                            steps_per_dispatch=K)

        packed = {"done": False}

        def scan_step(state):
            if not packed["done"]:  # first call: fold the step's 3-part state
                p, bstats, ostate = state
                state = [p, (bstats, ostate), cache.counter()]
                packed["done"] = True
            p, ob, ctr, loss = loop(state[0], state[1], state[2],
                                    cache.data, cache.labels)
            return [p, ob, ctr], loss

        # measure() counts dispatches; each carries K steps.
        results["device-cache-scan"] = measure(scan_step) * K

    base = results.get("synthetic")
    out = {"batch": batch, "n_images": args.n_images}
    for k, v in results.items():
        out[f"{k}_img_s"] = round(v, 1)
        if base and k != "synthetic":
            out[f"{k}_gap_pct"] = round((1 - v / base) * 100, 2)
    if args.json:
        print(json.dumps(out))
    else:
        for k, v in results.items():
            gap = f"  (gap {out[f'{k}_gap_pct']}%)" if f"{k}_gap_pct" in out else ""
            print(f"{k:13s}: {v:,.0f} img/s{gap}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
