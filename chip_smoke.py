"""chip_smoke.py — the quickest proof that the trainer still starts on the chip.

Drives horovod_tpu's main path once, the way a user's script would:
``hvd.init()`` → ``hvd.default_mesh()`` → ``hvd.jax.DistributedOptimizer`` →
a jitted ``shard_map`` step whose gradients go through ``parallel/fusion.py``
and ``collectives.bucketed_allreduce``. One process drives every local chip
by SPMD; the script starts no other process. It has no size and no platform
switch: ``main()`` always runs the full widths below, and a machine where
JAX finds no TPU makes it exit non-zero within seconds, printing no result.

Phases (each prints one line naming platform, device_kind and device count,
and its ``compile_s``; any failure raises, so the exit code is non-zero):

1. device      — ``jax.devices()[0].platform == "tpu"``.
2. collective  — a rank-dependent gradient tree through
                 ``hvd.jax.allreduce_gradients`` in several buckets, against
                 the analytic mean (a synthetic all-ones batch cannot tell a
                 broken allreduce from a correct one).
3. resnet      — ResNet-50, 224 px, 128 images per chip, bf16 compute,
                 SGD+momentum, donated carries: ``_build()`` below.
4. kernels     — ``ops.flash_attention`` forward and backward COMPILED
                 (``interpret=False``) at the shapes the repo claims, against
                 a float32 ``jax.numpy`` dense reference: the f32 cases with
                 their dots at "highest" and held tight, the bf16 case as the
                 trainer runs it.
5. transformer — ``TransformerLM(vocab=32000, dim=1024, heads=8, layers=12,
                 attention="flash")``, seq 4096, batch 1 per chip, AdamW.
6. four_chip   — only with >= 4 devices: ring-flash over an ``sp`` axis of 4,
                 the sharded and hierarchical optimizers on 2x2 meshes against
                 flat data parallelism, the data-parallel MoE and ``ppermute``
                 pipeline legs of ``__graft_entry__`` on the real devices.

The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
It reports no rate and no utilisation: the times it prints are single
observations of one run, not metrics. ``tests/test_chip_smoke.py`` imports the
phase functions and runs them at tiny sizes on the virtual CPU mesh.

Run it through the chip tool from the root of a checkout:
``python chip_smoke.py``. The compile cache is ``JAX_COMPILATION_CACHE_DIR``
when that is set, else ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# dtype -> (matmul precision the kernel is traced under, what its errors are
# held to as a share of the reference's largest magnitude). At the default
# precision the MXU takes even f32 operands in bf16 passes, so an f32 case
# there errs like the bf16 one (2e-3..7e-3 on the chip) and proves nothing
# about the kernel's arithmetic. The f32 cases therefore run with the dots at
# "highest" (Mosaic's fp32 contraction) and are held two orders tighter: on
# the chip out errs 2e-7..3e-7 and the gradients 2e-5..6e-5 of max|ref|. The
# bf16 case is the program the trainer runs — bf16 operands, default
# precision, bf16 outputs (one rounding alone is 4e-3).
KERNEL_HELD = {"float32": ("highest", 5e-4), "bfloat16": (None, 2e-2)}
# ring_flash against the single-device kernel, both at the default precision
# (at "highest" the ring's kernels overflow scoped VMEM at 1024 blocks): the
# two round to bf16 at different points, 1e-4..3e-4 of max|ref| on the chip.
RING_REL_TOL = 2e-3
# (seq, heads, kv_heads, head_dim, dtype): head_dim 128 and 64, T 1024 and
# 4096, MHA and GQA, f32 and the bf16 the transformer phase feeds them.
KERNEL_CASES = (
    (1024, 8, 8, 128, "float32"),
    (4096, 8, 8, 128, "float32"),
    (4096, 8, 2, 128, "float32"),
    (1024, 16, 16, 64, "float32"),
    (4096, 16, 4, 64, "float32"),
    (4096, 8, 8, 128, "bfloat16"),
)
# Sharded / hierarchical vs flat DP after one SGD step: every device computes
# the same local gradient in all three, so only the f32 reduction order
# differs (4 addends).
PARITY_TOL = dict(rtol=1e-5, atol=1e-5)


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def report(phase: str, **fields) -> None:
    d = device_info()
    tail = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[chip_smoke] phase={phase} platform={d['platform']} "
          f"device_kind={d['kind']!r} devices={d['count']} {tail}", flush=True)


def _fmt(xs, spec=".4g") -> str:
    return "[" + ",".join(format(float(x), spec) for x in xs) + "]"


# ------------------------------------------------------------------ 1. device

def require_tpu(what: str) -> dict:
    """The full-width steps run on the chip or not at all: exits non-zero,
    naming what jax found, when the first device is not a TPU. There is no
    CPU fallback under a device metric's name."""
    d = device_info()
    if d["platform"] != "tpu":
        raise SystemExit(
            f"{what} needs a TPU, but jax.devices()[0] is platform="
            f"{d['platform']!r} kind={d['kind']!r} ({d['count']} device(s)). "
            "There is no CPU fallback.")
    return d


def phase_device() -> dict:
    d = require_tpu("chip_smoke.py")
    report("device", ok=True)
    return d


# -------------------------------------------------------------- 2. collective

def phase_collective(mesh, num_buckets: int = 3, threshold: int = 16 << 10):
    """Rank-dependent gradients through the DistributedOptimizer's exchange
    in several buckets; every leaf must equal the analytic mean."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.compat import shard_map
    from horovod_tpu.parallel import fusion

    n = mesh.size
    rng = np.random.default_rng(0)
    base = {f"leaf{i}": jnp.asarray(rng.standard_normal(shape), jnp.float32)
            for i, shape in enumerate([(10, 100), (257,), (64, 33), (3000,),
                                       (7, 11, 13), (1,)])}
    planned = fusion.build_plan(base, threshold, num_buckets=num_buckets)
    check(planned.num_buckets > 1,
          f"collective check planned {planned.num_buckets} bucket(s); the "
          "threshold no longer splits the tree")

    def body(tree):
        r = jax.lax.axis_index(hvd.HVD_AXIS).astype(jnp.float32)
        grads = jax.tree_util.tree_map(lambda t: t * (r + 1.0) + r, tree)
        return hvd.jax.allreduce_gradients(
            grads, num_buckets=num_buckets, fusion_threshold=threshold)

    fn = jax.jit(shard_map(body, mesh=mesh, in_specs=(P(),), out_specs=P(),
                           check_vma=False))
    t0 = time.monotonic()
    got = jax.block_until_ready(fn(base))
    compile_s = time.monotonic() - t0
    # mean over ranks r of t*(r+1)+r
    worst = 0.0
    for k, t in base.items():
        want = np.asarray(t) * (n + 1) / 2.0 + (n - 1) / 2.0
        err = float(np.max(np.abs(np.asarray(got[k]) - want)))
        worst = max(worst, err)
        check(err <= 1e-5 * (1.0 + float(np.max(np.abs(want)))),
              f"allreduce of {k} is off the analytic mean by {err:.3e}")
    report("collective", compile_s=f"{compile_s:.2f}",
           buckets=planned.num_buckets, ranks=n,
           max_abs_err=f"{worst:.2e}", ok=True)


# ------------------------------------------------------------------ 3. resnet

def _build():
    """The full-width ResNet step: ResNet-50 at 224 px, 128 images per
    chip, built by :func:`build_resnet_step`. Raises off-chip
    (:func:`require_tpu`) — it never shrinks to fit a CPU."""
    from horovod_tpu.models import ResNet50

    require_tpu("the ResNet-50 step")
    # Per-device batch 128: the reference benchmark uses 64/GPU
    # (docs/benchmarks.md:22) sized for 2015 Pascal HBM; a v5e chip has the
    # memory and MXU width for 128.
    return build_resnet_step(ResNet50(num_classes=1000), image=224,
                             per_dev_batch=128)


def build_resnet_step(model, image, per_dev_batch, hierarchical=False):
    """Model + jitted train step + fresh state + mesh-sharded synthetic
    batch, at the size given. The one ResNet step builder: ``main()`` and
    examples/realdata_benchmark.py reach it through :func:`_build`, and the
    CPU tests call it at a tiny size.
    ``hierarchical`` runs the gradient allreduce as the
    RS(ici)→psum(dcn)→AG(ici) ladder over the 2-D ``('dcn','ici')`` mesh —
    only meaningful on multi-chip topologies."""
    import jax
    import jax.numpy as jnp
    import optax
    from horovod_tpu.compat import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd

    mesh = hvd.hierarchical_mesh() if hierarchical else hvd.default_mesh()
    # Data axis: the flat world, or both levels of the 2-D hierarchy.
    A = ("dcn", "ici") if hierarchical else hvd.HVD_AXIS
    n_dev = mesh.size
    batch = per_dev_batch * n_dev

    # Batch and state are placed as the step's specs lay them out, from the
    # start: an unplaced batch would be re-scattered from device 0 on every
    # step, and unplaced state makes the SECOND call compile again (its
    # inputs are then the first call's mesh-sharded outputs).
    data = NamedSharding(mesh, P(A))
    replicated = NamedSharding(mesh, P())
    x = jax.device_put(jnp.ones((batch, image, image, 3), jnp.float32), data)
    y = jax.device_put(jnp.zeros((batch,), jnp.int32), data)
    variables = jax.jit(lambda key, x: model.init(key, x, train=False))(
        jax.random.PRNGKey(0), jnp.ones((2, image, image, 3), jnp.float32))
    params = jax.device_put(variables["params"], replicated)
    # Per-rank BN stats: replicate the initial stats into a leading
    # device-axis dim; each shard owns row r and never syncs it in-step.
    batch_stats = jax.device_put(jax.tree_util.tree_map(
        lambda t: jnp.broadcast_to(t[None], (n_dev,) + t.shape),
        variables["batch_stats"]), data)

    # Fusion threshold: 256 MiB — the whole ~100 MB gradient set in one
    # bucket. HOROVOD_FUSION_THRESHOLD still overrides. The `or` spelling
    # keeps 256 MiB this step's own seed, not a second default for the
    # knob (the engine default stays config.py's 64 MiB).
    opt = hvd.jax.DistributedOptimizer(
        optax.sgd(0.01 * n_dev, momentum=0.9),
        fusion_threshold=int(
            os.environ.get("HOROVOD_FUSION_THRESHOLD") or 256 << 20),
        hierarchical=hierarchical,
    )
    opt_state = jax.device_put(opt.init(params), replicated)

    def loss_fn(params, batch_stats, x, y):
        logits, new_state = model.apply(
            {"params": params, "batch_stats": batch_stats}, x, train=True,
            mutable=["batch_stats"],
        )
        loss = optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()
        return loss, new_state["batch_stats"]

    def train_step(params, batch_stats, opt_state, x, y):
        # batch_stats arrive as this rank's (1, ...) shard: drop the rank dim
        # for the model, restore it for the sharded out_spec.
        local_stats = jax.tree_util.tree_map(lambda t: t[0], batch_stats)
        (loss, local_stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, local_stats, x, y
        )
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        batch_stats = jax.tree_util.tree_map(lambda t: t[None], local_stats)
        loss = jax.lax.pmean(loss, A)
        return params, batch_stats, opt_state, loss

    step = jax.jit(
        shard_map(
            train_step,
            mesh=mesh,
            in_specs=(P(), P(A), P(), P(A), P(A)),
            out_specs=(P(), P(A), P(), P()),
            check_vma=False,
        ),
        # Donate params/batch_stats/opt_state: they are consumed and
        # re-produced every step, so XLA can update in place instead of
        # holding two copies (HBM bandwidth is the usual TPU bottleneck).
        donate_argnums=(0, 1, 2),
    )
    return step, (params, batch_stats, opt_state), (x, y), batch, n_dev


def phase_resnet(built, steps: int = 5):
    """``built`` is what :func:`build_resnet_step` returns. One compile
    step plus ``steps`` fenced steps on a rank-distinct seeded batch."""
    import jax
    import numpy as np

    step, state, (x, y), batch, n_dev = built
    check(x.sharding.device_set == set(jax.devices()),
          f"the batch lives on {len(x.sharding.device_set)} of "
          f"{len(jax.devices())} devices")
    # Same shape, dtype and sharding as the builder's synthetic batch, but
    # seeded noise (each device's shard differs). Labels stay below 10 so
    # any head of >= 10 classes accepts them.
    x = jax.jit(lambda k: jax.random.normal(k, x.shape, x.dtype),
                out_shardings=x.sharding)(jax.random.PRNGKey(1))
    y = jax.jit(lambda k: jax.random.randint(k, y.shape, 0, 10, y.dtype),
                out_shardings=y.sharding)(jax.random.PRNGKey(2))

    state = list(state)
    losses, step_s = [], []
    for _ in range(steps + 1):      # call 0 compiles
        t0 = time.monotonic()
        *state, loss = step(*state, x, y)
        jax.block_until_ready((state, loss))
        step_s.append(time.monotonic() - t0)
        losses.append(float(loss))
    check(all(np.isfinite(losses)), f"non-finite ResNet loss: {losses}")
    check(losses[-1] < losses[0],
          f"ResNet loss did not fall over {steps} steps: {losses}")
    params = state[0]
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        shards = [np.asarray(s.data) for s in leaf.addressable_shards]
        check(len(shards) == len(jax.local_devices()),
              f"{jax.tree_util.keystr(path)} is not on every device")
        check(all(s.tobytes() == shards[0].tobytes() for s in shards[1:]),
              f"replicated parameter {jax.tree_util.keystr(path)} differs "
              "across devices after the last step")
    report("resnet", compile_s=f"{step_s[0]:.2f}", batch=batch,
           image=x.shape[1], steps=steps, step_s=_fmt(step_s[1:], ".3f"),
           loss=_fmt(losses), replicas_bit_equal=True, ok=True)


# ----------------------------------------------------------------- 4. kernels

def _dense_reference(q, k, v):
    """The repo's dense causal oracle (plain ``jax.numpy``), kv heads
    replicated for grouped-query inputs."""
    import jax.numpy as jnp

    from horovod_tpu.ops.ring_attention import causal_reference

    group = q.shape[2] // k.shape[2]
    return causal_reference(q, jnp.repeat(k, group, axis=2),
                            jnp.repeat(v, group, axis=2))


def _out_and_grads(fn, q, k, v, g):
    """``(out, dq, dk, dv)`` of ``fn(q, k, v)`` under the cotangent ``g``,
    as one jitted program."""
    import jax

    # g is an argument, not a closure: a closed-over array is baked into
    # the executable as a constant (tens of MB per compile-cache entry).
    def both(q, k, v, g):
        out, vjp = jax.vjp(fn, q, k, v)
        return (out, *vjp(g.astype(out.dtype)))

    return jax.jit(both)(q, k, v, g)


def _held_errors(what: str, got, want, rel_tol: float) -> str:
    """Hold each of out/dq/dk/dv to rel_tol x max|want| (compared in
    float32); returns the max abs errors formatted for the phase line."""
    import jax.numpy as jnp

    f32 = jnp.float32
    errs = []
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        err = float(jnp.max(jnp.abs(a.astype(f32) - b.astype(f32))))
        bound = rel_tol * float(jnp.max(jnp.abs(b.astype(f32))))
        check(err <= bound,
              f"{what}: {name} max abs err {err:.3e} exceeds "
              f"{rel_tol} x max|ref| = {bound:.3e}")
        errs.append(err)
    return "out/dq/dk/dv=" + "/".join(f"{e:.2e}" for e in errs)


def _qkvg(seq, heads, kv_heads, head_dim, dtype):
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.PRNGKey(seq + heads + head_dim), 4)
    shapes = [(1, seq, h, head_dim) for h in (heads, kv_heads, kv_heads)]
    q, k, v = (jax.random.normal(kk, s, jnp.float32).astype(dtype)
               for kk, s in zip(ks, shapes))
    g = jax.random.normal(ks[3], shapes[0], jnp.float32)
    return q, k, v, g


def phase_kernels(cases=KERNEL_CASES, interpret: bool = False):
    import jax

    from horovod_tpu.ops.flash_attention import (DEFAULT_BLOCK_K,
                                                 DEFAULT_BLOCK_Q,
                                                 flash_attention)

    def flash(q, k, v):
        # positional: custom_vjp nondiff_argnums
        return flash_attention(q, k, v, True, DEFAULT_BLOCK_Q,
                               DEFAULT_BLOCK_K, interpret)

    for seq, heads, kv_heads, head_dim, dtype in cases:
        precision, rel_tol = KERNEL_HELD[dtype]
        q, k, v, g = _qkvg(seq, heads, kv_heads, head_dim, dtype)
        t0 = time.monotonic()
        with jax.default_matmul_precision(precision):
            got = jax.block_until_ready(_out_and_grads(flash, q, k, v, g))
        wall = time.monotonic() - t0
        with jax.default_matmul_precision("highest"):
            q32, k32, v32 = (t.astype("float32") for t in (q, k, v))
            errs = _held_errors(
                f"flash at seq={seq} heads={heads}/{kv_heads} d={head_dim} "
                f"{dtype} precision={precision}", got,
                _out_and_grads(_dense_reference, q32, k32, v32, g), rel_tol)
        report("kernels", compile_s=f"{wall:.2f}", seq=seq,
               heads=f"{heads}/{kv_heads}", head_dim=head_dim, dtype=dtype,
               precision=precision or "default",
               blocks=f"{min(DEFAULT_BLOCK_Q, seq)}/{min(DEFAULT_BLOCK_K, seq)}",
               interpret=interpret,
               max_abs_err=errs,
               tol=f"{rel_tol}*max|ref|", ok=True)


# ------------------------------------------------------------- 5. transformer

def phase_transformer(model, seq: int, per_dev_batch: int = 1,
                      steps: int = 3):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.compat import shard_map

    mesh = hvd.default_mesh()
    batch = per_dev_batch * mesh.size
    replicated = NamedSharding(mesh, P())
    tokens = jax.device_put(
        np.random.default_rng(0).integers(0, model.vocab, (batch, seq),
                                          dtype=np.int32),
        NamedSharding(mesh, P(hvd.HVD_AXIS)))
    # Parameter shapes do not depend on the sequence length: init short.
    # State is placed replicated up front, as the step returns it, so the
    # second call does not compile again.
    params = jax.device_put(
        jax.jit(model.init)(jax.random.PRNGKey(0),
                            jnp.zeros((1, min(seq, 128)), jnp.int32))["params"],
        replicated)
    n_params = sum(p.size for p in jax.tree_util.tree_leaves(params))
    opt = hvd.jax.DistributedOptimizer(optax.adamw(3e-4))
    opt_state = jax.device_put(opt.init(params), replicated)

    def loss_fn(params, tokens):
        logits = model.apply({"params": params}, tokens)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), jnp.roll(tokens, -1, axis=1)).mean()

    def train_step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, jax.lax.pmean(loss, hvd.HVD_AXIS)

    step = jax.jit(
        shard_map(train_step, mesh=mesh,
                  in_specs=(P(), P(), P(hvd.HVD_AXIS)),
                  out_specs=(P(), P(), P()), check_vma=False),
        donate_argnums=(0, 1))
    losses, step_s = [], []
    for _ in range(steps + 1):      # call 0 compiles
        t0 = time.monotonic()
        params, opt_state, loss = step(params, opt_state, tokens)
        jax.block_until_ready((params, loss))
        step_s.append(time.monotonic() - t0)
        losses.append(float(loss))
    check(all(np.isfinite(losses)), f"non-finite transformer loss: {losses}")
    check(losses[-1] < losses[0],
          f"transformer loss did not fall over {steps} steps: {losses}")
    report("transformer", compile_s=f"{step_s[0]:.2f}",
           params=f"{n_params / 1e6:.0f}M", seq=seq, batch=batch,
           attention=model.attention, steps=steps,
           step_s=_fmt(step_s[1:], ".3f"), loss=_fmt(losses), ok=True)


# --------------------------------------------------------------- 6. four chips

def _ring_flash_leg(devices, t_local: int, interpret: bool):
    """ring_flash over an ``sp`` axis of 4 against the single-device kernel
    on the whole sequence (GQA, so the ring rotates the small kv blocks)."""
    import jax
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.compat import shard_map
    from horovod_tpu.ops.flash_attention import (DEFAULT_BLOCK_K,
                                                 DEFAULT_BLOCK_Q,
                                                 flash_attention)
    from horovod_tpu.ops.ring_flash import ring_flash_attention

    mesh = hvd.training_mesh(sp=4, devices=devices)
    q, k, v, g = _qkvg(4 * t_local, 4, 2, 128, "float32")
    seq_sharded = P(None, "sp")
    ring = shard_map(
        lambda a, b, c: ring_flash_attention(
            a, b, c, "sp", False, DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K, interpret),
        mesh=mesh, in_specs=seq_sharded, out_specs=seq_sharded,
        check_vma=False)

    def single(a, b, c):
        return flash_attention(a, b, c, True, DEFAULT_BLOCK_Q,
                               DEFAULT_BLOCK_K, interpret)

    return _held_errors(f"ring_flash at t_local={t_local}",
                        _out_and_grads(ring, q, k, v, g),
                        _out_and_grads(single, q, k, v, g), RING_REL_TOL)


def _optimizer_parity_leg(devices):
    """One SGD step each of flat DP, ``DistributedOptimizer(sharded=True)``
    on a ('batch','shard') = 2x2 mesh and ``hierarchical=True`` on a
    ``hierarchical_mesh(ici_size=2)`` = 2x2 mesh; the updated parameters of
    the latter two must match flat DP within PARITY_TOL."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.compat import shard_map

    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    params = {"w1": jax.random.normal(ks[0], (64, 96)) * 0.1,
              "b1": jnp.zeros((96,)),
              "w2": jax.random.normal(ks[1], (96, 10)) * 0.1,
              "b2": jnp.zeros((10,))}
    x = jax.random.normal(ks[2], (32, 64))
    y = jax.random.randint(ks[3], (32,), 0, 10)

    def loss_fn(p, x, y):
        h = jnp.tanh(x @ p["w1"] + p["b1"])
        return optax.softmax_cross_entropy_with_integer_labels(
            h @ p["w2"] + p["b2"], y).mean()

    def dp_step(mesh, axis, **opt_kw):
        opt = hvd.jax.DistributedOptimizer(
            optax.sgd(0.1), fusion_threshold=16 << 10, num_buckets=2,
            **opt_kw)

        def step(p, st, x, y):
            g = jax.grad(loss_fn)(p, x, y)
            upd, st = opt.update(g, st, p)
            return optax.apply_updates(p, upd)

        return jax.jit(shard_map(
            step, mesh=mesh, in_specs=(P(), P(), P(axis), P(axis)),
            out_specs=P(), check_vma=False))(params, opt.init(params), x, y)

    flat = dp_step(hvd.data_parallel_mesh(devices), hvd.HVD_AXIS)
    hier = dp_step(hvd.hierarchical_mesh(devices, ici_size=2),
                   ("dcn", "ici"), hierarchical=True)

    mesh = hvd.sharded_mesh(batch=2, shard=2, devices=devices)
    plan = hvd.jax.build_shard_plan(params, 2, threshold=16 << 10,
                                    num_buckets=2)
    sp = hvd.jax.shard_params(params, plan)
    opt = hvd.jax.DistributedOptimizer(optax.sgd(0.1), sharded=True,
                                       shard_plan=plan)
    st = opt.init(sp)
    specs = hvd.jax.shard_specs(st)
    data = P((hvd.BATCH_AXIS, hvd.SHARD_AXIS))

    def sharded_step(sp, st, x, y):
        g = jax.grad(loss_fn)(hvd.jax.gather_params(sp, plan), x, y)
        upd, st = opt.update(g, st, sp)
        return optax.apply_updates(sp, upd)

    sp = jax.jit(shard_map(
        sharded_step, mesh=mesh,
        in_specs=(P(hvd.SHARD_AXIS), specs, data, data),
        out_specs=P(hvd.SHARD_AXIS), check_vma=False))(sp, st, x, y)
    shard = hvd.jax.unshard_params(sp, plan)

    for name, got in (("sharded", shard), ("hierarchical", hier)):
        for k in params:
            a, b = np.asarray(got[k]), np.asarray(flat[k])
            check(not np.array_equal(b, np.asarray(params[k])),
                  f"flat DP left {k} unchanged")
            check(np.allclose(a, b, **PARITY_TOL),
                  f"{name} optimizer's {k} is off flat DP by "
                  f"{float(np.max(np.abs(a - b))):.3e} (tolerance "
                  f"{PARITY_TOL})")


def phase_four_chip(t_local: int = 1024, interpret: bool = False):
    import jax

    import __graft_entry__ as legs

    devices = jax.devices()[:4]
    t0 = time.monotonic()
    ring_errs = _ring_flash_leg(devices, t_local, interpret)
    _optimizer_parity_leg(devices)
    legs._moe_dp_step(4)        # dropless experts, data parallel
    legs._pipeline_pp_step(4)   # ppermute pipeline, against its oracle
    report("four_chip", compile_s=f"{time.monotonic() - t0:.2f}",
           ring_flash_t_local=t_local,
           ring_flash_max_abs_err=ring_errs,
           ring_flash_tol=f"{RING_REL_TOL}*max|single|",
           sharded_2x2=True, hierarchical_2x2=True, moe_data_parallel=True,
           pipeline_ppermute=True, parity_tol=PARITY_TOL["rtol"], ok=True)


# ----------------------------------------------------------------------- main

def main() -> int:
    from horovod_tpu.utils.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache(ROOT)
    device = phase_device()
    n_cached = (sum(f.endswith("-cache") for f in os.listdir(cache_dir))
                if os.path.isdir(cache_dir) else 0)
    print(f"[chip_smoke] compile cache {cache_dir} ({n_cached} entries at "
          "start)", flush=True)

    import horovod_tpu as hvd
    from horovod_tpu.models import TransformerLM

    hvd.init()
    try:
        phase_collective(hvd.default_mesh())
        phase_resnet(_build(), steps=5)
        phase_kernels()
        phase_transformer(
            TransformerLM(vocab=32000, dim=1024, heads=8, layers=12,
                          attention="flash"),
            seq=4096, per_dev_batch=1, steps=3)
        if device["count"] >= 4:
            phase_four_chip()
    finally:
        hvd.shutdown()
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
