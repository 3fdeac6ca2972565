"""Synthetic ResNet-50 training benchmark — the TPU equivalent of the
reference's examples/pytorch_synthetic_benchmark.py (BASELINE.md harness):
full training step (fwd + bwd + SGD update) on synthetic ImageNet-shaped data,
reporting images/sec.

Prints ONE JSON line:
  {"metric": "resnet50_images_per_sec", "value": N, "unit": "img/s",
   "vs_baseline": N}

vs_baseline compares per-chip throughput against the reference's only
published absolute number: 1656.82 img/s on 16 Pascal GPUs = 103.55 img/s
per device — measured on ResNet-101 (reference docs/benchmarks.md:22-38),
so the ratio is cross-model (BASELINE.md defines it this way; ResNet-101
per-chip numbers for a like-for-like comparison are in docs/benchmarks.md).

Batch-norm statistics are deliberately per-rank, exactly like the reference:
Horovod averages *gradients* only, never BN running stats (each worker keeps
local statistics; consistency comes from broadcast at checkpoint/restore
time — reference README.md:117-119, torch/__init__.py broadcast_parameters).
Here that is expressed natively: batch_stats are sharded over the mesh axis
(leading per-rank dim, in/out specs P(axis)), so the hot step runs zero
stat collectives; a single fused cross-rank average runs once after the
timed region, standing in for the checkpoint-time broadcast.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

REFERENCE_PER_DEVICE_IMG_S = 1656.82 / 16.0


def _smoke_on() -> bool:
    """HVD_BENCH_SMOKE=1: tiny model, few steps — the CI mode that makes a
    hanging benchmark fail in seconds instead of eating the harness timeout.
    The tiny model is reached ONLY through this explicit switch: a run
    without it that finds no TPU fails (:func:`require_tpu`)."""
    return os.environ.get("HVD_BENCH_SMOKE", "") not in ("", "0")


class _Budget:
    """Hard wall-clock budget for the whole bench run: a wedged stage must
    not eat the harness timeout. When HVD_BENCH_BUDGET_S (default 600 s)
    expires before the final metric line was printed, the watchdog thread
    names the stuck stage on stderr and exits NON-ZERO with no metric line
    — an expired budget is a failed measurement, never a number. Stages
    also let cooperative code skip optional work (``skip_if_low``) and
    report what was skipped.

    Install via :meth:`install`, which arms ONE watchdog per process and
    lets a later mode re-label it: main() installs before ``import jax``
    (backend init itself can wedge, which no in-mode watchdog would
    cover)."""

    _active: "Optional[_Budget]" = None

    @classmethod
    def install(cls, metric: str, unit: str) -> "_Budget":
        if cls._active is not None:
            cls._active.metric = metric
            cls._active.unit = unit
            return cls._active
        cls._active = cls(metric, unit)
        return cls._active

    def __init__(self, metric: str, unit: str) -> None:
        self.metric = metric
        self.unit = unit
        self.t0 = time.monotonic()
        self.total_s = float(os.environ.get("HVD_BENCH_BUDGET_S", "") or 600.0)
        self.stages_done: list[str] = []
        self.stages_skipped: list[str] = []
        self._stage = "startup"
        self._emitted = threading.Event()
        self._timer = threading.Timer(self.total_s, self._expire)
        self._timer.daemon = True
        self._timer.start()

    def remaining(self) -> float:
        return self.total_s - (time.monotonic() - self.t0)

    def stage(self, name: str) -> None:
        if self._stage not in ("startup",) + tuple(self.stages_done):
            self.stages_done.append(self._stage)
        self._stage = name

    def skip_if_low(self, name: str, need_s: float) -> bool:
        """True (and records the skip) when under ``need_s`` of budget is
        left for optional stage ``name``."""
        if self.remaining() < need_s:
            self.stages_skipped.append(name)
            print(f"bench: skipping stage {name!r} "
                  f"({self.remaining():.0f}s budget left < {need_s:.0f}s)",
                  file=sys.stderr)
            return True
        return False

    def emit(self, obj: dict) -> None:
        """Print the final JSON metric line exactly once and disarm."""
        if self._emitted.is_set():
            return
        self._emitted.set()
        self._timer.cancel()
        print(json.dumps(obj), flush=True)

    def disarm(self) -> None:
        """Stand down without emitting (modes that own their output)."""
        self._emitted.set()
        self._timer.cancel()

    def _expire(self) -> None:
        if self._emitted.is_set():
            return
        self._emitted.set()
        print(f"bench: FAILED — HVD_BENCH_BUDGET_S={self.total_s:g}s exceeded "
              f"in stage {self._stage!r} before {self.metric!r} was "
              f"measured (stages done: {self.stages_done}, skipped: "
              f"{self.stages_skipped})", file=sys.stderr, flush=True)
        # The wedged stage cannot be interrupted cooperatively (it may be
        # inside an XLA compile or a blocking collective), so the process
        # is ended from here.
        os._exit(1)


def require_tpu(what: str) -> None:
    """The measured paths run on the chip or not at all: exits non-zero,
    naming what jax found, when the first device is not a TPU. There is no
    CPU fallback under a device metric's name; the tiny CI model is asked
    for explicitly with HVD_BENCH_SMOKE=1."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"{what} needs a TPU, but jax.devices()[0] is platform="
            f"{devs[0].platform!r} kind={devs[0].device_kind!r} "
            f"({len(devs)} device(s)). There is no CPU fallback; "
            "HVD_BENCH_SMOKE=1 selects the tiny CI model explicitly.")


def _build(fusion_threshold=None, compression=None, hierarchical=False,
           num_buckets=None):
    """The benchmark step: ResNet-50 at 224 px, 128 images per chip, built
    by :func:`build_resnet_step`. Raises off-chip (:func:`require_tpu`) —
    it never shrinks to fit a CPU. The knob arguments exist for --autotune,
    which re-builds (re-jits) per candidate config — trace-time knobs can
    only be tuned between traces."""
    from horovod_tpu.models import ResNet50

    require_tpu("the ResNet-50 benchmark step")
    # Per-device batch 128: the reference benchmark uses 64/GPU
    # (docs/benchmarks.md:22) sized for 2015 Pascal HBM; a v5e chip has the
    # memory and MXU width for 128. (`or`: a mode-local fallback, not the
    # knob's default — the smoke step's is 8.)
    return build_resnet_step(
        ResNet50(num_classes=1000,
                 space_to_depth=bool(os.environ.get("HVD_BENCH_S2D"))),
        image=224,
        per_dev_batch=int(os.environ.get("HVD_BENCH_BATCH") or 128),
        fusion_threshold=fusion_threshold, compression=compression,
        hierarchical=hierarchical, num_buckets=num_buckets)


def build_resnet_step(model, image, per_dev_batch, fusion_threshold=None,
                      compression=None, hierarchical=False, num_buckets=None):
    """Model + jitted train step + fresh state + mesh-sharded synthetic
    batch, at the size given. The one ResNet step builder: bench.py's modes
    and examples/realdata_benchmark.py reach it through :func:`_build`,
    ``chip_smoke.py`` too (so its compile is the one ``python bench.py``
    finds in the cache), and the CPU tests call it at a tiny size.
    ``hierarchical`` runs the gradient allreduce as the
    RS(ici)→psum(dcn)→AG(ici) ladder over the 2-D ``('dcn','ici')`` mesh —
    only meaningful on multi-chip topologies. ``num_buckets`` > 1 splits
    the gradient allreduce into that many reverse-backward-order buckets
    (the overlap scheduler; None reads HOROVOD_NUM_BUCKETS)."""
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

    # Fusion threshold: the --autotune winner of an earlier installation
    # (256 MiB — the whole ~100 MB gradient set in one bucket); not
    # re-measured on this one. HOROVOD_FUSION_THRESHOLD still overrides,
    # and --autotune re-derives it. The `or` spelling keeps 256 MiB a
    # bench-local tuned seed, not a second default for the knob (the
    # engine default stays config.py's 64 MiB — tools/analyze flags
    # divergent defaults).
    tuned_default = int(os.environ.get("HOROVOD_FUSION_THRESHOLD") or 256 << 20)
    opt = hvd.jax.DistributedOptimizer(
        optax.sgd(0.01 * n_dev, momentum=0.9),
        fusion_threshold=fusion_threshold or tuned_default,
        # None = the HOROVOD_COMPRESSION env knob (explicit values win),
        # so the env var A/Bs the wire dtype on the main bench path too.
        compression=compression,
        hierarchical=hierarchical,
        num_buckets=num_buckets,
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


def _build_smoke(fusion_threshold=None, num_buckets=None, compression=None):
    """Tiny-MLP train step for smoke/CI runs and the CPU --buckets-ab /
    --compression-ab paths: same DistributedOptimizer hot path (fuse →
    (cast) → psum-per-bucket → unfuse) as the ResNet step, but compiles in
    seconds. 13 parameter leaves give the bucket planner real material to
    split. ``compression`` is a HOROVOD_COMPRESSION name or None (env)."""
    import jax
    import jax.numpy as jnp
    import optax
    from horovod_tpu.compat import shard_map
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.models import MLP

    mesh = hvd.default_mesh()
    n_dev = len(jax.devices())
    per_dev_batch = int(os.environ.get("HVD_BENCH_BATCH", 8))
    batch = per_dev_batch * n_dev
    model = MLP(features=(256, 256, 256, 256, 256, 10))
    x = jnp.ones((batch, 32 * 32), jnp.float32)
    y = jnp.zeros((batch,), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), x[:2])
    opt = hvd.jax.DistributedOptimizer(
        optax.sgd(0.01 * n_dev, momentum=0.9),
        fusion_threshold=fusion_threshold,
        num_buckets=num_buckets,
        compression=(hvd.Compression.by_name(compression)
                     if compression is not None else None),
        # Tiny model: every bucket is below the production min-bytes cut,
        # so the A/B must lower it for the cast to actually engage.
        compression_min_bytes=0 if compression else None,
    )
    opt_state = opt.init(params)

    def train_step(params, opt_state, x, y):
        def loss_fn(p):
            logits = model.apply(p, x)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, y).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, jax.lax.pmean(loss, hvd.HVD_AXIS)

    step = jax.jit(
        shard_map(train_step, mesh=mesh,
                  in_specs=(P(), P(), P(hvd.HVD_AXIS), P(hvd.HVD_AXIS)),
                  out_specs=(P(), P(), P()),
                  check_vma=False),
        donate_argnums=(0, 1),
    )
    return step, (params, opt_state), (x, y), batch, n_dev


def buckets_ab_main() -> None:
    """bench.py --buckets-ab: measure single-bucket vs K-bucket (overlap
    scheduler) throughput and report the jointly autotuned
    (fusion_threshold, num_buckets) — the win is measured per platform, not
    assumed (overlap depends on the XLA scheduler and the fabric).

    Uses the ResNet-50 step, which needs a TPU; the tiny-MLP smoke step
    only under an explicit HVD_BENCH_SMOKE=1. Prints one JSON line with
    both img/s numbers and the winner."""
    import horovod_tpu as hvd
    from horovod_tpu.jax.autotune import tune

    budget = _Budget.install("buckets_ab_images_per_sec", "img/s")
    budget.stage("init")
    hvd.init()
    smoke = _smoke_on()
    if smoke:
        thresholds = (1 << 20, 16 << 20)
        bucket_grid = (1, 2, 4, 8)
        warmup, iters, reps, gp_rounds = 2, 5, 2, 1
    else:
        require_tpu("bench.py --buckets-ab")
        thresholds = (64 << 20, 256 << 20)
        bucket_grid = (1, 2, 4, 8)
        warmup, iters, reps, gp_rounds = 3, 8, 3, 2
    batch_box = [0]

    def step_factory(fusion_threshold, num_buckets):
        if smoke:
            step, state, (x, y), batch, _ = _build_smoke(
                fusion_threshold, num_buckets)
            state = list(state)
            loss_box = [None]

            def run():
                p, o, loss_box[0] = step(*state, x, y)
                state[:] = (p, o)
        else:
            step, state, (x, y), batch, _ = _build(
                fusion_threshold=fusion_threshold, num_buckets=num_buckets)
            state = list(state)
            loss_box = [None]

            def run():
                p, bs, os_, loss_box[0] = step(*state, x, y)
                state[:] = (p, bs, os_)
        batch_box[0] = batch
        return run, lambda: float(loss_box[0])  # window-end hard sync

    budget.stage("tune")
    report = tune(
        step_factory,
        thresholds=thresholds,
        num_buckets=bucket_grid,
        warmup=warmup, iters=iters, reps=reps, gp_rounds=gp_rounds,
        log_path=os.environ.get("HVD_AUTOTUNE_LOG", ""),
        verbose=True,
    )
    print(report.knob_curve(), file=sys.stderr)
    batch = batch_box[0]
    singles = [m for m in report.table if m.num_buckets == 1]
    multis = [m for m in report.table if m.num_buckets > 1]
    best_single = max(singles, key=lambda m: m.steps_per_s)
    best_multi = max(multis, key=lambda m: m.steps_per_s)
    best = report.best
    budget.emit({
        "metric": "buckets_ab_images_per_sec",
        "value": round(best.steps_per_s * batch, 2),
        "unit": "img/s",
        "smoke": smoke,
        "single_bucket_img_s": round(best_single.steps_per_s * batch, 2),
        "bucketed_img_s": round(best_multi.steps_per_s * batch, 2),
        "bucketed_num_buckets": best_multi.num_buckets,
        "bucketed_vs_single": round(
            best_multi.steps_per_s / best_single.steps_per_s, 4),
        "autotuned": {"fusion_threshold": best.fusion_threshold,
                      "num_buckets": best.num_buckets},
    })


def controller_ab_main() -> None:
    """bench.py --controller-ab: COLD job driven by the runtime controller
    vs the offline-autotuned config (ISSUE 16 acceptance gate).

    Arm A (reference): the offline GP/EI sweep (jax/autotune.tune) over
    (fusion_threshold, num_buckets) — the throughput a job gets after
    paying the full offline tuning bill. Arm B (candidate): the SAME cold
    starting config, no offline sweep, with a
    :class:`~horovod_tpu.control.TrainingController` re-tuning the knobs
    live between measurement windows through a re-jit callback — every
    change canaried against the pre-change baseline and rolled back on
    regression. The emitted ``controller_convergence_ratio`` is the
    controller arm's converged throughput over the offline arm's best
    (ci.sh gates it at >= 0.90)."""
    import horovod_tpu as hvd
    from horovod_tpu.control import TrainingController
    from horovod_tpu.jax.autotune import measure_steps_per_s, tune

    budget = _Budget.install("controller_convergence_ratio", "x")
    budget.stage("init")
    hvd.init()
    smoke = _smoke_on()
    if smoke:
        thresholds = (1 << 20, 16 << 20)
        bucket_grid = (1, 2, 4)
        warmup, iters, reps = 2, 5, 2
        windows = 24
    else:
        require_tpu("bench.py --controller-ab")
        thresholds = (64 << 20, 256 << 20)
        bucket_grid = (1, 2, 4, 8)
        warmup, iters, reps = 3, 8, 2
        windows = 32
    batch_box = [0]

    def step_factory(fusion_threshold, num_buckets, compression=None):
        if smoke:
            step, state, (x, y), batch, _ = _build_smoke(
                fusion_threshold, num_buckets, compression)
            state = list(state)
            loss_box = [None]

            def run():
                p, o, loss_box[0] = step(*state, x, y)
                state[:] = (p, o)
        else:
            step, state, (x, y), batch, _ = _build(
                fusion_threshold=fusion_threshold, num_buckets=num_buckets,
                compression=compression)
            state = list(state)
            loss_box = [None]

            def run():
                p, bs, os_, loss_box[0] = step(*state, x, y)
                state[:] = (p, bs, os_)
        batch_box[0] = batch
        return run, lambda: float(loss_box[0])

    # -- arm A: the offline autotuner (the bill the controller avoids) ----
    budget.stage("offline-arm")
    report = tune(step_factory, thresholds=thresholds,
                  num_buckets=bucket_grid, warmup=warmup, iters=iters,
                  reps=reps, gp_rounds=1, verbose=False)
    offline = report.best.steps_per_s
    batch = batch_box[0]

    # -- arm B: cold start + live controller, NO offline sweep ------------
    budget.stage("controller-arm")
    cur = {"fusion_threshold": thresholds[0], "num_buckets": 1,
           "compression": None}
    box = {}

    def rebuild():
        box["run"], box["sync"] = step_factory(
            cur["fusion_threshold"], cur["num_buckets"],
            cur["compression"])

    def rejit(table):
        for k, v in table.items():
            if k == "compression":
                cur[k] = None if v in (None, "none") else str(v)
            elif k in cur:
                cur[k] = int(v)
        rebuild()

    rebuild()
    tc = TrainingController(rejit=rejit, canary_steps=2, cooldown_s=0.0)
    tc.loop.set_current("fusion_threshold", cur["fusion_threshold"])
    tc.loop.set_current("num_buckets", 1)
    decisions = 0
    rate = 0.0
    for w in range(windows):
        if budget.remaining() < 60:
            budget.stages_skipped.append(f"controller-windows-{w}..")
            break
        rate = measure_steps_per_s(box["run"], warmup=warmup, iters=iters,
                                   reps=1, sync=box["sync"])
        tc.on_step(rate)
        decisions = len(tc.loop.history)
    converged = tc.loop.baseline or rate
    ratio = converged / offline if offline > 0 else 0.0
    budget.emit({
        "metric": "controller_convergence_ratio",
        "value": round(ratio, 4),
        "unit": "x",
        "smoke": smoke,
        "offline_img_s": round(offline * batch, 2),
        "controller_img_s": round(converged * batch, 2),
        "offline_config": {"fusion_threshold": report.best.fusion_threshold,
                           "num_buckets": report.best.num_buckets},
        "controller_config": {k: v for k, v in tc.loop.values.items()
                              if k in ("fusion_threshold", "num_buckets",
                                       "compression")},
        "decisions": decisions,
        "commits": sum(1 for p in tc.loop.history
                       if p["verdict"] == "commit"),
        "rollbacks": sum(1 for p in tc.loop.history
                         if p["verdict"] == "rollback"),
    })


def autotune_main() -> None:
    """bench.py --autotune: tune the COMPILED hot path's knobs by re-jitting
    the ResNet-50 train step per candidate (VERDICT r2 missing #2; reference
    behavior parameter_manager.cc:145-233, moved to where TPU training
    actually spends time). Prints the measured knob curve and one JSON line
    with the winning config."""
    import horovod_tpu as hvd
    from horovod_tpu.jax.autotune import DEFAULT_THRESHOLDS, tune

    budget = _Budget.install("autotune_best_config", "steps/s")
    budget.stage("init")
    hvd.init()

    def step_factory(fusion_threshold, compression, hierarchical=False):
        comp = hvd.Compression.bf16 if compression == "bf16" else hvd.Compression.none
        step, state, (x, y), _, _ = _build(fusion_threshold, comp, hierarchical)
        state = list(state)
        loss_box = [None]

        def run():
            p, bs, os_, loss_box[0] = step(*state, x, y)
            state[:] = (p, bs, os_)

        return run, lambda: float(loss_box[0])  # window-end hard sync

    branches = [{"compression": "none"}, {"compression": "bf16"}]
    if hvd.hierarchical_mesh().shape.get("dcn", 1) > 1:
        # The RS->psum->AG ladder only exists to trade DCN for ICI traffic;
        # on a flat/single-chip topology it is pure overhead, so the
        # branches join the search only when there are two real levels to
        # trade (both pairings: compression halves the ladder's bytes too).
        branches.append({"compression": "none", "hierarchical": True})
        branches.append({"compression": "bf16", "hierarchical": True})
    budget.stage("tune")
    report = tune(
        step_factory,
        thresholds=DEFAULT_THRESHOLDS,
        branches=branches,
        warmup=3, iters=8, reps=3, gp_rounds=2,
        # mode-local fallback, not the knob default (other modes default
        # to no log) — hence `or`, which tools/analyze reads as a fallback
        log_path=os.environ.get("HVD_AUTOTUNE_LOG") or "autotune_compiled.csv",
        verbose=True,
    )
    print(report.knob_curve(), file=sys.stderr)
    budget.emit({
        "metric": "autotune_best_config",
        "value": round(report.best.steps_per_s, 3),
        "unit": "steps/s",
        "config": report.best.config,
    })


def roofline_main() -> None:
    """bench.py --roofline: profile the ResNet-50 step and report achieved
    HBM bandwidth / FLOP rate per HLO category (VERDICT r3 weak #1 — the
    'HBM-bound' claim, measured instead of asserted; full reading in
    docs/benchmarks.md). Caveat: bytes are XLA's model of op traffic, not a
    DRAM counter — see horovod_tpu/utils/roofline.py."""
    import horovod_tpu as hvd
    from horovod_tpu.utils.roofline import format_report, profile_device_ops

    budget = _Budget.install("resnet50_roofline", "GB/s")
    budget.stage("init")
    hvd.init()
    budget.stage("compile")
    step, (params, batch_stats, opt_state), (x, y), batch, n_dev = _build()
    state = [params, batch_stats, opt_state]
    loss_box = [None]

    def run():
        p, bs, os_, loss_box[0] = step(*state, x, y)
        state[:] = (p, bs, os_)

    for _ in range(6):  # compile + warm outside the trace
        run()
    float(loss_box[0])
    budget.stage("profile")
    rep = profile_device_ops(run, steps=5, sync=lambda: float(loss_box[0]))
    print(format_report(rep), file=sys.stderr)
    # Headline = the convolution category (where 79% of the step lives):
    # its window is long and its operands stream from HBM, so its achieved
    # GB/s is the trustworthy roofline number. The all-ops aggregate can
    # exceed the nominal roof because XLA's model bytes count VMEM-resident
    # and re-read operands at full price.
    conv = next((r for r in rep.get("categories", [])
                 if "convolution" in r["name"]), None)
    out = {"metric": "resnet50_roofline",
           "value": (conv or {}).get("gbs", 0.0),
           "unit": "GB/s",
           "hbm_gbs": (conv or {}).get("gbs"),
           "pct_hbm_roof": (conv or {}).get("pct_hbm_roof"),
           "conv_ms_per_step": (conv or {}).get("ms_per_step"),
           "device_ms_per_step": rep.get("device_ms_per_step"),
           "all_ops_model_gbs": rep.get("achieved_gbs"),
           "achieved_tflops": rep.get("achieved_tflops"),
           "ok": rep.get("ok", False)}
    if not rep.get("ok"):
        out["reason"] = rep.get("reason")
    budget.emit(out)


def _emit_metrics_snapshot(run, sync, steps_per_s=None) -> None:
    """bench.py --metrics: exercise both data planes' telemetry and print
    the pod-aggregated snapshot as one extra JSON line (ISSUE 2).

    - compiled plane: the benchmarked step already recorded its fusion-plan
      gauges at trace time (bucket count/bytes, occupancy, planned overlap
      bound); a short profiled window adds the MEASURED overlap-efficiency
      gauge on backends whose traces carry device spans (TPU).
    - eager plane: a few engine allreduces (the per-epoch metric-averaging
      pattern every training loop runs) populate the per-collective
      count/bytes/latency histograms.
    - aggregation: every rank's snapshot is allgathered over the engine and
      rank 0 prints the merged pod view (single-process worlds merge their
      own snapshot, same shape).
    """
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu import metrics as hvd_metrics

    from horovod_tpu.common import basics

    if steps_per_s is not None:
        hvd_metrics.registry().gauge(
            "horovod_steps_per_sec",
            help="measured training steps per second").set(steps_per_s)
    overlap = hvd_metrics.measure_overlap(run, steps=3, sync=sync)
    eng = basics.engine()
    for i in range(3):
        eng.run("allreduce", np.array([float(i)], np.float64),
                f"bench.metric.{i}")
    snap = hvd_metrics.snapshot()
    snaps = (hvd.allgather_object(snap, name="bench.metrics_snapshot")
             if hvd.size() > 1 else [snap])
    if hvd.rank() != 0:
        return
    pod = hvd_metrics.merge_snapshots(snaps)
    print(json.dumps({
        "metric": "metrics_pod_snapshot",
        "value": pod["ranks_reporting"],
        "unit": "ranks",
        "overlap_measured": overlap.get("ok", False),
        "snapshot": pod,
    }))


def eager_worker_main() -> None:
    """One rank of the eager micro-bench (spawned by ``--eager``): pure
    eager-engine collectives — deliberately NO jax import, so the measured
    path is the engine, not backend startup. ``HOROVOD_ENGINE`` picks the
    implementation (the --eager native A/B leg spawns ``native!`` worlds;
    default stays the Python reference plane). Prints one JSON line."""
    import hashlib

    import numpy as np

    from horovod_tpu.common.config import Config
    from horovod_tpu.common.engine import PyEngine
    from horovod_tpu.common.topology import Topology
    from horovod_tpu import metrics as hvd_metrics

    rank = int(os.environ["HOROVOD_RANK"])
    world = int(os.environ["HOROVOD_SIZE"])
    per_rank_mb = float(os.environ.get("HVD_EAGER_MB", "32"))
    iters = int(os.environ.get("HVD_EAGER_ITERS", "3"))
    neg_ops = int(os.environ.get("HVD_EAGER_NEG_OPS", "64"))
    # HVD_EAGER_LOCAL_SIZE > 1: lay the world out as a simulated
    # hosts x ranks-per-host grid (blocked, like the launcher assigns) —
    # the --hier-ab topology. Default stays the historical one-rank-per-
    # host world.
    lsz = max(1, int(os.environ.get("HVD_EAGER_LOCAL_SIZE", "1")))
    topo = (Topology(rank, world, rank % lsz, lsz, rank // lsz, world // lsz)
            if lsz > 1 else Topology(rank, world, 0, 1, rank, world))
    from horovod_tpu.common.config import _env_bool
    cfg = Config(cycle_time_ms=1.0, stall_check_disable=True,
                 hierarchical_allreduce=_env_bool(
                     "HOROVOD_HIERARCHICAL_ALLREDUCE"))
    if os.environ.get("HOROVOD_ENGINE", "python").startswith("native"):
        from horovod_tpu.cc.native_engine import NativeEngine

        eng = NativeEngine(topo, cfg)
    else:
        eng = PyEngine(topo, cfg)
    try:
        # HVD_EAGER_DTYPE: float64 (default, the historical --eager payload)
        # or float32 (--compression-ab: gradients are f32, and the wire
        # claim under test is the classic f32->16-bit halving).
        pay_dt = np.dtype(os.environ.get("HVD_EAGER_DTYPE", "float64"))
        n = max(1, int(per_rank_mb * (1 << 20) // pay_dt.itemsize))
        big = (np.arange(n, dtype=np.float64) * (rank + 1) / 7.0).astype(pay_dt)
        # Analytic truth for the tolerance check (--compression-ab): the
        # average over ranks of arange(n)*(r+1)/7 is arange(n)*(w+1)/14.
        expected = np.arange(n, dtype=np.float64) * (world + 1) / 14.0
        eng.run("allreduce", big, "warmup")  # connect + first negotiation
        outs = []
        t0 = time.monotonic()
        for i in range(iters):
            outs.append(eng.run("allreduce", big, "payload"))
        dt = time.monotonic() - t0
        payload_mb_s = per_rank_mb * iters / dt
        # Hash OUTSIDE the timed window (tobytes+sha256 of the result is
        # bench bookkeeping, not data-plane work).
        digest = hashlib.sha256()
        for out in outs:
            digest.update(out.tobytes())
        # Max relative error vs the analytic average — float-epsilon for
        # compression=none, ~1e-2 for the 16-bit wire dtypes.
        scale = float(np.abs(expected).max()) or 1.0
        max_rel_err = float(
            max(np.abs(out.astype(np.float64) - expected).max()
                for out in outs) / scale)
        del outs
        # Negotiation latency, cold vs cached: unique names every time
        # (cache can never hit) vs one name re-submitted (steady state).
        tiny = np.ones(4, np.float64)
        cold_hash = hashlib.sha256()
        t0 = time.monotonic()
        for i in range(neg_ops):
            cold_hash.update(eng.run(
                "allreduce", tiny, f"cold.{i}").tobytes())
        cold_s = time.monotonic() - t0
        eng.run("allreduce", tiny, "hot")  # bind the bit outside the window
        snap0 = hvd_metrics.registry().snapshot()["counters"]
        cached_hash = hashlib.sha256()
        t0 = time.monotonic()
        for i in range(neg_ops):
            cached_hash.update(eng.run("allreduce", tiny, "hot").tobytes())
        cached_s = time.monotonic() - t0
        snap1 = hvd_metrics.registry().snapshot()["counters"]

        def delta(series):
            return snap1.get(series, 0) - snap0.get(series, 0)

        stats = eng.cache_stats()
        print(json.dumps({
            "rank": rank,
            "payload_mb_s": round(payload_mb_s, 2),
            "payload_hash": digest.hexdigest(),
            "payload_max_rel_err": max_rel_err,
            "compression": stats.get("compression", "none"),
            # Both engines feed the same series pair, labeled by plane
            # ("eager" = python engine inline, "native" = the ctypes
            # delta-collector) — sum them so either engine reports here.
            "wire_bytes": snap1.get(
                'horovod_wire_bytes_total{plane="eager"}', 0) + snap1.get(
                'horovod_wire_bytes_total{plane="native"}', 0),
            "wire_bytes_saved": snap1.get(
                'horovod_wire_bytes_saved_total{plane="eager"}', 0)
            + snap1.get(
                'horovod_wire_bytes_saved_total{plane="native"}', 0),
            "cold_neg_ops_s": round(neg_ops / cold_s, 1),
            "cached_neg_ops_s": round(neg_ops / cached_s, 1),
            "cold_hash": cold_hash.hexdigest(),
            "cached_hash": cached_hash.hexdigest(),
            "ring_active": stats["ring_active"],
            "mirror": stats["mirror"],
            # Steady-state window deltas: with the cache hot, NO full
            # request lists and a small fixed control frame per tick.
            "window_full_requests": delta("horovod_engine_full_requests_total"),
            "window_control_bytes": delta("horovod_engine_control_bytes_total"),
            "window_exchanges": delta("horovod_engine_exchanges_total"),
            "window_hits": delta("horovod_engine_cache_hits_total"),
            "window_misses": delta("horovod_engine_cache_misses_total"),
            "star_bytes": snap1.get(
                'horovod_engine_data_bytes_total{plane="star"}', 0),
            "ring_bytes": snap1.get(
                'horovod_engine_data_bytes_total{plane="ring"}', 0),
            # Per-fabric-tier data-plane bytes (ISSUE 7): what --hier-ab
            # asserts the 1/local_size cross cut on.
            "plane": stats.get("plane", "star"),
            "tier_local_bytes": snap1.get(
                'horovod_wire_bytes_total{tier="local"}', 0),
            "tier_cross_bytes": snap1.get(
                'horovod_wire_bytes_total{tier="cross"}', 0),
        }), flush=True)
    finally:
        eng.shutdown()


def _spawn_eager_world(world: int, extra_env: dict, timeout_s: float):
    """Spawn ``world`` --eager-worker ranks; returns per-rank JSON dicts
    or None on failure/timeout (skip-and-report, never hang)."""
    import secrets as secrets_mod
    import socket as socket_mod
    import subprocess

    s = socket_mod.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    secret = secrets_mod.token_hex(16)
    procs = []
    for rank in range(world):
        env = dict(os.environ)
        env.update({
            "HOROVOD_RANK": str(rank), "HOROVOD_SIZE": str(world),
            "HOROVOD_COORD_ADDR": f"127.0.0.1:{port}",
            "HOROVOD_SECRET": secret, "HOROVOD_ENGINE": "python",
        })
        env.update(extra_env)
        procs.append(subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--eager-worker"],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True))
    outs = []
    try:
        for p in procs:
            stdout, stderr = p.communicate(timeout=timeout_s)
            if p.returncode != 0:
                print(f"eager worker failed:\n{stderr[-2000:]}",
                      file=sys.stderr)
                return None
            outs.append(json.loads(stdout.strip().splitlines()[-1]))
    except Exception as e:  # noqa: BLE001 - timeout/parse: report, don't hang
        print(f"eager world failed: {e}", file=sys.stderr)
        return None
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.communicate()
    return outs


def eager_main() -> None:
    """bench.py --eager: the eager-engine micro-bench. A/Bs the two data
    planes (peer ring vs rank-0 star relay) on a 4-proc Python-engine world
    and the two negotiation paths (cold = unique names, every one a full
    request; cached = steady-state bitvector ticks), asserting the results
    are bitwise identical in all four quadrants. One JSON line."""
    budget = _Budget.install("eager_allreduce_ring_speedup", "x")
    world = int(os.environ.get("HVD_EAGER_WORLD", "4"))
    if _smoke_on():
        os.environ.setdefault("HVD_EAGER_MB", "1")
        os.environ.setdefault("HVD_EAGER_ITERS", "3")
        os.environ.setdefault("HVD_EAGER_NEG_OPS", "32")
    stage_s = min(max(budget.remaining() / 3 - 10, 30), 240)
    budget.stage("ring-world")
    ring = _spawn_eager_world(
        world, {"HOROVOD_RING_DATA_PLANE": "1"}, stage_s)
    budget.stage("star-world")
    star = _spawn_eager_world(
        world, {"HOROVOD_RING_DATA_PLANE": "0"}, stage_s)
    # Native-vs-python A/B (ISSUE 13): the same payloads through the native
    # core's zero-copy byte path (HOROVOD_NATIVE_DATA_PLANE). Emits its own
    # gated record below — perf_gate --min-abs eager_native_speedup floors
    # it in CI. native! raises instead of silently falling back, so a
    # broken native build yields a partial record, never a fake 1.0x.
    budget.stage("native-world")
    native = _spawn_eager_world(world, {"HOROVOD_ENGINE": "native!"}, stage_s)
    out = {"metric": "eager_allreduce_ring_speedup", "value": 0.0,
           "unit": "x", "world": world,
           "payload_mb_per_rank": float(os.environ.get("HVD_EAGER_MB", "32")),
           "iters": int(os.environ.get("HVD_EAGER_ITERS", "3"))}
    if ring is None or star is None:
        out.update({"partial": True,
                    "reason": "a bench world failed or timed out",
                    "ring_ok": ring is not None, "star_ok": star is not None})
        print(json.dumps({
            "metric": "eager_native_speedup", "value": 0.0, "unit": "x",
            "partial": True,
            "reason": "a bench world failed or timed out"}), flush=True)
        budget.emit(out)
        return
    # Gated record: native-plane rank MB/s vs the python ring plane on the
    # identical payloads (bitwise-identical results — the canonical-order
    # contract — checked right here).
    if native is None:
        print(json.dumps({
            "metric": "eager_native_speedup", "value": 0.0, "unit": "x",
            "partial": True, "smoke": _smoke_on(),
            "reason": "the native-engine world failed or timed out"}),
            flush=True)
    else:
        native_mbs = min(r["payload_mb_s"] for r in native)
        ring_only_mbs = min(r["payload_mb_s"] for r in ring)
        print(json.dumps({
            "metric": "eager_native_speedup",
            "value": round(native_mbs / ring_only_mbs, 3),
            "unit": "x", "smoke": _smoke_on(), "world": world,
            "native_payload_mb_s": round(native_mbs, 2),
            "python_ring_payload_mb_s": round(ring_only_mbs, 2),
            "bitwise_identical_native_vs_python":
                {r["payload_hash"] for r in native}
                == {r["payload_hash"] for r in ring},
        }), flush=True)
    r0, s0 = ring[0], star[0]
    ring_mbs = min(r["payload_mb_s"] for r in ring)
    star_mbs = min(r["payload_mb_s"] for r in star)
    hashes = {r["payload_hash"] for r in ring} | {r["payload_hash"] for r in star}
    cold_cached_same = all(r["cold_hash"] == ring[0]["cold_hash"] for r in ring)
    mirror = r0["mirror"] or {"hits": 0, "misses": 1}
    out.update({
        "value": round(ring_mbs / star_mbs, 3),
        "ring_payload_mb_s": round(ring_mbs, 2),
        "star_payload_mb_s": round(star_mbs, 2),
        "ring_active": r0["ring_active"],
        "bitwise_identical_star_vs_ring": len(hashes) == 1,
        "cold_hashes_agree": cold_cached_same,
        "cold_neg_ops_s": r0["cold_neg_ops_s"],
        "cached_neg_ops_s": r0["cached_neg_ops_s"],
        "cache_hit_rate": round(
            r0["window_hits"] / max(
                r0["window_hits"] + r0["window_misses"], 1), 4),
        "overall_hit_rate": round(
            mirror["hits"] / max(mirror["hits"] + mirror["misses"], 1), 4),
        # Steady-state proof: zero full request lists in the cached window,
        # and the per-tick control frame stays small and fixed.
        "cached_window_full_requests": r0["window_full_requests"],
        "cached_window_control_bytes_per_exchange": round(
            r0["window_control_bytes"] / max(r0["window_exchanges"], 1), 1),
        "star_relay_bytes_in_ring_mode": r0["star_bytes"],
    })
    budget.emit(out)


def hier_ab_main() -> None:
    """bench.py --hier-ab: A/B the hierarchical fabric-aware eager plane
    (ISSUE 7) on a simulated 2-host x 2-rank grid.

    Two 4-proc Python-engine worlds move the same per-rank payload: the
    FLAT peer ring (hierarchical off — host-boundary neighbours carry the
    whole stream) vs the TWO-LEVEL plane (intra-host reduce-scatter →
    per-chunk leaders ring across hosts → intra-host allgather). The
    headline value is the worst-rank cross-host byte reduction
    (flat/hier, target ~local_size·(N-1)/N / ((C-1)/C) ≈ 3x on 2x2 — the
    ratio tools/hier_smoke.py gates at >= 1/0.35), with throughput and
    correctness riding along. One JSON line; an expired budget fails the
    run instead (:class:`_Budget`)."""
    budget = _Budget.install("hier_ab_cross_byte_reduction", "x")
    world = int(os.environ.get("HVD_EAGER_WORLD", "4"))
    # mode-local fallback (`or`): the hier A/B needs a >=2 grid; the knob's
    # default stays the flat micro-bench's 1 (tools/analyze registry)
    lsz = max(2, int(os.environ.get("HVD_EAGER_LOCAL_SIZE") or 2))
    if _smoke_on():
        os.environ.setdefault("HVD_EAGER_MB", "1")
        os.environ.setdefault("HVD_EAGER_ITERS", "3")
        os.environ.setdefault("HVD_EAGER_NEG_OPS", "16")
    grid_env = {"HOROVOD_RING_DATA_PLANE": "1",
                "HVD_EAGER_DTYPE": "float32",
                "HVD_EAGER_LOCAL_SIZE": str(lsz)}
    stage_s = min(max(budget.remaining() / 2 - 10, 30), 240)
    budget.stage("flat-grid")
    flat = _spawn_eager_world(
        world, dict(grid_env, HOROVOD_HIERARCHICAL_ALLREDUCE="0"), stage_s)
    budget.stage("hier-grid")
    hier = _spawn_eager_world(
        world, dict(grid_env, HOROVOD_HIERARCHICAL_ALLREDUCE="1"), stage_s)
    out = {"metric": "hier_ab_cross_byte_reduction", "value": 0.0,
           "unit": "x", "world": world, "local_size": lsz,
           "hosts": world // lsz, "smoke": _smoke_on(),
           "payload_mb_per_rank": float(os.environ.get("HVD_EAGER_MB", "32")),
           "iters": int(os.environ.get("HVD_EAGER_ITERS", "3"))}
    if flat is None or hier is None:
        out.update({"partial": True,
                    "reason": "a bench world failed or timed out",
                    "flat_ok": flat is not None, "hier_ok": hier is not None})
        budget.emit(out)
        return
    flat_cross = max(r["tier_cross_bytes"] for r in flat)
    hier_cross = max(r["tier_cross_bytes"] for r in hier)
    flat_mbs = min(r["payload_mb_s"] for r in flat)
    hier_mbs = min(r["payload_mb_s"] for r in hier)
    out.update({
        "value": round(flat_cross / max(hier_cross, 1), 3),
        "hier_plane_active": all(r["plane"] == "hier" for r in hier),
        "flat_plane": flat[0]["plane"],
        "flat_worst_rank_cross_bytes": int(flat_cross),
        "hier_worst_rank_cross_bytes": int(hier_cross),
        "cross_byte_ratio": round(hier_cross / max(flat_cross, 1), 4),
        "flat_payload_mb_s": round(flat_mbs, 2),
        "hier_payload_mb_s": round(hier_mbs, 2),
        "hier_vs_flat_speedup": round(hier_mbs / max(flat_mbs, 1e-9), 3),
        # Correctness riding along: every rank of each world agrees
        # bitwise, the analytic truth holds, and the steady-state cache
        # is unaffected by the plane swap.
        "flat_ranks_agree": len({r["payload_hash"] for r in flat}) == 1,
        "hier_ranks_agree": len({r["payload_hash"] for r in hier}) == 1,
        "hier_max_rel_err": max(r["payload_max_rel_err"] for r in hier),
        "hier_cache_hit_rate": round(
            hier[0]["window_hits"] / max(
                hier[0]["window_hits"] + hier[0]["window_misses"], 1), 4),
        "star_relay_bytes_in_hier_mode": hier[0]["star_bytes"],
    })
    budget.emit(out)


def compression_ab_main() -> None:
    """bench.py --compression-ab: A/B the on-the-wire gradient compression
    (ISSUE 5) on BOTH data planes.

    Ring plane: two 4-proc Python-engine worlds (HOROVOD_COMPRESSION=none
    vs bf16) move the same per-rank payload over the peer ring; the
    headline value is the bf16/none steady-state throughput ratio, with the
    wire-byte counters proving the reduction and the analytic max-rel-err
    proving the results stay within 16-bit tolerance (none stays exactly
    0 — bitwise identical to the uncompressed baseline). Compiled plane: a
    mini joint autotune over (fusion_threshold, num_buckets, compression)
    on the smoke MLP — the ISSUE 5 third search dimension — reporting the
    per-config steps/s. One JSON line; an expired budget fails the run
    instead (:class:`_Budget`)."""
    budget = _Budget.install("compression_ab_ring_speedup", "x")
    world = int(os.environ.get("HVD_EAGER_WORLD", "4"))
    if _smoke_on():
        os.environ.setdefault("HVD_EAGER_MB", "1")
        os.environ.setdefault("HVD_EAGER_ITERS", "3")
        os.environ.setdefault("HVD_EAGER_NEG_OPS", "16")
    stage_s = min(max(budget.remaining() / 4 - 10, 30), 240)
    # f32 payloads: what gradients actually are, and the wire claim under
    # test (f32 -> 16-bit = the classic 2x; phase-1 partials drop 4x from
    # the uncompressed plane's f64 accumulator width).
    budget.stage("ring-none")
    none = _spawn_eager_world(
        world, {"HOROVOD_RING_DATA_PLANE": "1", "HVD_EAGER_DTYPE": "float32",
                "HOROVOD_COMPRESSION": "none"}, stage_s)
    budget.stage("ring-bf16")
    bf16 = _spawn_eager_world(
        world, {"HOROVOD_RING_DATA_PLANE": "1", "HVD_EAGER_DTYPE": "float32",
                "HOROVOD_COMPRESSION": "bf16"}, stage_s)
    # Sparse leg (ISSUE 9): topk@1% on the same f32 payloads — the wire
    # claim here is the >= 10x byte cut (indices+values frames of the top
    # 1% by magnitude; the un-sent mass rides the error-feedback residual,
    # so per-step results are intentionally NOT the dense average — the
    # convergence claim lives in tests/test_compression.py, the byte claim
    # here and in tools/perf_gate.py's absolute floor).
    budget.stage("ring-topk")
    topk = _spawn_eager_world(
        world, {"HOROVOD_RING_DATA_PLANE": "1", "HVD_EAGER_DTYPE": "float32",
                "HOROVOD_COMPRESSION": "topk", "HOROVOD_TOPK_RATIO": "0.01"},
        stage_s)
    out = {"metric": "compression_ab_ring_speedup", "value": 0.0,
           "unit": "x", "world": world,
           "payload_mb_per_rank": float(os.environ.get("HVD_EAGER_MB", "32")),
           "iters": int(os.environ.get("HVD_EAGER_ITERS", "3"))}
    if none is None or bf16 is None or topk is None:
        out.update({"partial": True,
                    "reason": "a bench world failed or timed out",
                    "none_ok": none is not None, "bf16_ok": bf16 is not None,
                    "topk_ok": topk is not None})
        # The gated topk record must exist even on a wedged run (the
        # _Budget JSON-line contract): partial, so the gate SKIPs it
        # instead of either failing the floor or erroring on absence.
        print(json.dumps({
            "metric": "compression_ab_topk_byte_reduction", "value": 0.0,
            "unit": "x", "partial": True,
            "reason": "a bench world failed or timed out"}), flush=True)
        budget.emit(out)
        return
    none_mbs = min(r["payload_mb_s"] for r in none)
    bf16_mbs = min(r["payload_mb_s"] for r in bf16)
    topk_mbs = min(r["payload_mb_s"] for r in topk)
    wire = sum(r["wire_bytes"] for r in bf16)
    saved = sum(r["wire_bytes_saved"] for r in bf16)
    topk_wire = sum(r["wire_bytes"] for r in topk)
    topk_saved = sum(r["wire_bytes_saved"] for r in topk)
    out.update({
        "value": round(bf16_mbs / none_mbs, 3),
        "ring_none_mb_s": round(none_mbs, 2),
        "ring_bf16_mb_s": round(bf16_mbs, 2),
        "ring_topk_mb_s": round(topk_mbs, 2),
        "ring_active": bf16[0]["ring_active"],
        # Wire proof: bytes halved-or-better, results inside 16-bit
        # tolerance, and the uncompressed world untouched (exactly 0 error
        # vs the analytic truth = bitwise the PR 4 baseline).
        "wire_bytes_reduction": round((wire + saved) / max(wire, 1), 2),
        "bf16_max_rel_err": max(r["payload_max_rel_err"] for r in bf16),
        "none_max_rel_err": max(r["payload_max_rel_err"] for r in none),
        "none_ranks_agree": len({r["payload_hash"] for r in none}) == 1,
        "bf16_ranks_agree": len({r["payload_hash"] for r in bf16}) == 1,
        "topk_ranks_agree": len({r["payload_hash"] for r in topk}) == 1,
        "compression_ab_topk_speedup": round(topk_mbs / none_mbs, 3),
    })
    # Second gated metric line (perf_gate --min-abs
    # compression_ab_topk_byte_reduction=10): its own record so the
    # absolute floor composes with the ratio gate on the headline metric.
    print(json.dumps({
        "metric": "compression_ab_topk_byte_reduction",
        "value": round((topk_wire + topk_saved) / max(topk_wire, 1), 2),
        "unit": "x", "smoke": _smoke_on(), "world": world,
        "topk_ratio": 0.01,
        "topk_wire_bytes": int(topk_wire),
        "topk_vs_none_speedup": round(topk_mbs / none_mbs, 3),
    }), flush=True)
    # Compiled plane: the (threshold, buckets, wire-dtype) joint autotune on
    # the smoke MLP (full grids belong to --buckets-ab; this exercises the
    # third dimension end to end and reports the winner).
    if not budget.skip_if_low("compiled-ab", 45):
        budget.stage("compiled-ab")
        import horovod_tpu as hvd
        from horovod_tpu.jax.autotune import tune

        hvd.init()
        batch_box = [0]

        def step_factory(fusion_threshold, num_buckets, compression):
            step, state, (x, y), batch, _ = _build_smoke(
                fusion_threshold, num_buckets, compression)
            state = list(state)
            loss_box = [None]

            def run():
                p, o, loss_box[0] = step(*state, x, y)
                state[:] = (p, o)
            batch_box[0] = batch
            return run, lambda: float(loss_box[0])

        report = tune(step_factory, thresholds=(1 << 20,),
                      num_buckets=(1, 4), compressions=("none", "bf16"),
                      warmup=2, iters=5, reps=2, gp_rounds=0,
                      log_path=os.environ.get("HVD_AUTOTUNE_LOG", ""),
                      verbose=True)
        print(report.knob_curve(), file=sys.stderr)
        comp_best = {m.compression: max(
            (x for x in report.table if x.compression == m.compression),
            key=lambda x: x.steps_per_s) for m in report.table}
        batch = batch_box[0]
        out.update({
            "compiled_none_img_s": round(
                comp_best["none"].steps_per_s * batch, 2),
            "compiled_bf16_img_s": round(
                comp_best["bf16"].steps_per_s * batch, 2),
            "compiled_bf16_vs_none": round(
                comp_best["bf16"].steps_per_s
                / comp_best["none"].steps_per_s, 4),
            "autotuned": report.best.config,
        })
    budget.emit(out)


def _build_fsdp_ab(batch_sz: int, shard_sz: int, features,
                   fusion_threshold=None, num_buckets=None):
    """MLP train step for the DP-vs-sharded A/B (ISSUE 14): the same model,
    data, and init on a ('batch','shard') mesh — shard=1 runs the plain
    replicated DistributedOptimizer path, shard>1 the ZeRO
    reduce-scatter/allgather path. Returns (run, sync, info) where info
    carries the per-rank parameter+optimizer-state bytes and the losses
    list the run closure appends to (the parity probe)."""
    import jax
    import jax.numpy as jnp
    import optax
    from horovod_tpu.compat import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.models import MLP
    from horovod_tpu.parallel import sharded as hvd_sharded

    import numpy as np

    n_dev = batch_sz * shard_sz
    devs = jax.devices()[:n_dev]
    mesh = Mesh(np.asarray(devs).reshape(batch_sz, shard_sz),
                ("batch", "shard"))
    per_dev_batch = int(os.environ.get("HVD_BENCH_BATCH", 8))
    batch = per_dev_batch * n_dev
    dim = 128
    model = MLP(features=features)
    x = jnp.ones((batch, dim), jnp.float32)
    y = jnp.zeros((batch,), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), x[:2])
    A = ("batch", "shard")

    def loss_fn(p, x, y):
        logits = model.apply(p, x)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y + jnp.arange(y.shape[0]) % logits.shape[-1]).mean()

    losses: list = []
    if shard_sz == 1:
        opt = hvd.jax.DistributedOptimizer(
            optax.adam(1e-3), axis_name=A,
            fusion_threshold=fusion_threshold, num_buckets=num_buckets)
        opt_state = opt.init(params)
        state_bytes = hvd_sharded.state_bytes(
            {"params": params, "opt": opt_state})

        def train_step(p, o, x, y):
            loss, grads = jax.value_and_grad(loss_fn)(p, x, y)
            upd, o = opt.update(grads, o, p)
            return optax.apply_updates(p, upd), o, jax.lax.pmean(loss, A)

        step = jax.jit(shard_map(
            train_step, mesh=mesh,
            in_specs=(P(), P(), P(A), P(A)), out_specs=(P(), P(), P()),
            check_vma=False), donate_argnums=(0, 1))
        state = [params, opt_state]
    else:
        plan = hvd_sharded.build_shard_plan(
            params, shard_sz, threshold=fusion_threshold,
            num_buckets=num_buckets)
        sp = hvd_sharded.shard_params(params, plan)
        opt = hvd.jax.DistributedOptimizer(
            optax.adam(1e-3), sharded=True, shard_plan=plan,
            fusion_threshold=fusion_threshold, num_buckets=num_buckets)
        opt_state = opt.init(sp)
        specs = hvd_sharded.shard_specs(opt_state)
        # Per-rank persistent state: each rank owns 1/shard of every
        # (shard, chunk) buffer (params + both adam moments + counters).
        state_bytes = hvd_sharded.state_bytes(
            {"params": sp, "opt": opt_state}) // shard_sz

        def train_step(sp, o, x, y):
            full = hvd_sharded.gather_params(sp, plan)
            loss, grads = jax.value_and_grad(loss_fn)(full, x, y)
            upd, o = opt.update(grads, o, sp)
            return optax.apply_updates(sp, upd), o, jax.lax.pmean(loss, A)

        step = jax.jit(shard_map(
            train_step, mesh=mesh,
            in_specs=(P("shard"), specs, P(A), P(A)),
            out_specs=(P("shard"), specs, P()),
            check_vma=False), donate_argnums=(0, 1))
        state = [sp, opt_state]
    loss_box = [None]

    def run():
        p, o, loss_box[0] = step(*state, x, y)
        state[:] = (p, o)
        losses.append(loss_box[0])

    info = {"state_bytes_per_rank": int(state_bytes), "batch": batch,
            "losses": losses,
            "param_count": sum(int(l.size) for l in
                               jax.tree_util.tree_leaves(params))}
    return run, (lambda: float(loss_box[0])), info


def fsdp_ab_main() -> None:
    """bench.py --fsdp-ab: DP vs ZeRO-sharded A/B on the simulated
    ('batch','shard') mesh (ISSUE 14). Same model/data/init twice — the
    fully-replicated DP path (shard=1) against the sharded planner
    (shard=2) — reporting the headline per-rank parameter+optimizer-state
    memory reduction (the gated metric, floor 1.8x), step-time, loss-
    trajectory parity, analytic step wire bytes vs the DP allreduce, the
    largest trainable model size under a fixed per-rank budget, and a mini
    joint autotune exercising the mesh shape as the FIFTH dimension. One
    JSON line; an expired budget fails the run instead (:class:`_Budget`)."""
    import jax

    import horovod_tpu as hvd
    from horovod_tpu import metrics as hvd_metrics
    from horovod_tpu.jax.autotune import measure_steps_per_s, tune

    budget = _Budget.install("fsdp_ab_memory_reduction", "x")
    budget.stage("devices")
    # The A/B needs a 2-D mesh; on a CPU host spin up virtual devices (the
    # same simulated-mesh strategy the test suite uses). Must happen BEFORE
    # the first jax.devices() call — the backend initializes once.
    import re as _re

    want = int(os.environ.get("HVD_FSDP_AB_DEVICES", "8"))
    flags = os.environ.get("XLA_FLAGS", "")
    m = _re.search(r"xla_force_host_platform_device_count=(\d+)", flags)
    promised = int(m.group(1)) if m else 0
    if (os.environ.get("JAX_PLATFORMS", "").startswith("cpu")
            and promised < want):
        try:
            from horovod_tpu.compat import set_num_cpu_devices

            set_num_cpu_devices(want)
        except RuntimeError:
            pass
    n_dev = len(jax.devices())
    out = {"metric": "fsdp_ab_memory_reduction", "value": 0.0, "unit": "x",
           "smoke": _smoke_on(), "devices": n_dev}
    if n_dev < 4 or n_dev % 2:
        out.update({"partial": True,
                    "reason": f"need an even device count >= 4, have {n_dev}"})
        budget.emit(out)
        return
    hvd.init()
    smoke = _smoke_on()
    features = (256, 256, 10) if smoke else (1024, 1024, 1024, 10)
    steps = 6 if smoke else 12
    warmup, iters, reps = (2, 3, 2) if smoke else (3, 8, 3)
    shard = 2
    batch_dp, batch_sh = n_dev, n_dev // shard

    budget.stage("dp-leg")
    run_dp, sync_dp, info_dp = _build_fsdp_ab(batch_dp, 1, features)
    rate_dp = measure_steps_per_s(run_dp, warmup, iters, reps, sync=sync_dp)
    dp_plan = hvd_metrics.last_plan()
    dp_wire_b = sum(n for _, n in dp_plan or [])
    info_dp["losses"].clear()

    budget.stage("sharded-leg")
    run_sh, sync_sh, info_sh = _build_fsdp_ab(batch_sh, shard, features)
    rate_sh = measure_steps_per_s(run_sh, warmup, iters, reps, sync=sync_sh)
    shard_plan = hvd_metrics.last_shard_plan()
    info_sh["losses"].clear()

    budget.stage("parity")
    # Fresh states walked side by side: the sharded trajectory must match
    # DP within dtype tolerance (the bitwise shard=1 proof lives in
    # tests/test_sharded.py; this is the cross-shape check).
    run_dp2, _, info_dp2 = _build_fsdp_ab(batch_dp, 1, features)
    run_sh2, _, info_sh2 = _build_fsdp_ab(batch_sh, shard, features)
    for _ in range(steps):
        run_dp2()
        run_sh2()
    parity = max(abs(float(a) - float(b))
                 for a, b in zip(info_dp2["losses"], info_sh2["losses"]))

    dp_bytes = info_dp["state_bytes_per_rank"]
    sh_bytes = info_sh["state_bytes_per_rank"]
    hvd_metrics.record_sharded_state_bytes(sh_bytes * shard, shard)
    # Analytic per-rank ring wire volume: DP allreduce = 2B(N-1)/N; sharded
    # = scatter (s-1)/s + batch-psum 2(b-1)/b over the 1/s chunk + gather
    # (s-1)/s — the ZeRO equal-wire-cost claim, from the recorded plans.
    sc = (shard_plan or {}).get("bytes_per_step", {}).get("scatter", 0)
    ga = (shard_plan or {}).get("bytes_per_step", {}).get("gather", 0)
    b_ax = (shard_plan or {}).get("batch", batch_sh)
    dp_wire = 2.0 * dp_wire_b * (n_dev - 1) / n_dev
    sh_wire = (sc * (shard - 1) / shard
               + 2.0 * (b_ax - 1) / max(b_ax, 1) * (sc / shard)
               + ga * (shard - 1) / shard)
    out.update({
        "value": round(dp_bytes / max(sh_bytes, 1), 3),
        "shard": shard,
        "dp_state_bytes_per_rank": int(dp_bytes),
        "sharded_state_bytes_per_rank": int(sh_bytes),
        "param_count": info_dp["param_count"],
        "dp_img_s": round(rate_dp * info_dp["batch"], 2),
        "sharded_img_s": round(rate_sh * info_sh["batch"], 2),
        "sharded_vs_dp_step_time": round(rate_dp / max(rate_sh, 1e-9), 3),
        "loss_parity_max_abs_err": round(parity, 8),
        "wire_bytes_vs_dp": round(sh_wire / max(dp_wire, 1), 4),
        # Largest trainable model under a per-rank budget equal to the DP
        # footprint: sharding the state 1/shard lets ~shard-fold more
        # state bytes fit (minus padding) — the reason this refactor
        # unlocks models too big for one chip.
        "largest_trainable_state_bytes_dp": int(dp_bytes),
        "largest_trainable_state_bytes_sharded": int(
            dp_bytes * dp_bytes / max(sh_bytes, 1)),
    })
    # Mesh shape as the FIFTH joint-autotune dimension (jax/autotune.tune):
    # the tuner measures the same step over candidate ('batch','shard')
    # shapes beside (threshold, buckets) and reports the platform's winner.
    if not budget.skip_if_low("mesh-autotune", 40):
        budget.stage("mesh-autotune")

        def step_factory(fusion_threshold, mesh_shape):
            b, s = (int(v) for v in mesh_shape.split("x"))
            run, sync, _ = _build_fsdp_ab(b, s, features,
                                          fusion_threshold=fusion_threshold)
            return run, sync

        report = tune(step_factory, thresholds=(1 << 20,),
                      mesh_shapes=(f"{n_dev}x1", f"{n_dev // 2}x2"),
                      warmup=1 if smoke else 2, iters=3, reps=2,
                      gp_rounds=0, verbose=True)
        print(report.knob_curve(), file=sys.stderr)
        out["autotuned_mesh"] = report.best.config.get("mesh",
                                                       f"{n_dev}x1")
    budget.emit(out)


def _build_tp_ab(batch_sz: int, shard_sz: int, model_sz: int,
                 fusion_threshold=None, num_buckets=None):
    """Tensor-parallel train step for the model=1-vs-model=2 A/B
    (ISSUE 19): the same two-pair column/row-parallel block, data, and
    init on the 3-D ('batch','shard','model') mesh. model=1 compiles to
    exactly the 2-D ZeRO plan (the bitwise proof lives in
    tests/test_tensor_parallel.py); model>1 slices each pair's hidden
    dimension per model rank with one psum('model') per pair per
    direction. Returns (run, sync, info) with per-CHIP persistent
    parameter+optimizer-state bytes — the headline the gate floors."""
    import jax
    import jax.numpy as jnp
    import optax
    from horovod_tpu.compat import shard_map
    from jax.sharding import PartitionSpec as P

    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu.parallel import sharded as hvd_sharded
    from horovod_tpu.parallel import tensor as tp
    from horovod_tpu.parallel.mesh import sharded_mesh

    n_dev = batch_sz * shard_sz * model_sz
    devs = jax.devices()[:n_dev]
    mesh = sharded_mesh(batch=batch_sz, shard=shard_sz, model=model_sz,
                        devices=devs)
    per_dev_batch = int(os.environ.get("HVD_BENCH_BATCH", 8))
    # The model axis replicates data; batch rides ('batch','shard'). The
    # GLOBAL batch is pinned to the device count so the model=1 and
    # model=2 legs walk identical data (the loss-parity probe).
    batch = per_dev_batch * n_dev
    dim = 64
    hidden = int(os.environ.get("HVD_TP_AB_HIDDEN", 512))
    rng = np.random.default_rng(0)

    def mk_pair(d_in, h, d_out):
        return {
            "w_col": jnp.asarray(rng.normal(0, 0.05, (d_in, h)),
                                 jnp.float32),
            "b_col": jnp.zeros((h,), jnp.float32),
            "w_row": jnp.asarray(rng.normal(0, 0.05, (h, d_out)),
                                 jnp.float32),
            "b_row": jnp.zeros((d_out,), jnp.float32),
        }

    pairs = [mk_pair(dim, hidden, dim), mk_pair(dim, hidden, dim)]
    x = jnp.asarray(rng.normal(0, 1, (batch, dim)), jnp.float32)
    y = jnp.asarray(rng.normal(0, 1, (batch, dim)), jnp.float32)

    local = tp.tp_local_pairs(pairs, model_sz)
    plan = hvd_sharded.build_shard_plan(
        local[0], shard_sz, threshold=fusion_threshold,
        num_buckets=num_buckets, model_size=model_sz)
    sp = hvd_sharded.shard_params_model(local, plan)
    opt = hvd.jax.DistributedOptimizer(
        optax.adam(1e-3), sharded=True, shard_plan=plan,
        fusion_threshold=fusion_threshold, num_buckets=num_buckets)
    opt_state = opt.init(sp)
    specs = hvd_sharded.shard_specs(opt_state, model_axis="model")
    sp_spec = hvd_sharded.shard_specs(sp, model_axis="model")
    # Per-chip persistent state: the model-stacked (model*shard, chunk)
    # buffers spread over BOTH non-batch mesh axes.
    state_bytes = hvd_sharded.state_bytes(
        {"params": sp, "opt": opt_state}) // (model_sz * shard_sz)

    def loss_fn(p, x, y):
        return jnp.mean((tp.tp_apply(p, x) - y) ** 2)

    def train_step(sp, o, x, y):
        full = hvd_sharded.gather_params(sp, plan)
        loss, grads = jax.value_and_grad(loss_fn)(full, x, y)
        upd, o = opt.update(grads, o, sp)
        return (optax.apply_updates(sp, upd), o,
                jax.lax.pmean(loss, ("batch", "shard")))

    step = jax.jit(shard_map(
        train_step, mesh=mesh,
        in_specs=(sp_spec, specs, P(("batch", "shard")),
                  P(("batch", "shard"))),
        out_specs=(sp_spec, specs, P()),
        check_vma=False), donate_argnums=(0, 1))

    losses: list = []
    state = [sp, opt_state]
    loss_box = [None]

    def run():
        p, o, loss_box[0] = step(*state, x, y)
        state[:] = (p, o)
        losses.append(loss_box[0])

    info = {"state_bytes_per_chip": int(state_bytes), "batch": batch,
            "losses": losses, "dim": dim, "hidden": hidden,
            "param_count": sum(int(l.size) for l in
                               jax.tree_util.tree_leaves(pairs))}
    return run, (lambda: float(loss_box[0])), info


def tp_ab_main() -> None:
    """bench.py --tp-ab: tensor-parallel A/B on the simulated 3-D
    ('batch','shard','model') mesh (ISSUE 19). The same two-pair TP
    block, data, and init twice — model=1 (which compiles to the proven
    2-D plan) against model=2 (hidden dimension sliced per model rank,
    one psum('model') per pair per direction) — reporting the headline
    per-chip parameter+optimizer-state reduction (the gated metric, floor
    1.8x), TP step throughput, loss-trajectory parity, the analytic
    per-step TP wire bytes, and a mini joint autotune exercising the
    3-axis mesh string as the SIXTH dimension. One JSON line; an expired
    budget fails the run instead (:class:`_Budget`)."""
    import jax

    import horovod_tpu as hvd
    from horovod_tpu import metrics as hvd_metrics
    from horovod_tpu.jax.autotune import measure_steps_per_s, tune

    budget = _Budget.install("tp_ab_memory_reduction", "x")
    budget.stage("devices")
    import re as _re

    want = int(os.environ.get("HVD_TP_AB_DEVICES", "8"))
    flags = os.environ.get("XLA_FLAGS", "")
    m = _re.search(r"xla_force_host_platform_device_count=(\d+)", flags)
    promised = int(m.group(1)) if m else 0
    if (os.environ.get("JAX_PLATFORMS", "").startswith("cpu")
            and promised < want):
        try:
            from horovod_tpu.compat import set_num_cpu_devices

            set_num_cpu_devices(want)
        except RuntimeError:
            pass
    n_dev = len(jax.devices())
    out = {"metric": "tp_ab_memory_reduction", "value": 0.0, "unit": "x",
           "smoke": _smoke_on(), "devices": n_dev}
    if n_dev < 8 or n_dev % 4:
        out.update({"partial": True,
                    "reason": f"need a device count divisible by 4 and "
                              f">= 8, have {n_dev}"})
        budget.emit(out)
        return
    hvd.init()
    smoke = _smoke_on()
    steps = 6 if smoke else 12
    warmup, iters, reps = (2, 3, 2) if smoke else (3, 8, 3)
    model, shard = 2, 2
    batch_ref, batch_tp = n_dev // shard, n_dev // (shard * model)

    budget.stage("ref-leg")
    run_ref, sync_ref, info_ref = _build_tp_ab(batch_ref, shard, 1)
    rate_ref = measure_steps_per_s(run_ref, warmup, iters, reps,
                                   sync=sync_ref)
    info_ref["losses"].clear()

    budget.stage("tp-leg")
    run_tp, sync_tp, info_tp = _build_tp_ab(batch_tp, shard, model)
    rate_tp = measure_steps_per_s(run_tp, warmup, iters, reps, sync=sync_tp)
    tp_plan = hvd_metrics.last_shard_plan()
    info_tp["losses"].clear()

    budget.stage("parity")
    # Fresh states walked side by side: the TP trajectory must track the
    # model=1 trajectory within dtype tolerance (the bitwise proofs live
    # in tests/test_tensor_parallel.py; this is the cross-shape check).
    run_a, _, info_a = _build_tp_ab(batch_ref, shard, 1)
    run_b, _, info_b = _build_tp_ab(batch_tp, shard, model)
    for _ in range(steps):
        run_a()
        run_b()
    parity = max(abs(float(a) - float(b))
                 for a, b in zip(info_a["losses"], info_b["losses"]))

    ref_bytes = info_ref["state_bytes_per_chip"]
    tp_bytes = info_tp["state_bytes_per_chip"]
    hvd_metrics.record_sharded_state_bytes(
        tp_bytes * shard * model, shard, model_size=model)
    # Analytic TP wire volume per step: one psum('model') per pair per
    # direction over the [local_batch, dim] activation block.
    from horovod_tpu.parallel import tensor as _tp

    local_batch = info_tp["batch"] // (batch_tp * shard)
    pairs_n = 2
    tp_wire = 2 * pairs_n * _tp.tp_wire_bytes_per_pair(
        local_batch, info_tp["dim"])
    out.update({
        "value": round(ref_bytes / max(tp_bytes, 1), 3),
        "model": model,
        "shard": shard,
        "ref_state_bytes_per_chip": int(ref_bytes),
        "tp_state_bytes_per_chip": int(tp_bytes),
        "param_count": info_ref["param_count"],
        "ref_img_s": round(rate_ref * info_ref["batch"], 2),
        "tp_img_s": round(rate_tp * info_tp["batch"], 2),
        "tp_vs_ref_step_time": round(rate_ref / max(rate_tp, 1e-9), 3),
        "loss_parity_max_abs_err": round(parity, 8),
        "tp_wire_bytes_per_step": int(tp_wire),
        "plan_model_size": (tp_plan or {}).get("model", model),
    })
    # Mesh shape — now three axes — as the SIXTH joint-autotune dimension
    # (jax/autotune.tune): the tuner measures the same step over candidate
    # '<batch>x<shard>x<model>' strings beside (threshold, buckets).
    if not budget.skip_if_low("mesh-autotune", 40):
        budget.stage("mesh-autotune")

        def step_factory(fusion_threshold, mesh_shape):
            b, s, mdl = (int(v) for v in mesh_shape.split("x"))
            run, sync, _ = _build_tp_ab(b, s, mdl,
                                        fusion_threshold=fusion_threshold)
            return run, sync

        report = tune(step_factory, thresholds=(1 << 20,),
                      mesh_shapes=(f"{n_dev // 2}x2x1",
                                   f"{n_dev // 4}x2x2"),
                      warmup=1 if smoke else 2, iters=3, reps=2,
                      gp_rounds=0, verbose=True)
        print(report.knob_curve(), file=sys.stderr)
        out["autotuned_mesh"] = report.best.config.get(
            "mesh", f"{n_dev // 2}x2x1")
    budget.emit(out)


def serve_bench_main() -> None:
    """bench.py --serve: offered-load sweep over the serving vertical
    (ISSUE 10). Exports a tiny-MLP serving checkpoint, starts a 2-replica
    :class:`horovod_tpu.serving.InferenceServer`, and drives closed-loop
    HTTP clients at increasing concurrency; the JSON line reports the best
    sustained throughput with per-level p50/p99 and shed counts riding
    along — the offered-load curve that shows where admission control
    starts earning its keep.

    This mode is a CPU measurement and says so (``"platform": "cpu"`` in
    its line): the replicas are subprocesses that import jax, a chip
    belongs to one process at a time, and a parent that had touched the
    TPU would leave its children hanging on it. So the parent pins the CPU
    platform before its first jax use and exports the pin to the replicas
    it spawns. Serving has no device path until ROADMAP B2."""
    import tempfile
    import threading
    import urllib.error
    import urllib.request

    import numpy as np

    budget = _Budget.install("serve_bench_throughput_rps", "req/s")
    smoke = _smoke_on()
    budget.stage("export")
    os.environ["JAX_PLATFORMS"] = "cpu"   # inherited by the replicas
    import jax

    jax.config.update("jax_platforms", "cpu")

    from horovod_tpu import checkpoint as hvd_ckpt
    from horovod_tpu import serving
    from horovod_tpu.models import MLP

    dim = 64
    model = MLP(features=(32, 10) if smoke else (256, 128, 10))
    params = model.init(jax.random.PRNGKey(0),
                        np.zeros((2, dim), np.float32))["params"]
    tmp = tempfile.mkdtemp(prefix="hvd_serve_bench_")
    ckpt = os.path.join(tmp, "ckpt")
    hvd_ckpt.export_for_inference(ckpt, {"params": params})

    budget.stage("server-start")
    replicas = int(os.environ.get("HVD_SERVE_BENCH_REPLICAS", "2"))
    cfg = serving.ServeConfig.from_env(
        port=0, min_replicas=replicas, max_replicas=replicas,
        slo_ms=float(os.environ.get("HOROVOD_SERVE_SLO_MS", "") or 5000.0))
    server = serving.InferenceServer(ckpt, config=cfg).start()
    out = {"metric": "serve_bench_throughput_rps", "value": 0.0,
           "unit": "req/s", "platform": "cpu", "smoke": smoke,
           "replicas": replicas,
           "max_batch": cfg.max_batch, "sweep": []}
    try:
        if not server.wait_ready(min(120.0, max(budget.remaining() - 30, 10))):
            out.update({"partial": True,
                        "reason": "no replica became ready "
                                  + (server.manager.degraded_reason or "")})
            budget.emit(out)
            return
        url = f"http://127.0.0.1:{server.port}/v1/infer"
        body = json.dumps({"inputs": [0.5] * dim,
                           "deadline_ms": cfg.slo_ms}).encode()

        def drive(concurrency: int, seconds: float) -> dict:
            lat_ms: list[float] = []
            codes: dict[int, int] = {}
            lock = threading.Lock()
            stop_t = time.monotonic() + seconds

            def client():
                while time.monotonic() < stop_t:
                    t0 = time.monotonic()
                    try:
                        r = urllib.request.urlopen(urllib.request.Request(
                            url, data=body,
                            headers={"Content-Type": "application/json"}),
                            timeout=cfg.slo_ms / 1000.0 + 5)
                        r.read()
                        code = r.status
                    except urllib.error.HTTPError as e:
                        code = e.code
                    except OSError:
                        code = -1
                    with lock:
                        codes[code] = codes.get(code, 0) + 1
                        if code == 200:
                            lat_ms.append((time.monotonic() - t0) * 1e3)

            threads = [threading.Thread(target=client)
                       for _ in range(concurrency)]
            t0 = time.monotonic()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            dt = time.monotonic() - t0
            lat_ms.sort()

            def pct(p):
                return round(lat_ms[min(int(len(lat_ms) * p / 100),
                                        len(lat_ms) - 1)], 2) \
                    if lat_ms else 0.0

            return {"concurrency": concurrency,
                    "rps": round(len(lat_ms) / dt, 2),
                    "p50_ms": pct(50), "p99_ms": pct(99),
                    "shed_429": codes.get(429, 0),
                    "errors": sum(v for k, v in codes.items()
                                  if k not in (200, 429))}

        budget.stage("sweep")
        levels = (2, 8) if smoke else (1, 4, 8, 16)
        per_level_s = 1.5 if smoke else 5.0
        drive(2, 0.5)  # warmup: compile the buckets outside the sweep
        for c in levels:
            if budget.skip_if_low(f"load-{c}", per_level_s + 10):
                break
            out["sweep"].append(drive(c, per_level_s))
        stats = server.stats()["serving"]
        best = max(out["sweep"], key=lambda s: s["rps"], default=None)
        out.update({
            "value": best["rps"] if best else 0.0,
            "p50_ms_at_best": best["p50_ms"] if best else 0.0,
            "p99_ms_at_best": best["p99_ms"] if best else 0.0,
            "mean_batch_size": stats["mean_batch_size"],
            "shed_total": stats["admission"]["shed_total"],
        })
    finally:
        server.stop()
    budget.emit(out)


def serve_llm_bench_main() -> None:
    """bench.py --serve-llm: token-latency mode over the LLM serving
    plane (ISSUE 12). Stands up a 1-prefill + 1-decode LLMServer (TinyLM;
    replicas are numpy-only so bring-up never negotiates a backend) and
    drives closed-loop /v1/generate clients with mixed-length prompts.
    The JSON line reports decode tokens/s as the headline plus TTFT/TPOT
    p50/p99 and goodput-under-SLO (completed requests whose end-to-end
    latency stayed inside their deadline, per second) — the
    serving-plane figures ROADMAP item 3 names. One JSON line; an expired
    budget fails the run instead (:class:`_Budget`). Host numpy only: the
    LLM plane never initialises a jax backend."""
    import threading
    import urllib.error
    import urllib.request

    budget = _Budget.install("serve_llm_bench_decode_tokens_per_s", "tok/s")
    smoke = _smoke_on()
    budget.stage("server-start")

    from horovod_tpu.serving.config import LLMConfig, ServeConfig
    from horovod_tpu.serving.llm import LLMServer

    slo_ms = float(os.environ.get("HOROVOD_SERVE_LLM_SLO_MS", "") or 30000.0)
    cfg = ServeConfig.from_env(port=0, slo_ms=slo_ms)
    llm_cfg = LLMConfig.from_env(
        colocated=0,
        prefill_replicas=int(os.environ.get(
            "HVD_SERVE_BENCH_LLM_PREFILL", "1")),
        decode_replicas=int(os.environ.get(
            "HVD_SERVE_BENCH_LLM_DECODE", "1")))
    server = LLMServer(config=cfg, llm_config=llm_cfg).start()
    out = {"metric": "serve_llm_bench_decode_tokens_per_s", "value": 0.0,
           "unit": "tok/s", "smoke": smoke,
           "prefill_replicas": llm_cfg.prefill_replicas,
           "decode_replicas": llm_cfg.decode_replicas,
           "kv_blocks": llm_cfg.num_blocks,
           "block_size": llm_cfg.block_size, "sweep": []}
    try:
        if not server.wait_ready(min(60.0,
                                     max(budget.remaining() - 30, 10))):
            out.update({"partial": True,
                        "reason": "no llm replica became ready"})
            budget.emit(out)
            return
        url = f"http://127.0.0.1:{server.port}/v1/generate"
        max_new = 8 if smoke else 24
        prompt_lens = (1, 4, 9) if smoke else (1, 4, 9, 16, 25)

        def drive(concurrency: int, seconds: float) -> dict:
            lock = threading.Lock()
            lat_ms: list[float] = []
            ttft_ms: list[float] = []
            tpot_ms: list[float] = []
            tokens = [0]
            codes: dict[int, int] = {}
            in_slo = [0]
            stop_t = time.monotonic() + seconds

            def client(ci: int):
                j = 0
                while time.monotonic() < stop_t:
                    j += 1
                    n = prompt_lens[(ci + j) % len(prompt_lens)]
                    body = json.dumps({
                        "prompt": [(ci * 11 + j + k) % llm_cfg.vocab
                                   for k in range(n)],
                        "max_tokens": max_new,
                        "deadline_ms": slo_ms}).encode()
                    t0 = time.monotonic()
                    try:
                        r = urllib.request.urlopen(urllib.request.Request(
                            url, data=body,
                            headers={"Content-Type": "application/json"}),
                            timeout=slo_ms / 1000.0 + 5)
                        resp = json.loads(r.read())
                        code = r.status
                    except urllib.error.HTTPError as e:
                        code, resp = e.code, {}
                    except OSError:
                        code, resp = -1, {}
                    ms = (time.monotonic() - t0) * 1e3
                    with lock:
                        codes[code] = codes.get(code, 0) + 1
                        if code == 200:
                            lat_ms.append(ms)
                            ttft_ms.append(resp.get("ttft_ms", 0.0))
                            if resp.get("tpot_ms") is not None:
                                tpot_ms.append(resp["tpot_ms"])
                            tokens[0] += resp.get("n_tokens", 0)
                            if ms <= slo_ms:
                                in_slo[0] += 1

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(concurrency)]
            t0 = time.monotonic()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            dt = time.monotonic() - t0

            def pct(vals, p):
                if not vals:
                    return 0.0
                s = sorted(vals)
                return round(s[min(int(len(s) * p / 100), len(s) - 1)], 3)

            # TPOT excludes the first token, so decode tokens/s counts
            # generated-past-first (n_tokens - 1 per request)
            decode_tok = max(tokens[0] - codes.get(200, 0), 0)
            return {"concurrency": concurrency,
                    "decode_tokens_per_s": round(decode_tok / dt, 2),
                    "goodput_rps": round(in_slo[0] / dt, 2),
                    "requests_ok": codes.get(200, 0),
                    "shed_429": codes.get(429, 0),
                    "errors": sum(v for k, v in codes.items()
                                  if k not in (200, 429)),
                    "ttft_p50_ms": pct(ttft_ms, 50),
                    "ttft_p99_ms": pct(ttft_ms, 99),
                    "tpot_p50_ms": pct(tpot_ms, 50),
                    "tpot_p99_ms": pct(tpot_ms, 99),
                    "latency_p50_ms": pct(lat_ms, 50),
                    "latency_p99_ms": pct(lat_ms, 99)}

        budget.stage("sweep")
        levels = (2, 6) if smoke else (2, 6, 12)
        per_level_s = 2.0 if smoke else 5.0
        drive(2, 0.5)   # warmup
        for c in levels:
            if budget.skip_if_low(f"load-{c}", per_level_s + 10):
                break
            out["sweep"].append(drive(c, per_level_s))
        llm_stats = server.stats()["serving"]["llm"]
        best = max(out["sweep"], key=lambda s: s["decode_tokens_per_s"],
                   default=None)
        if best:
            out.update({
                "value": best["decode_tokens_per_s"],
                "goodput_rps_at_best": best["goodput_rps"],
                "ttft_p50_ms": best["ttft_p50_ms"],
                "ttft_p99_ms": best["ttft_p99_ms"],
                "tpot_p50_ms": best["tpot_p50_ms"],
                "tpot_p99_ms": best["tpot_p99_ms"],
                "mean_batch_occupancy": llm_stats["mean_batch_occupancy"],
                "preemptions": llm_stats["preemptions_total"],
            })

        def seq_window(srv, w, reqs=10):
            """Sequential single-client window -> engine tok/busy-s.
            One request in flight at a time keeps the decode loop
            uncontended, so the busy-time ratio is clean (same method
            as the llm_smoke spec A/B leg)."""
            prev = srv.stats()["serving"]["llm"]
            for j in range(reqs):
                n = prompt_lens[j % len(prompt_lens)]
                body = json.dumps({
                    "prompt": [(w * 13 + j + k) % llm_cfg.vocab
                               for k in range(n)],
                    "max_tokens": max_new}).encode()
                urllib.request.urlopen(urllib.request.Request(
                    f"http://127.0.0.1:{srv.port}/v1/generate", data=body,
                    headers={"Content-Type": "application/json"}),
                    timeout=30).read()
            cur = srv.stats()["serving"]["llm"]
            d_tok = cur["tokens_decode_total"] - prev["tokens_decode_total"]
            d_busy = cur["decode_busy_s"] - prev["decode_busy_s"]
            return d_tok / max(d_busy, 1e-9)

        # ISSUE 20 optional arm: speculative A/B — paired interleaved
        # windows, best per-pair engine-throughput ratio.
        if budget.remaining() > 45:
            budget.stage("spec-ab")
            arms = {k: LLMServer(
                config=ServeConfig.from_env(port=0, slo_ms=slo_ms),
                llm_config=LLMConfig.from_env(colocated=1, draft_k=k)
            ).start() for k in (0, 3)}
            try:
                if all(s.wait_ready(30) for s in arms.values()):
                    pairs = []
                    for w in range(3):
                        b = seq_window(arms[0], w)
                        s = seq_window(arms[3], w)
                        if w:        # window 0 is warmup
                            pairs.append((s / b, b, s))
                    ratio, b_best, s_best = max(pairs)
                    spec_llm = arms[3].stats()["serving"]["llm"]
                    out["spec_ab"] = {
                        "draft_k": 3, "speedup_x": round(ratio, 3),
                        "baseline_tok_per_busy_s": round(b_best, 1),
                        "spec_tok_per_busy_s": round(s_best, 1),
                        "acceptance_rate":
                            spec_llm["spec_acceptance_rate"]}
            finally:
                for s in arms.values():
                    s.stop()

        # ISSUE 20 optional arm: radix prefix replay through a small
        # pool (same shape as the llm_smoke leg: 4 hot 2-block system
        # prompts + 1 cold one squeezed by an 11-block pool).
        if budget.remaining() > 30:
            budget.stage("prefix-replay")
            psrv = LLMServer(
                config=ServeConfig.from_env(port=0, slo_ms=slo_ms),
                llm_config=LLMConfig.from_env(
                    colocated=1, prefix_cache=1, num_blocks=11,
                    max_active=4)).start()
            try:
                if psrv.wait_ready(30):
                    purl = f"http://127.0.0.1:{psrv.port}/v1/generate"

                    def ppost(prompt):
                        urllib.request.urlopen(urllib.request.Request(
                            purl, data=json.dumps(
                                {"prompt": prompt,
                                 "max_tokens": 4}).encode(),
                            headers={"Content-Type": "application/json"}),
                            timeout=30).read()

                    sysps = [[(s * 7 + i) % llm_cfg.vocab
                              for i in range(32)] for s in range(4)]
                    ppost([(5 * 7 + i) % llm_cfg.vocab
                           for i in range(32)] + [9])
                    for rnd in range(3):
                        for s, sys_p in enumerate(sysps):
                            for tail in range(3):
                                ppost(sys_p
                                      + [(rnd + 11 * tail + s) % 61 + 1])
                    pl = psrv.stats()["serving"]["llm"]
                    out["prefix_replay"] = {
                        "hit_rate": pl["prefix_hit_rate"],
                        "hit_tokens": pl["prefix_hit_tokens_total"],
                        "lookup_tokens": pl["prefix_lookup_tokens_total"],
                        "recovered_blocks": pl["recovered_blocks_total"],
                        "cow_copies": pl["cow_copies_total"]}
            finally:
                psrv.stop()
    finally:
        server.stop()
    budget.emit(out)


def _synth_hist(count: int, rank: int) -> dict:
    """Histogram snapshot in registry.to_dict shape (cumulative buckets)."""
    bounds = [1e-4 * (4.0 ** k) for k in range(11)]
    step = max(count // len(bounds), 1)
    cum = 0
    buckets = []
    for b in bounds:
        cum = min(cum + step, count)
        buckets.append([b, cum])
    buckets.append(["+Inf", count])
    return {"count": count, "sum": count * 0.01 + rank * 1e-4,
            "p50": 0.01, "p90": 0.02, "p99": 0.04, "buckets": buckets}


def _synth_snapshot(rank: int, tick: int) -> dict:
    """A realistic per-rank metrics snapshot: ~70 series of which only a
    handful CHANGE per collection tick (step counters, one latency
    histogram) — the regime the telemetry tree's delta compression exists
    for. Deterministic in (rank, tick), so both bench arms ship byte-
    identical information."""
    counters = {f'horovod_allreduce_ops_total{{bucket="{i}"}}':
                float(1000 + i) for i in range(40)}
    counters["horovod_steps_total"] = float(tick)
    counters["horovod_allreduce_bytes_total"] = tick * 1.5e6 + rank
    gauges = {f'horovod_fusion_buffer_bytes{{plane="{i}"}}':
              float((1 << 20) + i) for i in range(20)}
    gauges["horovod_step_time_s"] = 0.1 + 0.001 * ((rank + tick) % 7)
    hists = {f'horovod_allreduce_seconds{{op="{h}"}}':
             _synth_hist(100 * (tick if h == 0 else 1) + rank + h, rank)
             for h in range(6)}
    return {"schema": "horovod_tpu.metrics.v1",
            "time_unix_s": 1.7e9 + tick,
            "counters": counters, "gauges": gauges, "histograms": hists,
            "info": {"device": f"tpu:{rank}"}}


def _telemetry_scale_once(world: int, hosts: int, ticks: int) -> dict:
    """One grid size of the --telemetry-scale A/B.

    FLAT arm: ``world`` clients each push a FULL snapshot to the driver
    every tick (the pre-tree ``metrics`` path, TaskAgent.report_metrics).
    TREE arm: ranks push DELTAS to their host's TelemetryAgent; each
    leader pushes ONE delta-compressed host partial to the driver
    (``host_metrics``). Both arms are measured on the same real
    HMAC-framed wire (BasicService.stats bytes_in), and both pod views
    must come out bitwise identical — the reduction only counts if
    nothing was lost."""
    import secrets
    import shutil
    import tempfile

    from horovod_tpu.metrics.aggregate import merge_snapshots
    from horovod_tpu.runner.network import BasicClient
    from horovod_tpu.runner.service import DriverService
    from horovod_tpu.telemetry.agent import (RankTelemetryClient,
                                             TelemetryAgent)
    from horovod_tpu.tracing.bundle import make_bundle
    from horovod_tpu.tracing.flight import FlightRecorder

    key = secrets.token_bytes(32)
    per_host = world // hosts
    snaps = {}   # rank -> latest snapshot (the expected flat merge input)

    def settle(svc):
        # stats are flushed server-side right after each response is sent;
        # one drained tick later they are exact.
        deadline = time.monotonic() + 2.0
        last = -1
        while time.monotonic() < deadline:
            cur = svc.stats()["requests_total"]
            if cur == last:
                break
            last = cur
            time.sleep(0.02)
        return svc.stats()

    # -- flat arm ------------------------------------------------------------
    root = DriverService(world, key)
    clients = [BasicClient([("127.0.0.1", root.port)], key, timeout=30.0)
               for _ in range(world)]
    settle(root)
    base = root.stats()["bytes_in"]
    steady0 = None
    for t in range(1, ticks + 1):
        if t == 2:
            steady0 = settle(root)["bytes_in"]
        for r, c in enumerate(clients):
            snaps[r] = _synth_snapshot(r, t)
            c.request({"kind": "metrics", "rank": r, "snapshot": snaps[r]})
    st = settle(root)
    flat_bytes_per_tick = (st["bytes_in"] - steady0) / (ticks - 1)
    flat_conns = st["connections_total"]
    flat_pod = root.pod_metrics()
    for c in clients:
        c.close()
    root.stop()

    # -- tree arm ------------------------------------------------------------
    tmp = tempfile.mkdtemp(prefix="hvd-telemetry-scale-")
    root = DriverService(world, key)
    agents, rank_clients = [], []
    try:
        for h in range(hosts):
            fdir = os.path.join(tmp, f"host-{h:02d}")
            os.makedirs(fdir, exist_ok=True)
            fr = FlightRecorder(f"rank{h * per_host}", flight_dir=fdir)
            fr.event("bench", note="telemetry-scale synthetic record")
            fr.close()
            ag = TelemetryAgent(
                key, host_name=f"host-{h:02d}", flight_dir=fdir,
                trace_dir="", interval_s=3600.0,
                expected_ranks=range(h * per_host, (h + 1) * per_host))
            ag.attach_root([("127.0.0.1", root.port)], probe_rounds=2,
                           start_loop=False)
            agents.append(ag)
            for r in range(h * per_host, (h + 1) * per_host):
                rank_clients.append(RankTelemetryClient(
                    [("127.0.0.1", ag.port)], key, r))
        settle(root)
        steady0 = None
        for t in range(1, ticks + 1):
            if t == 2:
                steady0 = settle(root)["bytes_in"]
            for rc in rank_clients:
                rc.push(_synth_snapshot(rc.rank, t))
            for ag in agents:
                ag.push_to_root_once()
        st = settle(root)
        tree_bytes_per_tick = (st["bytes_in"] - steady0) / (ticks - 1)
        tree_conns = st["connections_total"]
        tree_pod = root.pod_metrics()
        leader_bytes = sum(settle(ag)["bytes_in"] for ag in agents)

        # one-command bundle THROUGH the leaders: wall-clock + coverage
        t0 = time.monotonic()
        bundle = make_bundle(
            os.path.join(tmp, "bundle"),
            leaders=[f"127.0.0.1:{ag.port}" for ag in agents],
            leader_key=key)
        bundle_s = time.monotonic() - t0
    finally:
        for rc in rank_clients:
            rc.close()
        for ag in agents:
            ag.stop()
        root.stop()
        shutil.rmtree(tmp, ignore_errors=True)

    pods_equal = flat_pod == tree_pod
    expected = merge_snapshots([snaps[r] for r in range(world)])
    expected.pop("time_unix_s", None)
    for pod in (flat_pod, tree_pod):
        pod.pop("time_unix_s", None)
    return {
        "world": world, "hosts": hosts, "ticks": ticks,
        "flat_root_bytes_per_tick": round(flat_bytes_per_tick),
        "tree_root_bytes_per_tick": round(tree_bytes_per_tick),
        "root_byte_reduction": round(
            flat_bytes_per_tick / max(tree_bytes_per_tick, 1.0), 2),
        "flat_root_connections": flat_conns,
        "tree_root_connections": tree_conns,
        "leader_ingest_bytes_total": leader_bytes,
        "pod_views_bitwise_equal": bool(pods_equal),
        "tree_pod_equals_flat_merge": bool(tree_pod == expected),
        "bundle_wall_clock_s": round(bundle_s, 3),
        "bundle_hosts_swept": bundle["hosts_swept"],
        "bundle_coverage_gaps": bundle["coverage_gaps"],
    }


def telemetry_scale_main() -> None:
    """bench.py --telemetry-scale: measure the telemetry tree's root-side
    cost against the flat O(world) fan-in, at world 64 (8 hosts x 8
    ranks) and 128 (16 x 8). Headline: root ingest bytes per collection
    tick, flat / tree — gated in ci.sh at >= 6x (measured ~>= 8x).
    Correctness rides along: both arms' pod views must be bitwise equal.
    Pure control-plane loopback TCP; runs before any jax import."""
    budget = _Budget.install("telemetry_scale_root_byte_reduction", "x")
    ticks = int(os.environ.get("HVD_TELEMETRY_TICKS", "") or
                ("4" if _smoke_on() else "6"))
    grids = [(64, 8)]
    if not _smoke_on():
        grids.append((128, 16))
    out = {"metric": "telemetry_scale_root_byte_reduction", "value": 0.0,
           "unit": "x", "smoke": _smoke_on(), "grids": []}
    try:
        for world, hosts in grids:
            if budget.skip_if_low(f"grid-{world}", 45):
                break
            budget.stage(f"grid-{world}")
            out["grids"].append(_telemetry_scale_once(world, hosts, ticks))
    except Exception as e:  # noqa: BLE001 - partial beats silent (contract)
        out.update({"partial": True, "reason": f"{type(e).__name__}: {e}"})
        budget.emit(out)
        return
    g64 = next((g for g in out["grids"] if g["world"] == 64), None)
    if g64 is not None:
        out["value"] = g64["root_byte_reduction"]
        out["bundle_wall_clock_s"] = g64["bundle_wall_clock_s"]
        out["pod_views_bitwise_equal"] = all(
            g["pod_views_bitwise_equal"] and g["tree_pod_equals_flat_merge"]
            for g in out["grids"])
    budget.emit(out)


def _control_scale_once(world: int, hosts: int, poll_rounds: int) -> dict:
    """One grid size of the --control-scale A/B.

    FLAT arm: ``world`` workers each speak the runner control protocol —
    register, wait_assignment, commit-time elastic_poll — straight to the
    driver (pre-tree TaskAgent path). TREE arm: each host's ranks speak
    the SAME protocol to their host's ControlAgent, which batches
    registrations (``host_register``), groups assignment waits
    (``host_wait_assignment``) and caches poll verdicts
    (``host_elastic_poll``), so the root sees O(hosts) connections and
    bytes. Both arms run the same three phases on the same HMAC-framed
    wire: cold rendezvous at full world, ``poll_rounds`` of commit-time
    membership polls, then an elastic reset with one member dropped."""
    import secrets
    import threading

    from horovod_tpu.ctrl.agent import ControlAgent
    from horovod_tpu.runner.network import BasicClient
    from horovod_tpu.runner.service import ElasticDriverService

    key = secrets.token_bytes(32)
    per_host = world // hosts

    def settle(svc):
        deadline = time.monotonic() + 2.0
        last = -1
        while time.monotonic() < deadline:
            cur = svc.stats()["requests_total"]
            if cur == last:
                break
            last = cur
            time.sleep(0.02)
        return svc.stats()

    def ctrl_bytes(st):
        return st["bytes_in"] + st["bytes_out"]

    def reg_req(i):
        return {"kind": "register", "index": i,
                "host_hash": f"host-{i // per_host:02d}",
                "addresses": [("127.0.0.1", 40000 + i)],
                "coord_port": 40000 + i, "jax_coord_port": 41000 + i}

    def rendezvous(pairs, min_gen):
        """All (index, client) pairs register + wait for an assignment in
        generation ``min_gen``; returns the full-world wall clock."""
        errs = []

        def one(i, c):
            c.request(reg_req(i))
            r = c.request({"kind": "wait_assignment", "index": i,
                           "min_generation": min_gen, "timeout": 120.0})
            if not (isinstance(r, dict) and r.get("ok")):
                errs.append((i, r))

        threads = [threading.Thread(target=one, args=p, daemon=True)
                   for p in pairs]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join(150.0)
        if errs:
            raise RuntimeError(f"rendezvous failed: {errs[:3]}")
        return time.monotonic() - t0

    def run_arm(tree: bool) -> dict:
        root = ElasticDriverService(key)
        agents, clients = [], []
        conn_base = root.stats()["connections_total"]
        try:
            if tree:
                for h in range(hosts):
                    ag = ControlAgent(key, host_name=f"host-{h:02d}",
                                      batch_s=0.005, poll_s=30.0)
                    ag.attach_root([("127.0.0.1", root.port)])
                    agents.append(ag)
                addr = lambda i: ("127.0.0.1", agents[i // per_host].port)  # noqa: E731
            else:
                addr = lambda i: ("127.0.0.1", root.port)  # noqa: E731
            clients = [BasicClient([addr(i)], key, timeout=150.0)
                       for i in range(world)]
            base = settle(root)

            root.begin_reset(set(range(world)))
            rendezvous_s = rendezvous(list(enumerate(clients)), 1)
            st1 = settle(root)

            # the commit-time steady state: every rank polls membership and
            # aligns its trace clock each round — the tree answers the
            # probe on-host and the poll from the per-host verdict cache
            for _ in range(poll_rounds):
                for i, c in enumerate(clients):
                    r = c.request({"kind": "elastic_poll", "index": i,
                                   "generation": 1})
                    if not r.get("ok") or r.get("reset_required"):
                        raise RuntimeError(f"bad poll verdict for {i}: {r}")
                    p = c.request({"kind": "clock_probe"})
                    if not p.get("ok"):
                        raise RuntimeError(f"clock probe failed for {i}: {p}")
            st2 = settle(root)

            # drop the last member; survivors re-rendezvous as generation 2
            root.begin_reset(set(range(world - 1)))
            reset_s = rendezvous(list(enumerate(clients))[:world - 1], 2)
            st3 = settle(root)
            return {
                "rendezvous_s": round(rendezvous_s, 3),
                "reset_s": round(reset_s, 3),
                "rendezvous_bytes": ctrl_bytes(st1) - ctrl_bytes(base),
                "poll_bytes_per_round": round(
                    (ctrl_bytes(st2) - ctrl_bytes(st1)) / poll_rounds),
                "reset_bytes": ctrl_bytes(st3) - ctrl_bytes(st2),
                "total_bytes": ctrl_bytes(st3) - ctrl_bytes(base),
                "root_connections": st3["connections_total"] - conn_base,
            }
        finally:
            for c in clients:
                c.close()
            for ag in agents:
                ag.stop()
            root.stop()

    flat = run_arm(tree=False)
    tree = run_arm(tree=True)
    return {
        "world": world, "hosts": hosts, "poll_rounds": poll_rounds,
        "flat": flat, "tree": tree,
        "root_byte_reduction": round(
            flat["total_bytes"] / max(tree["total_bytes"], 1), 2),
        "root_connection_reduction": round(
            flat["root_connections"] / max(tree["root_connections"], 1), 2),
        "rendezvous_speedup": round(
            flat["rendezvous_s"] / max(tree["rendezvous_s"], 1e-9), 2),
        "reset_speedup": round(
            flat["reset_s"] / max(tree["reset_s"], 1e-9), 2),
    }


def control_scale_main() -> None:
    """bench.py --control-scale: measure the control tree's root-side cost
    against the flat O(world) runner plane, at world 64 (8 hosts x 8
    ranks) and 128 (16 x 8). Headline: root control bytes across one
    cold rendezvous + steady-state polls + one elastic reset, flat /
    tree — gated in ci.sh at >= 6x. Latency rides along: tree
    rendezvous and elastic reset wall clock must not regress. Pure
    control-plane loopback TCP; runs before any jax import."""
    budget = _Budget.install("control_scale_root_byte_reduction", "x")
    poll_rounds = int(os.environ.get("HVD_CTRL_POLL_ROUNDS", "") or
                      ("3" if _smoke_on() else "6"))
    grids = [(64, 8)]
    if not _smoke_on():
        grids.append((128, 16))
    out = {"metric": "control_scale_root_byte_reduction", "value": 0.0,
           "unit": "x", "smoke": _smoke_on(), "grids": []}
    try:
        for world, hosts in grids:
            if budget.skip_if_low(f"grid-{world}", 60):
                break
            budget.stage(f"grid-{world}")
            out["grids"].append(_control_scale_once(world, hosts, poll_rounds))
    except Exception as e:  # noqa: BLE001 - partial beats silent (contract)
        out.update({"partial": True, "reason": f"{type(e).__name__}: {e}"})
        budget.emit(out)
        return
    g64 = next((g for g in out["grids"] if g["world"] == 64), None)
    if g64 is not None:
        out["value"] = g64["root_byte_reduction"]
        out["root_connection_reduction"] = g64["root_connection_reduction"]
        out["tree_rendezvous_s"] = g64["tree"]["rendezvous_s"]
        out["tree_reset_s"] = g64["tree"]["reset_s"]
    budget.emit(out)


def main() -> None:
    if "--eager-worker" in sys.argv:
        return eager_worker_main()
    if "--eager" in sys.argv:
        return eager_main()
    if "--compression-ab" in sys.argv:
        return compression_ab_main()
    if "--hier-ab" in sys.argv:
        return hier_ab_main()
    if "--telemetry-scale" in sys.argv:
        return telemetry_scale_main()
    if "--control-scale" in sys.argv:
        return control_scale_main()

    # The serving planes are CPU processes by statement, parent and
    # replicas alike (serve_bench_main): dispatched before this process
    # imports jax, so nothing here can claim the chip their children need.
    if "--serve-llm" in sys.argv:
        return serve_llm_bench_main()
    if "--serve" in sys.argv:
        return serve_bench_main()

    # Arm the watchdog BEFORE the first jax import: backend init itself can
    # wedge, and an expired budget must fail the run with the stuck stage
    # named. The metric/unit are picked per mode HERE so a pre-jax failure
    # names the right one.
    mode_metrics = {
        "--autotune": ("autotune_best_config", "steps/s"),
        "--controller-ab": ("controller_convergence_ratio", "x"),
        "--buckets-ab": ("buckets_ab_images_per_sec", "img/s"),
        "--fsdp-ab": ("fsdp_ab_memory_reduction", "x"),
        "--tp-ab": ("tp_ab_memory_reduction", "x"),
        "--roofline": ("resnet50_roofline", "GB/s"),
        "--scaling": ("scaling_suite", "n/a"),
    }
    metric, unit = next((m for flag, m in mode_metrics.items()
                         if flag in sys.argv),
                        ("resnet50_images_per_sec", "img/s"))
    budget = _Budget.install(metric, unit)
    budget.stage("jax-import")

    import jax

    import horovod_tpu as hvd
    from horovod_tpu.utils.compile_cache import configure_compile_cache

    # One compile cache for bench.py and chip_smoke.py: the operator's
    # JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache.
    configure_compile_cache(os.path.dirname(os.path.abspath(__file__)))

    if "--autotune" in sys.argv:
        return autotune_main()
    if "--controller-ab" in sys.argv:
        return controller_ab_main()
    if "--fsdp-ab" in sys.argv:
        return fsdp_ab_main()
    if "--tp-ab" in sys.argv:
        return tp_ab_main()
    if "--buckets-ab" in sys.argv:
        return buckets_ab_main()
    if "--roofline" in sys.argv:
        return roofline_main()
    if "--scaling" in sys.argv:
        # Scaling-efficiency curves (the reference's headline artifact,
        # README.md:53-58): eager ring worlds 2..16, compiled virtual mesh
        # 1..8, analytic pod projection. Full doc: docs/scaling.md. The
        # harness owns this mode's budget and output shape — stand down.
        budget.disarm()
        sys.path.insert(0, os.path.join(os.path.dirname(__file__), "examples"))
        import scaling_benchmark

        return scaling_benchmark.main()

    budget = _Budget.install("resnet50_images_per_sec", "img/s")
    budget.stage("init")
    hvd.init()
    from horovod_tpu.jax.autotune import measure_steps_per_s as _measure

    if _smoke_on():
        # CI smoke: tiny MLP, a handful of steps, same JSON shape. A hung
        # collective or compiler surfaces within ci.sh's short timeout
        # instead of silently eating the harness budget.
        budget.stage("compile+measure")
        step, (params, opt_state), (x, y), batch, n_dev = _build_smoke()
        state = [params, opt_state]
        loss_box = [None]

        def run_smoke():
            p, o, loss_box[0] = step(*state, x, y)
            state[:] = (p, o)

        rate = _measure(run_smoke, warmup=2, iters=5, reps=2,
                        sync=lambda: float(loss_box[0]))
        budget.emit({
            "metric": "resnet50_images_per_sec",
            "value": round(batch * rate, 2),
            "unit": "img/s",
            "smoke": True,
            "vs_baseline": 0.0,
        })
        if "--metrics" in sys.argv and not budget.skip_if_low("metrics", 30):
            _emit_metrics_snapshot(run_smoke, lambda: float(loss_box[0]),
                                   steps_per_s=rate)
        return

    # Apply tuned winners from --autotune: threshold via
    # HOROVOD_FUSION_THRESHOLD (read in _build) and the ladder via
    # HOROVOD_HIERARCHICAL_ALLREDUCE — the same env knobs the eager engine
    # honors (common/config.py), so the tuning loop closes for both paths.
    from horovod_tpu.common.config import Config

    budget.stage("compile")
    step, (params, batch_stats, opt_state), (x, y), batch, n_dev = _build(
        hierarchical=Config.from_env().hierarchical_allreduce)

    # Warmup (compile) + timed windows, reference-style (synthetic_benchmark
    # num_warmup_batches=10, num_batches_per_iter=10 over num_iters=10 with
    # mean±σ). Timing methodology is shared with the autotuner
    # (measure_steps_per_s): chained dispatches per window, ONE float(loss)
    # host-read fence per window, median window.
    from horovod_tpu.jax.autotune import measure_steps_per_s

    state = [params, batch_stats, opt_state]
    loss_box = [None]

    def run():
        p, bs, os_, loss_box[0] = step(*state, x, y)
        state[:] = (p, bs, os_)

    budget.stage("measure")
    rate = measure_steps_per_s(run, warmup=5, iters=20, reps=3,
                               sync=lambda: float(loss_box[0]))

    # Checkpoint-time stat consolidation (outside the timed region, like the
    # reference's broadcast-on-save): one fused mean over the rank dim.
    batch_stats = jax.tree_util.tree_map(lambda t: t.mean(axis=0), state[1])
    jax.block_until_ready(batch_stats)

    img_s = batch * rate
    per_chip = img_s / n_dev
    budget.emit({
        "metric": "resnet50_images_per_sec",
        "value": round(img_s, 2),
        "unit": "img/s",
        "vs_baseline": round(per_chip / REFERENCE_PER_DEVICE_IMG_S, 3),
    })
    if "--metrics" in sys.argv and not budget.skip_if_low("metrics", 60):
        _emit_metrics_snapshot(run, lambda: float(loss_box[0]),
                               steps_per_s=rate)


if __name__ == "__main__":
    sys.exit(main())
