"""One process, one cell, once.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Builds the mesh over exactly the cell's chips, makes state and batch on the
device from ``--seed``, holds the system's first steps against the
configuration's reference, warms up, measures, and prints ONE last line of
JSON (``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and, traced,
``breakdown``). There is no CPU mode: fewer devices than the cell asks for, or
a first device that is not a TPU, exits non-zero at once with no result line.

Everything that belongs to one configuration, one traffic mix or one per-layer
metric is a file of its own that this harness finds by the name in
``BENCHMARK.json``: ``configs/<config>.json`` + ``.py``, ``traffic/<mix>.json``,
``layer_metrics/<metric>.py``. Adding a cell adds files and entries and edits
nothing here.

The measured loop is the one a user's script runs (upstream's
``synthetic_benchmark``): steps dispatched back to back with donated carries,
the loss of every ``fence_every``-th step read on the host, ``fence_lag`` steps
behind the dispatch, and nothing else read. The window opens at such a read
and closes at the first one after ``--seconds``. ``--trace 1`` runs the same
loop for ``trace_groups`` fence groups under the profiler instead and reports
the per-layer metrics.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()     # set-up is counted from here

import argparse     # noqa: E402
import collections  # noqa: E402
import importlib.util   # noqa: E402
import json     # noqa: E402
import math     # noqa: E402
import os       # noqa: E402
import shutil   # noqa: E402
import statistics   # noqa: E402
import sys      # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT_DIR = os.path.join(ROOT, ".bench_out")      # listed in .gitignore
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def log(*parts):
    print("[bench]", *parts, flush=True)


class Phases:
    """One line per phase of set-up: seconds since the previous phase ended."""

    def __init__(self, start=T0):
        self.last = start

    def done(self, name):
        now = time.perf_counter()
        log(f"set-up phase {name}: {now - self.last:.2f} s")
        self.last = now


# ------------------------------------------------------------------ manifest

def load_module(path):
    name = "bench_" + os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_manifest(root=ROOT):
    return load_json(os.path.join(root, "BENCHMARK.json"))


def resolve_cell(manifest, workload, root=ROOT):
    """The cell's entry, its configuration (data + module), its traffic and
    the names of the metrics it reports."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    cell = cells[workload]
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    config_file = os.path.join(root, entry["file"])
    bench_dir = os.path.dirname(os.path.dirname(config_file))

    def reported(kind):
        return [m for m in manifest[kind]
                if workload in m.get("workloads", [workload])]

    return {
        "cell": cell,
        "config": load_json(config_file),
        "module": load_module(os.path.splitext(config_file)[0] + ".py"),
        "traffic": load_json(os.path.join(bench_dir, "traffic",
                                          cell["traffic"] + ".json")),
        "end_to_end": reported("end_to_end"),
        "per_layer": reported("per_layer"),
        "readers_dir": os.path.join(bench_dir, "layer_metrics"),
    }


# -------------------------------------------------------------------- window

def run_window(dispatch, fence, fence_every, lag, seconds, clock, compiles,
               max_groups=None, in_flight=()):
    """Fence groups until ``seconds`` have passed at a fence (or
    ``max_groups`` are done). ``dispatch()`` launches one step without waiting
    and returns its loss, still on the device; ``fence(loss)`` reads it on the
    host. The loss of every ``fence_every``-th step is read once ``lag``
    further steps are launched, so the device has work queued while the host
    wakes from the read (PERF.md, Findings: with no lag, a busy neighbour on
    the shared host added 27 ms to every group). ``in_flight`` are the losses
    of the steps an earlier window left running: the window then opens and
    closes on the same event, a fence's return with ``lag`` steps queued. A
    group fails when its loss is not finite or a compilation happened inside
    it."""
    opened = last = clock()
    groups, dispatch_s, in_flight = [], [], collections.deque(in_flight)
    compiled_before = compiles()
    while True:
        t = clock()
        in_flight.append(dispatch())
        dispatch_s.append(clock() - t)
        if len(in_flight) < fence_every + lag:
            continue
        for _ in range(fence_every - 1):
            in_flight.popleft()
        loss = fence(in_flight.popleft())
        now = clock()
        compiled = compiles()
        groups.append({"loss": loss, "compiled": compiled - compiled_before,
                       "closed": now, "seconds": now - last,
                       "failed": (compiled > compiled_before
                                  or not math.isfinite(loss))})
        last, compiled_before = now, compiled
        if now - opened >= seconds or (max_groups and len(groups) >= max_groups):
            break
    return {"opened": opened, "closed": last, "groups": groups,
            "dispatch_s": dispatch_s, "in_flight": list(in_flight)}


# -------------------------------------------------------------------- device

def require_devices(chips):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise SystemExit(
            f"benchmarks/run.py: this cell needs {chips} TPU chip(s); JAX "
            f"found {len(devices)} device(s) of platform "
            f"{devices[0].platform!r}. There is no CPU mode.")
    return devices[:chips]


def hbm_bytes(devices):
    """The fullest chip's HBM occupancy now: live buffers plus the runtime's
    reservation for program scratch, which stays at its high-water mark. On
    this runtime ``peak_bytes_in_use`` alone leaves out every program's
    temporaries (PERF.md, Findings). A device that does not report a key is an
    error, not a zero."""
    fullest = 0
    for d in devices:
        stats = d.memory_stats()
        if not stats or not {"bytes_in_use", "bytes_reserved"} <= set(stats):
            raise RuntimeError(f"{d} reports no memory statistics: {stats}")
        fullest = max(fullest, stats["bytes_in_use"] + stats["bytes_reserved"])
    return fullest


def load_peaks(device_kind):
    peaks = load_json(os.path.join(HERE, "peaks.json"))
    if device_kind not in peaks:
        raise RuntimeError(f"no published peaks for device kind "
                           f"{device_kind!r} in benchmarks/peaks.json")
    return peaks[device_kind]


class CompileCounter:
    """Counts compilations and cache hits through JAX's own monitoring
    events."""

    def __init__(self):
        import jax.monitoring

        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.compiles += 1

    def _event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1


# --------------------------------------------------------------------- check

def hold_first_steps(resolved, mesh, seed, built, phases, **model_overrides):
    """The exchange against the analytic mean, then the system's first two
    steps against the configuration's reference. Returns (correct, first call
    seconds, losses); a failed comparison is logged, not raised, so the run
    still reports."""
    from benchmarks.reference import exchange, plain_step

    module, config, traffic = (resolved[k] for k in ("module", "config",
                                                     "traffic"))
    correct = True
    try:
        log("exchange max_abs_err", exchange.check_exchange(mesh, seed))
        phases.done("exchange check")
        reference = module.reference(config, traffic, mesh, seed,
                                     **model_overrides)
        phases.done("reference")
    except AssertionError as e:
        log("INCORRECT:", e)
        correct, reference = False, {"kind": "none"}
    system = {"losses": []}
    if reference["kind"] == "step":
        system["before"] = plain_step.take_sample(built["state"][0],
                                                  reference["indices"])
    first_call_s = None
    for _ in range(2):
        t = time.perf_counter()
        *built["state"], loss = built["step"](*built["state"], *built["batch"])
        system["losses"].append(float(loss))
        first_call_s = first_call_s or time.perf_counter() - t
    if reference["kind"] == "step":
        system["after"] = plain_step.take_sample(built["state"][0],
                                                 reference["indices"])
        try:
            log("step vs plain reference:", json.dumps(plain_step.compare(
                system, reference, config["tolerance"])))
        except AssertionError as e:
            log("INCORRECT:", e)
            correct = False
    elif reference["kind"] == "kernel":
        log("kernels vs f32 reference (share of max|ref|):",
            json.dumps(reference["observed"]))
    return correct, first_call_s, system["losses"]


# ---------------------------------------------------------------------- main

def abstract(tree):
    import jax

    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
        tree)


def read_layer_metrics(resolved, context):
    values = {}
    for metric in resolved["per_layer"]:
        reader = load_module(os.path.join(resolved["readers_dir"],
                                          metric["name"] + ".py"))
        value = reader.read(context)
        if value is not None:     # a reader that finds nothing returns nothing
            values[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return values


def run_cell(resolved, devices, seed, seconds, trace, phases=None,
             **model_overrides):
    """Build, check, warm up, measure; returns the object of the last line.
    ``model_overrides`` is for the CPU tests (``flash_interpret=True``); the
    command passes none."""
    import jax

    import horovod_tpu as hvd
    from horovod_tpu.metrics.overlap import last_plan

    from benchmarks import reduce_trace

    cell, traffic, config, module = (
        resolved[k] for k in ("cell", "traffic", "config", "module"))
    chips = len(devices)
    phases = phases or Phases(time.perf_counter())
    counter = CompileCounter()
    trace_dir = os.path.join(OUT_DIR, "trace", cell["name"])
    mesh = hvd.data_parallel_mesh(devices)
    built = module.build(config, traffic, mesh, seed, **model_overrides)
    phases.done("build state and batch")
    correct, step_compile_s, losses = hold_first_steps(
        resolved, mesh, seed, built, phases, **model_overrides)
    phases.done("first two steps and comparison")
    log("step_compile_s", step_compile_s, "compilations so far",
        counter.compiles, "cache hits", counter.cache_hits)
    log("fusion plan of the step [(bucket, bytes)]:", last_plan())

    state, step, batch = built["state"], built["step"], built["batch"]

    def dispatch():
        with jax.profiler.TraceAnnotation(reduce_trace.DISPATCH):
            *state[:], loss = step(*state, *batch)
        return loss

    def fence(loss):
        with jax.profiler.TraceAnnotation(reduce_trace.FENCE):
            return float(loss)

    def compiles():
        return counter.compiles

    fence_every, lag = traffic["fence_every"], traffic["fence_lag"]
    warm = run_window(dispatch, fence, fence_every, lag, math.inf,
                      time.perf_counter, compiles,
                      max_groups=traffic["warmup_groups"])
    losses += [g["loss"] for g in warm["groups"]]
    phases.done("warm-up")
    if not all(map(math.isfinite, losses)) or not losses[-1] < losses[0]:
        log("INCORRECT: the loss did not fall on the repeated batch:", losses)
        correct = False

    carried = warm["in_flight"]
    try:
        if trace:       # a trace holds whole steps only: drain, then start
            shutil.rmtree(trace_dir, ignore_errors=True)
            losses += [fence(loss) for loss in carried]
            carried = []
            jax.profiler.start_trace(trace_dir)
        setup_s = time.perf_counter() - T0
        window = run_window(
            dispatch, fence, fence_every, lag, math.inf if trace else seconds,
            time.perf_counter, compiles,
            max_groups=traffic["trace_groups"] if trace else None,
            in_flight=carried)
        drained = [fence(loss) for loss in window["in_flight"]]
        if trace:
            jax.profiler.stop_trace()
        memory = hbm_bytes(devices)
        log("memory_stats of the first device:", devices[0].memory_stats())
        log("losses", losses + [g["loss"] for g in window["groups"]] + drained)
        seconds_open = window["closed"] - window["opened"]
        steps = len(window["groups"]) * fence_every
        log("group seconds", json.dumps([g["seconds"] for g in window["groups"]]))
        log("window_s", seconds_open, "groups", len(window["groups"]),
            "compilations in all", counter.compiles, "cache hits",
            counter.cache_hits, "host_dispatch_ms_per_step (median)",
            statistics.median(window["dispatch_s"]) * 1e3)

        kind = devices[0].device_kind
        device = {"platform": devices[0].platform, "kind": kind,
                  "count": chips, "memory_peak_bytes": memory}
        result = {"correct": correct, "attempted": len(window["groups"]),
                  "failed": sum(g["failed"] for g in window["groups"])}
        if trace:
            hlo = step.lower(*abstract(state), *abstract(batch)).compile().as_text()
            reduced = reduce_trace.reduce(
                reduce_trace.load(reduce_trace.find_xplane(trace_dir)),
                len(window["dispatch_s"]),      # every step the trace holds
                reduce_trace.scopes_from_hlo(hlo))
            if reduced is None:
                raise RuntimeError("the trace holds no device operation")
            log("reduced trace:", json.dumps(
                {k: v for k, v in reduced.items() if k != "breakdown"}))
            result["metrics"] = read_layer_metrics(resolved, {
                "trace": reduced, "chips": chips, "peaks": load_peaks(kind),
                "dispatch_s": window["dispatch_s"],
                "step_compile_s": step_compile_s,
                "cost": module.cost(config, traffic, chips), "log": log})
            device.update(busy_s=reduced["busy_s"],
                          window_s=reduced["window_s"])
            result["breakdown"] = reduced["breakdown"]
        else:
            values = {
                config["throughput_metric"]:
                    steps * built["samples_per_step"] / seconds_open / chips,
                "step_ms": seconds_open / steps * 1e3,
                "peak_hbm_gib": memory / 2 ** 30,
                "setup_s": setup_s,
            }
            result["metrics"] = {
                m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                for m in resolved["end_to_end"]}
        result["device"] = device
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    return result


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = parser.parse_args(argv)

    sys.path.insert(0, ROOT)
    resolved = resolve_cell(load_manifest(), args.workload)
    devices = require_devices(resolved["cell"]["chips"])
    import horovod_tpu as hvd
    from horovod_tpu.utils.compile_cache import configure_compile_cache

    log("compile cache", configure_compile_cache(ROOT))
    hvd.init()
    phases = Phases()
    phases.done("imports, devices, hvd.init")
    try:
        result = run_cell(resolved, devices, args.seed, args.seconds,
                          args.trace, phases)
    finally:
        hvd.shutdown()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
