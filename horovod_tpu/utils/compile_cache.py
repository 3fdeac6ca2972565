"""Where the persistent XLA compilation cache lives.

One rule for every entry point (``chip_smoke.py``, ``benchmarks/run.py``,
the test harness): a directory placed from outside with
``JAX_COMPILATION_CACHE_DIR`` is JAX's to read — nothing is set in code, so
the operator's directory is the only one used. Without it the cache sits at a FIXED path inside the
checkout (``<checkout>/.jax_cache``): the path is part of the cache's
identity across runs, so it carries no pid, time or temp component.

The in-checkout directory is owned by this module, which lets it guard the
one hazard JAX's own key does not cover: XLA:CPU entries embed the
compiling host's CPU features, and loading them on a different host warns
of "execution errors such as SIGILL". The directory is stamped with the
host's CPU identity and started empty when the stamp does not match.

The compile ledger, further down, is what the compiled path says about its own
set-up: one entry per tracing, lowering, backend compilation and cache load
that JAX reports through ``jax.monitoring``, with the function's name and a
``time.perf_counter()`` stamp, and the same totals in the metrics registry.
"""

from __future__ import annotations

import collections
import hashlib
import os
import platform
import re
import shutil
import threading
import time

ENV = "JAX_COMPILATION_CACHE_DIR"
_STAMP = "host_cpu"


def _host_cpu_id() -> str:
    flags = ""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    flags = line.split(":", 1)[1].strip()
                    break
    except OSError:
        pass
    ident = f"{platform.machine()} {flags}"
    return hashlib.sha256(ident.encode()).hexdigest()[:16]


def _start_empty_on_foreign_host(path: str) -> None:
    os.makedirs(path, exist_ok=True)
    stamp = os.path.join(path, _STAMP)
    host = _host_cpu_id()
    try:
        with open(stamp) as f:
            if f.read().strip() == host:
                return
    except FileNotFoundError:
        pass
    for name in os.listdir(path):
        entry = os.path.join(path, name)
        if os.path.isdir(entry):
            shutil.rmtree(entry)
        else:
            os.remove(entry)
    with open(stamp, "w") as f:
        f.write(host + "\n")


def configure_compile_cache(checkout: str) -> str:
    """Point JAX's persistent compilation cache at the right directory and
    return it. Call before the first compile. ``checkout`` is the root of
    the source tree the caller runs from."""
    install_compile_ledger()
    placed = os.environ.get(ENV)
    if placed:
        return placed
    import jax

    path = os.path.join(os.path.abspath(checkout), ".jax_cache")
    _start_empty_on_foreign_host(path)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


# ------------------------------------------------------------ compile ledger

PHASES = ("trace", "lower", "backend", "cache_load")
_PHASE_OF_EVENT = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}
_CACHE_COUNTERS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}
LEDGER_ENTRIES = 4096       # the newest entries kept; the totals count all
_WRAPPED = re.compile(r"^\w+\((.*)\)$")      # jit(step) -> step


class _Ledger:
    def __init__(self):
        self.lock = threading.Lock()
        self.thread = threading.local()     # depth, pending cache load
        self.entries = collections.deque(maxlen=LEDGER_ENTRIES)
        self.seconds = dict.fromkeys(PHASES, 0.0)
        self.count = dict.fromkeys(PHASES, 0)
        self.cache = dict.fromkeys(_CACHE_COUNTERS.values(), 0)


_ledger = _Ledger()
_installed = False


def _registry():
    from ..metrics.registry import registry

    return registry()


def _on_start(event, value, **kw):
    # JAX records the start time of each compile phase as a scalar when the
    # phase opens: that is what tells an event nested in another (the jits a
    # flax model calls while the step is traced: ~1300 per ResNet-50) from a
    # phase of its own.
    if event in _PHASE_OF_EVENT:     # a cache load has no start of its own
        t = _ledger.thread
        t.depth = getattr(t, "depth", 0) + 1


def _on_duration(event, seconds, fun_name="", **kw):
    phase = _PHASE_OF_EVENT.get(event)
    if phase is None:
        return
    stamp = time.perf_counter()
    t = _ledger.thread
    if phase == "cache_load":       # reported inside its backend phase
        if getattr(t, "depth", 0) > 1:
            return
    else:
        t.depth = max(0, getattr(t, "depth", 1) - 1)
        if t.depth:     # nested: the enclosing phase's seconds hold it
            return
    unwrapped = _WRAPPED.match(fun_name)
    entry = {"phase": phase, "seconds": float(seconds), "stamp": stamp,
             "fun_name": unwrapped.group(1) if unwrapped else fun_name}
    if phase == "cache_load":       # named by the backend entry that follows
        t.loading = entry
    elif phase == "backend" and getattr(t, "loading", None) is not None:
        t.loading["fun_name"], t.loading = entry["fun_name"], None
    with _ledger.lock:
        _ledger.entries.append(entry)
        _ledger.seconds[phase] += entry["seconds"]
        _ledger.count[phase] += 1
    _registry().counter(
        "horovod_compile_seconds_total",
        help="seconds this process spent tracing, lowering, in the backend "
             "compiler (cache loads included) and loading from the "
             "persistent cache, outermost phases only",
        phase=phase).inc(max(0.0, entry["seconds"]))


def _on_event(event, **kw):
    key = _CACHE_COUNTERS.get(event)
    if key is None:
        return
    with _ledger.lock:
        _ledger.cache[key] += 1
    if key == "cache_hits":
        _registry().counter(
            "horovod_compile_cache_hits_total",
            help="executables loaded from the persistent compilation "
                 "cache").inc()
    else:
        _registry().counter(
            "horovod_compile_cache_misses_total",
            help="executables compiled and written to the persistent "
                 "compilation cache (JAX writes only those over its size "
                 "and time thresholds)").inc()


def install_compile_ledger() -> None:
    """Start listening to JAX's compile events. Called by
    ``configure_compile_cache`` and ``hvd.init()``; a second call adds
    nothing."""
    global _installed
    with _ledger.lock:
        if _installed:
            return
        _installed = True
    import jax.monitoring

    jax.monitoring.register_scalar_listener(_on_start)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)


def compile_ledger() -> dict:
    """What this process has compiled so far. ``entries``: the newest
    ``LEDGER_ENTRIES`` outermost phases in order, each ``{"phase",
    "fun_name", "seconds", "stamp"}`` with ``stamp`` the phase's END on
    ``time.perf_counter()`` (it began ``seconds`` earlier). A ``cache_load``
    lies inside the ``backend`` entry that follows it, so real compilation is
    ``backend`` less ``cache_load``, and the programs compiled, not loaded,
    are ``count["backend"] - count["cache_load"]``. ``seconds`` and ``count``
    are totals per phase over every entry ever made; ``cache_hits`` and
    ``cache_misses`` are JAX's own counts (a miss is an executable written to
    the cache). Everything is empty until ``install_compile_ledger``."""
    with _ledger.lock:
        return {"entries": [dict(e) for e in _ledger.entries],
                "seconds": dict(_ledger.seconds),
                "count": dict(_ledger.count), **_ledger.cache}
