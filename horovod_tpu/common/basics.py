"""Lifecycle state — the TPU analog of HorovodBasics + HorovodGlobalState.

The reference's HorovodBasics (horovod/common/__init__.py:51-154) is a ctypes
wrapper over the C ABI (horovod_init/_rank/_size/..., operations.h:76-106,
operations.cc:2413-2468). Here the same contract is split:

- topology & lifecycle live in this Python object (no MPI to spin up);
- the native background engine (horovod_tpu/cc) is attached lazily for the
  eager/host data plane and owns the coordinator tick, fusion planner,
  timeline and stall check, exactly like the reference's background thread
  (operations.cc:1695-2380);
- the compiled data plane needs no runtime state at all: mesh axes are the
  communicators.

``init()`` is idempotent (reference InitializeHorovodOnce test_and_set guard,
operations.cc:2384-2401); ``shutdown()`` allows re-init (operations.cc:2424-2432).
"""

from __future__ import annotations

import atexit
import os
import threading
from typing import Optional, Sequence

from .config import Config
from .topology import Topology, detect, num_devices, num_local_devices
from ..utils.logging import log


class NotInitializedError(RuntimeError):
    def __init__(self) -> None:
        super().__init__(
            "Horovod has not been initialized; use hvd.init()."
        )


class _State:
    """Singleton global state (reference HorovodGlobalState, operations.cc:115)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.initialized = False
        self.topology: Optional[Topology] = None
        self.config: Optional[Config] = None
        self.engine = None          # native engine handle, attached lazily
        self.mesh = None            # default data-parallel mesh, created lazily
        self.metrics_server = None  # HTTP exposition (HOROVOD_METRICS_PORT)
        self._atexit_registered = False


_state = _State()


def _maybe_init_jax_distributed() -> None:
    """Join the JAX distributed runtime when launched for it.

    This is the compiled plane's world formation — the analog of the
    reference's MPI_COMM_WORLD + NCCL communicator setup
    (operations.cc:1728-1797), done once per process before any backend use.
    The launcher (horovod_tpu.runner) negotiates a coordination-service
    address on rank 0's host and exports it as HOROVOD_JAX_COORDINATOR;
    opting in (hvdrun --jax-distributed / run(jax_distributed=True) /
    HOROVOD_JAX_DISTRIBUTED=1) makes init() federate the processes so
    ``jax.devices()`` becomes the GLOBAL device list and jitted collectives
    span process boundaries (N hosts x M local chips, the pod execution
    shape). Off by default: eager/torch-only jobs don't need a JAX backend
    at all. One worker per TPU HOST: several workers on one TPU host are
    refused (:func:`_refuse_workers_sharing_tpu_host`).
    """
    if os.environ.get("HOROVOD_JAX_DISTRIBUTED") != "1":
        return
    coord = os.environ.get("HOROVOD_JAX_COORDINATOR")
    if not coord:
        raise RuntimeError(
            "HOROVOD_JAX_DISTRIBUTED=1 but no HOROVOD_JAX_COORDINATOR: "
            "launch through horovod_tpu.runner (hvdrun --jax-distributed), "
            "or export the coordinator address yourself.")
    if "HOROVOD_SIZE" not in os.environ or "HOROVOD_RANK" not in os.environ:
        raise RuntimeError(
            "HOROVOD_JAX_DISTRIBUTED=1 needs HOROVOD_RANK and HOROVOD_SIZE "
            "(process_id / num_processes for the JAX runtime); the launcher "
            "exports them — a hand-rolled launch must too.")
    import jax

    from ..compat import distributed_is_initialized

    if distributed_is_initialized():
        return  # re-init after shutdown(): the runtime outlives the hvd state
    _refuse_workers_sharing_tpu_host()
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():  # pragma: no cover - misuse
        raise RuntimeError(
            "hvd.init() with HOROVOD_JAX_DISTRIBUTED=1 must run before any "
            "JAX computation: the backend is already initialized, so this "
            "process can no longer join the multi-process runtime.")
    # Cross-process collectives on the CPU backend (virtual-device testing,
    # SURVEY.md §4) ride gloo; a no-op for the TPU backend, which uses ICI/DCN.
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coord,
        num_processes=int(os.environ["HOROVOD_SIZE"]),
        process_id=int(os.environ["HOROVOD_RANK"]),
    )
    # Log from env, not jax.process_index()/device_count(): those would
    # force full backend initialization inside init() as a side effect of a
    # debug message.
    log("debug",
        f"joined JAX distributed runtime at {coord} as process "
        f"{os.environ['HOROVOD_RANK']}/{os.environ['HOROVOD_SIZE']}")


def _refuse_workers_sharing_tpu_host() -> None:
    """Several workers on ONE TPU host cannot each take chips: a chip belongs
    to one process at a time, and nothing in ``runner/`` pins chips per
    process, so every worker's libtpu claims every local chip. Observed on a
    four-chip v5e host (PR 21): with ``hvdrun -np 2 --jax-distributed`` the
    second worker loses the chips, drops to the CPU backend and both hang
    for minutes in the distributed runtime's barriers before dying. So the
    shape is refused up front, with the one that works named. Workers pinned
    to the CPU platform (the virtual-device test path) and hosts without TPU
    chips are not affected."""
    if int(os.environ.get("HOROVOD_LOCAL_SIZE") or 1) <= 1:
        return
    import jax

    if (jax.config.jax_platforms or "").split(",")[0] == "cpu":
        return
    # Counting chips must not start a backend (that would take them). jax has
    # no public call for it; this is the PCI scan its own cloud_tpu_init uses,
    # private as of jax 0.9.0 — the installation this code is written for.
    from jax._src import hardware_utils

    chips, _ = hardware_utils.num_available_tpu_chips_and_device_id()
    if chips == 0:
        return
    raise RuntimeError(
        f"HOROVOD_JAX_DISTRIBUTED=1 with {os.environ['HOROVOD_LOCAL_SIZE']} "
        f"workers on one TPU host ({chips} chips): a chip belongs to one "
        "process at a time and the launcher pins no chips per worker, so the "
        "workers would fight for the same chips and hang. On one host run "
        "ONE process — it drives every local chip by SPMD (plain `python "
        "train.py`, or `hvdrun -np 1`); use --jax-distributed with one "
        "worker per HOST (`hvdrun -H host1:1,host2:1`).")


def init(comm: Optional[Sequence[int]] = None) -> None:
    """Initialize. ``comm`` may be a list of ranks forming a subset world
    (reference horovod_init with ranks[], operations.cc:2415; mpi4py comms have
    no TPU analog and raise)."""
    with _state._lock:
        if _state.initialized:
            return
        _maybe_init_jax_distributed()
        topo = detect()
        if comm is not None:
            if not isinstance(comm, (list, tuple)):
                raise ValueError(
                    "comm must be a list of ranks on TPU (MPI communicators do not exist here)"
                )
            ranks = sorted(set(comm))
            if any(not (0 <= r < topo.size) for r in ranks):
                raise ValueError(
                    f"comm {ranks} contains ranks outside the launched world "
                    f"of size {topo.size}")
            if topo.rank not in ranks and topo.size > 1:
                raise ValueError(
                    f"rank {topo.rank} is not a member of comm {ranks}: this "
                    "process cannot participate in the sub-world's "
                    "collectives. Only member processes may call "
                    "init(comm=...); non-members should skip Horovod work "
                    "(or exit) — they must NOT fall back to init(), which "
                    "would target the same coordinator address.")
            if topo.size > 1 and len(ranks) != topo.size:
                if ranks[0] != 0:
                    # The sub-world's rank 0 binds HOROVOD_COORD_ADDR, which
                    # names ORIGINAL rank 0's host: on a multi-host job where
                    # the member at ranks[0] lives elsewhere, that bind fails
                    # (EADDRNOTAVAIL). Warn with the fix up front.
                    log("warning",
                        f"init(comm={ranks}): member rank {ranks[0]} will "
                        "bind the coordinator at HOROVOD_COORD_ADDR. If it "
                        "is not on the same host as the original rank 0, "
                        "re-export HOROVOD_COORD_ADDR on every member to an "
                        "address local to that member before init.")
                # Sub-world semantics (reference horovod_init with ranks[],
                # operations.cc:2415): rank/size are re-indexed within the
                # subset — the member at ranks[0] becomes rank 0 and binds
                # the coordinator address, so the control plane and ring are
                # exactly a world of len(ranks). Host coordinates are NOT
                # preserved: a member only knows its own host placement, not
                # the other members', so any local/cross guess would build
                # wrong topology (the round-3 bug: local_size=min(...) could
                # group ranks that share no host). The subset world uses the
                # consistent one-rank-per-host view — local_rank 0, hierarchy
                # simply not available — which every rank derives identically
                # from `ranks` alone. A ranks list naming the FULL world is
                # plain init (reference accepts this too) and keeps the real
                # host topology — the branch guard above.
                topo = Topology(
                    rank=ranks.index(topo.rank),
                    size=len(ranks),
                    local_rank=0,
                    local_size=1,
                    cross_rank=ranks.index(topo.rank),
                    cross_size=len(ranks),
                )
        _state.topology = topo
        _state.config = Config.from_env()
        _state.initialized = True
        _start_metrics(topo, _state.config)
        from ..utils.compile_cache import install_compile_ledger

        install_compile_ledger()
        if not _state._atexit_registered:
            atexit.register(shutdown)
            _state._atexit_registered = True
        log("debug", f"horovod_tpu initialized: {topo}", rank=topo.rank)


def _start_metrics(topo: Topology, config: Config) -> None:
    """Always-on registry identity gauges; HTTP exposition only when
    HOROVOD_METRICS_PORT is set. Rank r on a host serves at
    port + local_rank so co-located workers never collide (docs/metrics.md);
    failure to bind is a warning, not an init failure — telemetry must
    never take the job down."""
    from ..metrics import registry, start_metrics_server

    reg = registry()
    reg.gauge("horovod_rank", help="this process's rank").set(topo.rank)
    reg.gauge("horovod_size", help="world size").set(topo.size)
    reg.gauge("horovod_local_rank").set(topo.local_rank)
    port = getattr(config, "metrics_port", 0)
    if port:
        try:
            _state.metrics_server = start_metrics_server(port + topo.local_rank)
            log("debug",
                f"metrics exposition at http://127.0.0.1:"
                f"{_state.metrics_server.port}/metrics", rank=topo.rank)
        except OSError as e:
            log("warning",
                f"HOROVOD_METRICS_PORT={port}: cannot bind metrics server "
                f"({e}); exposition disabled for this rank", rank=topo.rank)


def shutdown() -> None:
    """Tear down (reference horovod_shutdown, operations.cc:2424-2432);
    re-init is allowed afterwards."""
    with _state._lock:
        if not _state.initialized:
            return
        if _state.metrics_server is not None:
            try:
                _state.metrics_server.stop()
            except Exception:  # pragma: no cover
                pass
            _state.metrics_server = None
        if _state.engine is not None:
            try:
                _state.engine.shutdown()
            except Exception as e:  # pragma: no cover
                log("warning", f"engine shutdown failed: {e}")
            _state.engine = None
        # Close this process's trace recorder (the engines only flush: the
        # recorder outlives elastic engine rebuilds, but not the session).
        try:
            from ..tracing import close_recorder

            close_recorder()
        except Exception:  # pragma: no cover - tracing never blocks teardown
            pass
        _state.mesh = None
        _state.topology = None
        _state.config = None
        _state.initialized = False


def is_initialized() -> bool:
    return _state.initialized


def _topo() -> Topology:
    if not _state.initialized or _state.topology is None:
        raise NotInitializedError()
    return _state.topology


def rank() -> int:
    return _topo().rank


def size() -> int:
    return _topo().size


def local_rank() -> int:
    return _topo().local_rank


def local_size() -> int:
    return _topo().local_size


def cross_rank() -> int:
    return _topo().cross_rank


def cross_size() -> int:
    return _topo().cross_size


def is_homogeneous() -> bool:
    return _topo().is_homogeneous


def config() -> Config:
    if not _state.initialized or _state.config is None:
        raise NotInitializedError()
    return _state.config


def mpi_threads_supported() -> bool:
    """Parity shim for hvd.mpi_threads_supported() (operations.cc:2460-2467).
    There is no MPI on TPU; the host control plane is always thread-safe."""
    _topo()
    return True


def default_mesh():
    """Lazily-created 1-D 'hvd' mesh over all visible chips."""
    _topo()
    if _state.mesh is None:
        from ..parallel.mesh import data_parallel_mesh

        _state.mesh = data_parallel_mesh()
    return _state.mesh


def engine():
    """Lazily attach the native eager engine (host data plane)."""
    _topo()
    if _state.engine is None:
        from . import engine as engine_mod

        _state.engine = engine_mod.create(_topo(), config())
    return _state.engine
