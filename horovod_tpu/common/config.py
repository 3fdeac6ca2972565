"""Runtime configuration for horovod_tpu.

Mirrors the reference's env-var config surface (horovod/common/operations.h:56-66,
parsed once in BackgroundThreadLoop, operations.cc:1837-1909). All knobs are
environment variables read once at init(); the autotuner may override the
non-pinned ones at runtime, exactly like the reference's ParameterManager
(parameter_manager.cc:145-233).

TPU-first differences:
- fusion threshold applies to gradient-bucket concatenation before a single
  ``psum`` (the XLA collective replaces ncclAllReduce);
- cycle time drives the host-side negotiation engine used by the eager
  (torch / numpy) path only — inside ``jit`` ordering is static at trace time.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field


def _env_int(name: str, default: int) -> int:
    v = os.environ.get(name)
    if v is None or v == "":
        return default
    try:
        return int(v)
    except ValueError:
        return default


def _env_float(name: str, default: float) -> float:
    v = os.environ.get(name)
    if v is None or v == "":
        return default
    try:
        return float(v)
    except ValueError:
        return default


def _env_bool(name: str, default: bool = False) -> bool:
    v = os.environ.get(name)
    if v is None or v == "":  # unset and empty both mean "use the default"
        return default
    return v.lower() not in ("0", "false", "no")


# Default tensor fusion threshold: 64 MiB (reference operations.cc:1838).
DEFAULT_FUSION_THRESHOLD = 64 * 1024 * 1024
# Default gradient bucket count for the compiled allreduce path: 1 keeps the
# historical single-fused-buffer behaviour; >1 splits the gradient set into
# that many reverse-backward-order buckets so XLA can overlap early buckets'
# allreduce with the rest of the backward pass (fusion.build_plan).
DEFAULT_NUM_BUCKETS = 1
# Default cycle time: 5 ms (reference operations.cc:1844).
DEFAULT_CYCLE_TIME_MS = 5.0
# Buckets below this byte size skip wire compression on the compiled plane:
# the cast pair costs more than the bytes it saves on tiny buffers, and
# non-gradient scalars (loss, counters) keep full precision.
DEFAULT_COMPRESSION_MIN_BYTES = 4096


def _env_compression() -> str:
    """HOROVOD_COMPRESSION={none,fp16,bf16,topk,adaptive}: the wire format
    every data plane applies to gradient payloads (docs/compression.md).
    ``topk@<ratio>`` specs (the autotune spelling) are kept verbatim —
    the engine's parse_spec extracts the ratio. Unknown values warn and
    fall back to none — config parsing never takes the job down."""
    from ..compression import WIRE_DTYPES, parse_spec

    v = os.environ.get("HOROVOD_COMPRESSION", "none").lower() or "none"
    if v not in WIRE_DTYPES and parse_spec(v) == ("none", None):
        import sys

        print(f"[horovod_tpu/warning] unknown HOROVOD_COMPRESSION={v!r}; "
              f"expected one of {sorted(WIRE_DTYPES)} or 'topk@<ratio>'; "
              "using 'none'", file=sys.stderr)
        return "none"
    return v
# Stall-check warning period: 60 s (reference operations.cc:258 STALL_WARNING_TIME).
STALL_WARNING_TIME_S = 60.0
# Stall-shutdown escalation: 0 disables (reference STALL_SHUTDOWN_TIME is
# likewise opt-in); > 0 makes the watchdog fail collectives stalled past it.
STALL_SHUTDOWN_TIME_S = 0.0


def _env_stall_check_time(default: float = STALL_WARNING_TIME_S) -> float:
    """HOROVOD_STALL_CHECK_TIME (reference spelling) with the historical
    HOROVOD_STALL_WARNING_TIME accepted as a fallback alias."""
    v = os.environ.get("HOROVOD_STALL_CHECK_TIME")
    if v not in (None, ""):
        try:
            return float(v)
        except ValueError:
            pass
    return _env_float("HOROVOD_STALL_WARNING_TIME", default)

def clamp_shm_bytes(v: int) -> int:
    """Mirror of the native clamp (shm_ring.h shm_ring_capacity): power of
    two in [64 KiB, 1 GiB], so config() reports the EFFECTIVE capacity."""
    v = max(1 << 16, min(int(v), 1 << 30))
    p = 1
    while p * 2 <= v:
        p *= 2
    return p


@dataclass
class Config:
    """Knobs parsed from the environment, one field per reference env var."""

    fusion_threshold: int = DEFAULT_FUSION_THRESHOLD      # HOROVOD_FUSION_THRESHOLD
    num_buckets: int = DEFAULT_NUM_BUCKETS                # HOROVOD_NUM_BUCKETS
    cycle_time_ms: float = DEFAULT_CYCLE_TIME_MS          # HOROVOD_CYCLE_TIME
    timeline: str = ""                                    # HOROVOD_TIMELINE
    timeline_mark_cycles: bool = False                    # HOROVOD_TIMELINE_MARK_CYCLES
    autotune: bool = False                                # HOROVOD_AUTOTUNE
    autotune_log: str = ""                                # HOROVOD_AUTOTUNE_LOG
    stall_check_disable: bool = False                     # HOROVOD_STALL_CHECK_DISABLE
    # HOROVOD_STALL_CHECK_TIME (alias: HOROVOD_STALL_WARNING_TIME)
    stall_warning_s: float = STALL_WARNING_TIME_S
    stall_shutdown_s: float = STALL_SHUTDOWN_TIME_S       # HOROVOD_STALL_SHUTDOWN_TIME
    metrics_port: int = 0                                 # HOROVOD_METRICS_PORT (0 = off)
    hierarchical_allreduce: bool = False                  # HOROVOD_HIERARCHICAL_ALLREDUCE
    hierarchical_allgather: bool = False                  # HOROVOD_HIERARCHICAL_ALLGATHER
    # Shared-memory data plane for same-host ring links (cc/src/shm_ring.h;
    # the reference's NCCL-shm / MPI shared-window intra-host paths,
    # operations.cc:929-1034). The native binding exports these into the
    # env right before engine init, so Config(shm=...) works like every
    # other field whether or not the env var was set.
    # Env-aware defaults (field factories, unlike the static defaults
    # above): a directly-constructed Config(cycle_time_ms=...) — the test
    # idiom — must still honor HOROVOD_SHM=0 from the launcher env, because
    # the binding UNCONDITIONALLY exports these two back into the env.
    shm: bool = field(                                    # HOROVOD_SHM (0 disables)
        default_factory=lambda: _env_bool("HOROVOD_SHM", True))
    shm_bytes: int = field(                               # HOROVOD_SHM_BYTES
        default_factory=lambda: clamp_shm_bytes(
            _env_int("HOROVOD_SHM_BYTES", 16 << 20)))
    # Steady-state fast path (docs/eager-engine.md). Env-aware defaults for
    # the same reason as shm above: tests construct Config(...) directly and
    # the launcher env must still win.
    cache_capacity: int = field(                          # HOROVOD_CACHE_CAPACITY (0 disables)
        default_factory=lambda: max(
            0, _env_int("HOROVOD_CACHE_CAPACITY", 1024)))
    ring_data_plane: bool = field(                        # HOROVOD_RING_DATA_PLANE (0 disables)
        default_factory=lambda: _env_bool("HOROVOD_RING_DATA_PLANE", True))
    # On-the-wire gradient compression (ISSUE 5, docs/compression.md).
    # Env-aware defaults like shm/cache above: tests and bench workers
    # construct Config(...) directly and the launcher env must still win.
    compression: str = field(                             # HOROVOD_COMPRESSION
        default_factory=_env_compression)
    compression_error_feedback: bool = field(             # HOROVOD_COMPRESSION_ERROR_FEEDBACK
        default_factory=lambda: _env_bool(
            "HOROVOD_COMPRESSION_ERROR_FEEDBACK", False))
    compression_min_bytes: int = field(                   # HOROVOD_COMPRESSION_MIN_BYTES
        default_factory=lambda: max(0, _env_int(
            "HOROVOD_COMPRESSION_MIN_BYTES", DEFAULT_COMPRESSION_MIN_BYTES)))
    # Sparse top-k wire format (ISSUE 9, docs/compression.md): fraction of
    # entries a topk-compressed gradient keeps. Env-aware default like the
    # compression fields above. 0.0 means "unset" — resolution falls back
    # to HOROVOD_TOPK_RATIO / the 1% default at use time.
    topk_ratio: float = field(                            # HOROVOD_TOPK_RATIO
        default_factory=lambda: _env_float("HOROVOD_TOPK_RATIO", 0.0))
    # Fabric-aware compiled plane (ISSUE 7, docs/hierarchical.md): a wire
    # dtype and a bucket-size cap applied to the DCN (cross-host) tier of
    # the hierarchical ladder only. Empty dcn_compression inherits the
    # global HOROVOD_COMPRESSION; dcn_fusion_threshold 0 means no separate
    # DCN cap. Env-aware defaults for the same reason as the fields above.
    dcn_compression: str = field(                         # HOROVOD_DCN_COMPRESSION
        default_factory=lambda: os.environ.get(
            "HOROVOD_DCN_COMPRESSION", "").lower())
    dcn_fusion_threshold: int = field(                    # HOROVOD_DCN_FUSION_THRESHOLD
        default_factory=lambda: max(0, _env_int(
            "HOROVOD_DCN_FUSION_THRESHOLD", 0)))
    # Sharded data parallelism (ISSUE 14, docs/sharded.md). HOROVOD_MESH
    # names the 2-D ('batch','shard') mesh shape as "<batch>x<shard>"
    # (empty = pure DP, shard=1); HOROVOD_SHARD_PARAMS flips
    # DistributedOptimizer onto the ZeRO wire pattern (reduce-scatter
    # grads into the owning shard, bucketed allgather parameter refresh).
    # Env-aware defaults for the same reason as the fields above.
    mesh: str = field(                                    # HOROVOD_MESH
        default_factory=lambda: os.environ.get("HOROVOD_MESH", "").strip())
    shard_params: bool = field(                           # HOROVOD_SHARD_PARAMS
        default_factory=lambda: _env_bool("HOROVOD_SHARD_PARAMS", False))
    # Distributed tracing (ISSUE 6, docs/tracing.md): non-empty directory
    # enables per-rank span capture on every data plane. Env-aware default
    # like compression above: workers constructed with Config(...) directly
    # must still honor the launcher-exported HOROVOD_TRACE_DIR.
    trace_dir: str = field(                               # HOROVOD_TRACE_DIR
        default_factory=lambda: os.environ.get("HOROVOD_TRACE_DIR", ""))
    log_level: str = "warning"                            # HOROVOD_LOG_LEVEL
    log_hide_time: bool = False                           # HOROVOD_LOG_HIDE_TIME
    # Which env vars were explicitly pinned (autotuner must not override,
    # reference operations.cc:1840-1879 "fixed=true").
    pinned: set = field(default_factory=set)

    @classmethod
    def from_env(cls) -> "Config":
        cfg = cls(
            fusion_threshold=_env_int("HOROVOD_FUSION_THRESHOLD", DEFAULT_FUSION_THRESHOLD),
            num_buckets=max(1, _env_int("HOROVOD_NUM_BUCKETS", DEFAULT_NUM_BUCKETS)),
            cycle_time_ms=_env_float("HOROVOD_CYCLE_TIME", DEFAULT_CYCLE_TIME_MS),
            timeline=os.environ.get("HOROVOD_TIMELINE", ""),
            timeline_mark_cycles=_env_bool("HOROVOD_TIMELINE_MARK_CYCLES"),
            autotune=_env_bool("HOROVOD_AUTOTUNE"),
            autotune_log=os.environ.get("HOROVOD_AUTOTUNE_LOG", ""),
            stall_check_disable=_env_bool("HOROVOD_STALL_CHECK_DISABLE"),
            stall_warning_s=_env_stall_check_time(),
            stall_shutdown_s=_env_float("HOROVOD_STALL_SHUTDOWN_TIME",
                                        STALL_SHUTDOWN_TIME_S),
            metrics_port=_env_int("HOROVOD_METRICS_PORT", 0),
            hierarchical_allreduce=_env_bool("HOROVOD_HIERARCHICAL_ALLREDUCE"),
            hierarchical_allgather=_env_bool("HOROVOD_HIERARCHICAL_ALLGATHER"),
            # shm / shm_bytes: omitted — their default_factory already reads
            # the env, and duplicating the parse here would give two places
            # for the semantics to drift apart.
            log_level=os.environ.get("HOROVOD_LOG_LEVEL", "warning").lower(),
            log_hide_time=_env_bool("HOROVOD_LOG_HIDE_TIME"),
        )
        for var in (
            "HOROVOD_FUSION_THRESHOLD",
            "HOROVOD_NUM_BUCKETS",
            "HOROVOD_CYCLE_TIME",
            "HOROVOD_HIERARCHICAL_ALLREDUCE",
            "HOROVOD_HIERARCHICAL_ALLGATHER",
        ):
            if os.environ.get(var) not in (None, ""):
                cfg.pinned.add(var)
        return cfg
