"""Unified telemetry for horovod_tpu (ISSUE 2 tentpole).

One always-on, process-local registry that every layer reports through:

- ``hvd.metrics.registry()`` — counters / gauges / histograms
  (registry.py). Fed by the eager engines (collective count/bytes/latency,
  stall warnings), the fusion planner (bucket geometry, occupancy,
  planned overlap), and the timeline (dropped events).
- ``hvd.metrics.snapshot()`` — the JSON view; ``render_prometheus()`` the
  scrape text; ``HOROVOD_METRICS_PORT`` serves both over local HTTP
  (exposition.py, started by ``hvd.init()``).
- :class:`StallWatchdog` — HOROVOD_STALL_CHECK_TIME straggler warnings
  naming tensors + missing ranks, HOROVOD_STALL_SHUTDOWN_TIME escalation
  (watchdog.py; the native engine's coordinator scan feeds the same
  registry through the c_api collector).
- ``measure_overlap`` / plan gauges — the compiled path's bucket
  overlap-efficiency instruments (overlap.py).
- ``profile_step`` — where a warmed step's device time goes, by the names of
  ``common/device_names.py`` (device_profile.py; imported on first use).
- ``merge_snapshots`` — pod-wide aggregation of per-rank snapshots
  (aggregate.py; used by the runner's DriverService and MetricsCallback).

Full reference: docs/metrics.md.
"""

from __future__ import annotations

from .aggregate import merge_snapshots  # noqa: F401
from .anomaly import AnomalyDetector  # noqa: F401
from .exposition import MetricsServer, start_metrics_server  # noqa: F401
from .overlap import (  # noqa: F401
    last_plan,
    last_shard_plan,
    last_tier_plan,
    last_wire_plan,
    measure_overlap,
    record_attn_gate_width,
    record_chunked_loss_plan,
    record_dsa_census,
    record_dsa_select_plan,
    record_flash_plan,
    record_flash_window_plan,
    record_gdn_kernel_scan,
    record_gdn_plan,
    record_kda_beta_range,
    record_kda_fused_mixer,
    record_kda_plan,
    record_loop_exit_mass,
    record_loop_plan,
    record_mamba_fused_passes,
    record_moe_dispatch_rows,
    record_moe_grouped_plan,
    record_moe_live_rows,
    record_moe_router_recomputed,
    record_moe_router_saved_bytes,
    record_plan,
    record_shard_plan,
    record_short_conv_plan,
    record_ssd_plan,
    record_sharded_state_bytes,
    record_tier_plan,
    record_wire_plan,
)
from .registry import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    registry,
)
from .schema import validate_snapshot  # noqa: F401
from .watchdog import StallInfo, StallReport, StallWatchdog  # noqa: F401


def profile_step(*args, **kwargs) -> dict:
    """``device_profile.profile_step``: profile a warmed step and return the
    table of its device time by the program's names. The module is imported
    here, on first use, and by nothing at ``import horovod_tpu``."""
    from .device_profile import profile_step as run

    return run(*args, **kwargs)


def snapshot() -> dict:
    """JSON-able snapshot of this process's registry."""
    return registry().snapshot()


def render_prometheus() -> str:
    """Prometheus text exposition of this process's registry."""
    return registry().render_prometheus()
