"""Pod-wide distributed tracing (ISSUE 6 tentpole; docs/tracing.md).

Set ``HOROVOD_TRACE_DIR=/path`` (or ``Config(trace_dir=...)``) and every
collective gets a trace ID at first enqueue — ``<name>#<submission-seq>``,
deterministic and identical across ranks — that links its spans (enqueue,
negotiate, cache-tick, wire send/recv per hop, reduce, done) across ALL
ranks and both eager data planes:

- eager Python engine: spans from common/engine.py + ring-hop IO from
  runner/network.py's Channel hook; the request dicts and ring directives
  carry the ID so the coordinator verifies cross-rank agreement;
- native C++ engine: cc/src/engine.cc stamps ``Request.trace_seq`` on the
  wire (cc/src/wire.h) and records spans drained through
  ``hvd_trace_drain`` into the same per-rank file (cc/native_engine.py).

Workflow: run with the env set, then merge + analyze:

    python -m horovod_tpu.tracing.collector /tmp/trace --critical-path

which writes one clock-aligned Perfetto/Chrome ``trace.json`` (clock.py
NTP-style offsets over the coordinator channel) and prints the per-phase
straggler attribution (critical_path.py). The same attribution feeds
``horovod_critical_path_seconds`` / ``horovod_straggler_*`` gauges and the
stall watchdog's report.
"""

from __future__ import annotations

import os
import threading
from typing import Optional

from .clock import estimate_offset_ns  # noqa: F401
from .collector import build_trace, load_spans, merge_trace  # noqa: F401
from .critical_path import (  # noqa: F401
    PHASES,
    analyze,
    analyze_dir,
    export_gauges,
    format_summary,
)
from .flight import (  # noqa: F401
    FlightRecorder,
    config_fingerprint,
    get_flight,
    init_flight,
    read_ring,
)
from .recorder import (  # noqa: F401
    TraceRecorder,
    proc_span_path,
    span_path,
    trace_id,
)
from .serve import (  # noqa: F401
    ServeTracer,
    get_serve_tracer,
    init_serve_tracer,
    serve_trace_id,
)

_lock = threading.Lock()
_recorder: Optional[TraceRecorder] = None


def trace_dir_from_env() -> str:
    return os.environ.get("HOROVOD_TRACE_DIR", "")


def init_recorder(trace_dir: str, rank: int) -> Optional[TraceRecorder]:
    """Open (or return) this process's span recorder. Idempotent per
    process; a later call with a different directory re-points it (elastic
    re-init)."""
    global _recorder
    if not trace_dir:
        return None
    with _lock:
        if _recorder is not None and _recorder.path == span_path(trace_dir,
                                                                 rank):
            return _recorder
        if _recorder is not None:
            _recorder.close()
        _recorder = TraceRecorder(span_path(trace_dir, rank), rank)
        return _recorder


def get_recorder() -> Optional[TraceRecorder]:
    """The process recorder, or None when tracing is off."""
    return _recorder


def close_recorder() -> None:
    global _recorder
    with _lock:
        if _recorder is not None:
            _recorder.close()
            _recorder = None
