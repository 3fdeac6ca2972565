"""What the per-name ``device_trace`` readers share: the traced window's
device time by the program's own names (``horovod_tpu/common/device_names.py``),
computed by the PROGRAM's reader (``horovod_tpu/metrics/device_profile.py``:
a partition of the first device's busy time, a loop and its body counted once)
from the ``.xplane.pb`` the harness leaves under ``<repo>/.bench_out/trace/``
until the readers are done. Loaded and partitioned once a process; the whole
table goes to the run's log. A program without that module, or a run without
a trace, gives ``None``, and so does every reader."""

from __future__ import annotations

import glob
import os
import time

from benchmarks.reduce_trace import DISPATCH, FENCE

TRACE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), ".bench_out", "trace")
_tables = []    # [table or None], filled by the first reader of the process


def _table(run):
    try:
        from horovod_tpu.metrics import device_profile
    except ImportError:     # a program older than its device-profile reader
        return None
    paths = glob.glob(os.path.join(TRACE_DIR, "**", "*.xplane.pb"),
                      recursive=True)
    reduced = run.get("trace")
    if not paths or not reduced:
        return None
    started = time.perf_counter()
    profile = device_profile.load(max(paths, key=os.path.getmtime))
    window = device_profile.window_of(profile, DISPATCH, FENCE)
    if window is None or device_profile.first_device(profile) is None:
        return None
    table = device_profile.by_name(profile, reduced["steps"], window,
                                   host_states=(DISPATCH, FENCE))
    run["log"](f"device time by the program's names (read in "
               f"{time.perf_counter() - started:.2f} s):\n"
               + device_profile.format_table(table))
    return table


def table(run):
    """``device_profile.by_name``'s table of this process's traced window."""
    if not _tables:
        _tables.append(_table(run))
    return _tables[0]


def ms(run, *names):
    """Milliseconds per step under ``names`` together (``unnamed`` is one):
    0.0 for a name the program knows and the window never ran, ``None`` where
    there is no table or the program does not know a name."""
    found = table(run)
    if found is None:
        return None
    seconds = dict(found["seconds"], unnamed=found["unnamed"])
    if not all(name in seconds for name in names):
        return None
    return sum(seconds[name] for name in names) * 1e3
