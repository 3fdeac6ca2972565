"""Telemetry subsystem tests (ISSUE 2): registry semantics + thread safety,
histogram percentiles, Prometheus/JSON exposition (including the HTTP
server and the checked-in snapshot schema), pod aggregation, the stall
watchdog (unit + an injected two-process stall that must name the missing
rank within HOROVOD_STALL_CHECK_TIME), and the compiled-path bucket overlap
gauges' consistency with the fusion planner (test_overlap.py's plan).
"""

from __future__ import annotations

import json
import os
import sys
import textwrap
import threading
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np
import pytest

from launch_util import launch_world  # noqa: E402

from horovod_tpu.metrics import (  # noqa: E402
    MetricsRegistry,
    StallInfo,
    StallWatchdog,
    merge_snapshots,
    start_metrics_server,
    validate_snapshot,
)
from horovod_tpu.metrics.registry import DEFAULT_BYTE_BUCKETS  # noqa: E402


# ------------------------------------------------------------------ registry


def test_counter_gauge_basics():
    reg = MetricsRegistry()
    c = reg.counter("c_total", help="h", op="allreduce")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    # get-or-create: same (name, labels) -> same object; new labels -> new
    assert reg.counter("c_total", op="allreduce") is c
    assert reg.counter("c_total", op="allgather") is not c
    g = reg.gauge("g")
    g.set(7)
    g.inc(-2)
    assert g.value == 5.0


def test_registry_thread_safety():
    """1000 increments from each of 8 threads across shared counter,
    gauge, and histogram must all land (the lock-cheap claim)."""
    reg = MetricsRegistry()
    c = reg.counter("t_total")
    h = reg.histogram("t_seconds")

    def worker(i):
        for k in range(1000):
            c.inc()
            h.observe(0.001 * ((i + k) % 10 + 1))
            # concurrent get-or-create of the same series must never race
            reg.counter("t_total").inc(0)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 8000
    assert h.count == 8000


def test_histogram_percentiles_and_bounds():
    reg = MetricsRegistry()
    h = reg.histogram("lat", buckets=[0.001 * (2 ** i) for i in range(12)])
    vals = [0.001 * i for i in range(1, 101)]      # 1ms..100ms uniform
    for v in vals:
        h.observe(v)
    assert h.count == 100
    assert abs(h.sum - sum(vals)) < 1e-9
    # estimates stay inside the observed range and are ordered
    p50, p90, p99 = h.percentile(50), h.percentile(90), h.percentile(99)
    assert min(vals) <= p50 <= p90 <= p99 <= max(vals)
    # and roughly where a uniform distribution puts them (bucketed estimate)
    assert 0.02 <= p50 <= 0.08
    assert p90 >= 0.05
    d = h.to_dict()
    assert d["count"] == 100 and d["buckets"][-1][0] == "+Inf"


def test_prometheus_rendering():
    reg = MetricsRegistry()
    reg.counter("hvd_ops_total", help="ops done", op="allreduce").inc(4)
    reg.gauge("hvd_up").set(1)
    reg.histogram("hvd_lat_seconds", buckets=[0.1, 1.0]).observe(0.5)
    text = reg.render_prometheus()
    assert '# TYPE hvd_ops_total counter' in text
    assert '# HELP hvd_ops_total ops done' in text
    assert 'hvd_ops_total{op="allreduce"} 4.0' in text
    assert '# TYPE hvd_lat_seconds histogram' in text
    assert 'hvd_lat_seconds_bucket{le="+Inf"} 1' in text
    assert 'hvd_lat_seconds_count 1' in text
    assert text.endswith("\n")


def test_snapshot_schema_and_pod_merge():
    reg = MetricsRegistry()
    reg.counter("n_total", op="allreduce").inc(2)
    reg.gauge("rate").set(10.0)
    reg.histogram("lat").observe(0.25)
    reg.set_info("stall_report", {"text": "x"})
    snap = reg.snapshot()
    assert validate_snapshot(snap) == []
    other = MetricsRegistry()
    other.counter("n_total", op="allreduce").inc(3)
    other.gauge("rate").set(30.0)
    other.histogram("lat").observe(0.75)
    pod = merge_snapshots([snap, other.snapshot(), None])
    assert validate_snapshot(pod) == []
    assert pod["ranks"] == 3 and pod["ranks_reporting"] == 2
    assert pod["counters"]['n_total{op="allreduce"}'] == 5
    assert pod["gauges"]["rate"] == {"min": 10.0, "max": 30.0, "mean": 20.0}
    assert pod["histograms"]["lat"]["count"] == 2
    assert pod["info"]["0"]["stall_report"]["text"] == "x"


def test_schema_validator_catches_violations():
    from horovod_tpu.metrics.schema import validate

    schema = {"type": "object", "required": ["a"],
              "properties": {"a": {"type": "integer", "minimum": 0}}}
    assert validate({"a": 1}, schema) == []
    assert validate({"a": -1}, schema)
    assert validate({"a": "x"}, schema)
    assert validate({}, schema)
    assert validate({"a": True}, schema)  # bool must not satisfy integer


def test_collector_runs_before_snapshot():
    reg = MetricsRegistry()
    calls = []

    def collect(r):
        calls.append(1)
        r.gauge("from_collector").set(42)

    reg.register_collector(collect)
    snap = reg.snapshot()
    assert snap["gauges"]["from_collector"] == 42 and calls
    reg.unregister_collector(collect)
    reg.snapshot()
    assert len(calls) == 1


# ---------------------------------------------------------------- exposition


def test_http_exposition_endpoints():
    reg = MetricsRegistry()
    reg.counter("served_total").inc(3)
    srv = start_metrics_server(0, reg)
    try:
        base = f"http://127.0.0.1:{srv.port}"
        text = urllib.request.urlopen(f"{base}/metrics", timeout=5).read().decode()
        assert "served_total 3.0" in text
        snap = json.loads(urllib.request.urlopen(
            f"{base}/metrics.json", timeout=5).read())
        assert validate_snapshot(snap) == []
        assert snap["counters"]["served_total"] == 3
        ok = urllib.request.urlopen(f"{base}/healthz", timeout=5).read()
        assert ok == b"ok\n"
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"{base}/nope", timeout=5)
    finally:
        srv.stop()


# ------------------------------------------------------------------ watchdog


def test_watchdog_warns_and_publishes_report():
    reg = MetricsRegistry()
    infos = [StallInfo(name="grad.7", op="allreduce", age_s=0.0,
                       missing_ranks=[1, 3])]
    wd = StallWatchdog(check_time_s=0.1, rank=0, reg=reg,
                       poll_interval_s=0.02)
    wd.add_source(lambda: infos)
    try:
        infos[0].age_s = 0.5  # past the threshold
        deadline = time.monotonic() + 2.0
        while wd.report() is None and time.monotonic() < deadline:
            time.sleep(0.02)
        rep = wd.report()
        assert rep is not None, "watchdog never reported"
        assert rep["stalled"][0]["name"] == "grad.7"
        assert rep["stalled"][0]["missing_ranks"] == [1, 3]
        assert "grad.7" in rep["text"] and "missing ranks: 1, 3" in rep["text"]
        assert reg.counter("horovod_stall_warnings_total").value >= 1
    finally:
        wd.stop()


def test_watchdog_escalates_past_shutdown_time():
    reg = MetricsRegistry()
    aborted = []
    wd = StallWatchdog(check_time_s=0.05, shutdown_time_s=0.2, rank=0,
                       on_abort=aborted.append, reg=reg,
                       poll_interval_s=0.02)
    wd.add_source(lambda: [StallInfo("t", "allreduce", age_s=1.0)])
    try:
        deadline = time.monotonic() + 2.0
        while not aborted and time.monotonic() < deadline:
            time.sleep(0.02)
        assert aborted and aborted[0].name == "t"
        assert len(aborted) == 1, "abort must fire once per tensor"
        time.sleep(0.1)
        assert len(aborted) == 1
        assert reg.counter("horovod_stall_aborts_total").value == 1
    finally:
        wd.stop()


# ---------------------------------------------- injected stall, two processes


@pytest.mark.engine
def test_injected_stall_watchdog_names_missing_rank():
    """Rank 1 delays submitting tensor `lonely` past HOROVOD_STALL_CHECK_TIME
    (0.5s): rank 0's watchdog must publish a structured report naming BOTH
    the tensor and missing rank 1 within a few check windows, the warning
    must hit stderr, and the collective must still complete once rank 1
    joins (acceptance criterion: report within HOROVOD_STALL_CHECK_TIME)."""
    script = textwrap.dedent("""
        import json, os, sys, time
        sys.path.insert(0, os.environ["HVD_REPO"])
        import numpy as np
        import horovod_tpu as hvd
        from horovod_tpu.common import basics
        from horovod_tpu import metrics

        hvd.init()
        eng = basics.engine()
        rank = hvd.rank()
        t0 = time.monotonic()
        h = None
        if rank == 0:
            h = eng.enqueue("allreduce", np.ones(4), "lonely")
            deadline = time.monotonic() + 4.0
            rep = None
            while time.monotonic() < deadline:
                rep = metrics.registry().get_info("stall_report")
                if rep:
                    break
                time.sleep(0.05)
            report_age = time.monotonic() - t0
        else:
            time.sleep(2.0)
            h = eng.enqueue("allreduce", np.ones(4), "lonely")
        out = eng.synchronize(h, timeout=30)
        ok = bool(np.allclose(out, 1.0))
        result = {"ok": ok, "rank": rank}
        if rank == 0:
            result["report"] = rep
            result["report_age_s"] = report_age
            snap = metrics.snapshot()
            result["warnings"] = snap["counters"].get(
                "horovod_stall_warnings_total", 0)
        eng.shutdown()
        print(json.dumps(result))
    """)
    results = launch_world(
        2, script, timeout=120,
        extra_env={"HOROVOD_ENGINE": "python",
                   "JAX_PLATFORMS": "cpu",
                   "HOROVOD_STALL_CHECK_TIME": "0.5"})
    r0 = next(r for r in results if r["out"]["rank"] == 0)
    assert r0["out"]["ok"] is True
    rep = r0["out"]["report"]
    assert rep, f"no stall report on rank 0; stderr:\n{r0['stderr'][-2000:]}"
    stalled = {s["name"]: s for s in rep["stalled"]}
    assert "lonely" in stalled
    assert stalled["lonely"]["missing_ranks"] == [1]
    assert stalled["lonely"]["op"] == "allreduce"
    # reported within ~3 check windows of the 0.5s HOROVOD_STALL_CHECK_TIME
    assert r0["out"]["report_age_s"] < 2.0, r0["out"]["report_age_s"]
    assert r0["out"]["warnings"] >= 1
    assert "lonely" in r0["stderr"] and "missing ranks: 1" in r0["stderr"]


@pytest.mark.engine
def test_stall_shutdown_time_fails_collective():
    """Past HOROVOD_STALL_SHUTDOWN_TIME the watchdog fails the stalled
    collective with an error naming the missing rank instead of hanging."""
    script = textwrap.dedent("""
        import json, os, sys, time
        sys.path.insert(0, os.environ["HVD_REPO"])
        import numpy as np
        import horovod_tpu as hvd
        from horovod_tpu.common import basics
        from horovod_tpu.common.engine import HorovodInternalError

        hvd.init()
        eng = basics.engine()
        rank = hvd.rank()
        err = ""
        if rank == 0:
            h = eng.enqueue("allreduce", np.ones(4), "doomed")
            try:
                eng.synchronize(h, timeout=20)
            except HorovodInternalError as e:
                err = str(e)
        else:
            time.sleep(5.0)   # never submits `doomed` within the threshold
        eng.shutdown()
        print(json.dumps({"rank": rank, "err": err}))
    """)
    results = launch_world(
        2, script, timeout=120,
        extra_env={"HOROVOD_ENGINE": "python",
                   "JAX_PLATFORMS": "cpu",
                   "HOROVOD_STALL_CHECK_TIME": "0.4",
                   "HOROVOD_STALL_SHUTDOWN_TIME": "1.2"})
    r0 = next(r for r in results if r["out"]["rank"] == 0)
    assert "stalled" in r0["out"]["err"], r0["out"]["err"]
    assert "doomed" in r0["out"]["err"]
    assert "missing ranks: 1" in r0["out"]["err"]


# ----------------------------------------------- engine feed points (local)


def test_engine_feeds_registry(hvd):
    """Whichever engine implementation is active (native preferred, Python
    fallback), the per-op count/bytes/latency series must populate."""
    from horovod_tpu import metrics
    from horovod_tpu.common import basics

    eng = basics.engine()
    before = metrics.snapshot()["counters"].get(
        'horovod_collectives_total{op="allreduce"}', 0)
    arr = np.arange(16, dtype=np.float32)
    for i in range(3):
        eng.run("allreduce", arr, f"m.{i}")
    snap = metrics.snapshot()
    assert snap["counters"][
        'horovod_collectives_total{op="allreduce"}'] == before + 3
    assert snap["counters"][
        'horovod_collective_bytes_total{op="allreduce"}'] >= 3 * arr.nbytes
    hist = snap["histograms"]['horovod_collective_seconds{op="allreduce"}']
    assert hist["count"] >= 3 and hist["p50"] > 0


# ------------------------------------------ compiled-path overlap (mesh8)


def test_bucket_overlap_metrics_consistent_with_plan(mesh8):
    """The recorded plan gauges must match fusion.build_plan exactly, and
    the planned overlap-efficiency bound must be monotone non-decreasing in
    K (more buckets -> smaller unhideable tail) — the metrics counterpart
    of test_overlap.py's planning invariants."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from horovod_tpu import metrics
    from horovod_tpu.compat import shard_map
    from horovod_tpu.parallel import fusion

    grads = {
        "w1": jnp.ones((8, 33, 7)),
        "w2": jnp.ones((8, 129)),
        "w3": jnp.ones((8, 5, 5)),
        "w4": jnp.ones((8, 257)),
    }
    reg = metrics.registry()
    hist_before = reg.histogram(
        "horovod_fusion_bucket_bytes",
        buckets=DEFAULT_BYTE_BUCKETS).count
    planned = []
    recorded_buckets = 0
    for k in (1, 2, 4, 8):
        out = jax.jit(shard_map(
            lambda g, nb=k: fusion.fused_allreduce(g, num_buckets=nb),
            mesh=mesh8, in_specs=P("hvd"), out_specs=P(),
            check_vma=False))(grads)
        jax.block_until_ready(out)
        plan = fusion.build_plan(
            jax.tree_util.tree_map(lambda t: t[0], grads), num_buckets=k)
        # ^ per-shard tree: inside shard_map leaves carry the per-rank shape
        rec = metrics.last_plan()
        assert rec is not None
        assert reg.gauge("horovod_fusion_buckets").value == plan.num_buckets
        assert len(rec) == plan.num_buckets
        plan_bytes = [sum(d.size * d.dtype.itemsize for d in b)
                      for b in plan.buckets]
        assert [n for _, n in rec] == plan_bytes
        assert reg.gauge("horovod_fusion_planned_bytes").value == sum(plan_bytes)
        recorded_buckets += plan.num_buckets
        planned.append(
            (k, reg.gauge("horovod_overlap_efficiency_planned").value))
    assert planned[0][1] == 0.0          # K=1: nothing can be hidden
    effs = [e for _, e in planned]
    assert effs == sorted(effs), effs    # monotone in K
    assert effs[-1] > 0.5                # 8 buckets hide most of the bytes
    snap = metrics.snapshot()
    assert snap["histograms"]["horovod_fusion_bucket_bytes"]["count"] \
        >= hist_before + recorded_buckets


# measure_overlap's interval math over the device-profile loader:
# tests/test_device_profile.py (test_overlap_report_interval_math,
# test_measure_overlap_keeps_its_keys_and_gauges).


# ------------------------------- the sigmoid router's names under jax.checkpoint


@pytest.mark.parametrize("how,recomputed", [
    ("remat_model", 0), ("bare_checkpoint", 1), ("experts_alone", 1),
    ("nothing_recomputed", 0)])
def test_moe_router_gauges_say_whether_the_router_runs_again(how, recomputed):
    """``horovod_moe_router_recomputed`` (trace time): 0 where
    ``TransformerLM(remat=True)``'s policy saves the sigmoid router's names
    (``ops.moe.ROUTER_SAVED``) and where nothing is recomputed, 1 under a bare
    ``jax.checkpoint`` or a policy without the two new names; beside it the
    bytes of the two (N, top_k) float32 arrays the layer names."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu import metrics
    from horovod_tpu.models import MoEMLP, TransformerLM
    from horovod_tpu.ops import moe as ops_moe

    if how == "remat_model":
        x = jnp.arange(48, dtype=jnp.int32).reshape(2, 24) % 64
        module = TransformerLM(
            vocab=64, dim=32, heads=2, layers=2, moe_experts=8, moe_top_k=3,
            moe_hidden=16, moe_every=1, moe_router="sigmoid",
            dtype=jnp.float32, remat=True)
    else:
        x = jnp.ones((2, 24, 32))
        module = MoEMLP(dim=32, hidden=16, n_experts=8, top_k=3,
                        dtype=jnp.float32, router="sigmoid")
    variables = jax.eval_shape(module.init, jax.random.PRNGKey(0), x)

    def loss(params):   # a new function a case: the gauges are set by a trace
        return jnp.sum(module.apply({**variables, "params": params}, x) ** 2)

    if how in ("bare_checkpoint", "experts_alone"):
        loss = jax.checkpoint(loss, policy=None if how == "bare_checkpoint"
                              else ops_moe.save_names(ops_moe.CHOSEN_EXPERTS))
    jax.eval_shape(jax.grad(loss), variables["params"])
    gauges = metrics.registry().snapshot()["gauges"]
    assert gauges["horovod_moe_router_recomputed"] == recomputed
    assert gauges["horovod_moe_router_saved_bytes_per_layer"] == 2 * 48 * 3 * 4


# ------------------------------------------------------- runner aggregation


def test_driver_service_pod_metrics():
    from horovod_tpu.runner.service import DriverService

    svc = DriverService.__new__(DriverService)  # no sockets needed
    svc.num_proc = 2
    svc._lock = threading.Lock()
    svc._cv = threading.Condition(svc._lock)
    svc._results = {}
    svc._metrics = {}
    assert svc.pod_metrics() is None
    reg = MetricsRegistry()
    reg.counter("steps_total").inc(5)
    svc.handle({"kind": "metrics", "rank": 0, "snapshot": reg.snapshot()},
               ("127.0.0.1", 1))
    reg2 = MetricsRegistry()
    reg2.counter("steps_total").inc(7)
    svc.handle({"kind": "result", "rank": 1,
                "value": {"ok": True, "value": 1,
                          "metrics": reg2.snapshot()}}, ("127.0.0.1", 2))
    pod = svc.pod_metrics()
    assert pod["ranks_reporting"] == 2
    assert pod["counters"]["steps_total"] == 12
    assert validate_snapshot(pod) == []


def test_metrics_callback_single_process(hvd, tmp_path):
    from horovod_tpu.callbacks import MetricsCallback
    from horovod_tpu import metrics

    path = tmp_path / "pod.json"
    cb = MetricsCallback(snapshot_path=str(path))
    cb.on_train_begin()
    cb.on_epoch_begin(0)
    time.sleep(0.01)
    cb.on_epoch_end(0, {"steps": 50})
    assert metrics.registry().gauge("horovod_steps_per_sec").value > 0
    cb.on_train_end()
    assert cb.pod_snapshot is not None
    assert cb.pod_snapshot["ranks_reporting"] == 1
    on_disk = json.loads(path.read_text())
    assert validate_snapshot(on_disk) == []
    assert on_disk["counters"].get("horovod_epochs_total", 0) >= 1


def test_http_exposition_bind_retry_on_busy_port():
    """EADDRINUSE slides the exporter up a small port window instead of
    crashing hvd.init (ISSUE 8 satellite: an elastic respawn lands where
    the previous generation's exporter still holds port + local_rank)."""
    import socket as _socket

    reg = MetricsRegistry()
    blocker = _socket.socket()
    blocker.bind(("127.0.0.1", 0))
    blocker.listen(1)
    busy = blocker.getsockname()[1]
    try:
        srv = start_metrics_server(busy, reg)
        try:
            assert srv.port != busy
            assert busy < srv.port < busy + 16
            ok = urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/healthz", timeout=5).read()
            assert ok == b"ok\n"
        finally:
            srv.stop()
    finally:
        blocker.close()


def test_http_exposition_window_exhaustion_raises(monkeypatch):
    import socket as _socket

    monkeypatch.setenv("HOROVOD_METRICS_PORT_WINDOW", "1")
    reg = MetricsRegistry()
    blocker = _socket.socket()
    blocker.bind(("127.0.0.1", 0))
    blocker.listen(1)
    try:
        with pytest.raises(OSError):
            start_metrics_server(blocker.getsockname()[1], reg)
    finally:
        blocker.close()
