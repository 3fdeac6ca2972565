"""Distributed tracing tests (ISSUE 6, docs/tracing.md).

Coverage per the issue checklist:
- trace-ID propagation across a 4-proc eager ring: ONE deterministic ID
  per collective, spans from every rank, hop-level wire spans, directive
  echo agreement (no mismatch warnings);
- clock-offset estimator accuracy units (known offset + jitter);
- critical-path attribution on a synthetic span set with an injected
  straggler (rank + phase + >=80% share), including the negotiate-clipping
  rule that keeps a punctual rank's blocking exchange from diluting the
  skew verdict;
- Perfetto/Chrome-trace strict validity of the merged file;
- perf-gate pass/fail units against fixture bench JSON.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from launch_util import REPO, launch_world

sys.path.insert(0, os.path.join(REPO, "tools"))

import perf_gate  # noqa: E402  (tools/perf_gate.py)
from horovod_tpu.tracing import (  # noqa: E402
    TraceRecorder,
    analyze,
    build_trace,
    estimate_offset_ns,
    export_gauges,
    load_spans,
    merge_trace,
    span_path,
    trace_id,
)


# ---------------------------------------------------------------- recorder

def test_recorder_writes_meta_then_spans(tmp_path):
    path = str(tmp_path / "spans-rank3.jsonl")
    rec = TraceRecorder(path, rank=3, clock_offset_ns=1234)
    rec.point("a#1", "a", "allreduce", "enqueue", bytes=64)
    rec.span("a#1", "a", "allreduce", "negotiate", 100, 200, cached=False)
    rec.close()
    lines = [json.loads(l) for l in open(path)]
    assert lines[0]["meta"] == 1
    assert lines[0]["rank"] == 3
    assert lines[0]["clock_offset_ns"] == 1234
    assert lines[1]["phase"] == "enqueue"
    assert lines[1]["t0"] == lines[1]["t1"]
    assert lines[2] == {"tid": "a#1", "rank": 3, "name": "a",
                        "op": "allreduce", "phase": "negotiate",
                        "t0": 100, "t1": 200, "cached": False}


def test_recorder_survives_unwritable_path():
    rec = TraceRecorder("/proc/definitely/not/writable/spans.jsonl", rank=0)
    before = rec.dropped
    for _ in range(3):
        rec.point("x#1", "x", "allreduce", "enqueue")
    assert rec.dropped >= before + 3   # counted, not raised
    rec.close()


def test_trace_id_deterministic():
    assert trace_id("grad.7", 3) == "grad.7#3"
    assert span_path("/tmp/t", 2).endswith("spans-rank2.jsonl")


# ------------------------------------------------------------------- clock

def test_clock_offset_estimator_accuracy():
    true_offset = 5_000_000   # 5 ms between the two clocks
    calls = {"n": 0}

    def probe():
        # Simulated server: local clock + true offset, plus asymmetric
        # jitter on some rounds — the min-RTT filter must reject those.
        calls["n"] += 1
        import time

        jitter = 2_000_000 if calls["n"] % 3 == 0 else 0
        if jitter:
            time.sleep(0.002)
        return time.monotonic_ns() + true_offset + jitter

    offset, err = estimate_offset_ns(probe, rounds=10)
    assert abs(offset - true_offset) < 1_000_000, (offset, err)
    assert err >= 0


def test_clock_offset_estimator_all_failures_raise():
    def probe():
        raise ConnectionError("down")

    with pytest.raises(ConnectionError):
        estimate_offset_ns(probe, rounds=3)


# ----------------------------------------------------------- critical path

def _synthetic_spans(world=4, straggler=2, delay_ns=500_000_000, n=3):
    """n collectives; `straggler` enqueues `delay_ns` late on each."""
    spans = []
    t = 1_000_000_000
    for i in range(n):
        tid = f"g.{i}#1"
        gate = t + delay_ns
        for r in range(world):
            enq = gate if r == straggler else t + r * 1000
            spans.append({"tid": tid, "rank": r, "name": f"g.{i}",
                          "op": "allreduce", "phase": "enqueue",
                          "t0": enq, "t1": enq})
            # Punctual ranks' negotiate spans BLOCK across the gate — the
            # analyzer must clip them, not book them as negotiation.
            spans.append({"tid": tid, "rank": r, "name": f"g.{i}",
                          "op": "allreduce", "phase": "negotiate",
                          "t0": enq + 100, "t1": gate + 2_000_000,
                          "cached": False})
            spans.append({"tid": tid, "rank": r, "name": f"g.{i}",
                          "op": "allreduce", "phase": "wire_send",
                          "t0": gate + 2_000_000, "t1": gate + 5_000_000,
                          "bytes": 4096})
            spans.append({"tid": tid, "rank": r, "name": f"g.{i}",
                          "op": "allreduce", "phase": "reduce",
                          "t0": gate + 5_000_000, "t1": gate + 5_500_000})
            spans.append({"tid": tid, "rank": r, "name": f"g.{i}",
                          "op": "allreduce", "phase": "done",
                          "t0": gate + 6_000_000, "t1": gate + 6_000_000})
        t = gate + 10_000_000
    return spans


def test_critical_path_attributes_injected_straggler():
    delay = 500_000_000
    n = 3
    report = analyze(_synthetic_spans(straggler=2, delay_ns=delay, n=n))
    assert report["collectives"] == n
    assert report["multi_rank_collectives"] == n
    strag = report["straggler"]
    assert strag is not None
    assert strag["rank"] == 2
    assert strag["phase"] == "compute_skew"
    injected = delay * n / 1e9
    attributed = report["skew_seconds_by_rank"][2]
    assert attributed >= 0.8 * injected
    # >=80% of ALL blocked time lands on the straggler: the negotiate
    # clipping rule is what makes this hold.
    assert strag["share_of_blocked"] >= 0.8
    # negotiation only counts post-gate time: 2ms per rank per collective
    assert report["phase_seconds"]["negotiation"] <= 0.010
    assert report["phase_seconds"]["wire"] > 0
    assert report["phase_seconds"]["reduce"] > 0


def test_critical_path_cache_vs_negotiation_split():
    spans = []
    for r in range(2):
        spans.append({"tid": "x#1", "rank": r, "name": "x",
                      "op": "allreduce", "phase": "enqueue",
                      "t0": 1000, "t1": 1000})
        spans.append({"tid": "x#1", "rank": r, "name": "x",
                      "op": "allreduce", "phase": "negotiate",
                      "t0": 1000, "t1": 2000, "cached": True})
    report = analyze(spans)
    assert report["phase_seconds"]["cache"] > 0
    assert report["phase_seconds"]["negotiation"] == 0


def test_critical_path_single_rank_no_skew():
    spans = [{"tid": "y#1", "rank": 0, "name": "y", "op": "allreduce",
              "phase": "enqueue", "t0": 0, "t1": 0},
             {"tid": "y#1", "rank": 0, "name": "y", "op": "allreduce",
              "phase": "done", "t0": 100, "t1": 100}]
    report = analyze(spans)
    assert report["multi_rank_collectives"] == 0
    assert report["straggler"] is None


def test_export_gauges_publishes_attribution():
    from horovod_tpu.metrics import registry

    report = analyze(_synthetic_spans())
    export_gauges(report)
    reg = registry()
    assert reg.gauge("horovod_straggler_rank").value == 2
    assert reg.gauge("horovod_critical_path_seconds",
                     phase="compute_skew").value > 0
    info = reg.get_info("straggler_attribution")
    assert info and info["straggler"]["rank"] == 2


def test_watchdog_report_enriched_with_attribution():
    from horovod_tpu.metrics import StallWatchdog, StallInfo, registry

    export_gauges(analyze(_synthetic_spans()))
    wd = StallWatchdog(check_time_s=0.01, rank=0, poll_interval_s=10.0)
    try:
        wd.add_source(lambda: [StallInfo(name="g.0", op="allreduce",
                                         age_s=5.0, missing_ranks=[2])])
        wd._scan()
        rep = registry().get_info("stall_report")
        assert rep is not None
        assert rep["straggler_attribution"]["straggler"]["rank"] == 2
    finally:
        wd.stop()


# ------------------------------------------------------- merge / perfetto

def _write_rank_file(tmp_path, rank, offset_ns, spans):
    path = span_path(str(tmp_path), rank)
    with open(path, "w") as f:
        f.write(json.dumps({"meta": 1, "rank": rank, "clock": "monotonic_ns",
                            "clock_offset_ns": offset_ns}) + "\n")
        for s in spans:
            f.write(json.dumps(s) + "\n")


def test_merge_applies_clock_offsets_and_is_strict_json(tmp_path):
    # Rank 1's clock reads 1s behind; its meta offset corrects it.
    _write_rank_file(tmp_path, 0, 0, [
        {"tid": "a#1", "rank": 0, "name": "a", "op": "allreduce",
         "phase": "enqueue", "t0": 5_000_000_000, "t1": 5_000_000_000}])
    _write_rank_file(tmp_path, 1, 1_000_000_000, [
        {"tid": "a#1", "rank": 1, "name": "a", "op": "allreduce",
         "phase": "enqueue", "t0": 4_000_000_000, "t1": 4_000_000_000}])
    spans, metas = load_spans(str(tmp_path))
    assert sorted(metas) == [0, 1]
    ts = {s["rank"]: s["t0"] for s in spans}
    assert ts[0] == ts[1] == 5_000_000_000   # aligned
    out = str(tmp_path / "trace.json")
    merge_trace(str(tmp_path), out)
    with open(out) as f:
        trace = json.load(f)   # STRICT parse from disk
    events = trace["traceEvents"]
    assert isinstance(events, list) and events
    span_events = [e for e in events if e["ph"] in ("X", "i")]
    assert {e["pid"] for e in span_events} == {0, 1}
    for e in span_events:
        assert isinstance(e["ts"], (int, float))
        assert e["args"]["tid"] == "a#1"
        if e["ph"] == "X":
            assert e["dur"] >= 0
    # metadata process names present for Perfetto track labeling
    assert any(e.get("name") == "process_name" for e in events)


def test_build_trace_lane_mapping():
    spans = [{"tid": "t#1", "rank": 0, "name": "t", "op": "allreduce",
              "phase": p, "t0": 10, "t1": 20}
             for p in ("negotiate", "wire_send", "wire_recv", "reduce")]
    trace = build_trace(spans)
    lanes = {e["cat"]: e["tid"] for e in trace["traceEvents"]
             if e["ph"] == "X"}
    assert lanes["wire_send"] != lanes["wire_recv"]
    assert lanes["negotiate"] != lanes["reduce"]


def test_load_spans_skips_torn_lines(tmp_path):
    path = span_path(str(tmp_path), 0)
    with open(path, "w") as f:
        f.write(json.dumps({"meta": 1, "rank": 0,
                            "clock_offset_ns": 0}) + "\n")
        f.write(json.dumps({"tid": "a#1", "rank": 0, "name": "a",
                            "op": "allreduce", "phase": "enqueue",
                            "t0": 1, "t1": 1}) + "\n")
        f.write('{"tid": "b#1", "rank": 0, "na')   # torn tail (crash)
    spans, _ = load_spans(str(tmp_path))
    assert len(spans) == 1


# --------------------------------------------------------------- perf gate

def _gate(args):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "perf_gate.py")] + args,
        capture_output=True, text=True)


def _write(tmp_path, name, obj):
    p = str(tmp_path / name)
    with open(p, "w") as f:
        json.dump(obj, f)
    return p


REC = {"metric": "resnet50_images_per_sec", "value": 1000.0, "unit": "img/s"}


def test_perf_gate_passes_on_baseline(tmp_path):
    base = _write(tmp_path, "base.json", REC)
    cur = _write(tmp_path, "cur.json", REC)
    r = _gate(["--current", cur, "--baseline", base])
    assert r.returncode == 0, r.stdout + r.stderr


def test_perf_gate_fails_20pct_regression(tmp_path):
    base = _write(tmp_path, "base.json", REC)
    cur = _write(tmp_path, "cur.json", dict(REC, value=800.0))
    r = _gate(["--current", cur, "--baseline", base])
    assert r.returncode == 1, r.stdout + r.stderr
    assert "REGRESSION" in r.stdout


def test_perf_gate_per_metric_threshold(tmp_path):
    base = _write(tmp_path, "base.json", REC)
    cur = _write(tmp_path, "cur.json", dict(REC, value=800.0))
    r = _gate(["--current", cur, "--baseline", base,
               "--per-metric", "resnet50_images_per_sec=0.75"])
    assert r.returncode == 0, r.stdout + r.stderr


def test_perf_gate_smoke_and_full_never_compared(tmp_path):
    base = _write(tmp_path, "base.json", REC)   # full-mode baseline
    cur = _write(tmp_path, "cur.json",
                 dict(REC, value=1.0, smoke=True))  # tiny smoke number
    r = _gate(["--current", cur, "--baseline", base,
               "--allow-missing-baseline"])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "no comparable baseline" in r.stdout


def test_perf_gate_harness_shape_and_history(tmp_path):
    # BENCH_r0*.json shape: {"parsed": {...}} — best value wins as reference
    _write(tmp_path, "BENCH_r01.json", {"parsed": dict(REC, value=900.0)})
    _write(tmp_path, "BENCH_r02.json", {"parsed": dict(REC, value=1000.0)})
    cur = _write(tmp_path, "cur.json", dict(REC, value=860.0))
    r = _gate(["--current", cur,
               "--history", str(tmp_path / "BENCH_r0*.json")])
    assert r.returncode == 0, r.stdout + r.stderr   # 0.86 >= 0.85 vs best
    cur2 = _write(tmp_path, "cur2.json", dict(REC, value=840.0))
    r = _gate(["--current", cur2,
               "--history", str(tmp_path / "BENCH_r0*.json")])
    assert r.returncode == 1, r.stdout + r.stderr


def test_perf_gate_partial_skipped_and_empty_is_error(tmp_path):
    cur = _write(tmp_path, "cur.json",
                 dict(REC, value=0.0, partial=True, reason="budget"))
    base = _write(tmp_path, "base.json", REC)
    r = _gate(["--current", cur, "--baseline", base,
               "--allow-missing-baseline"])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "SKIP partial" in r.stdout
    empty = _write(tmp_path, "empty.json", {"no": "metrics"})
    r = _gate(["--current", empty, "--baseline", base])
    assert r.returncode == 2


def test_perf_gate_skips_nonzero_rc_bench_records(tmp_path):
    """ISSUE 9 satellite: a harness record from a bench that exited
    non-zero (the pre-watchdog BENCH_r05 rc=124 shape) is skipped
    outright — even when its tail happens to contain parseable JSON
    fragments, which must never become a comparison baseline."""
    import perf_gate

    bad = _write(tmp_path, "BENCH_bad.json", {
        "n": 5, "cmd": "python some_bench", "rc": 124, "parsed": None,
        # A metric line stranded in the killed process's stderr tail:
        # scraping it would fabricate a 9000 img/s baseline.
        "tail": json.dumps(dict(REC, value=9000.0))})
    assert perf_gate.load_records(bad) == []
    # rc=0 harness records still parse through their "parsed" payload.
    good = _write(tmp_path, "BENCH_good.json",
                  {"rc": 0, "parsed": dict(REC, value=900.0)})
    assert [r["value"] for r in perf_gate.load_records(good)] == [900.0]
    # End to end: the rc!=0 file contributes no baseline, so a current run
    # far below the stranded tail value still passes against the real one.
    cur = _write(tmp_path, "cur.json", dict(REC, value=860.0))
    r = _gate(["--current", cur, "--baseline", bad, "--baseline", good])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "skipping" in r.stdout and "rc=124" in r.stdout


def test_perf_gate_require_metric(tmp_path):
    cur = _write(tmp_path, "cur.json", REC)
    r = _gate(["--current", cur, "--allow-missing-baseline",
               "--require-metric", "something_else"])
    assert r.returncode == 2


def test_perf_gate_self_check(tmp_path):
    cur = _write(tmp_path, "cur.json", REC)
    r = _gate(["--current", cur, "--self-check"])
    assert r.returncode == 0, r.stdout + r.stderr


def test_perf_gate_load_records_from_log_lines(tmp_path):
    p = str(tmp_path / "bench.log")
    with open(p, "w") as f:
        f.write("WARNING: some jax noise\n")
        f.write("bench: skipping stage 'x'\n")
        f.write(json.dumps(REC) + "\n")
    recs = perf_gate.load_records(p)
    assert recs == [REC]


# ------------------------------------------- 4-proc eager ring propagation

RING_WORKER = r"""
import os, sys, time
sys.path.insert(0, os.environ["HVD_REPO"])
import json
import numpy as np
from horovod_tpu.common.engine import PyEngine
from horovod_tpu.common.config import Config
from horovod_tpu.common.topology import Topology

rank = int(os.environ["HOROVOD_RANK"]); world = int(os.environ["HOROVOD_SIZE"])
topo = Topology(rank=rank, size=world, local_rank=rank, local_size=world,
                cross_rank=0, cross_size=1)
eng = PyEngine(topo, Config(cycle_time_ms=2.0, stall_check_disable=True))
assert eng._ring is not None, "expected the ring data plane in a 4-world"
for i in range(3):
    out = eng.run("allreduce", np.full(512, float(rank + 1), np.float32),
                  f"g.{i}")
eng.shutdown()
print(json.dumps({"rank": rank, "ok": True}))
"""


@pytest.mark.fast
def test_trace_id_propagation_4proc_eager_ring(tmp_path):
    """One trace ID per collective across a 4-proc RING world: spans on all
    ranks, hop-level wire spans, coordinator echo accepted silently."""
    trace_dir = str(tmp_path / "trace")
    results = launch_world(4, RING_WORKER,
                           extra_env={"HOROVOD_TRACE_DIR": trace_dir,
                                      "JAX_PLATFORMS": "cpu"})
    for r in results:
        assert r["out"]["ok"]
        # propagation must be verified silently: any disagreement logs a
        # trace-id mismatch warning
        assert "trace id mismatch" not in r["stderr"]
        assert "trace-id disagreement" not in r["stderr"]
    spans, metas = load_spans(trace_dir)
    assert sorted(metas) == [0, 1, 2, 3]
    by_tid: dict = {}
    phases_by_tid: dict = {}
    for s in spans:
        by_tid.setdefault(s["tid"], set()).add(s["rank"])
        phases_by_tid.setdefault(s["tid"], set()).add(s["phase"])
    for i in range(3):
        tid = f"g.{i}#1"
        assert by_tid.get(tid) == {0, 1, 2, 3}, by_tid
        assert {"enqueue", "negotiate", "wire_send", "wire_recv", "reduce",
                "done"} <= phases_by_tid[tid], phases_by_tid[tid]
    # non-coordinator ranks estimated a clock offset (meta present even if
    # near-zero on one host)
    assert all("clock_offset_ns" in m for m in metas.values())
    report = analyze(spans)
    assert report["multi_rank_collectives"] == 3


# ------------------------------------------------------------ native engine

NATIVE_WORKER = r"""
import os, sys
sys.path.insert(0, os.environ["HVD_REPO"])
import json
import numpy as np
from horovod_tpu.cc.native_engine import NativeEngine
from horovod_tpu.common.config import Config
from horovod_tpu.common.topology import Topology

rank = int(os.environ["HOROVOD_RANK"]); world = int(os.environ["HOROVOD_SIZE"])
host, port = os.environ["HOROVOD_COORD_ADDR"].rsplit(":", 1)
topo = Topology(rank=rank, size=world, local_rank=rank, local_size=world,
                cross_rank=0, cross_size=1)
eng = NativeEngine(topo, Config(cycle_time_ms=2.0, stall_check_disable=True))
for i in range(3):
    out = eng.run("allreduce", np.full(256, float(rank + 1), np.float32),
                  f"ng.{i}")
    assert abs(float(out[0]) - (world + 1) / 2.0) < 1e-6, float(out[0])
eng.shutdown()
print(json.dumps({"rank": rank, "ok": True}))
"""


@pytest.mark.fast
def test_trace_native_engine_2proc(tmp_path):
    """Native plane: Request.trace_seq rides the wire, engine.cc spans are
    drained through hvd_trace_drain into the same span files, and both
    ranks' spans share each collective's ID."""
    pytest.importorskip("ctypes")
    from horovod_tpu.cc import lib_path, NativeBuildError

    try:
        lib_path()
    except NativeBuildError:
        pytest.skip("native core unavailable")
    trace_dir = str(tmp_path / "trace")
    results = launch_world(2, NATIVE_WORKER,
                           extra_env={"HOROVOD_TRACE_DIR": trace_dir,
                                      "JAX_PLATFORMS": "cpu"})
    for r in results:
        assert r["out"]["ok"]
    spans, metas = load_spans(trace_dir)
    assert sorted(metas) == [0, 1]
    native = [s for s in spans if s.get("engine") == "native"]
    assert native, "no native-tagged spans drained"
    by_tid: dict = {}
    phases: set = set()
    for s in native:
        by_tid.setdefault(s["tid"], set()).add(s["rank"])
        phases.add(s["phase"])
    for i in range(3):
        assert by_tid.get(f"ng.{i}#1") == {0, 1}, by_tid
    assert {"enqueue", "negotiate", "wire", "done"} <= phases, phases
    report = analyze(spans)
    assert report["multi_rank_collectives"] == 3
