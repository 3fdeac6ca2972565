"""Serving observability (ISSUE 15): flight recorder, anomaly detector,
serving-plane tracer, mixed-plane collector merge, debug bundles, the
replica stall-watchdog wiring, and perf_gate --trend.

Everything here is deterministic: the anomaly rules are driven by hand
(synthetic registry series, explicit tick() calls), the flight ring's
SIGKILL survival is proven with a real killed subprocess, and the trend
satellite is asserted against records in the driver's shape written here.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time

import pytest

from launch_util import REPO

from horovod_tpu.metrics.anomaly import (
    AnomalyDetector,
    DEMOTION_STORM,
    PREEMPT_STORM,
    WARMUP_TICKS,
)
from horovod_tpu.metrics.registry import MetricsRegistry
from horovod_tpu.tracing import flight as flight_mod
from horovod_tpu.tracing.bundle import make_bundle
from horovod_tpu.tracing.collector import build_trace, load_spans
from horovod_tpu.tracing.flight import (
    FlightRecorder,
    config_fingerprint,
    read_ring,
)
from horovod_tpu.tracing.serve import ServeTracer, serve_trace_id


# ------------------------------------------------------------------ flight

def test_flight_ring_mmap_roundtrip_and_wrap(tmp_path):
    fr = FlightRecorder("llm-decode-9", flight_dir=str(tmp_path),
                        capacity=16)
    for i in range(40):   # wraps the 16-slot ring
        fr.retain({"tid": f"req:gen:{i}", "phase": "decode", "i": i})
    recs = fr.records()
    assert len(recs) == 16
    assert [r["i"] for r in recs] == list(range(24, 40))   # newest 16
    ring = read_ring(FlightRecorder.ring_path(str(tmp_path),
                                              "llm-decode-9"))
    assert ring["proc"] == "llm-decode-9"
    assert [r["i"] for r in ring["records"]] == list(range(24, 40))
    assert ring["meta"]["fingerprint"]["hash"]
    fr.close()


def test_flight_oversize_record_truncates_not_drops(tmp_path):
    fr = FlightRecorder("p", flight_dir=str(tmp_path), capacity=16)
    fr.retain({"tid": "req:gen:1", "phase": "decode", "blob": "x" * 4096})
    (rec,) = fr.records()
    assert rec == {"flight_truncated": 1, "tid": "req:gen:1",
                   "phase": "decode", "flight_event": None}
    fr.close()


def test_flight_event_attrs_may_carry_kind_key(tmp_path):
    """Regression: anomaly events carry their own ``kind`` attr — it must
    not collide with the event-name parameter."""
    fr = FlightRecorder("p2", flight_dir=str(tmp_path), capacity=16)
    fr.event("anomaly", kind="ttft_slo", slo_s=2.0)
    (rec,) = fr.records()
    assert rec["flight_event"] == "anomaly" and rec["kind"] == "ttft_slo"
    fr.close()


def test_flight_dump_carries_ring_metrics_and_fingerprint(tmp_path):
    fr = FlightRecorder("router", flight_dir=str(tmp_path), capacity=32)
    fr.event("replica_death", replica=3, reason="kill")
    path = fr.dump("replica-death-3")
    assert os.path.basename(path).startswith("flight-router-001-")
    with open(path) as f:
        doc = json.load(f)
    assert doc["reason"] == "replica-death-3"
    assert doc["fingerprint"]["hash"]
    assert any(r.get("flight_event") == "replica_death"
               for r in doc["records"])
    assert "counters" in doc["metrics"]
    fr.close()


def test_flight_in_memory_mode_and_noop_dump():
    fr = FlightRecorder("memproc", flight_dir="", capacity=16)
    for i in range(20):
        fr.retain({"i": i})
    assert [r["i"] for r in fr.records()] == list(range(4, 20))
    assert fr.dump("whatever") == ""   # nowhere to write, never raises


def test_config_fingerprint_redacts_secrets(monkeypatch):
    monkeypatch.setenv("HOROVOD_SECRET", "deadbeef")
    monkeypatch.setenv("HVD_SERVE_SECRET", "deadbeef")
    monkeypatch.setenv("HOROVOD_SERVE_TOKEN", "tok")
    monkeypatch.setenv("HOROVOD_CYCLE_TIME", "5")
    fp = config_fingerprint()
    joined = json.dumps(fp)
    assert "deadbeef" not in joined and '"tok"' not in joined
    assert fp["env"].get("HOROVOD_CYCLE_TIME") == "5"


def test_flight_ring_survives_sigkill(tmp_path):
    """The black-box property: a SIGKILL'd process's ring decodes from
    disk with its final records intact."""
    child = (
        "import os, sys, time\n"
        f"sys.path.insert(0, {str(REPO)!r})\n"
        "from horovod_tpu.tracing.flight import FlightRecorder\n"
        f"fr = FlightRecorder('victim', flight_dir={str(tmp_path)!r},"
        " capacity=64)\n"
        "for i in range(50):\n"
        "    fr.retain({'tid': f'req:gen:{i}', 'phase': 'decode',"
        " 'i': i})\n"
        "print('ready', flush=True)\n"
        "time.sleep(60)\n")
    p = subprocess.Popen([sys.executable, "-c", child],
                         stdout=subprocess.PIPE, text=True)
    assert p.stdout.readline().strip() == "ready"
    os.kill(p.pid, signal.SIGKILL)
    p.wait(timeout=30)
    ring = read_ring(FlightRecorder.ring_path(str(tmp_path), "victim"))
    assert ring["proc"] == "victim"
    assert [r["i"] for r in ring["records"]] == list(range(50))


# ----------------------------------------------------------------- anomaly

def _det(reg, **kw):
    kw.setdefault("slo_s", 2.0)
    kw.setdefault("cooldown_s", 0.0)
    det = AnomalyDetector(reg=reg, **kw)
    det._flight = FlightRecorder("t", flight_dir="", capacity=64)
    return det


def test_anomaly_quiet_on_empty_and_nominal_registry():
    reg = MetricsRegistry()
    det = _det(reg)
    tok = reg.counter("horovod_serve_llm_tokens_total", phase="decode")
    for i in range(20):
        tok.inc(50)    # steady throughput, no demand queued, no sheds
        assert det.tick(now=float(i)) == []
    assert det.history == []


def test_anomaly_ttft_slo_via_projected_wait_and_p99():
    reg = MetricsRegistry()
    det = _det(reg)
    g = reg.gauge("horovod_serve_projected_wait_seconds")
    g.set(1.9)
    assert det.tick(now=0.0) == []
    g.set(5.0)
    assert det.tick(now=1.0) == ["ttft_slo"]
    assert det.history[-1]["projected_wait_s"] == 5.0
    # p99 path: a TTFT histogram past the SLO fires too
    reg2 = MetricsRegistry()
    det2 = _det(reg2)
    h = reg2.histogram("horovod_serve_llm_ttft_seconds")
    for _ in range(100):
        h.observe(6.0)
    assert det2.tick(now=0.0) == ["ttft_slo"]


def test_anomaly_preempt_and_demotion_storms():
    reg = MetricsRegistry()
    det = _det(reg)
    pre = reg.counter("horovod_serve_llm_preemptions_total")
    det.tick(now=0.0)
    pre.inc(PREEMPT_STORM - 1)
    assert det.tick(now=1.0) == []
    pre.inc(PREEMPT_STORM)
    assert det.tick(now=2.0) == ["preempt_storm"]
    dm = reg.counter("horovod_plane_demotions_total")
    dm.inc(DEMOTION_STORM - 1)
    assert det.tick(now=3.0) == []
    dm.inc(1)   # trailing-window sum reaches the storm threshold
    assert det.tick(now=4.0) == ["demotion_storm"]


def test_anomaly_drain_collapse_needs_demand_and_warm_baseline():
    reg = MetricsRegistry()
    det = _det(reg)
    tok = reg.counter("horovod_serve_llm_tokens_total", phase="decode")
    waiting = reg.gauge("horovod_serve_llm_waiting_sequences")
    now = 0.0
    for _ in range(WARMUP_TICKS + 2):
        tok.inc(100)
        waiting.set(4)
        assert det.tick(now=now) == []
        now += 1
    # collapse WITHOUT demand: never fires (idle is not an anomaly)
    waiting.set(0)
    for _ in range(6):
        assert det.tick(now=now) == []
        now += 1
    # collapse WITH demand: fires after the consecutive-tick rule (and
    # refires each window with the zero test cooldown)
    waiting.set(4)
    fired = []
    for _ in range(6):
        fired += det.tick(now=now)
        now += 1
    assert fired and set(fired) == {"drain_collapse"}


def test_anomaly_shed_spike_and_cooldown():
    reg = MetricsRegistry()
    det = _det(reg, cooldown_s=100.0)
    shed = reg.counter("horovod_serve_shed_total")
    det.tick(now=0.0)
    shed.inc(50)
    assert det.tick(now=1.0) == ["shed_spike"]
    shed.inc(500)
    assert det.tick(now=2.0) == []     # cooldown suppresses the refire
    assert reg.snapshot()["counters"][
        'horovod_anomaly_total{kind="shed_spike"}'] == 1.0


def test_anomaly_firing_lands_in_flight_ring():
    reg = MetricsRegistry()
    det = _det(reg)
    reg.gauge("horovod_serve_projected_wait_seconds").set(9.0)
    assert det.tick(now=0.0) == ["ttft_slo"]
    recs = det._flight.records()
    assert any(r.get("flight_event") == "anomaly"
               and r.get("kind") == "ttft_slo" for r in recs)


# ----------------------------------------------- serve tracer / collector

def test_serve_trace_ids_never_collide_with_training_scheme():
    assert serve_trace_id("gen", 12) == "req:gen:12"
    assert "#" not in serve_trace_id("infer", 99)


def test_serve_tracer_writes_proc_file_flight_always_on(tmp_path,
                                                        monkeypatch):
    monkeypatch.setenv("HOROVOD_TRACE_DIR", str(tmp_path))
    monkeypatch.setenv("HOROVOD_FLIGHT_DIR", "")
    flight_mod._flight = None   # fresh process singleton
    t = ServeTracer("serve-router")
    assert t.enabled
    t.span("req:gen:1", "admit", 100, 200, rid=1, decision="ok")
    t.point("req:gen:1", "retire", tokens=3)
    t.flush()
    path = os.path.join(str(tmp_path), "spans-serve-router.jsonl")
    lines = [json.loads(ln) for ln in open(path)]
    assert lines[0]["meta"] == 1 and lines[0]["proc"] == "serve-router"
    assert lines[1]["phase"] == "admit" and lines[1]["proc"] == \
        "serve-router"
    # flight retention happened even with no flight dir (memory ring)
    assert any(r.get("phase") == "retire" for r in t.flight.records())
    t.close()
    # with tracing OFF the tracer still retains into the ring
    monkeypatch.delenv("HOROVOD_TRACE_DIR")
    t2 = ServeTracer("llm-decode-0")
    assert not t2.enabled
    t2.span("it:llm-decode-0:1", "decode", 1, 2, seqs=[4])
    assert t2.flight.records()[-1]["phase"] == "decode"


def test_collector_merges_mixed_planes_with_proc_rows(tmp_path,
                                                      monkeypatch):
    from horovod_tpu.tracing import TraceRecorder, span_path

    for r in range(2):
        rec = TraceRecorder(span_path(str(tmp_path), r), rank=r)
        rec.point("grad.0#1", "grad.0", "allreduce", "enqueue")
        rec.close()
    monkeypatch.setenv("HOROVOD_TRACE_DIR", str(tmp_path))
    monkeypatch.setenv("HOROVOD_FLIGHT_DIR", "")
    flight_mod._flight = None
    t = ServeTracer("llm-decode-0")
    t.span("it:llm-decode-0:1", "decode", 10, 20, seqs=[7], n=1)
    t.point("req:gen:7", "retire", tokens=2)
    t.flush()
    # torn tail from a killed replica must not break the merge
    with open(os.path.join(str(tmp_path),
                           "spans-llm-decode-0.jsonl"), "a") as f:
        f.write('{"tid": "req:g')
    t.close()
    spans, metas = load_spans(str(tmp_path))
    assert sorted(k for k in metas if isinstance(k, int)) == [0, 1]
    assert [k for k in metas if not isinstance(k, int)] == \
        ["llm-decode-0"]
    trace = build_trace(spans, metas)
    names = {e["args"]["name"] for e in trace["traceEvents"]
             if e.get("name") == "process_name"}
    assert names == {"rank 0", "rank 1", "llm-decode-0"}
    decode_lanes = {e["tid"] for e in trace["traceEvents"]
                    if e.get("cat") == "decode"}
    retire_lanes = {e["tid"] for e in trace["traceEvents"]
                    if e.get("cat") == "retire"}
    assert decode_lanes and retire_lanes and \
        decode_lanes.isdisjoint(retire_lanes)
    json.loads(json.dumps(trace))   # strict round trip


# ---------------------------------------------------------------- bundle

def test_bundle_names_dead_replica_and_decodes_ring(tmp_path):
    flight_dir = str(tmp_path / "flight")
    router = FlightRecorder("serve-router", flight_dir=flight_dir,
                            capacity=32)
    router.event("replica_death", replica=2, pid=999, state_was="serving",
                 reason="decode dispatch failed")
    router.event("anomaly", kind="ttft_slo", slo_s=2.0)
    router.dump("replica-death-2")
    victim = FlightRecorder("llm-decode-2", flight_dir=flight_dir,
                            capacity=32)
    victim.retain({"tid": "it:llm-decode-2:9", "phase": "decode",
                   "seqs": [5]})
    victim.close()
    router.close()
    out = str(tmp_path / "bundle")
    summary = make_bundle(out, flight_dir=flight_dir)
    assert summary["dead_replicas"] == [2]
    manifest = open(os.path.join(out, "MANIFEST.md")).read()
    assert "replica 2 died" in manifest
    assert "anomaly `ttft_slo` fired" in manifest
    decoded = json.load(open(os.path.join(
        out, "flight", "flight-llm-decode-2.ring.json")))
    assert decoded["records"][0]["phase"] == "decode"


def test_bundle_cli_exits_1_on_nothing(tmp_path):
    r = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.tracing.bundle",
         "--trace-dir", str(tmp_path / "no"), "--flight-dir",
         str(tmp_path / "nope"), "-o", str(tmp_path / "out")],
        capture_output=True, text=True, cwd=REPO,
        env=dict(os.environ, HOROVOD_TRACE_DIR="", HOROVOD_FLIGHT_DIR=""))
    assert r.returncode == 1, r.stdout + r.stderr


# --------------------------------------- scheduler / engine instrumentation

class _FakeTracer:
    proc = "llm-decode-0"

    def __init__(self):
        self.recs = []

    def span(self, tid, phase, t0, t1=None, **attrs):
        self.recs.append(dict(tid=tid, phase=phase, **attrs))

    def point(self, tid, phase, **attrs):
        self.span(tid, phase, 0, 0, **attrs)


def _scheduler(tracer, num_blocks=16, block_size=4, max_active=2):
    from horovod_tpu.serving.llm.kv_cache import PagedKVCache
    from horovod_tpu.serving.llm.scheduler import (
        IterationScheduler,
        Sequence,
    )
    from horovod_tpu.serving.model import tiny_lm_params

    cache = PagedKVCache(num_blocks, block_size, 16)
    sched = IterationScheduler(cache, tiny_lm_params(),
                               max_active=max_active, tracer=tracer)
    return sched, Sequence


def test_scheduler_emits_iteration_spans_with_member_seqs():
    tr = _FakeTracer()
    sched, Sequence = _scheduler(tr)
    for rid in (1, 2):
        sched.submit(Sequence(rid, [3, 17], 4))
    while sched.running or sched.waiting:
        sched.step()
    decode = [r for r in tr.recs if r["phase"] == "decode"]
    assert decode, tr.recs
    # ONE span per iteration, member rids in args — both sequences ride
    # the same span while both are running.
    assert any(set(r["seqs"]) == {1, 2} for r in decode)
    assert all(r["tid"].startswith("it:llm-decode-0:") for r in decode)
    admits = [r for r in tr.recs if r["phase"] == "admit"]
    retires = [r for r in tr.recs if r["phase"] == "retire"]
    assert {r["tid"] for r in admits} == {"req:gen:1", "req:gen:2"}
    assert {r["tid"] for r in retires} == {"req:gen:1", "req:gen:2"}
    assert all(r["tokens"] == 4 for r in retires)


def test_scheduler_preempt_and_kv_pressure_events():
    tr = _FakeTracer()
    # 6 blocks x 2 tokens: two sequences growing toward 4 blocks each
    # must fight over the 6-block pool
    sched, Sequence = _scheduler(tr, num_blocks=6, block_size=2,
                                 max_active=2)
    sched.submit(Sequence(1, [3], 8))
    sched.submit(Sequence(2, [5], 8))
    for _ in range(40):
        sched.step()
        if not sched.running and not sched.waiting:
            break
    preempts = [r for r in tr.recs if r["phase"] == "preempt"]
    pressure = [r for r in tr.recs if r["phase"] == "kv_pressure"]
    assert preempts and pressure
    assert pressure[0]["free"] <= sched.cache.alloc.num_blocks


def test_scheduler_sequences_debug_view():
    tr = _FakeTracer()
    sched, Sequence = _scheduler(tr, max_active=1)
    sched.submit(Sequence(1, [3, 17], 4))
    sched.submit(Sequence(2, [5], 4))
    sched.step()
    rows = sched.sequences()
    by_rid = {r["rid"]: r for r in rows}
    assert by_rid[1]["state"] == "running" and by_rid[1]["slot"] == 0
    assert by_rid[1]["blocks"] >= 1 and by_rid[1]["tokens_out"] >= 1
    assert by_rid[2]["state"] == "waiting" and by_rid[2]["slot"] == -1


def test_decode_engine_stall_infos_names_stuck_sequences():
    from horovod_tpu.serving.llm.generator import DecodeEngine

    tr = _FakeTracer()
    sched, Sequence = _scheduler(tr)
    engine = DecodeEngine(sched)   # NOT started: the loop never runs
    assert engine.stall_infos() == []
    sched.submit(Sequence(7, [3], 4))
    sched.step()
    sched.last_progress_t = time.monotonic() - 9.0
    infos = engine.stall_infos()
    assert [i.name for i in infos] == ["seq:7"]
    assert infos[0].op == "decode" and infos[0].age_s >= 9.0


def test_watchdog_on_warn_hook_fires_once_per_fresh_batch():
    from horovod_tpu.metrics import StallInfo, StallWatchdog

    calls = []
    wd = StallWatchdog(check_time_s=0.01, rank=0, poll_interval_s=10.0,
                       on_warn=lambda stalled: calls.append(
                           [s.name for s in stalled]))
    try:
        wd.add_source(lambda: [StallInfo(name="seq:3", op="decode",
                                         age_s=5.0)])
        wd._scan()
        wd._scan()   # same tensor inside the rate-limit window: no refire
        assert calls == [["seq:3"]]
    finally:
        wd.stop()


def test_refresh_projection_keeps_gauge_live():
    from horovod_tpu.serving.admission import KVAdmission
    from horovod_tpu.serving.config import LLMConfig

    reg = MetricsRegistry()
    adm = KVAdmission(LLMConfig(num_blocks=24, block_size=4), reg=reg)
    adm.observe_release(20, 1.0)
    for _ in range(40):               # decay the release EWMA hard
        adm.observe_release(0, 0.05)
    wait = adm.refresh_projection(free_blocks=2, queued_blocks=20)
    assert wait > 2.0
    assert reg.gauge("horovod_serve_projected_wait_seconds").value == wait
    # an idle pool projects zero
    assert adm.refresh_projection(free_blocks=24, queued_blocks=0) == 0.0


# ---------------------------------------------------------- perf_gate trend

def _trend(args):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "perf_gate.py"),
         "--trend"] + args, capture_output=True, text=True, cwd=REPO)


def test_perf_gate_trend_skips_a_timed_out_record(tmp_path):
    """The driver's record shape: four usable runs and one that exited
    rc=124 with nothing parsed. The timed-out run is excluded, and the
    trajectory of the rest is monotone up, so latest == best."""
    rec = {"metric": "resnet50_images_per_sec", "unit": "img/s"}
    for i, v in enumerate((1700.0, 2470.0, 2490.0, 2500.0), 1):
        with open(tmp_path / f"BENCH_r{i:02d}.json", "w") as f:
            json.dump({"n": i, "rc": 0, "parsed": dict(rec, value=v)}, f)
    with open(tmp_path / "BENCH_r05.json", "w") as f:
        json.dump({"n": 5, "rc": 124, "tail": "", "parsed": None}, f)
    r = _trend(["--history", str(tmp_path / "BENCH_r0*.json")])
    assert r.returncode == 0, r.stdout + r.stderr
    (line,) = [ln for ln in r.stdout.splitlines()
               if "resnet50_images_per_sec" in ln]
    assert "n=4" in line and "latest/best=1.000" in line
    assert "skipping" in r.stdout and "rc=124" in r.stdout


def test_perf_gate_trend_tracks_best_vs_latest(tmp_path):
    rec = {"metric": "m", "value": 100.0, "unit": "u"}
    for i, v in enumerate((100.0, 200.0, 150.0)):
        with open(tmp_path / f"BENCH_r{i:02d}.json", "w") as f:
            json.dump({"rc": 0, "parsed": dict(rec, value=v)}, f)
    r = _trend(["--history", str(tmp_path / "BENCH_r*.json")])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "n=3 best=200 latest=150 latest/best=0.750" in r.stdout


def test_perf_gate_trend_empty_history_errors(tmp_path):
    r = _trend(["--history", str(tmp_path / "nope*.json")])
    assert r.returncode == 2
