#!/usr/bin/env python
"""CI perf-regression gate (ISSUE 6): hold the structured output of the
``tools/*_smoke.py`` legs to floors and baselines; exit nonzero on a
regression.

A smoke prints one JSON line ``{"metric": ..., "value": ..., "unit": ...}``
per gated figure (``"partial": true`` flags a line of a wedged run, which
the gate skips). This gate reads those lines from:

- ``--current FILE`` — the run under test (a smoke's log, a raw JSON line,
  or a harness-shaped ``{"parsed": {...}}`` file);
- ``--baseline FILE`` / ``--history GLOB`` — prior results (saved logs or
  harness-shaped records; ci.sh passes none: its legs gate on
  ``--require-metric`` / ``--min-abs`` alone).

A current metric is compared against the BEST comparable baseline value —
same metric name and same smoke flag (a tiny-model CPU smoke number must
never be judged against a real-chip run, and vice versa). The verdict per
metric is ``current / best_baseline >= threshold``; the default
``--min-ratio 0.85`` fails a 20% throughput regression with headroom for
run-to-run noise, and ``--per-metric name=ratio`` overrides per series.

Exit codes: 0 = pass (or nothing comparable with
``--allow-missing-baseline``), 1 = regression / gate self-check failure,
2 = structural error (no parseable current metrics, missing required
metric).

``--self-check`` is the live-fire test ci.sh runs every build: it
synthesizes a baseline 25% above the current run (equivalently: treats the
current run as a 20% regression against that baseline) and verifies the
gate FAILS it — so a silently broken gate cannot keep passing CI.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import Optional


def _records_from_obj(obj) -> list[dict]:
    recs: list[dict] = []
    if isinstance(obj, dict):
        if "metric" in obj and "value" in obj:
            recs.append(obj)
        if isinstance(obj.get("parsed"), dict):          # BENCH_r0*.json shape
            recs.extend(_records_from_obj(obj["parsed"]))
        if isinstance(obj.get("metrics"), list):          # multi-metric bundle
            for m in obj["metrics"]:
                recs.extend(_records_from_obj(m))
    elif isinstance(obj, list):
        for m in obj:
            recs.extend(_records_from_obj(m))
    return recs


def load_records(path: str) -> list[dict]:
    """Extract metric records from a file: whole-file JSON first, else every
    parseable JSON line (bench logs mix warnings with the metric line).

    Harness-shaped records (``{"rc": ..., "tail": ..., "parsed": ...}``)
    from a bench run that exited non-zero are skipped OUTRIGHT — their
    ``tail`` is the truncated stderr of a killed process (the pre-watchdog
    BENCH_r05 rc=124 shape), and scraping partial JSON fragments out of it
    would compare today's run against a number the bench never finished
    producing."""
    with open(path) as f:
        text = f.read()
    try:
        obj = json.loads(text)
        if isinstance(obj, dict) and "rc" in obj:
            try:
                rc = int(obj["rc"])
            except (TypeError, ValueError):
                rc = -1
            if rc != 0:
                print(f"perf gate: skipping {path}: bench record exited "
                      f"rc={obj['rc']} (partial tail not parsed)")
                return []
        recs = _records_from_obj(obj)
        if recs:
            return recs
    except ValueError:
        pass
    recs = []
    for line in text.splitlines():
        line = line.strip()
        if not (line.startswith("{") and line.endswith("}")):
            continue
        try:
            recs.extend(_records_from_obj(json.loads(line)))
        except ValueError:
            continue
    return recs


def _usable(rec: dict) -> bool:
    try:
        v = float(rec.get("value", 0))
    except (TypeError, ValueError):
        return False
    return v > 0 and not rec.get("partial")


def _smoke_flag(rec: dict) -> bool:
    return bool(rec.get("smoke"))


def best_baseline(metric: str, smoke: bool, baselines: list[dict]
                  ) -> Optional[float]:
    vals = [float(r["value"]) for r in baselines
            if r.get("metric") == metric and _usable(r)
            and _smoke_flag(r) == smoke]
    return max(vals) if vals else None


def run_gate(current: list[dict], baselines: list[dict], min_ratio: float,
             per_metric: dict, allow_missing: bool,
             require: list[str], floors: Optional[dict] = None) -> int:
    floors = floors or {}
    usable = [r for r in current if _usable(r)]
    partial = [r for r in current if r.get("partial")]
    for r in partial:
        print(f"perf gate: SKIP partial result for {r.get('metric')!r} "
              f"({r.get('reason', 'no reason')})")
    if not usable and not partial:
        print("perf gate: ERROR — no parseable metric records in the "
              "current run", file=sys.stderr)
        return 2
    seen = {r.get("metric") for r in current}
    missing_req = [m for m in require if m not in seen]
    if missing_req:
        print(f"perf gate: ERROR — required metrics absent from the "
              f"current run: {missing_req}", file=sys.stderr)
        return 2
    failures = 0
    compared = 0
    for rec in usable:
        metric = rec["metric"]
        cur = float(rec["value"])
        # Absolute floors (--min-abs): for ratio-shaped metrics whose
        # healthy value is a known constant — e.g. the hier-ab cross-byte
        # reduction, where a future change silently re-inflating DCN
        # traffic must fail CI even on a bootstrap run with no baseline.
        if metric in floors:
            floor = float(floors[metric])
            verdict = "OK" if cur >= floor else "REGRESSION"
            print(f"perf gate: {metric} = {cur:g} vs floor {floor:g} "
                  f"-> {verdict}")
            if cur < floor:
                failures += 1
            compared += 1
        ref = best_baseline(metric, _smoke_flag(rec), baselines)
        if ref is None:
            print(f"perf gate: {metric} = {cur:g} {rec.get('unit', '')} "
                  "(no comparable baseline)")
            continue
        compared += 1
        threshold = float(per_metric.get(metric, min_ratio))
        ratio = cur / ref
        verdict = "OK" if ratio >= threshold else "REGRESSION"
        print(f"perf gate: {metric} = {cur:g} vs baseline {ref:g} "
              f"(ratio {ratio:.3f}, threshold {threshold:g}) -> {verdict}")
        if ratio < threshold:
            failures += 1
    if failures:
        print(f"perf gate: FAILED — {failures} metric(s) regressed",
              file=sys.stderr)
        return 1
    if compared == 0 and not allow_missing:
        print("perf gate: ERROR — no baseline was comparable to any "
              "current metric (pass --allow-missing-baseline for bootstrap "
              "runs)", file=sys.stderr)
        return 2
    print(f"perf gate: OK ({compared} compared, "
          f"{len(usable) - compared} uncompared, {len(partial)} partial)")
    return 0


def run_trend(paths: list[str]) -> int:
    """``--trend``: one line per metric across the bench history — the
    best/latest/ratio trajectory VERDICT rounds kept re-deriving by hand.
    Records from non-zero-rc bench runs are excluded by load_records
    (the BENCH_r05 rc=124 shape never becomes a data point)."""
    series: dict[tuple, list] = {}
    n_files = 0
    for p in paths:
        if not os.path.exists(p):
            continue
        recs = [r for r in load_records(p) if _usable(r)]
        if recs:
            n_files += 1
        for r in recs:
            series.setdefault((r["metric"], _smoke_flag(r)), []).append(
                (os.path.basename(p), float(r["value"]),
                 r.get("unit", "")))
    if not series:
        print("perf gate trend: no usable records in the history",
              file=sys.stderr)
        return 2
    for (metric, smoke), points in sorted(series.items()):
        vals = [v for _, v, _ in points]
        best, latest = max(vals), vals[-1]
        tag = " (smoke)" if smoke else ""
        traj = " -> ".join(f"{v:g}" for _, v, _ in points)
        print(f"perf gate trend: {metric}{tag}: n={len(vals)} "
              f"best={best:g} latest={latest:g} "
              f"latest/best={latest / best:.3f} | {traj} "
              f"{points[-1][2]}".rstrip())
    print(f"perf gate trend: {len(series)} metric(s) across "
          f"{n_files} record file(s)")
    return 0


def self_check(current: list[dict], min_ratio: float) -> int:
    """Prove the gate detects a 20% regression on today's own numbers."""
    usable = [r for r in current if _usable(r)]
    if not usable:
        print("perf gate self-check: no usable current metrics to check "
              "against", file=sys.stderr)
        return 2
    synthetic = [dict(r, value=float(r["value"]) / 0.8) for r in usable]
    rc = run_gate(usable, synthetic, min_ratio, {}, allow_missing=False,
                  require=[])
    if rc == 1:
        print("perf gate self-check: OK (synthetic 20% regression detected)")
        return 0
    print("perf gate self-check: FAILED — a 20% regression passed the gate",
          file=sys.stderr)
    return 1


def main(argv: Optional[list] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--current", default=None,
                    help="bench output of the run under test")
    ap.add_argument("--baseline", action="append", default=[],
                    help="baseline file (repeatable)")
    ap.add_argument("--history", action="append", default=[],
                    help="glob of prior bench results (repeatable)")
    ap.add_argument("--min-ratio", type=float, default=0.85,
                    help="fail when current/baseline drops below this "
                         "(default 0.85: catches a 20%% regression)")
    ap.add_argument("--per-metric", action="append", default=[],
                    metavar="METRIC=RATIO",
                    help="per-metric threshold override (repeatable)")
    ap.add_argument("--min-abs", action="append", default=[],
                    metavar="METRIC=VALUE",
                    help="absolute floor: fail when the current value of "
                         "METRIC drops below VALUE, baseline or not "
                         "(repeatable; for ratio metrics with a known "
                         "healthy constant, e.g. "
                         "hier_ab_cross_byte_reduction=2.85)")
    ap.add_argument("--require-metric", action="append", default=[],
                    help="fail unless the current run reports this metric")
    ap.add_argument("--allow-missing-baseline", action="store_true",
                    help="pass when no baseline is comparable (bootstrap)")
    ap.add_argument("--self-check", action="store_true",
                    help="verify the gate fails a synthetic 20%% regression "
                         "of the current run, then exit")
    ap.add_argument("--trend", action="store_true",
                    help="print one best/latest/ratio trajectory line per "
                         "metric across --baseline/--history records "
                         "(skipped-rc records excluded), then exit")
    args = ap.parse_args(argv)

    if args.trend:
        paths = list(args.baseline)
        for g in args.history:
            paths.extend(sorted(glob.glob(g)))
        if args.current and os.path.exists(args.current):
            paths.append(args.current)
        return run_trend(paths)
    if args.current is None:
        ap.error("--current is required (except with --trend)")
    if not os.path.exists(args.current):
        print(f"perf gate: ERROR — current file {args.current} not found",
              file=sys.stderr)
        return 2
    current = load_records(args.current)
    if args.self_check:
        return self_check(current, args.min_ratio)

    per_metric = {}
    for spec in args.per_metric:
        name, _, ratio = spec.partition("=")
        try:
            per_metric[name] = float(ratio)
        except ValueError:
            print(f"perf gate: ERROR — bad --per-metric {spec!r}",
                  file=sys.stderr)
            return 2
    floors = {}
    for spec in args.min_abs:
        name, _, val = spec.partition("=")
        try:
            floors[name] = float(val)
        except ValueError:
            print(f"perf gate: ERROR — bad --min-abs {spec!r}",
                  file=sys.stderr)
            return 2
    baselines: list[dict] = []
    paths = list(args.baseline)
    for g in args.history:
        paths.extend(sorted(glob.glob(g)))
    for p in paths:
        if os.path.exists(p):
            baselines.extend(load_records(p))
    return run_gate(current, baselines, args.min_ratio, per_metric,
                    args.allow_missing_baseline, args.require_metric,
                    floors=floors)


if __name__ == "__main__":
    sys.exit(main())
