#!/usr/bin/env python
"""CI smoke for the hierarchical fabric-aware eager plane (ISSUE 7, wired
into ci.sh).

Spawns 4-process Python-engine worlds laid out as a simulated 2-host x
2-rank grid (blocked coordinates, exactly what the launcher assigns) and
asserts the two-level contract end to end:

1. plane selection: HOROVOD_HIERARCHICAL_ALLREDUCE=1 on the grid activates
   the two-level plane on EVERY rank; off keeps the flat PR-4 ring; the
   coordinator relays zero tensor bytes either way;
2. cross-host bytes: the two-level plane's worst-rank cross-host bytes are
   <= 0.35x the flat ring's (measured ~1/3 on 2x2: 2*(B/L)*(C-1)/C against
   the flat boundary rank's 2*B*(N-1)/N);
3. bitwise identity: flat == hier == star, uncompressed AND under bf16
   wire compression. Payloads are integer-valued floats, so every
   accumulation order is exact (f64/f32/bf16 alike) and any hash mismatch
   is a real schedule/routing bug (misdirected chunk, wrong offset, bad
   scaling) — for free-form payloads the planes are additionally pinned to
   the shared grid oracle inside tests/test_hierarchical_plane.py;
4. steady state unchanged: the hier world's post-warmup cache hit rate
   stays >= 95% with zero full request lists — the response-cache fast
   path is plane-agnostic.

Exits non-zero with a reason on any violation. Wall-clock budget: ~40 s.
"""

from __future__ import annotations

import json
import os
import secrets
import socket
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

WORLD = 4
LOCAL_SIZE = 2
WARMUP_STEPS = 2
STEPS = 20
TENSORS = 6

WORKER = r"""
import hashlib, json, os, sys
sys.path.insert(0, os.environ["HVD_REPO"])
import numpy as np
from horovod_tpu.common.config import Config
from horovod_tpu.common.engine import PyEngine
from horovod_tpu.common.topology import Topology
from horovod_tpu import metrics as hvd_metrics

rank = int(os.environ["HOROVOD_RANK"]); world = int(os.environ["HOROVOD_SIZE"])
L = int(os.environ["SMOKE_LOCAL_SIZE"])
warmup = int(os.environ["SMOKE_WARMUP"]); steps = int(os.environ["SMOKE_STEPS"])
tensors = int(os.environ["SMOKE_TENSORS"])
hier = os.environ.get("SMOKE_HIER", "0") == "1"
topo = Topology(rank, world, rank % L, L, rank // L, world // L)
eng = PyEngine(topo, Config(cycle_time_ms=1.0, stall_check_disable=True,
                            hierarchical_allreduce=hier))
try:
    digest = hashlib.sha256()

    def step(i):
        for t in range(tensors):
            # Integer-valued floats with partial sums <= 4*(15+rank+i+t)
            # < 256 — inside bf16's exact-integer range (8-bit mantissa),
            # and the world-of-4 average divides by a power of two: every
            # reduction order, compressed or not, yields the identical
            # bits, so the cross-plane hash comparison is exact by
            # construction and any mismatch is a schedule/routing bug.
            x = ((np.arange(32 << 10, dtype=np.float32) % 16)
                 + rank + i + t)
            out = eng.run("allreduce", x, f"grad.{t}")
            digest.update(out.tobytes())

    for i in range(warmup):
        step(i)
    reg = hvd_metrics.registry()
    snap0 = reg.snapshot()["counters"]
    for i in range(warmup, steps):
        step(i)
    snap1 = reg.snapshot()["counters"]

    def delta(series):
        return snap1.get(series, 0) - snap0.get(series, 0)

    stats = eng.cache_stats()
    print(json.dumps({
        "rank": rank,
        "hash": digest.hexdigest(),
        "plane": stats["plane"],
        "compression": stats.get("compression", "none"),
        "window_hits": delta("horovod_engine_cache_hits_total"),
        "window_misses": delta("horovod_engine_cache_misses_total"),
        "window_full_requests": delta("horovod_engine_full_requests_total"),
        "star_bytes": snap1.get(
            'horovod_engine_data_bytes_total{plane="star"}', 0),
        "tier_local": snap1.get(
            'horovod_wire_bytes_total{tier="local"}', 0),
        "tier_cross": snap1.get(
            'horovod_wire_bytes_total{tier="cross"}', 0),
    }), flush=True)
finally:
    eng.shutdown()
"""


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def fail(msg: str) -> None:
    print(f"hier smoke FAILED: {msg}", file=sys.stderr)
    sys.exit(1)


def run_world(hier: bool, ring: bool = True,
              compression: str = "none") -> list[dict]:
    port = free_port()
    secret = secrets.token_hex(16)
    procs = []
    for rank in range(WORLD):
        env = dict(os.environ)
        env.update({
            "HVD_REPO": REPO,
            "HOROVOD_RANK": str(rank), "HOROVOD_SIZE": str(WORLD),
            "HOROVOD_COORD_ADDR": f"127.0.0.1:{port}",
            "HOROVOD_SECRET": secret,
            "HOROVOD_ENGINE": "python",
            "HOROVOD_RING_DATA_PLANE": "1" if ring else "0",
            "HOROVOD_COMPRESSION": compression,
            "SMOKE_HIER": "1" if hier else "0",
            "SMOKE_LOCAL_SIZE": str(LOCAL_SIZE),
            "SMOKE_WARMUP": str(WARMUP_STEPS),
            "SMOKE_STEPS": str(STEPS),
            "SMOKE_TENSORS": str(TENSORS),
        })
        procs.append(subprocess.Popen(
            [sys.executable, "-c", WORKER], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
    outs = []
    try:
        for p in procs:
            stdout, stderr = p.communicate(timeout=120)
            if p.returncode != 0:
                fail(f"worker rc={p.returncode}:\n{stderr[-2000:]}")
            outs.append(json.loads(stdout.strip().splitlines()[-1]))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.communicate()
    return outs


def main() -> int:
    flat = run_world(hier=False)
    hier = run_world(hier=True)

    # 1. plane selection + zero coordinator relay bytes
    if any(r["plane"] != "ring" for r in flat):
        fail(f"flat world planes {[r['plane'] for r in flat]} (want ring)")
    if any(r["plane"] != "hier" for r in hier):
        fail(f"hier world planes {[r['plane'] for r in hier]} "
             "(want hier on every rank: all-or-nothing barrier)")
    for r in flat + hier:
        if r["star_bytes"] != 0:
            fail(f"rank {r['rank']} ({r['plane']}): coordinator relayed "
                 f"{r['star_bytes']} tensor bytes (want 0)")

    # 2. the cross-byte cut: worst-rank cross-host
    #    bytes <= 0.35x flat (measured ~1/3 on the 2x2 grid).
    flat_cross = max(r["tier_cross"] for r in flat)
    hier_cross = max(r["tier_cross"] for r in hier)
    if flat_cross <= 0:
        fail("flat grid world recorded no cross-host bytes "
             "(tier accounting broken)")
    ratio = hier_cross / flat_cross
    if ratio > 0.35:
        fail(f"hier worst-rank cross bytes {hier_cross} vs flat "
             f"{flat_cross}: ratio {ratio:.3f} > 0.35 — the ladder is not "
             "cutting DCN traffic")
    if min(r["tier_local"] for r in hier) <= 0:
        fail("hier world recorded no intra-host bytes")

    # 3. bitwise identity across planes (exact-arithmetic payloads)
    if len({r["hash"] for r in flat}) != 1:
        fail("flat-plane results differ across ranks")
    if len({r["hash"] for r in hier}) != 1:
        fail("hier-plane results differ across ranks")
    if flat[0]["hash"] != hier[0]["hash"]:
        fail("flat and hier planes disagree bitwise")
    star = run_world(hier=False, ring=False)
    if {r["hash"] for r in star} != {hier[0]["hash"]}:
        fail("star and hier planes disagree bitwise")
    comp_hier = run_world(hier=True, compression="bf16")
    comp_flat = run_world(hier=False, compression="bf16")
    if len({r["hash"] for r in comp_hier}) != 1:
        fail("bf16 hier results differ across ranks")
    if comp_hier[0]["hash"] != comp_flat[0]["hash"]:
        fail("bf16 flat and hier planes disagree bitwise")
    comp_cross = max(r["tier_cross"] for r in comp_hier)
    if comp_cross >= hier_cross:
        fail(f"bf16 hier cross bytes {comp_cross} not below uncompressed "
             f"{hier_cross} — the 16-bit wire is not reaching the cross "
             "fabric")

    # 4. steady state unchanged: the plane swap must not disturb the
    #    response-cache fast path.
    for r in hier:
        window = r["window_hits"] + r["window_misses"]
        rate = r["window_hits"] / max(window, 1)
        if rate < 0.95:
            fail(f"rank {r['rank']}: hier-world post-warmup hit rate "
                 f"{rate:.2%} < 95%")
        if r["window_full_requests"] != 0:
            fail(f"rank {r['rank']}: {r['window_full_requests']} full "
                 "request lists in the hier steady-state window (want 0)")

    print(f"hier smoke OK: cross bytes/rank {hier_cross} vs flat "
          f"{flat_cross} (ratio {ratio:.3f} <= 0.35), flat==hier==star "
          f"bitwise, bf16 flat==hier bitwise (cross {comp_cross}), "
          f"hit rate {hier[0]['window_hits']}"
          f"/{hier[0]['window_hits'] + hier[0]['window_misses']}, "
          "star relay bytes 0")
    return 0


if __name__ == "__main__":
    sys.exit(main())
