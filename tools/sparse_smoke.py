#!/usr/bin/env python
"""CI smoke for sparse top-k gradient compression and the adaptive
per-tier policy (ISSUE 9, wired into ci.sh).

Spawns 4-process Python-engine worlds laid out as a simulated 2-host x
2-rank grid (the hier_smoke topology) and asserts the sparse-wire contract
end to end:

1. DCN byte cut: with HOROVOD_COMPRESSION=topk at HOROVOD_TOPK_RATIO=0.01
   the two-level plane's worst-rank cross-host (DCN) wire bytes drop
   >= 10x vs the dense hier world;
2. bitwise identity with sparsification ON: star == flat ring == hier.
   Payloads are integer-valued floats with partial sums inside f32's
   exact-integer range, so every fold order is exact and any hash
   mismatch is a real select/merge/routing bug (free-form payloads are
   additionally pinned to the canonical oracles in
   tests/test_compression.py);
3. steady state unchanged: the topk world's post-warmup response-cache
   hit rate stays >= 95% with zero full request lists — sparse frames
   ride the same negotiation fast path;
4. adaptive policy (common/policy.py): HOROVOD_COMPRESSION=adaptive on
   the grid demonstrably picks DIFFERENT formats per fabric tier — the
   policy table says ici=none / dcn=topk, the cross tier shows the sparse
   cut while the local tier still moves dense-order bytes.

Exits non-zero with a reason on any violation. Wall-clock budget: ~45 s.
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
STEPS = 12
TENSORS = 4
ELEMS = 32 << 10  # 128 KiB f32 >= HOROVOD_TOPK_MIN_BYTES: adaptive picks topk

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
tensors = int(os.environ["SMOKE_TENSORS"]); n = int(os.environ["SMOKE_ELEMS"])
hier = os.environ.get("SMOKE_HIER", "0") == "1"
topo = Topology(rank, world, rank % L, L, rank // L, world // L)
eng = PyEngine(topo, Config(cycle_time_ms=1.0, stall_check_disable=True,
                            hierarchical_allreduce=hier))
try:
    digest = hashlib.sha256()

    def step(i):
        for t in range(tensors):
            # Integer-valued floats, ranking shared across ranks (the
            # multiplicative (rank+1) scale preserves magnitude order), so
            # the top-1% supports coincide, every partial sum stays inside
            # f32's exact-integer range even as the error-feedback
            # residuals accumulate over `steps`, and the world-of-4
            # average divides by a power of two: all planes and encodings
            # produce the identical bits by construction.
            x = ((np.arange(n, dtype=np.float32) % 97 + 1)
                 * np.float32(rank + 1))
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
        "policy": stats.get("policy"),
        "window_hits": delta("horovod_engine_cache_hits_total"),
        "window_misses": delta("horovod_engine_cache_misses_total"),
        "window_full_requests": delta("horovod_engine_full_requests_total"),
        "star_bytes": snap1.get(
            'horovod_engine_data_bytes_total{plane="star"}', 0),
        "tier_local": snap1.get(
            'horovod_wire_bytes_total{tier="local"}', 0),
        "tier_cross": snap1.get(
            'horovod_wire_bytes_total{tier="cross"}', 0),
        "saved_topk": snap1.get(
            'horovod_wire_bytes_saved_total{method="topk"}', 0),
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
    print(f"sparse smoke FAILED: {msg}", file=sys.stderr)
    sys.exit(1)


def run_world(compression: str, hier: bool = True,
              ring: bool = True) -> list[dict]:
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
            "HOROVOD_TOPK_RATIO": "0.01",
            "SMOKE_HIER": "1" if hier else "0",
            "SMOKE_LOCAL_SIZE": str(LOCAL_SIZE),
            "SMOKE_WARMUP": str(WARMUP_STEPS),
            "SMOKE_STEPS": str(STEPS),
            "SMOKE_TENSORS": str(TENSORS),
            "SMOKE_ELEMS": str(ELEMS),
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
    dense = run_world("none")
    topk = run_world("topk")

    # 1. the >= 10x DCN byte cut at topk@1%
    if any(r["plane"] != "hier" for r in dense + topk):
        fail(f"expected hier plane everywhere, got "
             f"{[r['plane'] for r in dense + topk]}")
    dense_cross = max(r["tier_cross"] for r in dense)
    topk_cross = max(r["tier_cross"] for r in topk)
    if dense_cross <= 0:
        fail("dense world recorded no cross-host bytes")
    if topk_cross <= 0:
        fail("topk world recorded no cross-host bytes")
    reduction = dense_cross / topk_cross
    if reduction < 10.0:
        fail(f"topk@1% cross-host bytes {topk_cross} vs dense {dense_cross}: "
             f"{reduction:.1f}x < 10x — the sparse wire is not reaching DCN")
    if min(r["saved_topk"] for r in topk) <= 0:
        fail("horovod_wire_bytes_saved_total{method=topk} not counting")

    # 2. star == flat ring == hier bitwise with sparsification on
    if len({r["hash"] for r in topk}) != 1:
        fail("topk hier results differ across ranks")
    flat = run_world("topk", hier=False)
    star = run_world("topk", hier=False, ring=False)
    if any(r["plane"] != "ring" for r in flat):
        fail("flat topk world did not activate the flat ring")
    if any(r["plane"] != "star" for r in star):
        fail("star topk world activated a peer plane")
    if {r["hash"] for r in flat} != {topk[0]["hash"]}:
        fail("topk flat ring and hier planes disagree bitwise")
    if {r["hash"] for r in star} != {topk[0]["hash"]}:
        fail("topk star and hier planes disagree bitwise")
    if topk[0]["hash"] == dense[0]["hash"]:
        fail("topk world produced the dense hash (sparsification inert)")

    # 3. steady state unchanged under sparsification
    for r in topk:
        window = r["window_hits"] + r["window_misses"]
        rate = r["window_hits"] / max(window, 1)
        if rate < 0.95:
            fail(f"rank {r['rank']}: topk post-warmup hit rate {rate:.2%} "
                 "< 95%")
        if r["window_full_requests"] != 0:
            fail(f"rank {r['rank']}: {r['window_full_requests']} full "
                 "requests in the topk steady-state window (want 0)")

    # 4. adaptive policy picks different formats per tier
    adaptive = run_world("adaptive")
    pol = adaptive[0]["policy"] or {}
    if pol.get("ici") == pol.get("dcn"):
        fail(f"adaptive policy table did not split by tier: {pol}")
    if pol.get("dcn") != "topk" or pol.get("ici") != "none":
        fail(f"adaptive table expected ici=none/dcn=topk for the big "
             f"gradient, got {pol}")
    ad_cross = max(r["tier_cross"] for r in adaptive)
    ad_local = max(r["tier_local"] for r in adaptive)
    dense_local = max(r["tier_local"] for r in dense)
    ad_red = dense_cross / max(ad_cross, 1)
    if ad_red < 10.0:
        fail(f"adaptive cross bytes {ad_cross} vs dense {dense_cross}: "
             f"{ad_red:.1f}x < 10x — the policy is not sparsifying DCN")
    if ad_local < dense_local / 3:
        fail(f"adaptive local bytes {ad_local} vs dense {dense_local}: the "
             "local tier should stay near dense width (full-width-on-ICI)")
    if {r["hash"] for r in adaptive} != {topk[0]["hash"]}:
        # Same value-changing format (topk on every tensor >= the floor)
        # on these payloads, different hop framings only -> same bits.
        fail("adaptive world diverged bitwise from the explicit-topk world")

    print(f"sparse smoke OK: topk@1% cross bytes {topk_cross} vs dense "
          f"{dense_cross} ({reduction:.1f}x cut), star==ring==hier bitwise, "
          f"hit rate {topk[0]['window_hits']}"
          f"/{topk[0]['window_hits'] + topk[0]['window_misses']}, "
          f"adaptive ici={pol.get('ici')}/dcn={pol.get('dcn')} "
          f"(cross {ad_red:.1f}x cut, local {ad_local:.0f}B ~ dense "
          f"{dense_local:.0f}B)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
