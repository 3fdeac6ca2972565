#!/usr/bin/env bash
# CI entry point (the reference's .travis.yml test step, SURVEY.md §2.7):
# fast tier + one real launcher end-to-end, then the slow tier if SLOW=1.
#
#   ./ci.sh            # fast tests + launcher smoke (~4 min on a 1-core box)
#   SLOW=1 ./ci.sh     # everything (adds the re-tiered multi-process e2e set)
set -euo pipefail
cd "$(dirname "$0")"

echo "== wheel builds (packaging parity: reference setup.py/Dockerfile) =="
rm -rf build/ dist-ci/
python -m pip wheel . --no-deps --no-build-isolation -w dist-ci/ -q
ls dist-ci/horovod_tpu-*.whl
# The wheel must carry the native core sources so the lazy build works on
# hosts that install the wheel without the repo checkout.
python - <<'PY'
import glob, zipfile
whl = glob.glob("dist-ci/horovod_tpu-*.whl")[0]
names = zipfile.ZipFile(whl).namelist()
assert any(n.endswith("cc/Makefile") for n in names), names
assert any(n.endswith("src/engine.cc") for n in names), "native sources missing from wheel"
print("wheel contents ok:", whl)
PY
rm -rf dist-ci/ build/

echo "== native core builds and loads (regression guard for -lrt/shm_open) =="
make -C horovod_tpu/cc
python - <<'PY'
import ctypes, os
# A missing -lrt builds cleanly but dies at dlopen with "undefined symbol:
# shm_open" — load the library here so the link line can't silently regress.
lib = ctypes.CDLL(os.path.join("horovod_tpu", "cc", "libhvd_core.so"))
for sym in ("hvd_init", "hvd_pm_create", "hvd_pm_set_num_buckets",
            "hvd_compression"):
    assert hasattr(lib, sym), sym
print("native core loads ok (shm_open resolved)")
PY

echo "== conformance analyzer (ISSUE 11: protocol/knob/metric/lock parity across both engines; generated specs must regenerate byte-identically — hard fail on any unsuppressed finding) =="
timeout -k 10 120 python -m tools.analyze --check
git diff --exit-code -- docs/protocol_spec.json docs/config_registry.json \
  || { echo "generated spec files changed on disk — commit the --emit-spec output"; exit 1; }

echo "== sanitizer smoke (asan/ubsan/tsan builds of the native core; shm/ring-engine tests under ASan+UBSan with zero reports) =="
timeout -k 10 600 python tools/sanitize_smoke.py

echo "== trace smoke (2-proc with injected straggler: merged clock-aligned Perfetto trace, one trace ID across ranks, critical-path analyzer names rank+phase with >=80% attribution; perf-gate pass/fail fixtures) =="
timeout -k 10 180 env JAX_PLATFORMS=cpu python tools/trace_smoke.py

echo "== eager smoke (4-proc: steady-state cache hit rate >= 95%, ring data plane carrying the bytes, star==ring bitwise; bf16 wire >= 2x fewer bytes within tolerance; ISSUE 13 native-plane leg: native==python bitwise incl. sparse topk with method-labeled byte savings, native >= 1.3x python-plane MB/s gated below) =="
timeout -k 10 360 python tools/eager_smoke.py | tee /tmp/hvd_eager_smoke.log
python tools/perf_gate.py --current /tmp/hvd_eager_smoke.log \
  --require-metric eager_native_speedup \
  --min-abs eager_native_speedup=1.3
# live-fire: a synthetic 20% regression of today's own numbers must FAIL the gate
python tools/perf_gate.py --current /tmp/hvd_eager_smoke.log --self-check

echo "== hier smoke (simulated 2-host x 2-rank grid: two-level plane active, worst-rank cross-host bytes <= 0.35x flat, flat==hier==star bitwise incl. bf16, cache hit rate unchanged) =="
timeout -k 10 240 python tools/hier_smoke.py

echo "== sparse smoke (ISSUE 9: topk@1% cuts DCN bytes >= 10x on the 2-host grid, star==ring==hier bitwise with sparsification on, steady-state hit rate unchanged, adaptive policy picks ici=none/dcn=topk) =="
timeout -k 10 240 python tools/sparse_smoke.py

echo "== fsdp smoke (ISSUE 14 sharded data parallelism: 8-device mesh trains a model whose DP state exceeds the simulated per-rank budget; memory gauge >= 1.8x reduction at shard=2, loss parity with the DP control, wire bytes <= 1.1x DP allreduce, pad tail stays zero) =="
timeout -k 10 240 env JAX_PLATFORMS=cpu python tools/fsdp_smoke.py

echo "== tp smoke (ISSUE 19 sharded serving: model_shards=2 mesh replica group serves a model whose per-chip footprint exceeds the framed chip budget — the unsharded pool provably refuses to start, generations stay token-for-token oracle-exact under mixed load, and a SIGKILL'd sharded decode replica recovers with zero failed/diverged requests) =="
timeout -k 10 300 env JAX_PLATFORMS=cpu python tools/tp_smoke.py | tee /tmp/hvd_tp_smoke.log
python tools/perf_gate.py --current /tmp/hvd_tp_smoke.log \
  --require-metric tp_smoke_memory_reduction \
  --min-abs tp_smoke_memory_reduction=1.8

echo "== metrics smoke (2-proc train, stall check + exposition; snapshot vs docs/metrics_schema.json, timeline JSON shape) =="
timeout -k 10 180 env JAX_PLATFORMS=cpu python tools/metrics_smoke.py

echo "== elastic smoke (3-proc train, kill one worker at step 5: survivors resume from last commit, dead slot blacklisted, resets in pod metrics) =="
timeout -k 10 180 env JAX_PLATFORMS=cpu python tools/elastic_smoke.py

echo "== chaos smoke (ISSUE 8 escalation ladder: injected delay absorbed by retries, link reset demotes ring->star bitwise-identically with 0 elastic resets then re-promotes, corrupt/drop frames rejected, killed rank escalates to exactly 1 elastic reset) =="
timeout -k 10 300 env JAX_PLATFORMS=cpu python tools/chaos_smoke.py

echo "== serve smoke (ISSUE 10 serving vertical: 2-replica continuous batching coalesces (mean batch > 1), p99 under the smoke SLO with zero sheds at nominal load, schema-valid /stats, raw-training-checkpoint refusal, replica kill mid-load recovers with zero failed client requests) =="
timeout -k 10 300 env JAX_PLATFORMS=cpu python tools/serve_smoke.py | tee /tmp/hvd_serve_smoke.log
python tools/perf_gate.py --current /tmp/hvd_serve_smoke.log \
  --require-metric serve_smoke_throughput_rps \
  --min-abs serve_smoke_throughput_rps=25

echo "== llm smoke (ISSUE 12 token-level serving + ISSUE 20 decode path: 1-prefill + 1-decode topology, every generation oracle-exact (zero cross-request contamination), mean decode-batch occupancy > 1 under mixed-length load, TTFT p99 under the smoke SLO, decode-replica SIGKILL recovers via re-prefill requeue with zero failed client requests; ISSUE 20 legs: speculative A/B paired-window engine decode throughput >= 1.3x with acceptance >= 0.5, radix prefix replay hit rate >= 0.5 with >= 1 block recovered under pool pressure and every shared-prefix response oracle-exact, chunked streams reassemble to the exact non-streaming body with first chunk inside the TTFT SLO) =="
timeout -k 10 420 env JAX_PLATFORMS=cpu python tools/llm_smoke.py | tee /tmp/hvd_llm_smoke.log
python tools/perf_gate.py --current /tmp/hvd_llm_smoke.log \
  --require-metric llm_smoke_decode_tokens_per_s \
  --require-metric llm_smoke_spec_acceptance \
  --require-metric llm_smoke_spec_speedup_x \
  --require-metric llm_smoke_prefix_hit_rate \
  --require-metric llm_smoke_stream_tpot_headroom_x \
  --min-abs llm_smoke_decode_tokens_per_s=150 \
  --min-abs llm_smoke_spec_acceptance=0.5 \
  --min-abs llm_smoke_spec_speedup_x=1.3 \
  --min-abs llm_smoke_prefix_hit_rate=0.5 \
  --min-abs llm_smoke_stream_tpot_headroom_x=1.0

echo "== obs smoke (ISSUE 15 observability: injected decode slowdown fires the ttft_slo anomaly + flight dump; SIGKILL'd decode replica's mmap flight ring survives; one-command bundle names the dead replica, merges a strict mixed-plane trace, and a /v1/generate request is followable admit->queue->prefill->handoff->decode->retire with TTFT decomposed by phase) =="
timeout -k 10 300 env JAX_PLATFORMS=cpu python tools/obs_smoke.py

echo "== pod obs smoke (ISSUE 17 telemetry tree: 8-host x 8-rank grid through per-host leaders — O(hosts) root connections, host-then-root merge bitwise == flat, composed rank->leader->root clock offsets, one rank SIGKILL'd mid-run: one-command bundle through the leaders names the dead rank's host coverage gap and an unreachable leader, the dead ring decode is in the bundle, silent host fires telemetry_lag naming it) =="
timeout -k 10 300 env JAX_PLATFORMS=cpu python tools/pod_obs_smoke.py | tee /tmp/hvd_pod_obs_smoke.log
python tools/perf_gate.py --current /tmp/hvd_pod_obs_smoke.log \
  --require-metric pod_obs_root_byte_reduction \
  --min-abs pod_obs_root_byte_reduction=6

echo "== controller smoke (ISSUE 16 self-driving performance: 4-proc DCN bandwidth-collapse goes sparse via a canaried knob epoch within 20 steps and recovers full width bitwise-identically; decode-slowdown collapse fires drain_collapse, the committed target_queue cut scales the decode pool out and goodput recovers with zero failed requests; a healthy plane sees zero firings and zero proposals) =="
timeout -k 10 300 env JAX_PLATFORMS=cpu python tools/controller_smoke.py | tee /tmp/hvd_controller_smoke.log
python tools/perf_gate.py --current /tmp/hvd_controller_smoke.log \
  --require-metric controller_smoke_recovery_ratio \
  --min-abs controller_smoke_recovery_ratio=1.3

echo "== ctrl smoke (ISSUE 18 control tree + async checkpoints: 8-host x 8-rank grid rendezvous through per-host control leaders with O(hosts) root connections, one rank SIGKILL'd AND one leader killed mid-run folded into exactly one elastic reset, survivors resume from the background async commit, the joiner host cold-starts by streaming the committed checkpoint bitwise-identically from a surviving leader, root control bytes gated >= 6x under flat replay) =="
timeout -k 10 300 env JAX_PLATFORMS=cpu python tools/ctrl_smoke.py | tee /tmp/hvd_ctrl_smoke.log
python tools/perf_gate.py --current /tmp/hvd_ctrl_smoke.log \
  --require-metric ctrl_smoke_root_byte_reduction \
  --min-abs ctrl_smoke_root_byte_reduction=6

echo "== fast tier (includes the launcher e2e: test_run_happy_path) =="
python -m pytest tests/ -m fast -q

if [[ "${SLOW:-0}" == "1" ]]; then
  echo "== slow tier =="
  python -m pytest tests/ -m slow -q
fi
echo "CI OK"
