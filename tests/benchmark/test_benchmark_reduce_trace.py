"""``benchmarks/reduce_trace.py`` on hand-built interval lists, on a synthetic
TPU-shaped trace built from a text proto with known answers, and on a small
``.xplane.pb`` recorded on the virtual CPU mesh (``fixtures/``)."""

from __future__ import annotations

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmarks import reduce_trace as rt  # noqa: E402

# Event names as the v5e trace gives them (copied from a chip trace).
FUSION = ('%fusion.692 = bf16[16,1024,3072]{2,1,0:T(8,128)(2,1)} fusion(f32[1024]'
          '{0:T(1024)} %params.1, f32[16,1024]{1,0:T(8,128)S(1)} %fusion.1194), '
          'kind=kOutput, calls=%fused_computation.1147')
TUPLE_FUSION = ('%fusion.111 = (f32[1024,32000]{1,0:T(8,128)}, f32[]{:T(128)S(6)}) '
                'fusion(f32[16,1024]{1,0:T(8,128)S(1)} %x), kind=kLoop')
KERNEL = ('%block_0.3 = (bf16[128,1024,128]{2,1,0:T(8,128)(2,1)S(1)}, f32[128,8,1024]'
          '{2,1,0:T(8,128)}) custom-call(bf16[128,1024,128]{2,1,0:T(8,128)(2,1)S(1)} '
          '%bitcast.1544), custom_call_target="tpu_custom_call", '
          'operand_layout_constraints={bf16[128,1024,128]{2,1,0}}')
CONCAT = ('%custom-call.286 = f32[1024,3072]{1,0:T(8,128)S(1)} custom-call(f32[256,3072]'
          '{1,0:T(8,128)S(1)} %slice-done.920), custom_call_target="ConcatBitcast"')
ALL_REDUCE = ('%psum.38 = f32[32768000]{0:T(1024)} all-reduce(f32[32768000]{0:T(1024)} '
              '%reshape.315), channel_id=1, replica_groups={{0,1,2,3}}')
AR_START = ('%all-reduce-start.2 = f32[25557032]{0:T(1024)} all-reduce-start(f32[25557032]'
            '{0:T(1024)} %fusion.9), channel_id=1')
AR_DONE = ('%all-reduce-done.2 = f32[25557032]{0:T(1024)} all-reduce-done(f32[25557032]'
           '{0:T(1024)} %all-reduce-start.2)')
COPY_START = ('%copy-start.115 = (f32[4096,1024]{1,0:T(8,128)S(1)}, f32[4096,1024]'
              '{1,0:T(8,128)}, u32[]{:S(2)}) copy-start(f32[4096,1024]{1,0:T(8,128)} %p)')


def test_union_does_not_count_overlapping_ops_twice():
    # The case utils/roofline gets wrong: it sums durations (7), not the union.
    ops = [(0, 4), (2, 5)]
    assert rt.length(rt.union(ops)) == 5
    assert rt.union([(5, 6), (0, 1), (1, 2), (8, 9), (8.5, 8.7)]) == \
        [(0, 2), (5, 6), (8, 9)]
    assert rt.union([(3, 3), (4, 2)]) == []


def test_covered_subtract_and_clip():
    merged = [(0, 2), (5, 6), (8, 9)]
    assert rt.covered((1, 8.5), merged) == 1 + 1 + 0.5
    assert rt.covered((2, 5), merged) == 0
    assert rt.subtract([(0, 10)], merged) == [(2, 5), (6, 8), (9, 10)]
    assert rt.subtract([(1, 3), (5.5, 8.5)], merged) == [(2, 3), (6, 8)]
    assert rt.subtract(merged, [(0, 10)]) == []
    assert rt.clip([(0, 4), (6, 9), (10, 12)], 3, 7) == [(3, 4), (6, 7)]


@pytest.mark.parametrize("text, name, opcode, kind", [
    (FUSION, "fusion.692", "fusion", "compute"),
    (TUPLE_FUSION, "fusion.111", "fusion", "compute"),
    (KERNEL, "block_0.3", "custom-call", "kernel"),
    (CONCAT, "custom-call.286", "custom-call", "compute"),
    (ALL_REDUCE, "psum.38", "all-reduce", "collective"),    # by opcode, not by name
    (AR_START, "all-reduce-start.2", "all-reduce-start", "collective"),
    (AR_DONE, "all-reduce-done.2", "all-reduce-done", "collective"),
    (COPY_START, "copy-start.115", "copy-start", "compute"),
    ("  ROOT %tuple.5 = (f32[2]{0}) tuple(f32[2]{0} %a)", "tuple.5", "tuple", "compute"),
    ("bench_dispatch", "bench_dispatch", None, "compute"),
])
def test_instruction_name_opcode_and_kind(text, name, opcode, kind):
    assert rt.parse_instruction(text) == (name, opcode)
    assert rt.op_kind(text, opcode) == kind


def test_scopes_from_the_compiled_modules_text():
    hlo = "\n".join([
        "ENTRY %main.47 (p: f32[8]) -> f32[8] {",
        '  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fc.1, metadata={op_name='
        '"jit(train_step)/shard_map/bench_fwd_bwd/transpose(jvp(Block_0))/dot_general" stack_frame_id=9}',
        '  %psum.38 = f32[8]{0} all-reduce(%fusion.1), channel_id=1, to_apply=%r, metadata={op_name='
        '"jit(train_step)/shard_map/bench_optimizer/hvd_fused_allreduce_k1/psum"}, backend_config={"a":{"b":1}}',
        "  %copy.2 = f32[8]{0} copy(%psum.38)",
        '  ROOT %fusion.3 = f32[8]{0} fusion(%copy.2), kind=kLoop, calls=%fc.2, metadata={op_name="jit(train_step)/shard_map/psum"}',
        "}"])
    scopes = rt.scopes_from_hlo(hlo)
    assert scopes["fusion.1"][0] == "bench_fwd_bwd"
    assert scopes["psum.38"][0] == "bench_optimizer"
    assert scopes["fusion.3"] == ("", "jit(train_step)/shard_map/psum")
    assert "copy.2" not in scopes       # no metadata: attributed to no scope


MS = 1_000_000_000      # picoseconds in a millisecond


def _plane(plane_id, name, lines):
    """``lines``: {line name: [(event name, start ms, length ms), ...]}."""
    names = sorted({n for events in lines.values() for n, _, _ in events})
    ids = {n: i + 1 for i, n in enumerate(names)}
    out = [f'planes {{ id: {plane_id} name: "{name}"']
    for n, i in ids.items():
        escaped = n.replace("\\", "\\\\").replace('"', '\\"')
        out.append(f'  event_metadata {{ key: {i} value {{ id: {i} name: "{escaped}" }} }}')
    for k, (line, events) in enumerate(lines.items()):
        out.append(f'  lines {{ id: {k + 1} name: "{line}" timestamp_ns: 0')
        for n, start, dur in events:
            out.append(f"    events {{ metadata_id: {ids[n]} offset_ps: {int(start * MS)} "
                       f"duration_ps: {int(dur * MS)} }}")
        out.append("  }")
    out.append("}")
    return "\n".join(out)


@pytest.fixture()
def synthetic_trace(tmp_path):
    """Two steps on two chips, every answer known by hand (times in ms).
    Chip 0: fwd op 0-4 and an overlapping fwd op 2-5; kernel 5-6; a synchronous
    all-reduce 6-8 with an optimizer op 7-7.5 under it; an asynchronous
    all-reduce 8-10 (its -start and -done halves on "XLA Ops" too) with an
    optimizer op 8.5-9.5 under it; then idle 10-12 while the host is in the
    fence. Chip 1 runs the same but its kernel lasts until 6.5. A copy in
    flight on the async line and ops outside the window must not count."""
    from jax.profiler import ProfileData

    def chip(kernel_end):
        return {
            "XLA Ops": [
                ("%before.1 = f32[8]{0} fusion(%p), kind=kLoop", -3.0, 1.0),
                (FUSION, 0.0, 4.0), (TUPLE_FUSION, 2.0, 3.0),
                (KERNEL, 5.0, kernel_end - 5.0),
                (ALL_REDUCE, 6.0, 2.0),
                ("%fusion.7 = f32[8]{0} fusion(%p), kind=kLoop", 7.0, 0.5),
                (AR_START, 8.0, 0.1), (AR_DONE, 9.9, 0.1),
                ("%fusion.8 = f32[8]{0} fusion(%p), kind=kLoop", 8.5, 1.0),
            ],
            "Async XLA Ops": [(AR_START, 8.0, 2.0), (COPY_START, 0.0, 12.0)],
            "Steps": [("0", 0.0, 12.0)],
        }

    host = {"python": [("bench_dispatch", 0.0, 0.2), ("bench_dispatch", 0.3, 0.2),
                       ("bench_fence", 0.6, 11.4), ("other", 0.0, 12.0)]}
    text = "\n".join([_plane(1, "/device:TPU:0", chip(6.0)),
                      _plane(2, "/device:TPU:1", chip(6.5)),
                      _plane(3, "/host:CPU", host)])
    path = tmp_path / "synthetic.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    scope_of = {"fusion.692": ("bench_fwd_bwd", "a/bench_fwd_bwd/block_3/dot_general"),
                "fusion.111": ("bench_fwd_bwd", "a/bench_fwd_bwd/block_4/dot_general"),
                "block_0.3": ("bench_fwd_bwd", "a/bench_fwd_bwd/pallas_call"),
                "fusion.7": ("bench_optimizer", "a/bench_optimizer/mul"),
                "fusion.8": ("bench_optimizer", "a/bench_optimizer/add")}
    return rt.reduce(rt.load(str(path)), steps=2, scope_of=scope_of)


def test_synthetic_trace_window_busy_and_idle(synthetic_trace):
    r = synthetic_trace
    assert r["window_s"] == pytest.approx(12e-3)        # dispatch start to fence end
    assert r["first"] == "/device:TPU:0" and r["slowest"] == "/device:TPU:0"
    first = r["devices"]["/device:TPU:0"]
    # union 0-5, 5-6, 6-8, 8-10 = 10 ms; summing durations would give 14.7
    assert first["busy_s"] == pytest.approx(10e-3)
    assert r["busy_s"] == pytest.approx(10e-3)          # mean over the chips
    idle_share = 1 - r["busy_s"] / r["window_s"]
    assert idle_share == pytest.approx(2 / 12)


def test_synthetic_trace_collectives_kernels_and_scopes(synthetic_trace):
    first = synthetic_trace["devices"]["/device:TPU:0"]
    # one synchronous (2 ms) + one asynchronous span (2 ms); the async pair's
    # halves on "XLA Ops" are not counted again
    assert first["collective_s"] == pytest.approx(4e-3)
    # exposed: 6-7 and 7.5-8 of the first, 8-8.5 and 9.5-10 of the second
    assert first["collective_exposed_s"] == pytest.approx(2.5e-3)
    assert first["kernel_s"] == pytest.approx(1e-3)
    assert synthetic_trace["devices"]["/device:TPU:1"]["kernel_s"] == pytest.approx(1.5e-3)
    assert first["compute_s"]["bench_fwd_bwd"] == pytest.approx(7e-3)   # summed device time
    assert first["compute_s"]["bench_optimizer"] == pytest.approx(1.5e-3)
    # per-step division is the readers': two steps in this window
    assert first["kernel_s"] / synthetic_trace["steps"] == pytest.approx(0.5e-3)


def test_synthetic_trace_breakdown(synthetic_trace):
    b = synthetic_trace["breakdown"]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    top = dict(map(tuple, b["device_ops"]))
    # the same op of two layers is one entry, numbers taken out of its name
    assert top["fusion [bench_fwd_bwd] bench_fwd_bwd/block_N/dot_general"] == \
        pytest.approx(7e-3)
    assert any(k.startswith("collective") for k in top)
    assert any(k.startswith("kernel [bench_fwd_bwd]") for k in top)
    assert b["idle_gaps"] == [["host in fence (loss read)", pytest.approx(2e-3)]]


def test_recorded_cpu_trace_has_annotations_but_no_device():
    """The recorded fixture: two fence groups of two steps on the 4-device
    virtual CPU mesh. The loader finds the benchmark's annotations; a CPU
    trace has no device plane, so the reduction returns nothing and every
    reader's metric is left out - never a CPU number under a device name."""
    trace = rt.load(os.path.join(HERE, "fixtures", "cpu_mesh.xplane.pb"))
    assert len(trace["host"][rt.DISPATCH]) == 4
    assert len(trace["host"][rt.FENCE]) == 2
    assert trace["host"][rt.DISPATCH] == sorted(trace["host"][rt.DISPATCH])
    assert trace["devices"] == {}
    assert rt.reduce(trace, steps=4) is None
    assert rt.find_xplane(os.path.join(HERE, "fixtures")).endswith("cpu_mesh.xplane.pb")
