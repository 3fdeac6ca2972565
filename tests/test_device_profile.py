"""metrics/device_profile.py: the partition of a device's busy time by the
program's names on hand-made events, the loader on a CPU trace (no device
plane) and on a recorded v5e trace of a tiny program (``tests/fixtures/
device_profile/``: made by ISSUE 34's first chip run; three steps of a jitted
function with ``hvd_mla_proj``, a ``lax.scan`` under ``hvd_ssd_scan``, a Pallas
kernel named ``hvd_moe_experts_gmm`` inside ``hvd_moe_experts`` and a sine
under ``hvd_flash_fwd``), ``measure_overlap`` over the new loader, and the
benchmark's readers of the table."""

from __future__ import annotations

import os
import shutil
import subprocess
import sys

import pytest

from horovod_tpu.common import device_names
from horovod_tpu.metrics import device_profile as dp
from horovod_tpu.metrics.device_profile import Op

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CPU_TRACE = os.path.join(REPO, "tests", "benchmark", "fixtures",
                         "cpu_mesh.xplane.pb")
TPU_TRACE = os.path.join(REPO, "tests", "fixtures", "device_profile",
                         "v5e_tiny.xplane.pb")
TPU_HLO = os.path.join(REPO, "tests", "fixtures", "device_profile",
                       "v5e_tiny_hlo.txt")
NEW_READERS = [
    "flash_fwd_ms_per_step", "flash_bwd_dq_ms_per_step",
    "flash_bwd_dkv_ms_per_step", "ssd_scan_ms_per_step",
    "mamba_proj_ms_per_step", "mamba_conv_ms_per_step",
    "mamba_gate_norm_ms_per_step", "moe_route_ms_per_step",
    "moe_dispatch_ms_per_step", "moe_combine_ms_per_step",
    "moe_grouped_ms_per_step", "moe_shared_ms_per_step",
    "mla_proj_ms_per_step", "mla_rope_ms_per_step", "fusion_pack_ms_per_step",
    "fusion_unpack_ms_per_step", "unnamed_device_ms_per_step"]


def op(start, end, op_name="", opcode="fusion", instruction="i"):
    return Op(float(start), float(end), instruction, opcode, op_name)


def table(ops, steps=1, asyncs=(), host=None, **kw):
    profile = {"devices": {"/device:TPU:0": {"ops": list(ops),
                                             "async": list(asyncs)}},
               "host": host or {}}
    return dp.by_name(profile, steps, **kw)


def ns(found, name):
    return found["seconds"][name] * found["steps"] * 1e9


# ------------------------------------------------------------------ names

@pytest.mark.parametrize("op_name,expected", [
    ("jit(step)/hvd_ssd_scan/while/body/closed_call/dot_general",
     "hvd_ssd_scan"),
    ("jit(step)/hvd_moe_experts/hvd_moe_experts_gmm/pallas_call",
     "hvd_moe_experts_gmm"),          # the last name on the path wins
    ("jit(step)/hvd_moe_experts/jit(silu)/mul", "hvd_moe_experts"),
    ("jit(step)/hvd_moe_experts_gmmx/pallas_call", None),   # no substring
    ("jit(step)/xhvd_moe_route/top_k", None),
    ("jit(step)/bench_optimizer/hvd_fused_allreduce_k3/psum",
     "hvd_fused_allreduce_k"),        # the one name found by prefix
    ("jit(step)/hvd_fused_allreduce_kx/psum", None),
    ("jit(step)/hvd_flash_fwd_k3/pallas_call", None),  # prefix: that name only
    ("jit(step)/transpose(jvp(hvd_moe_route))/mul", "hvd_moe_route"),
    ("jit(hvd_mla_rope)/mul", "hvd_mla_rope"),
    # one instruction for several source ops: their op_names, ";" between
    ("jit(f)/mixer/hvd_ssd_scan/transpose;jit(f)/mixer/reshape",
     "hvd_ssd_scan"),
    ("jit(f)/mixer/reshape;jit(f)/hvd_mamba_proj;jit(f)/x", "hvd_mamba_proj"),
    ("", None),
])
def test_name_of_is_the_last_component_that_is_a_program_name(op_name,
                                                              expected):
    assert dp.name_of(op_name) == expected


def test_all_is_every_constant_of_device_names_and_the_reverse():
    constants = {k: v for k, v in vars(device_names).items()
                 if k.isupper() and isinstance(v, str)}
    assert sorted(device_names.ALL) == sorted(constants.values())
    assert len(set(device_names.ALL)) == len(device_names.ALL) >= 27
    assert all(name.startswith("hvd_") for name in device_names.ALL)
    assert set(device_names.PREFIXES) <= set(device_names.ALL)


# -------------------------------------------------------------- partition

def test_a_while_and_its_body_count_once():
    loop = op(0, 100, "", "while")                     # no tf_op of its own
    body = [op(10 * i + 1, 10 * i + 9,
               "jit(f)/hvd_ssd_scan/while/body/dot_general")
            for i in range(10)]
    found = table([loop] + body)
    assert found["busy"] * 1e9 == pytest.approx(100)
    assert ns(found, "hvd_ssd_scan") == pytest.approx(80)
    # the loop's own moments have no name and nothing named round them
    assert found["unnamed"] * 1e9 == pytest.approx(20)
    assert found["calls"]["hvd_ssd_scan"] == 10
    # with the caller's map the loop is the scan's too, still once
    named = table([loop._replace(op_name="jit(f)/hvd_ssd_scan/while")] + body)
    assert ns(named, "hvd_ssd_scan") == pytest.approx(100)
    assert named["unnamed"] == 0


def test_innermost_name_wins_and_a_nameless_event_takes_the_one_around_it():
    found = table([
        op(0, 100, "jit(f)/hvd_moe_dispatch/while", "while"),
        op(10, 30, "jit(f)/while/body/gather"),          # lost its scope
        op(40, 60, "jit(f)/hvd_moe_experts/hvd_moe_experts_gmm/pallas_call",
           "custom-call"),
        op(45, 50, "jit(f)/hvd_moe_experts/mul"),         # inside the kernel
        op(70, 80, "jit(f)/hvd_moe_combine/while/body/add"),
    ])
    assert ns(found, "hvd_moe_dispatch") == pytest.approx(100 - 20 - 10)
    assert ns(found, "hvd_moe_experts_gmm") == pytest.approx(15)
    assert ns(found, "hvd_moe_experts") == pytest.approx(5)
    assert ns(found, "hvd_moe_combine") == pytest.approx(10)
    assert found["unnamed"] == 0 and found["busy"] * 1e9 == pytest.approx(100)
    assert found["calls"]["hvd_moe_dispatch"] == 2    # the loop and its gather


def test_the_grouped_kernels_are_not_counted_under_the_experts_scope():
    found = table([
        op(0, 10, "jit(f)/hvd_moe_experts/jit(silu)/mul"),
        op(10, 40, "jit(_gmm_call)/hvd_moe_experts_gmm/pallas_call"),
        op(40, 60, "jit(_tgmm_call)/hvd_moe_experts_tgmm/pallas_call"),
        op(60, 65, "jit(f)/bench_optimizer/hvd_fused_allreduce_k14/div"),
    ])
    assert ns(found, "hvd_moe_experts") == pytest.approx(10)
    assert ns(found, "hvd_moe_experts_gmm") == pytest.approx(30)
    assert ns(found, "hvd_moe_experts_tgmm") == pytest.approx(20)
    assert ns(found, "hvd_fused_allreduce_k") == pytest.approx(5)


def test_names_and_unnamed_sum_to_busy_and_every_name_is_a_key():
    ops = [op(0, 50, "jit(f)/hvd_mla_proj/dot_general"), op(50, 70, "x/y"),
           op(90, 120, "", "copy"), op(100, 110, "jit(f)/hvd_mla_rope/mul"),
           op(115, 130, "jit(f)/hvd_flash_fwd/pallas_call")]   # overlaps on
    found = table(ops, steps=2)
    assert set(found["seconds"]) == set(device_names.ALL)
    assert found["seconds"]["hvd_ring_flash_fwd"] == 0.0
    total = sum(found["seconds"].values()) + found["unnamed"]
    assert total == pytest.approx(found["busy"], rel=1e-9)
    assert found["busy"] * 2e9 == pytest.approx(50 + 20 + 40)
    # of two events that cover a moment, the one that started later has it
    assert ns(found, "hvd_flash_fwd") == pytest.approx(15)
    assert found["unnamed"] * 2e9 == pytest.approx(20 + 15)
    assert found["idle"] * 2e9 == pytest.approx(20)


def test_the_window_clips_events_and_idle_gaps_go_to_the_host_state():
    host = {"bench_dispatch": [(0.0, 30.0)], "bench_fence": [(30.0, 100.0)]}
    found = table([op(-10, 10, "hvd_flash_fwd/pallas_call"),
                   op(20, 25, "a/b"), op(60, 120, "hvd_flash_bwd_dq/c")],
                  host=host, window=(0.0, 100.0),
                  host_states=("bench_dispatch", "bench_fence"))
    assert ns(found, "hvd_flash_fwd") == pytest.approx(10)
    assert ns(found, "hvd_flash_bwd_dq") == pytest.approx(40)
    assert found["idle"] * 1e9 == pytest.approx(45)
    gaps = {k: v * 1e9 for k, v in found["idle_gaps"].items()}
    # 10-20 under dispatch; 25-60 mostly under the fence
    assert gaps == pytest.approx({"bench_dispatch": 10, "bench_fence": 35})


def test_an_asynchronous_collective_counts_where_no_op_covers_it():
    halves = [op(0, 2, "o/hvd_fused_allreduce_k1/psum", "all-reduce-start"),
              op(50, 52, "o/hvd_fused_allreduce_k1/psum", "all-reduce-done")]
    span = op(0, 52, "o/hvd_fused_allreduce_k1/psum", "all-reduce-start")
    # A wire cast of the flat path (no buffer is filled there since PR 59).
    found = table(halves + [op(2, 30, "hvd_fusion_pack/convert_element_type")],
                  asyncs=[span])
    assert ns(found, "hvd_fusion_pack") == pytest.approx(28)
    assert ns(found, "hvd_fused_allreduce_k") == pytest.approx(2 + 2 + 20)
    assert found["busy"] * 1e9 == pytest.approx(52) and found["idle"] == 0


def test_a_profile_without_a_device_gives_an_empty_table():
    found = dp.by_name({"devices": {}, "host": {}}, 3)
    assert found["device"] is None and found["busy"] == 0
    assert set(found["seconds"].values()) == {0.0}
    assert found["unnamed"] == 0 and found["idle_gaps"] == {}


# ------------------------------------------------------------------- load

def test_load_on_a_cpu_trace_finds_the_annotations_and_no_device():
    profile = dp.load(CPU_TRACE)
    assert profile["devices"] == {}
    assert len(profile["host"]["bench_dispatch"]) >= 1
    assert len(profile["host"]["bench_fence"]) >= 1
    window = dp.window_of(profile, "bench_dispatch", "bench_fence")
    assert window[1] > window[0]
    assert dp.window_of(profile, "no_such_annotation", "bench_fence") is None
    found = dp.by_name(profile, 4, window)
    assert found["device"] is None and found["busy"] == 0.0
    # the same events as the binding the benchmark's reduction reads with
    from benchmarks import reduce_trace

    assert profile["host"]["bench_dispatch"] == pytest.approx(
        reduce_trace.load(CPU_TRACE)["host"]["bench_dispatch"], abs=1.0)


def test_load_on_a_v5e_trace_takes_op_name_from_the_metadatas_tf_op():
    profile = dp.load(TPU_TRACE)
    assert list(profile["devices"]) == ["/device:TPU:0"]
    found = profile["devices"]["/device:TPU:0"]
    assert found["async"] == []        # copies in flight are DMA, not ops
    by_instruction = {o.instruction: o for o in found["ops"]}
    kernel = by_instruction["hvd_moe_experts_gmm.1"]
    assert kernel.opcode == "custom-call"
    assert kernel.op_name == ("jit(step)/hvd_moe_experts/"
                              "hvd_moe_experts_gmm/pallas_call")
    assert by_instruction["convolution_tanh_fusion.2"].op_name == (
        "jit(step)/hvd_ssd_scan/while/body/closed_call/inner/dot_general")
    loop = by_instruction["while"]
    assert loop.opcode == "while" and loop.op_name == ""   # none in the trace
    assert len(profile["host"]["bench_dispatch"]) == 3
    # the caller's map names what the trace leaves bare, and only that
    with open(TPU_HLO) as f:
        names = dp.op_names_from_hlo(f.read())
    assert names["while"] == "jit(step)/hvd_ssd_scan/while"
    named = dp.load(TPU_TRACE, names)["devices"]["/device:TPU:0"]["ops"]
    assert {o.op_name for o in named if o.instruction == "while"} == {
        "jit(step)/hvd_ssd_scan/while"}
    assert [o.op_name for o in named if o.opcode != "while"] == [
        o.op_name or names.get(o.instruction, "") for o in found["ops"]
        if o.opcode != "while"]


def test_the_table_of_a_v5e_trace():
    found = dp.by_name(dp.load(TPU_TRACE), 3)
    assert found["device"] == "/device:TPU:0" and found["steps"] == 3
    calls, seconds = found["calls"], found["seconds"]
    assert calls["hvd_moe_experts_gmm"] == calls["hvd_mla_proj"] == 1
    assert calls["hvd_moe_experts"] == 1           # the add, not the kernel
    assert calls["hvd_ssd_scan"] == 8              # 4 x (copy + product)
    # the loop (6.79 us) holds its body (6.77): counted once
    assert 6.5e-6 < seconds["hvd_ssd_scan"] < 6.79e-6
    assert 1.4e-6 < seconds["hvd_mla_proj"] < 1.6e-6
    assert 1.5e-7 < seconds["hvd_moe_experts_gmm"] < 2.0e-7
    total = sum(seconds.values()) + found["unnamed"]
    assert total == pytest.approx(found["busy"], rel=1e-9)
    assert 19e-6 < found["busy"] < 21e-6           # three modules of 20 us
    text = dp.format_table(found)
    assert text.splitlines()[1].split() == ["name", "ms/step", "%", "busy",
                                            "calls/step"]
    assert "hvd_ssd_scan" in text and "unnamed" in text
    assert "hvd_ring_flash_fwd" not in text        # rows of no time are left out


def test_find_xplane_takes_the_newest_and_says_when_there_is_none(tmp_path):
    with pytest.raises(FileNotFoundError):
        dp.find_xplane(str(tmp_path))
    for stamp in ("2026_01_01", "2026_01_02"):
        os.makedirs(tmp_path / "plugins" / "profile" / stamp)
        shutil.copy(CPU_TRACE, tmp_path / "plugins" / "profile" / stamp
                    / "host.xplane.pb")
    assert "2026_01_02" in dp.find_xplane(str(tmp_path))


# ---------------------------------------------------------------- overlap

OVERLAP_KEYS = {"ok", "collectives", "collective_ms", "hidden_ms",
                "overlap_efficiency", "spans"}


def overlap_profile():
    """One collective hidden under compute, one exposed, on each of two
    devices; device 1's compute must not hide device 0's collective."""
    def device(shift):
        return {"ops": [op(shift + 0, shift + 100_000, "f/dot_general"),
                        op(shift + 200_000, shift + 250_000, "o/psum",
                           "all-reduce", "all-reduce.2"),
                        op(shift + 20_000, shift + 21_000, "o/psum",
                           "all-reduce-start", "all-reduce-start.1"),
                        op(shift + 69_000, shift + 70_000, "o/psum",
                           "all-reduce-done", "all-reduce-done.1")],
                "async": [op(shift + 20_000, shift + 70_000, "o/psum",
                             "all-reduce-start", "all-reduce-start.1")]}
    return {"devices": {"/device:TPU:0": device(0),
                        "/device:TPU:1": device(150_000)}, "host": {}}


def test_overlap_report_interval_math():
    """One collective fully hidden under compute, one fully exposed ->
    efficiency 0.5; an asynchronous pair counts once, by its span."""
    from horovod_tpu.metrics.overlap import overlap_report

    rep = overlap_report(dp.collective_overlap(overlap_profile()))
    assert set(rep) == OVERLAP_KEYS
    assert rep["ok"] and rep["collectives"] == 4
    assert rep["collective_ms"] == pytest.approx(0.2)
    assert rep["hidden_ms"] == pytest.approx(0.1)
    assert rep["overlap_efficiency"] == pytest.approx(0.5)
    assert set(rep["spans"][0]) == {"name", "ms", "hidden_ms", "start_us",
                                    "end_us"}
    assert [s["name"] for s in rep["spans"]] == [
        "all-reduce-start.1", "all-reduce-start.1", "all-reduce.2",
        "all-reduce.2"]
    assert rep["spans"][0]["hidden_ms"] == pytest.approx(0.05)
    # host-only traces (CPU backend) degrade explicitly, not silently
    none = overlap_report(dp.collective_overlap(dp.load(CPU_TRACE)))
    assert none["ok"] is False and "reason" in none


def test_measure_overlap_keeps_its_keys_and_gauges(monkeypatch, tmp_path):
    import horovod_tpu as hvd
    from horovod_tpu.metrics import overlap

    calls = []
    # on the CPU the trace holds no device plane: not ok, and no gauge moves
    rep = overlap.measure_overlap(lambda: calls.append(1), steps=2,
                                  sync=lambda: calls.append("sync"),
                                  logdir=str(tmp_path))
    assert calls == [1, 1, "sync"]
    assert rep["ok"] is False and rep["logdir"] == str(tmp_path)
    assert "no device collective spans" in rep["reason"]
    monkeypatch.setattr(dp, "load", lambda path: overlap_profile())
    rep = overlap.measure_overlap(lambda: None, steps=1, logdir=str(tmp_path))
    assert set(rep) == OVERLAP_KEYS | {"logdir"} and rep["ok"]
    gauges = hvd.metrics.snapshot()["gauges"]
    assert gauges["horovod_overlap_efficiency_measured"] == pytest.approx(0.5)
    assert gauges["horovod_overlap_collective_ms"] == pytest.approx(0.2)
    assert gauges["horovod_overlap_hidden_ms"] == pytest.approx(0.1)
    # and nothing in overlap.py opens the viewer's capped export any more
    with open(overlap.__file__) as f:
        assert "trace.json" not in f.read()


def test_profile_step_on_a_backend_without_device_planes(tmp_path):
    import jax
    import jax.numpy as jnp

    import horovod_tpu as hvd

    step = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((8, 8))
    out = [step(x)]
    rep = hvd.metrics.profile_step(lambda: out.append(step(x)), steps=2,
                                   sync=lambda: out[-1].block_until_ready(),
                                   compiled=step.lower(x).compile(),
                                   logdir=str(tmp_path))
    assert len(out) == 3 and rep["steps"] == 2
    assert rep["ok"] is False and "no TPU device plane" in rep["reason"]
    assert rep["busy"] == 0 and rep["logdir"] == str(tmp_path)


# -------------------------------------------------------------- off: free

def test_import_horovod_tpu_does_not_load_the_module():
    code = ("import sys, horovod_tpu as hvd; hvd.init(); "
            "import horovod_tpu.metrics.overlap; "
            "assert callable(hvd.metrics.profile_step); "
            "print('horovod_tpu.metrics.device_profile' in sys.modules)")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=REPO, timeout=300,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == "False"


# ------------------------------------------------- the benchmark's readers

@pytest.fixture
def named_device_time(monkeypatch, tmp_path):
    from benchmarks import named_device_time as module

    monkeypatch.setattr(module, "TRACE_DIR", str(tmp_path))
    monkeypatch.setattr(module, "_tables", [])
    return module


def load_reader(name):
    from benchmarks import run

    return run.load_module(os.path.join(REPO, "benchmarks", "layer_metrics",
                                        name + ".py"))


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_gives_nothing_without_a_trace(name, named_device_time):
    logged = []
    assert load_reader(name).read({"trace": {"steps": 3},
                                   "log": logged.append}) is None
    assert load_reader(name).read({"log": logged.append}) is None
    assert logged == []


def test_the_new_readers_are_the_manifests_and_read_the_programs_names():
    from benchmarks import run

    manifest = run.load_manifest(REPO)
    new = [m for m in manifest["per_layer"] if m["name"] in NEW_READERS]
    assert [m["name"] for m in new] == NEW_READERS       # appended, in order
    # one run of entries; later PRs append theirs behind it
    first = manifest["per_layer"].index(new[0])
    assert manifest["per_layer"][first:first + len(new)] == new
    for m in new:
        assert (m["unit"], m["better"], m["source"]) == ("ms", "lower",
                                                         "device_trace")
        with open(os.path.join(REPO, "benchmarks", "layer_metrics",
                               m["name"] + ".py")) as f:
            source = f.read()
        read = [n for n in (*device_names.ALL, "unnamed")
                if f'"{n}"' in source]
        assert read, m["name"]


def test_the_readers_on_a_v5e_trace(named_device_time, tmp_path):
    where = tmp_path / "cell" / "plugins" / "profile" / "2026_09_28"
    os.makedirs(where)
    shutil.copy(TPU_TRACE, where / "host.xplane.pb")
    logged = []
    run = {"trace": {"steps": 3}, "log": logged.append}
    # the window runs from the first bench_dispatch to the last bench_fence;
    # on this 3 ms trace the host's clock lies 0.6 ms behind the device's, so
    # the first of the three modules falls before the window
    grouped = load_reader("moe_grouped_ms_per_step").read(run)
    assert grouped == pytest.approx(2 * 1.74e-4 / 3, rel=0.05)
    assert load_reader("ssd_scan_ms_per_step").read(run) == pytest.approx(
        2 * 6.77e-3 / 3, rel=0.02)
    assert load_reader("mla_proj_ms_per_step").read(run) == pytest.approx(
        2 * 1.5e-3 / 3, rel=0.02)
    assert load_reader("flash_fwd_ms_per_step").read(run) == pytest.approx(
        2 * 6.35e-3 / 3, rel=0.02)
    # a name the program knows and the window never ran: 0.0, not None
    assert load_reader("flash_bwd_dq_ms_per_step").read(run) == 0.0
    assert load_reader("mamba_conv_ms_per_step").read(run) == 0.0
    assert load_reader("unnamed_device_ms_per_step").read(run) > 0
    assert named_device_time.ms(run, "hvd_no_such_name") is None
    # loaded once, logged once, whole
    assert len(logged) == 1 and "hvd_ssd_scan" in logged[0]
    assert "calls/step" in logged[0]


# ------------------------------------ what is left unnamed (ISSUE 50's rows)

@pytest.mark.parametrize("op_name,expected", [
    ("jit(step)/bench_fwd_bwd/jvp(hvd_lm_head)/while/body/closed_call/"
     "dot_general", "hvd_lm_head"),
    ("jit(step)/transpose(jvp(hvd_attn))/jit(_bwd_rule)/hvd_flash_bwd_dq/"
     "pallas_call", "hvd_flash_bwd_dq"),       # the kernel inside keeps its time
    ("jit(step)/block_0/block_0._mixer/hvd_attn/block_0._attention/"
     "hvd_attn_proj/o_proj/dot_general", "hvd_attn_proj"),
    ("jit(step)/block_0/block_0._mixer/hvd_attn/block_0._attention/reshape",
     "hvd_attn"),
    ("jit(step)/hvd_mtp/while/body/closed_call/dot_general", "hvd_mtp"),
    ("jit(step)/block_1/moe/moe._routed/hvd_moe_logits/dot_general",
     "hvd_moe_logits"),
    ("jit(step)/block_1/moe/moe._routed/hvd_moe_route/top_k", "hvd_moe_route"),
])
def test_name_of_on_the_steps_own_names(op_name, expected):
    assert dp.name_of(op_name) == expected


def test_unnamed_ops_say_what_no_name_reaches():
    """Sorted, at most five, no more than ``unnamed`` together, each the
    INNERMOST nameless event's instruction, opcode and ``op_name`` with the
    numbers cut (every leaf's copy of the optimizer's add is one row); a
    nameless event inside a named one is the name's, not a row."""
    update = "jit(train_step)/bench_optimizer/add"
    ops = [op(0, 10, "jit(f)/hvd_mlp/mlp_in/dot_general"),
           op(10, 40, "jit(f)/hvd_ssd_scan/while", "while"),
           op(12, 20, "", "copy", "copy.7"),              # the scan's
           op(40, 70, update, "fusion", "fusion.12"),
           op(70, 90, update, "fusion", "fusion.345"),
           op(90, 130, "", "while", "while.3"),
           op(95, 125, "jit(f)/loss/reduce_max", "reduce", "reduce.9")]
    ops += [op(130 + 2 * i, 132 + 2 * i, "", kind, f"{kind}.{i}")
            for i, kind in enumerate(("reshape", "copy-done", "slice", "pad"))]
    found = table(ops, steps=2)
    rows = found["unnamed_ops"]
    assert len(rows) == dp.UNNAMED_OPS == 5
    assert [r[:3] for r in rows[:3]] == [
        ("fusion.N", "fusion", update),
        ("reduce.N", "reduce", "jit(f)/loss/reduce_max"),
        ("while.N", "while", "")]                    # what its body left
    assert [r[3] * 2e9 for r in rows[:3]] == pytest.approx([50, 30, 10])
    seconds = [r[3] for r in rows]
    assert seconds == sorted(seconds, reverse=True)
    assert sum(seconds) <= found["unnamed"] == pytest.approx(98 / 2e9)
    assert not any(r[0].startswith("copy.") for r in rows)
    text = dp.format_table(found)
    lines = text.splitlines()
    assert lines[0].endswith("named 29.0% of busy")     # 40 of 138
    at = next(i for i, line in enumerate(lines) if line.startswith("unnamed"))
    assert lines[at + 1].split() == ["fusion.N", "0.000", "36.23", "fusion",
                                     update]
    assert lines[at + 3].split()[-3:] == ["while", "(no", "op_name)"]
    assert len([l for l in lines if l.startswith("  ")]) == 5


def test_an_exposed_nameless_collective_is_a_row_of_unnamed():
    found = table([op(0, 10, "jit(f)/hvd_mlp/dot_general")], asyncs=[
        op(5, 30, "jit(f)/psum", "all-reduce-start", "all-reduce-start.4")])
    assert found["unnamed_ops"] == [
        ("all-reduce-start.N", "all-reduce-start", "jit(f)/psum",
         pytest.approx(20e-9))]
    assert found["unnamed"] == pytest.approx(20e-9)


def test_a_table_with_nothing_unnamed_has_no_rows():
    found = table([op(0, 10, "jit(f)/hvd_mlp/dot_general")])
    assert found["unnamed_ops"] == [] and found["unnamed"] == 0.0
    assert "named 100.0% of busy" in dp.format_table(found)
    empty = dp.by_name({"devices": {}, "host": {}}, 1)
    assert empty["unnamed_ops"] == []
    assert "named 0.0% of busy" in dp.format_table(empty)


def test_the_v5e_traces_unnamed_rows():
    found = dp.by_name(dp.load(TPU_TRACE), 3)
    rows = found["unnamed_ops"]
    assert [r[0] for r in rows[:3]] == ["copy-done.N", "convert.N",
                                        "reduce_sum.N"]
    assert rows[2][1:3] == ("reduce", "jit(step)/reduce_sum")
    assert 0 < sum(r[3] for r in rows) <= found["unnamed"] * (1 + 1e-9)
    assert "named 75.9% of busy" in dp.format_table(found).splitlines()[0]
