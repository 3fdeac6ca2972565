"""The nine readers ISSUE 50 adds under ``benchmarks/layer_metrics/``: eight
read one of the program's names each from the whole trace's table
(``benchmarks/named_device_time.py``), the ninth the share of the device's
busy time that has a name at all."""

from __future__ import annotations

import os
import shutil

import pytest

from horovod_tpu.common import device_names
from horovod_tpu.metrics import device_profile as dp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TPU_TRACE = os.path.join(REPO, "tests", "fixtures", "device_profile",
                         "v5e_tiny.xplane.pb")
READS = {
    "mlp_ms_per_step": device_names.MLP,
    "attn_proj_ms_per_step": device_names.ATTN_PROJ,
    "attn_other_ms_per_step": device_names.ATTN,
    "norm_add_ms_per_step": device_names.NORM_ADD,
    "embed_ms_per_step": device_names.EMBED,
    "lm_head_ms_per_step": device_names.LM_HEAD,
    "moe_logits_ms_per_step": device_names.MOE_LOGITS,
    "moe_weight_cast_ms_per_step": device_names.MOE_WEIGHT_CAST,
    "named_device_share_pct": None,
}
LM_CELLS = {"lm217m_long_1chip", "lm217m_short_1chip", "olmoe_seq4096_1chip",
            "granite4h_long_1chip", "kanana2_seq8192_1chip",
            "laguna_xs2_seq16384_1chip", "nemotron3s_seq8192_1chip",
            "keye_vl2_seq16384_1chip", "kimi_linear_seq16384_1chip",
            "solar_open2_seq8192_1chip", "lfm2_seq8192_1chip",
            "olmo_hybrid_seq16384_1chip", "ouro_seq8192_1chip"}
PINNED_CELLS = {"laguna_xs2_seq16384_1chip", "keye_vl2_seq16384_1chip"}


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


@pytest.mark.parametrize("name", READS)
def test_a_reader_gives_nothing_without_a_trace(name, named_device_time):
    logged = []
    assert load_reader(name).read({"trace": {"steps": 3},
                                   "log": logged.append}) is None
    assert load_reader(name).read({"log": logged.append}) is None
    assert logged == []


def test_the_readers_are_the_manifests_last_entries():
    from benchmarks import run

    manifest = run.load_manifest(REPO)
    new = [m for m in manifest["per_layer"] if m["name"] in READS]
    assert [m["name"] for m in new] == list(READS)      # appended, in order
    first = manifest["per_layer"].index(new[0])
    assert manifest["per_layer"][first:first + len(new)] == new
    for m in new:
        assert (m["source"], m["moves"]) == ("device_trace", "step_ms")
        assert m["layer"] == ("Experts" if m["name"].startswith("moe_") else
                              "Device" if READS[m["name"]] is None else
                              "Models")
        assert (m["unit"], m["better"]) == (
            ("%", "higher") if READS[m["name"]] is None else ("ms", "lower"))
        # a name for the language models' step: no ResNet cell reads it,
        # and not the two cells whose own tests pin the metrics that list
        # them (tests/benchmark/test_benchmark_laguna.py, ..._keye.py)
        assert set(m["workloads"]) <= LM_CELLS - PINNED_CELLS, m["name"]


@pytest.mark.parametrize("name,scope", [(n, s) for n, s in READS.items() if s])
def test_a_reader_reads_its_one_name(name, scope, named_device_time,
                                     monkeypatch):
    seconds = {n: 0.0 for n in device_names.ALL}
    seconds[scope] = 0.25
    monkeypatch.setattr(named_device_time, "_tables", [
        {"seconds": seconds, "unnamed": 0.5, "busy": 1.0}])
    assert load_reader(name).read({}) == 250.0
    # a program older than the name: nothing, and no error
    del seconds[scope]
    assert load_reader(name).read({}) is None


def test_the_loop_exit_reader_reads_its_one_name(named_device_time,
                                                  monkeypatch):
    """``loop_exit_ms_per_step`` (PR 67) is not among the nine: the cell that
    added it lists it alone. It reads ``hvd_loop_exit`` and no other name."""
    seconds = {n: 0.0 for n in device_names.ALL}
    seconds[device_names.LOOP_EXIT] = 0.002
    seconds[device_names.LM_HEAD] = 0.25    # the head's passes are not the exit's
    monkeypatch.setattr(named_device_time, "_tables", [
        {"seconds": seconds, "unnamed": 0.5, "busy": 1.0}])
    assert load_reader("loop_exit_ms_per_step").read({}) == 2.0
    del seconds[device_names.LOOP_EXIT]     # a program older than the name
    assert load_reader("loop_exit_ms_per_step").read({}) is None


def test_the_named_share_on_a_v5e_trace(named_device_time, tmp_path):
    where = tmp_path / "cell" / "plugins" / "profile" / "2026_10_01"
    os.makedirs(where)
    shutil.copy(TPU_TRACE, where / "host.xplane.pb")
    logged = []
    run = {"trace": {"steps": 3}, "log": logged.append}
    share = load_reader("named_device_share_pct").read(run)
    found = named_device_time.table(run)
    assert share == 100.0 * (found["busy"] - found["unnamed"]) / found["busy"]
    unnamed_ms = load_reader("unnamed_device_ms_per_step").read(run)
    assert share == pytest.approx(
        100.0 * (1 - unnamed_ms / (found["busy"] * 1e3)))
    assert 50.0 < share < 100.0
    # names the program knows and this tiny program never ran
    for name, scope in READS.items():
        if scope:
            assert load_reader(name).read(run) == 0.0
    # the table went to the log once, with what its remainder is
    assert len(logged) == 1 and "% of busy" in logged[0].splitlines()[1]
    rows = [l for l in logged[0].splitlines() if l.startswith("  ")]
    assert 1 <= len(rows) <= dp.UNNAMED_OPS


def test_the_named_share_of_an_idle_device_is_nothing(named_device_time,
                                                      monkeypatch):
    monkeypatch.setattr(named_device_time, "_tables", [
        {"seconds": {}, "unnamed": 0.0, "busy": 0.0}])
    assert load_reader("named_device_share_pct").read({}) is None
