"""Each configuration's builder, reference and ``correct`` check at a tiny
size on the 4-device virtual CPU mesh, the window arithmetic with a fake
clock, the last line's shape, the no-TPU exit, and ``benchmarks/flops.py``
against hand counts. The Pallas interpreter is asked for by the test
(``flash_interpret=True``), never by an option of ``run.py``."""

from __future__ import annotations

import copy
import json
import math
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import flops, run  # noqa: E402

LM_TINY = {"vocab": 256, "dim": 64, "heads": 2, "head_dim": 32, "layers": 2,
           "mlp_ratio": 4, "attention": "flash",
           "optimizer": {"name": "adamw", "learning_rate": 3e-4},
           "throughput_metric": "train_tok_per_s_per_chip"}
RESNET_TINY = {"stage_sizes": [1, 1], "num_filters": 8, "num_classes": 10,
               "image": 32,
               "optimizer": {"name": "sgd", "learning_rate_per_chip": 0.01,
                             "momentum": 0.9, "fusion_threshold_bytes": 1 << 20},
               "throughput_metric": "train_img_per_s_per_chip"}
LOOP = {"fence_every": 2, "fence_lag": 1, "warmup_groups": 1,
        "trace_groups": 1}
CASES = {
    "resnet50": (RESNET_TINY, {**LOOP, "global_batch": 8, "reference": "step"}, {}),
    "lm217m_step": (LM_TINY, {**LOOP, "seq": 128, "global_rows": 4,
                              "reference": "step", "reference_micro_rows": 2},
                    {"flash_interpret": True}),
    "lm217m_kernel": (LM_TINY, {**LOOP, "seq": 256, "global_rows": 4,
                                "reference": "kernel", "reference_slice": 128},
                      {"flash_interpret": True}),
}


def resolved_tiny(case):
    """The manifest's own entry for the configuration, with the tiny sizes in
    place of the file's and the tolerances of the file kept."""
    tiny, traffic, overrides = CASES[case]
    manifest = run.load_manifest()
    name = case.split("_")[0]
    cell = next(w for w in manifest["workloads"] if w["config"] == name)
    resolved = run.resolve_cell(manifest, cell["name"])
    resolved["config"] = {**resolved["config"], **copy.deepcopy(tiny)}
    resolved["traffic"] = dict(traffic)
    return resolved, overrides


@pytest.fixture()
def cpu_memory(monkeypatch):
    # The CPU backend reports no memory statistics; run.py treats that as an
    # error on purpose, so the tests stand in a number.
    monkeypatch.setattr(run, "hbm_bytes", lambda devices: 3 << 30)


@pytest.mark.parametrize("case", sorted(CASES))
def test_cell_end_to_end_tiny(case, hvd, cpu_memory, capsys):
    resolved, overrides = resolved_tiny(case)
    result = run.run_cell(resolved, jax.devices()[:4], seed=3, seconds=0.0,
                          trace=0, **overrides)
    out = capsys.readouterr().out
    assert result["correct"] is True, out
    assert set(result) == {"correct", "attempted", "failed", "metrics", "device"}
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {m["name"] for m in resolved["end_to_end"]}
    for metric in result["metrics"].values():
        assert set(metric) == {"value", "unit"}
        assert math.isfinite(metric["value"]) and metric["value"] > 0
    assert result["device"] == {"platform": "cpu", "kind": "cpu", "count": 4,
                                "memory_peak_bytes": 3 << 30}
    json.dumps(result)      # the last line is one JSON object
    assert "INCORRECT" not in out
    if CASES[case][1]["reference"] == "step":
        assert "step vs plain reference" in out
    else:
        assert "kernels vs f32 reference" in out


def test_a_broken_exchange_is_not_correct(hvd, cpu_memory, monkeypatch, capsys):
    """Why the exchange check exists: with the all-reduce left out the
    rank-dependent gradients no longer match the analytic mean."""
    monkeypatch.setattr(hvd.jax, "allreduce_gradients",
                        lambda grads, **kw: grads)
    resolved, overrides = resolved_tiny("resnet50")
    result = run.run_cell(resolved, jax.devices()[:4], seed=3, seconds=0.0,
                          trace=0, **overrides)
    assert result["correct"] is False
    assert "analytic mean" in capsys.readouterr().out


def test_a_wrong_step_is_not_correct(hvd, cpu_memory, capsys):
    """The step check must tell a wrong optimizer from the right one: the
    reference is told another learning rate than the system runs."""
    resolved, overrides = resolved_tiny("resnet50")
    module = resolved["module"]
    real = module.reference

    def doubled(config, *a, **kw):
        config = copy.deepcopy(config)
        config["optimizer"]["learning_rate_per_chip"] *= 2
        return real(config, *a, **kw)

    module.reference = doubled
    result = run.run_cell(resolved, jax.devices()[:4], seed=3, seconds=0.0,
                          trace=0, **overrides)
    assert result["correct"] is False
    assert "differ from the plain step's" in capsys.readouterr().out


class FakeDevice:
    """A fake clock and a device that takes 0.1 s a step, one after the other.
    ``dispatch`` costs the host 1 ms; ``fence`` waits until the step is done
    and then for the host to wake, which takes ``wake_s``."""

    def __init__(self, wake_s=0.0, nan_step=None, compile_at_dispatch=None):
        self.now, self.free_at, self.dispatched, self.compiled = 100.0, 100.0, 0, 0
        self.wake_s, self.nan_step = wake_s, nan_step
        self.compile_at_dispatch = compile_at_dispatch

    def clock(self):
        return self.now

    def compiles(self):
        return self.compiled

    def dispatch(self):
        self.now += 0.001
        self.dispatched += 1
        if self.dispatched == self.compile_at_dispatch:
            self.compiled += 1
        self.free_at = max(self.free_at, self.now) + 0.1
        return self.dispatched, self.free_at    # the step and when it is done

    def fence(self, handle):
        step, done_at = handle
        self.now = max(self.now, done_at) + self.wake_s
        return float("nan") if step == self.nan_step else float(step)


def run_fake(device, lag, seconds, **kw):
    return run.run_window(device.dispatch, device.fence, 10, lag, seconds,
                          device.clock, device.compiles, **kw)


def test_window_arithmetic_with_a_fake_clock():
    device = FakeDevice(nan_step=20, compile_at_dispatch=25)
    window = run_fake(device, 0, 3.5)
    # groups close near 1, 2, 3, 4 s: the first fence after 3.5 s
    assert len(window["groups"]) == 4 and device.dispatched == 40
    assert window["opened"] == 100.0 and window["in_flight"] == []
    assert window["closed"] == pytest.approx(104.0, abs=0.05)
    assert [g["failed"] for g in window["groups"]] == [False, True, True, False]
    # every fence reads the loss of its own group's last step
    assert [g["loss"] for g in window["groups"]][2:] == [30.0, 40.0]
    assert sum(g["seconds"] for g in window["groups"]) == pytest.approx(
        window["closed"] - window["opened"])
    assert len(window["dispatch_s"]) == 40
    assert window["dispatch_s"][0] == pytest.approx(0.001)
    capped = run_fake(device, 0, math.inf, max_groups=2)
    assert len(capped["groups"]) == 2


def test_a_lagged_fence_keeps_a_slow_host_out_of_the_step_time():
    """What the driver's check tripped over: with the loss read straight after
    its own step, the device idles while the host wakes, so a host that wakes
    27 ms late makes every group 27 ms longer. One step queued behind the read
    hides that; carried over from the warm-up, the window opens and closes on
    the same event and counts exactly the steps between."""
    def step_s(lag, wake_s):
        device = FakeDevice(wake_s=wake_s)
        warm = run_fake(device, lag, math.inf, max_groups=2)
        assert len(warm["in_flight"]) == lag
        window = run_fake(device, lag, 5.0, in_flight=warm["in_flight"])
        assert len(window["in_flight"]) == lag
        assert [g["loss"] for g in window["groups"]][:2] == [30.0, 40.0]
        steps = 10 * len(window["groups"])
        assert device.dispatched == 20 + steps + lag
        return (window["closed"] - window["opened"]) / steps

    assert step_s(0, 0.0) == pytest.approx(0.1001)
    assert step_s(0, 0.027) == pytest.approx(0.1028)      # +2.7% from the host
    assert step_s(1, 0.0) == pytest.approx(0.1)
    assert step_s(1, 0.027) == pytest.approx(0.1)


def test_without_a_tpu_the_command_exits_nonzero_and_prints_no_result():
    manifest = run.load_manifest()
    proc = subprocess.run(
        manifest["command"] + ["--workload", manifest["workloads"][0]["name"],
                               "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert "There is no CPU mode" in proc.stderr
    assert '"correct"' not in proc.stdout


def test_flops_against_hand_counts():
    # One ResNet bottleneck, 56x56, 256 -> 64 -> 64 -> 256, no projection:
    # multiply-adds 56*56*(256*64 + 9*64*64 + 64*256), times 2.
    macs = 56 * 56 * (256 * 64 + 9 * 64 * 64 + 64 * 256)
    assert flops.bottleneck_forward_flops(56, 256, 64, 1) == (2 * macs, 56, 256)
    # The first block of stage 2 (stride 2, v1.5: in the 3x3) with projection.
    macs = (56 * 56 * 256 * 128 + 28 * 28 * 9 * 128 * 128
            + 28 * 28 * 128 * 512 + 28 * 28 * 256 * 512)
    assert flops.bottleneck_forward_flops(56, 256, 128, 2) == (2 * macs, 28, 512)
    # ResNet-50 is the well-known ~4.1 G multiply-adds per 224 px image.
    assert flops.resnet_forward_flops(224, (3, 4, 6, 3), 64, 1000) == \
        pytest.approx(2 * 4.09e9, rel=0.01)
    # One transformer layer, T = 1024, dim 1024, 8 heads of 128, forward:
    # per token qkv 2*1024*3072 + o 2*1024*1024 + mlp 2*2*1024*4096; causal
    # HALF of QK^T and PV: 2 products * 2*T*T*128 * 8 heads / 2.
    t, d = 1024, 1024
    dense = t * (2 * d * 3 * d + 2 * d * d + 4 * d * 4 * d)
    attention = 2 * (2 * t * t * 128) * 8 // 2
    assert flops.transformer_layer_forward_flops(t, d, 8) == dense + attention
    assert attention / (dense + attention) == pytest.approx(0.0769, abs=1e-3)
    # Whole step = 3 x forward (backward is twice the forward).
    assert flops.transformer_step_flops(t, 2, 32000, d, 8, 12) == \
        3 * 2 * (12 * (dense + attention) + t * 2 * d * 32000)
    # The flash kernels: 2 forward + 5 backward products on the causal half.
    cost = flops.flash_step_cost(t, 2, 8, 128, 12)
    assert cost["flops"] == 2 * 12 * 7 * (t * t * 128 * 8)
    assert cost["bytes"] == 12 * (12 * 2 * t * 8 * 128 * 2 + 2 * 2 * t * 8 * 4)
