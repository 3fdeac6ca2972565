"""chip_smoke.py's contract, exercised on every tier-1 run without a size
switch in its ``main()``: off-chip the script fails fast and names what it
found, and its phase functions — imported, and run here at tiny sizes on
the virtual CPU mesh with the Pallas interpreter asked for explicitly —
pass on a correct system and fail on a broken one."""

from __future__ import annotations

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def test_off_chip_exits_nonzero_and_names_the_platform():
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=REPO, capture_output=True,
        text=True, timeout=120, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert "platform='cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout          # no result line


def test_build_raises_off_chip(hvd):
    """The full-width ResNet step never shrinks to fit a CPU: without a TPU
    it exits non-zero and the message names the platform it found."""
    with pytest.raises(SystemExit, match="platform='cpu'"):
        chip_smoke._build()


@pytest.mark.slow
@pytest.mark.parametrize("hierarchical", [False, True])
def test_build_runs_one_step(hvd, hierarchical):
    """The step builds and runs on the virtual mesh in BOTH data-plane
    shapes (flat hvd axis and the hierarchical ('dcn','ici') ladder)."""
    import jax
    import numpy as np

    from horovod_tpu.models import ResNet50

    step, state, (x, y), batch, n_dev = chip_smoke.build_resnet_step(
        ResNet50(num_classes=1000), image=32, per_dev_batch=1,
        hierarchical=hierarchical)
    assert x.sharding.device_set == set(jax.devices())
    # snapshot BEFORE the call: the step donates its inputs
    leaves0 = [np.array(a) for a in jax.tree_util.tree_leaves(state[0])]
    params, batch_stats, opt_state, loss = step(*state, x, y)
    assert np.isfinite(float(loss))
    assert batch == n_dev  # 1 per device
    # the step must actually move parameters (optimizer ran)
    leaves1 = [np.asarray(a) for a in jax.tree_util.tree_leaves(params)]
    assert any(not np.array_equal(a, b) for a, b in zip(leaves0, leaves1))


def test_collective_check(hvd, capsys):
    chip_smoke.phase_collective(hvd.default_mesh())
    line = capsys.readouterr().out
    assert "phase=collective platform=cpu" in line and "devices=8" in line
    assert "compile_s=" in line and "ranks=8" in line


def test_collective_check_catches_an_absent_allreduce(hvd, monkeypatch):
    """The reason the phase exists: rank-dependent gradients make a no-op
    exchange visible (a synthetic all-ones batch would not)."""
    monkeypatch.setattr(hvd.jax, "allreduce_gradients",
                        lambda grads, **kw: grads)
    with pytest.raises(chip_smoke.SmokeFailure, match="analytic mean"):
        chip_smoke.phase_collective(hvd.default_mesh())


def test_tiny_resnet_step(hvd, capsys):
    from horovod_tpu.models.resnet import BottleneckBlock, ResNet

    # ResNet-50's block and stem, two stages of one block at 8 filters.
    tiny = ResNet(stage_sizes=(1, 1), block_cls=BottleneckBlock,
                  num_classes=10, num_filters=8)
    chip_smoke.phase_resnet(
        chip_smoke.build_resnet_step(tiny, image=32, per_dev_batch=2),
        steps=5)
    line = capsys.readouterr().out
    assert "phase=resnet" in line and "replicas_bit_equal=True" in line


def test_tiny_flash_step_interpreted(capsys):
    chip_smoke.phase_kernels(cases=((64, 4, 2, 32, "float32"),),
                             interpret=True)
    line = capsys.readouterr().out
    assert "phase=kernels" in line and "interpret=True" in line
    assert "precision=highest" in line
    assert "max_abs_err=out/dq/dk/dv=" in line


def test_flash_step_tells_bf16_arithmetic_from_f32(monkeypatch):
    """What the f32 cases' tight bound is for: operands rounded to bf16 on
    the way in (what the MXU does at the default precision) must fail."""
    import importlib

    import jax.numpy as jnp

    # the package re-exports the function under the module's name
    fa = importlib.import_module("horovod_tpu.ops.flash_attention")
    real = fa.flash_attention

    def rounded(q, k, v, *rest):
        q, k, v = (t.astype(jnp.bfloat16).astype(t.dtype) for t in (q, k, v))
        return real(q, k, v, *rest)

    monkeypatch.setattr(fa, "flash_attention", rounded)
    with pytest.raises(chip_smoke.SmokeFailure, match="exceeds"):
        chip_smoke.phase_kernels(cases=((64, 4, 2, 32, "float32"),),
                                 interpret=True)


def test_tiny_transformer_trainer(hvd, capsys):
    from horovod_tpu.models import TransformerLM

    chip_smoke.phase_transformer(
        TransformerLM(vocab=64, dim=32, heads=4, layers=2, attention="flash",
                      flash_interpret=True),
        seq=64, per_dev_batch=1, steps=3)
    assert "phase=transformer" in capsys.readouterr().out


def test_four_device_legs_on_the_virtual_mesh(hvd, capsys):
    chip_smoke.phase_four_chip(t_local=16, interpret=True)
    line = capsys.readouterr().out
    assert "phase=four_chip" in line and "pipeline_ppermute=True" in line
    assert "moe_data_parallel=True" in line
