"""bench.py is the driver's measurement surface — its step must build and
run on the virtual mesh in BOTH data-plane shapes (flat hvd axis and the
hierarchical ('dcn','ici') ladder the --autotune branch uses on pods)."""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # noqa: E402


@pytest.mark.slow
@pytest.mark.parametrize("hierarchical", [False, True])
def test_bench_build_runs_one_step(hvd, hierarchical):
    import jax

    from horovod_tpu.models import ResNet50

    step, state, (x, y), batch, n_dev = bench.build_resnet_step(
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


def test_bench_build_raises_off_chip(hvd):
    """The benchmark step never shrinks to fit a CPU: without a TPU it
    exits non-zero and the message names the platform it found."""
    with pytest.raises(SystemExit, match="platform='cpu'"):
        bench._build()
