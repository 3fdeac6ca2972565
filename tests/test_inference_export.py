"""Inference export round-trips — reference docs/inference.md's contract
(serving must not need the distributed machinery), restated for state:
train distributed -> export_for_inference -> restore in a FRESH process
(no hvd.init) -> identical logits to the consolidated in-training forward."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import checkpoint

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _BNModel:
    """Tiny deterministic linear+BN forward shared by trainer and server
    (module-level so a fresh process can import it by path)."""

    @staticmethod
    def apply(state, x):
        h = x @ np.asarray(state["params"]["w"], np.float64)
        mean = np.asarray(state["batch_stats"]["mean"], np.float64)
        var = np.asarray(state["batch_stats"]["var"], np.float64)
        return (h - mean) / np.sqrt(var + 1e-5)


def test_export_merges_stacked_stats_and_drops_opt_state(tmp_path):
    """Single-process sharded layout: stats carry a leading device dim; the
    export averages it, drops opt_state, and load_for_inference restores
    without any init."""
    stacked = {
        "mean": jnp.asarray([[1.0, 2.0], [3.0, 4.0], [5.0, 6.0], [7.0, 8.0]]),
        "var": jnp.ones((4, 2)),
    }
    state = {
        "params": {"w": jnp.arange(6.0).reshape(3, 2)},
        "batch_stats": stacked,
        "opt_state": {"momentum": jnp.ones(3)},
    }
    serving = checkpoint.export_for_inference(
        str(tmp_path / "serve"), state, stacked_stats_axis=0)
    assert "opt_state" not in serving
    np.testing.assert_allclose(np.asarray(serving["batch_stats"]["mean"]),
                               [4.0, 5.0])

    restored = checkpoint.load_for_inference(str(tmp_path / "serve"))
    assert set(restored) == {"params", "batch_stats"}
    np.testing.assert_allclose(np.asarray(restored["batch_stats"]["mean"]),
                               [4.0, 5.0])
    x = np.ones((2, 3))
    np.testing.assert_allclose(_BNModel.apply(restored, x),
                               _BNModel.apply(serving, x))


@pytest.mark.slow
def test_multiprocess_roundtrip_fresh_process_same_logits(tmp_path):
    """The VERDICT r3 done-criterion: train 2 ranks (divergent per-rank BN
    stats) -> export -> restore on 1 fresh process -> same logits."""
    from horovod_tpu.runner import run

    ckpt = str(tmp_path / "serve")

    def train_fn(ckpt):
        import jax

        jax.config.update("jax_platforms", "cpu")  # workers never claim the chip
        import numpy as np

        import horovod_tpu as hvd
        from horovod_tpu import checkpoint

        hvd.init()
        r = hvd.rank()
        # "Training": params kept in sync (as DistributedOptimizer would),
        # BN stats divergent per rank (each saw its own shard).
        state = {
            "params": {"w": np.arange(6.0).reshape(3, 2)},
            "batch_stats": {"mean": np.full(2, float(r)),
                            "var": np.full(2, 1.0 + r)},
            "opt_state": {"momentum": np.ones(3)},
        }
        serving = checkpoint.export_for_inference(ckpt, state)
        # consolidated in-training logits, the oracle for the fresh process
        x = np.ones((2, 3))
        h = x @ serving["params"]["w"]
        logits = (h - serving["batch_stats"]["mean"]) / np.sqrt(
            serving["batch_stats"]["var"] + 1e-5)
        hvd.shutdown()
        return logits.tolist()

    results = run(train_fn, args=(ckpt,), num_proc=2, timeout=120)
    oracle = np.asarray(results[0])
    np.testing.assert_allclose(np.asarray(results[1]), oracle)  # ranks agree

    # Fresh process: restores and serves with NO horovod init; its stats
    # must be the cross-rank average (mean 0.5, var 1.5), not rank 0's.
    server = (
        "import sys, json; sys.path.insert(0, %r)\n"
        "import jax; jax.config.update('jax_platforms', 'cpu')\n"
        "import numpy as np\n"
        "from horovod_tpu.checkpoint import load_for_inference\n"
        "state = load_for_inference(%r)\n"
        "assert 'opt_state' not in state\n"
        "assert np.allclose(state['batch_stats']['mean'], 0.5)\n"
        "x = np.ones((2, 3))\n"
        "h = x @ state['params']['w']\n"
        "logits = (h - state['batch_stats']['mean']) / np.sqrt(state['batch_stats']['var'] + 1e-5)\n"
        "print(json.dumps(logits.tolist()))\n" % (REPO, ckpt)
    )
    out = subprocess.run([sys.executable, "-c", server], capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    served = np.asarray(json.loads(out.stdout.strip().splitlines()[-1]))
    np.testing.assert_allclose(served, oracle, rtol=1e-12)


@pytest.mark.slow
def test_flax_model_roundtrip_logits(tmp_path):
    """Full flax path: BN model trained (stats mutated) on the stacked
    layout, exported, reloaded, and served single-replica — logits equal
    the inline consolidated forward."""
    import flax.linen as nn

    class Net(nn.Module):
        @nn.compact
        def __call__(self, x, train: bool = False):
            x = nn.Dense(8)(x)
            x = nn.BatchNorm(use_running_average=not train, momentum=0.9)(x)
            return nn.Dense(4)(x)

    net = Net()
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 5))
    variables = net.init(jax.random.PRNGKey(1), x)
    # stacked per-device stats, rows made divergent as if each device saw
    # different shards
    stacked = jax.tree_util.tree_map(
        lambda t: jnp.stack([t + i for i in range(4)]),
        variables["batch_stats"])
    state = {"params": variables["params"], "batch_stats": stacked,
             "opt_state": {"junk": jnp.zeros(3)}}
    checkpoint.export_for_inference(str(tmp_path / "flax"), state,
                                    stacked_stats_axis=0)
    restored = checkpoint.load_for_inference(str(tmp_path / "flax"))
    merged = jax.tree_util.tree_map(lambda t: jnp.mean(t, axis=0), stacked)
    ref = net.apply({"params": variables["params"], "batch_stats": merged}, x)
    got = net.apply({"params": restored["params"],
                     "batch_stats": restored["batch_stats"]}, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-6)


@pytest.mark.slow
def test_torch_consolidate_bn_stats(tmp_path):
    """Torch path: divergent running stats across 2 ranks are averaged in
    place; rank 0's state_dict then serves in a fresh torch-only process."""
    from horovod_tpu.runner import run

    pt = str(tmp_path / "model.pt")

    def train_fn(pt):
        import torch

        import horovod_tpu.torch as hvd

        hvd.init()
        torch.manual_seed(0)
        model = torch.nn.Sequential(torch.nn.Linear(4, 3),
                                    torch.nn.BatchNorm1d(3))
        with torch.no_grad():
            model[1].running_mean.fill_(float(hvd.rank()))
            model[1].running_var.fill_(1.0 + hvd.rank())
        hvd.broadcast_parameters(model.state_dict(), root_rank=0)
        with torch.no_grad():  # re-diverge the stats after the broadcast
            model[1].running_mean.fill_(float(hvd.rank()))
            model[1].running_var.fill_(1.0 + hvd.rank())
        hvd.consolidate_bn_stats(model)
        mean = model[1].running_mean.tolist()
        var = model[1].running_var.tolist()
        if hvd.rank() == 0:
            torch.save(model.state_dict(), pt)
        hvd.shutdown()
        return mean, var

    results = run(train_fn, args=(pt,), num_proc=2, timeout=120)
    for mean, var in results:
        np.testing.assert_allclose(mean, [0.5] * 3)
        np.testing.assert_allclose(var, [1.5] * 3)

    server = (
        "import torch\n"
        "model = torch.nn.Sequential(torch.nn.Linear(4, 3), torch.nn.BatchNorm1d(3))\n"
        "model.load_state_dict(torch.load(%r, weights_only=True))\n"
        "assert torch.allclose(model[1].running_mean, torch.full((3,), 0.5))\n"
        "print('served')\n" % pt
    )
    out = subprocess.run([sys.executable, "-c", server], capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "served" in out.stdout
