"""Example scripts run end-to-end under the launcher — the reference CI runs
its MNIST examples as integration tests (.travis.yml:116-140, shrunk via sed;
here the examples take small shapes natively)."""

from __future__ import annotations

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_example(cmd, timeout=300, env_extra=None, with_stderr=False):
    env = dict(os.environ)
    # Prepend to (never replace) whatever PYTHONPATH the environment has.
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.update(env_extra or {})
    proc = subprocess.run(cmd, capture_output=True, text=True, timeout=timeout,
                          cwd=REPO, env=env)
    assert proc.returncode == 0, f"stderr:\n{proc.stderr[-3000:]}"
    return (proc.stdout, proc.stderr) if with_stderr else proc.stdout


@pytest.mark.slow
def test_pytorch_mnist_example_2proc():
    out = run_example([
        sys.executable, "-m", "horovod_tpu.runner", "-np", "2", "--",
        sys.executable, "examples/pytorch_mnist.py",
    ])
    assert "epoch 3" in out
    assert "averaged over 2 ranks" in out


@pytest.mark.slow
def test_jax_mnist_example_single():
    out = run_example([sys.executable, "examples/jax_mnist.py"],
                      env_extra={"MNIST_STEPS": "3", "HVD_FORCE_CPU": "1"})
    assert "epoch 2" in out


@pytest.mark.slow
def test_pytorch_synthetic_benchmark_2proc():
    out = run_example([
        sys.executable, "-m", "horovod_tpu.runner", "-np", "2", "--",
        sys.executable, "examples/pytorch_synthetic_benchmark.py",
        "--num-iters", "2", "--num-batches-per-iter", "2",
        "--num-warmup-batches", "1",
    ])
    assert "Img/sec per device" in out
    assert "Total img/sec on 2 device(s)" in out


@pytest.mark.slow
def test_pytorch_mnist_callbacks_2proc():
    out = run_example([
        sys.executable, "-m", "horovod_tpu.runner", "-np", "2", "--",
        sys.executable, "examples/pytorch_mnist_callbacks.py",
    ], env_extra={"MNIST_EPOCHS": "3", "MNIST_STEPS": "4"})
    assert "epoch 3" in out
    assert "averaged over 2 ranks" in out
    # warmup ramped lr toward lr*size=0.02 over 2 epochs
    assert "lr 0.0200" in out


@pytest.mark.slow
def test_jax_mnist_advanced_2proc():
    """keras_mnist_advanced twin: warmup ramps lr toward base*size and the
    epoch-end metrics are engine-averaged across ranks."""
    out = run_example([
        sys.executable, "-m", "horovod_tpu.runner", "-np", "2", "--",
        sys.executable, "examples/jax_mnist_advanced.py",
    ], env_extra={"MNIST_EPOCHS": "3", "MNIST_STEPS": "4",
                  "HVD_FORCE_CPU": "1"})
    assert "epoch 2" in out
    assert "averaged over 2 ranks" in out
    assert "lr 0.0100" in out  # base 0.005 ramped to base*size at warmup end


@pytest.mark.slow
def test_jax_mnist_eager_2proc():
    """tensorflow_mnist_eager twin: gradients allreduced per step through
    the background engine, not in-jit collectives."""
    out, err = run_example([
        sys.executable, "-m", "horovod_tpu.runner", "-np", "2", "--",
        sys.executable, "examples/jax_mnist_eager.py",
    ], env_extra={"MNIST_EPOCHS": "2", "MNIST_STEPS": "4",
                  "HVD_FORCE_CPU": "1"}, with_stderr=True)
    assert "epoch 1" in out
    assert "eager engine, averaged over 2 ranks" in out
    # Clean coordinated shutdown: a worker that learns of shutdown from the
    # response broadcast must ANNOUNCE its departure (engine.cc one-extra-
    # tick protocol) — a silent exit makes the coordinator log every normal
    # multi-process teardown as a lost rank.
    assert "lost (connection dropped without shutdown)" not in err, err[-2000:]


@pytest.mark.slow
@pytest.mark.parametrize("extra", [[], ["--remat", "--loss-chunk", "16"],
                                   ["--scan-steps", "3", "--bf16-logits"]],
                         ids=["full-logits", "remat-chunked",
                              "scan-bf16-logits"])
def test_transformer_benchmark_flash_gqa(extra):
    """The tokens/s harness runs end-to-end with flash attention + GQA on
    tiny shapes (interpret-mode kernels on CPU) — both the default
    full-logits branch and the remat + chunked-loss long-context branch."""
    out = run_example([
        sys.executable, "examples/transformer_benchmark.py",
        "--dim", "32", "--heads", "4", "--kv-heads", "2", "--layers", "2",
        "--vocab", "64", "--seq-len", "64", "--num-warmup", "1",
        "--num-iters", "2", "--attention", "flash", *extra,
    ], env_extra={"HVD_FORCE_CPU": "1"})
    assert "Tokens/sec" in out
    assert "kv 2" in out


@pytest.mark.slow
def test_jax_word2vec_sparse_path():
    out = run_example(
        [sys.executable, "examples/jax_word2vec.py"],
        env_extra={"HVD_FORCE_CPU": "1", "W2V_EPOCHS": "1", "W2V_STEPS": "3",
                   "W2V_VOCAB": "200", "W2V_DIM": "16", "W2V_BATCH": "32"})
    assert "sparse rows/step" in out


@pytest.mark.slow
def test_jax_imagenet_resume(tmp_path):
    ck = str(tmp_path / "ckjax")
    args = [sys.executable, "examples/jax_imagenet_resnet50.py",
            "--epochs", "3", "--steps-per-epoch", "2", "--batch-size", "4",
            "--image-size", "16", "--checkpoint-dir", ck]
    env = {"HVD_FORCE_CPU": "1"}
    out1 = run_example(args + ["--stop-after-epoch", "1"], env_extra=env)
    assert '"epoch": 1' in out1 and "stopped_after_epoch" in out1
    out2 = run_example(args, env_extra=env)
    assert '"resumed_from": 1' in out2
    assert '"epoch": 2' in out2 and '"epoch": 3' in out2
