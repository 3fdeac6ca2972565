"""Test harness: virtual 8-device CPU mesh.

Mirrors the reference CI strategy (SURVEY.md §4): one suite that self-adapts
to the topology it finds. Multi-*device* semantics run on an 8-device virtual
CPU platform (`--xla_force_host_platform_device_count=8`); multi-*process*
eager-engine semantics are tested in-process against the TCP coordinator.

The virtual devices and the CPU platform are pinned before anything can
initialise a backend: tests never see the chip (``chip_smoke.py``, run
through the chip tool, is what does).
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

from horovod_tpu.utils.compile_cache import (  # noqa: E402
    ENV as _CACHE_ENV, configure_compile_cache)

# Persistent XLA compilation cache: the fast tier is dominated by CPU
# compiles of the same jitted steps every run; warm runs skip them. The
# shared helper places it (JAX_COMPILATION_CACHE_DIR, else
# <checkout>/.jax_cache, started empty on a host whose CPU differs from the
# entries'); the env setdefault is kept so SPAWNED WORKER processes
# (launch_util, runner tests, mp_train_script) inherit the directory.
os.environ.setdefault(_CACHE_ENV, configure_compile_cache(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")))
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.3")
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.3)

import pytest  # noqa: E402


def pytest_collection_modifyitems(items):
    """Every test not marked slow is the fast tier: `-m fast` (or the
    equivalent `-m "not slow"`) is the sub-2-minute developer loop; `-m slow`
    holds the XLA-compile-heavy and multi-minute e2e tests."""
    for item in items:
        if "slow" not in item.keywords:
            item.add_marker(pytest.mark.fast)


@pytest.fixture()
def hvd():
    import horovod_tpu as hvd

    hvd.init()
    yield hvd
    hvd.shutdown()


@pytest.fixture()
def mesh8():
    from horovod_tpu.parallel.mesh import data_parallel_mesh

    assert jax.device_count() == 8, "virtual CPU mesh not active"
    return data_parallel_mesh()


@pytest.fixture()
def mesh_2x4():
    """('dcn','ici') hierarchical mesh: 2 virtual nodes × 4 chips."""
    from horovod_tpu.parallel.mesh import hierarchical_mesh

    return hierarchical_mesh(ici_size=4)
